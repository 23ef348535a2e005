"""EVA attention as EvaByte trains it: exact softmax over two key sets with
different visibility rules and one normaliser.

Byte ``t`` lies in chunk ``c(t) = t // chunk`` and window ``w(t) = t //
window`` (a window is a whole number of chunks).  A query sees

* **its own window, causally**: ``L(t) = {j : w(j) = w(t), j <= t}``, the
  bytes themselves;
* **every chunk of every earlier window, as one summary each**: ``R(t) = {c
  : (c + 1) chunk <= w(t) window}``, through the learnt per-head pooling of
  `chunk_summaries`: ``pi_j = softmax_{j in c}(scale phi . k_j)``, ``k~_c =
  sum_j pi_j k_j + mu``, ``v~_c = sum_j pi_j v_j``.

``z_t = sum_L exp(scale q_t . k_j) + sum_R exp(scale q_t . k~_c)`` and ``o_t``
the two weighted sums over ``z_t``: nothing approximated, nothing dropped.

Two forms of the one function, `eva_attention(..., impl=)`:

* ``"dense"``: both score matrices whole under masks built from ``w`` and
  ``c``, one softmax over their concatenation.  Plain `jax.numpy`: the CPU
  path, what a model is initialised with, and the oracle of the tests.  Any
  whole number of chunks, the last window as short as it comes.
* ``"mosaic"`` / ``"interpret"``: the local part through the flash kernels
  with the ``S / window`` windows of a row as rows of the batch (``[B S/W,
  W, H, D]``, causal: a window is a head's whole sequence there, and no ``[S,
  S]`` or ``[W, W]`` score matrix is ever in HBM), asked for the row
  statistics too (`flash_attention(return_lse=True)`); the summaries' part
  window by window in `jax.numpy` (window ``w`` against the first ``w W /
  chunk`` summaries: the pairs that exist and no mask; ``S / chunk`` columns
  a row at most, 1 / ``chunk`` of the square); and the exact join ``lse =
  logaddexp(lse_L, lse_R)``, ``o = o_L e^{lse_L - lse} + o_R e^{lse_R -
  lse}`` in f32.  Gradients reach the kernels through both of their outputs.
  A row must be whole windows: a ragged last window is refused, not padded.

Scopes (`jax.named_scope`): ``eva_summary`` round the pooling, ``eva_attn``
round the rest, ``eva_local`` inside it round the flash calls.  The second
result is the **remote mass**: the mean over heads and over the queries that
have summaries to see (``w(t) >= 1``) of the share of ``z_t`` the summaries
hold; 0 where no query has any.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention

IMPLS = ("dense", "mosaic", "interpret")


def chunk_summaries(k, v, phi, mu, *, chunk: int, scale: float):
    """``k: [B, S, H, D]`` (rotated), ``v: [B, S, H, Dv]``, ``phi, mu: [H,
    D]`` -> ``(k~ [B, S / chunk, H, D], v~ [B, S / chunk, H, Dv])`` in f32:
    the pooling weights are a softmax over a chunk's own ``chunk`` keys,
    scored against ``phi`` with the attention's ``scale``; ``mu`` is added
    to the key summary only."""
    b, s, h, d = k.shape
    if s % chunk:
        raise ValueError(f"{s} bytes are not whole chunks of {chunk}")
    k = k.astype(jnp.float32).reshape(b, s // chunk, chunk, h, d)
    v = v.astype(jnp.float32).reshape(b, s // chunk, chunk, h, v.shape[-1])
    pi = jax.nn.softmax(
        jnp.einsum("bnchd,hd->bnch", k, phi.astype(jnp.float32)) * scale,
        axis=2)[..., None]
    return jnp.sum(pi * k, axis=2) + mu.astype(jnp.float32), \
        jnp.sum(pi * v, axis=2)


def _dense(q, k, v, k_sum, v_sum, *, window, chunk, scale):
    s, n_sum = q.shape[1], k_sum.shape[1]
    t = jnp.arange(s)
    local = (t[:, None] // window == t[None, :] // window) \
        & (t[None, :] <= t[:, None])
    remote = (jnp.arange(n_sum)[None, :] + 1) * chunk \
        <= (t[:, None] // window) * window
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32),
        jnp.concatenate([k.astype(jnp.float32), k_sum], axis=1)) * scale
    probs = jax.nn.softmax(jnp.where(
        jnp.concatenate([local, remote], axis=1), scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, jnp.concatenate(
        [v.astype(jnp.float32), v_sum], axis=1))
    has_remote = remote.any(axis=1)
    mass = jnp.sum(probs[..., s:], axis=-1) * has_remote
    return out, jnp.sum(mass) / jnp.maximum(
        1, mass.shape[0] * mass.shape[1] * jnp.sum(has_remote))


def _windows(q, k, v, k_sum, v_sum, *, window, chunk, scale, impl):
    b, s, h, d = q.shape
    if s % window:
        raise ValueError(
            f"a row of {s} bytes is not whole windows of {window}: the "
            f"kernels' form refuses a ragged last window (impl='dense' "
            f"takes one)")
    n, per = s // window, window // chunk
    rows = lambda x: x.reshape(b * n, window, h, x.shape[-1])
    with jax.named_scope("eva_local"):
        o_loc, lse_loc = flash_attention(
            rows(q), rows(k), rows(v), causal=True, scale=scale,
            return_lse=True, impl=impl)
    o_loc = o_loc.astype(jnp.float32).reshape(b, n, window, h, -1)
    lse_loc = lse_loc.reshape(b, n, h, window)
    outs, masses = [o_loc[:, 0]], []
    for w in range(1, n):   # window w against the summaries before it
        q_w = q[:, w * window:(w + 1) * window]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_w,
                            k_sum[:, :w * per].astype(q.dtype),
                            preferred_element_type=jnp.float32) * scale
        lse_rem = jax.nn.logsumexp(scores, axis=-1)          # [B, H, W]
        o_rem = jnp.einsum(
            "bhqk,bkhd->bqhd",
            jnp.exp(scores - lse_rem[..., None]).astype(v.dtype),
            v_sum[:, :w * per].astype(v.dtype),
            preferred_element_type=jnp.float32)
        lse = jnp.logaddexp(lse_loc[:, w], lse_rem)
        share = lambda part: jnp.exp(part - lse).transpose(0, 2, 1)[..., None]
        outs.append(o_loc[:, w] * share(lse_loc[:, w])
                    + o_rem * share(lse_rem))
        masses.append(jnp.mean(jnp.exp(lse_rem - lse)))
    mass = jnp.mean(jnp.stack(masses)) if masses else jnp.zeros((),
                                                                jnp.float32)
    return jnp.concatenate(outs, axis=1), mass


def eva_attention(q, k, v, phi, mu, *, window: int, chunk: int,
                  scale: float | None = None, impl: str = "dense"):
    """``q, k: [B, S, H, D]`` (rotated), ``v: [B, S, H, Dv]``, ``phi, mu:
    [H, D]`` -> ``(o [B, S, H, Dv] in q's dtype, remote mass, a scalar)``.
    ``impl``: ``"dense"`` (plain, any whole number of chunks), ``"mosaic"``
    (the flash kernels on the chip) or ``"interpret"`` (the same kernels
    under the Pallas interpreter), both over whole windows only."""
    if impl not in IMPLS:
        raise ValueError(f"impl {impl!r}: know {IMPLS}")
    if window % chunk:
        raise ValueError(f"a window of {window} bytes is not whole chunks "
                         f"of {chunk}")
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    with jax.named_scope("eva_summary"):
        k_sum, v_sum = chunk_summaries(k, v, phi, mu, chunk=chunk,
                                       scale=scale)
    sizes = dict(window=window, chunk=chunk, scale=scale)
    with jax.named_scope("eva_attn"):
        if impl == "dense":
            out, mass = _dense(q, k, v, k_sum, v_sum, **sizes)
        else:
            out, mass = _windows(q, k, v, k_sum, v_sum, impl=impl, **sizes)
    return out.astype(q.dtype), mass
