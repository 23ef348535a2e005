"""SambaY with differential attention (Phi-4-mini-flash-reasoning,
``phi4flash``): a decoder whose first half mixes a selective state-space
scan (Mamba-1) with attention under a sliding window, and whose second half,
the cross-decoder, owns no keys, values or scan of its own: it reads one
earlier layer's.

Every block is pre-norm, ``h = x + Mixer(LN(x)); out = h + MLP(LN(h))``,
LayerNorm with a bias, the MLP a bias-free `models.moe.SwiGLU`; parameters
f32 and matrix products in ``dtype``.  No position enters anywhere
(`positions` is accepted and not read, so that the model takes the
`lm_batch` contract).  The head is the embedding's transpose: one tensor in
the parameter tree.  ``layers`` lists each block's kind and its published
index (the differential attention's ``lambda_init`` reads it):

* ``mamba``: ``[x; z] = W_in u``; ``x = silu(conv(x) + b)`` (causal,
  depthwise, `models.kimi_linear.causal_conv_silu`); ``[delta; B; C] = W_x
  x``; ``dt = softplus(W_dt delta + b_dt)``; ``A = -exp(A_log)``; ``y`` the
  scan of `ops.selective_scan.selective_scan` (``dt``, the decays and the
  state f32); ``out = W_out(y * silu(z))``.
* ``mamba_memory``: the same, and its ``y`` (with the ``D`` skip, before the
  gate) is handed on as the memory ``m``.
* ``swa`` / ``full_kv``: differential attention (below) under a window of
  ``window`` keys / under the causal mask alone; ``full_kv`` hands on its
  ``k`` and ``v``.
* ``gmu``: ``out = W_out(silu(W_in u) * m)``.
* ``cross``: differential attention with a ``q`` of its own and
  ``full_kv``'s ``k``, ``v``, causal.

**Differential attention.**  Query heads ``(2p, 2p + 1)`` are the pair ``(q1,
q2)`` and kv heads ``(2g, 2g + 1)``, ``g = p // 2``, the pair ``(k1, k2)``
with ``V = [v_2g; v_2g+1]`` (twice a head's width): ``o_p = softmax(q1
k1^T) V - lam softmax(q2 k2^T) V``, ``lam = exp(lq1 . lk1) - exp(lq2 . lk2)
+ lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 index)``; then ``(1 -
lam_init) RMSNorm(o_p)`` with one learnt scale a layer, the pairs side by
side into ``W_o``.  Both softmaxes go through the one ``attn`` callable
(`ops.flash_attention.flash_attention` on the chip) as ``n_heads`` heads
with a v twice as wide as q / k, the kv pair repeated over its query pairs
here; the subtraction is outside it.

**What crosses blocks.**  Each half of each block is rematerialised on its
own (`nn.remat`).  ``m`` and ``full_kv``'s ``k``, ``v`` leave the half-block
that makes them and enter the ones that read them as arguments, so they are
kept for the backward pass, not recomputed, and their gradients are the sum
over every reader.  The mixer half is rematerialised under
`ops.flash_attention.SAVE_FLASH`: an attention layer keeps its flash call's
output and row statistics, so the backward pass does not run the forward
kernel again; a Mamba or GMU layer names nothing it makes, and its half is
the program it is without the policy.

Scopes for the device trace (`jax.named_scope`): ``ssm`` (the scan alone),
``swa`` and ``full_attn`` (the attention calls of the window layers / of
``full_kv`` and ``cross``), ``diff`` (subtraction, norm, scaling), ``gmu``
(the gated memory unit whole: its two products and the gate between them,
which XLA fuses into them), ``head_loss``.  `make_sambay_loss` is an
aux-style loss for `MPI_PS.compile_step(loss, has_aux=True,
aux=sambay_aux(model))`; every attention layer's ``lam`` leaves the step
under ``aux["counters"]["diff_lambda"]``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.flash_attention import SAVE_FLASH
from ..ops.selective_scan import selective_scan
from ..parallel.ring_attention import dense_attention
from .kimi_linear import _a_log_init, _dt_bias_init, causal_conv_silu
from .moe import SwiGLU, bias_free_dense as _dense

KINDS = ("mamba", "swa", "mamba_memory", "full_kv", "gmu", "cross")
ATTENTION_KINDS = ("swa", "full_kv", "cross")


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    """The sizes of one SambaY model (or one pipeline stage's share)."""

    vocab_size: int
    d_model: int
    d_ff: int
    n_heads: int                       # query heads; pairs of them subtract
    n_kv_heads: int
    window: int
    layers: "tuple[tuple[str, int], ...]"   # (kind, published index)
    d_inner: int
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    eps: float = 1e-5
    dtype: Any = jnp.float32

    def __post_init__(self):
        kinds = [k for k, _ in self.layers]
        if not set(kinds) <= set(KINDS):
            raise ValueError(f"layer kinds {kinds}: know {KINDS}")
        for reader, source in (("gmu", "mamba_memory"), ("cross", "full_kv")):
            if reader in kinds and source not in kinds[:kinds.index(reader)]:
                raise ValueError(f"a {reader!r} layer reads an earlier "
                                 f"{source!r} layer's output")
        if self.n_heads % 2 or self.n_kv_heads % 2 \
                or (self.n_heads // 2) % (self.n_kv_heads // 2):
            raise ValueError("query and kv heads pair up, and kv pairs "
                             "divide the query pairs")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def n_attention(self) -> int:
        return sum(k in ATTENTION_KINDS for k, _ in self.layers)


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _biased(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=True, dtype=dtype,
                    param_dtype=jnp.float32, name=name)


class Mamba(nn.Module):
    """``u -> (W_out(y * silu(z)), y)``: Mamba-1 around `selective_scan`."""

    cfg: SambaYConfig

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        n, r = c.d_state, c.dt_rank
        x, z = jnp.split(_dense(2 * c.d_inner, c.dtype, "in_proj")(u), 2,
                         axis=-1)
        kernel = self.param("conv", nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=0, out_axis=1),
            (c.d_conv, c.d_inner), jnp.float32)
        conv_bias = self.param("conv_bias", nn.initializers.zeros,
                               (c.d_inner,), jnp.float32)
        x = causal_conv_silu(x, kernel, conv_bias)
        dbc = _dense(r + 2 * n, c.dtype, "x_proj")(x)
        dt_bias = self.param("dt_bias", _dt_bias_init, (c.d_inner,),
                             jnp.float32)
        dt = jax.nn.softplus(_dense(c.d_inner, c.dtype, "dt_proj")(
            dbc[..., :r]).astype(jnp.float32) + dt_bias)
        a_log = self.param("A_log", _a_log_init, (c.d_inner, n), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (c.d_inner,),
                          jnp.float32)
        with jax.named_scope("ssm"):
            y = selective_scan(
                x, dt, -jnp.exp(a_log),
                dbc[..., r:r + n].astype(jnp.float32),
                dbc[..., r + n:].astype(jnp.float32), skip)
        return _dense(c.d_model, c.dtype, "out_proj")(y * nn.silu(z)), y


class DiffAttention(nn.Module):
    """``(u, kv) -> (out, (k, v), lam)``: ``kv`` None projects this layer's
    own keys and values from ``u``, else (``cross``) only a q, and attends
    to the ``kv`` it is given.  ``attn(q, k, v)`` is causal attention over
    ``[B, S, H, D]``, under this layer's window if it has one."""

    cfg: SambaYConfig
    index: int
    attn: Callable
    span: str

    @nn.compact
    def __call__(self, u, kv=None):
        c = self.cfg
        b, s, _ = u.shape
        h, hk, d = c.n_heads, c.n_kv_heads, c.head_dim
        if kv is None:
            qkv = _biased((h + 2 * hk) * d, c.dtype, "qkv_proj")(u)
            q = qkv[..., :h * d]
            k = qkv[..., h * d:(h + hk) * d].reshape(b, s, hk, d)
            v = qkv[..., (h + hk) * d:].reshape(b, s, hk, d)
        else:
            q = _biased(h * d, c.dtype, "q_proj")(u)
            k, v = kv
        # kv pair g serves the query pairs 2g .. 2g + (h / hk) - 1: q head
        # 2p + r meets k head 2 (p // (h / hk)) + r and both v heads of it
        group = h // hk
        k_rep = jnp.broadcast_to(
            k.reshape(b, s, hk // 2, 1, 2, d),
            (b, s, hk // 2, group, 2, d)).reshape(b, s, h, d)
        v_rep = jnp.broadcast_to(
            v.reshape(b, s, hk // 2, 1, 2 * d),
            (b, s, hk // 2, 2 * group, 2 * d)).reshape(b, s, h, 2 * d)
        with jax.named_scope(self.span):
            o = self.attn(q.reshape(b, s, h, d), k_rep, v_rep)
        with jax.named_scope("diff"):
            vec = lambda name: self.param(
                name, nn.initializers.normal(0.1), (d,), jnp.float32)
            init = lambda_init(self.index)
            lam = jnp.exp(jnp.sum(vec("lambda_q1") * vec("lambda_k1"))) \
                - jnp.exp(jnp.sum(vec("lambda_q2") * vec("lambda_k2"))) + init
            o = o.astype(jnp.float32).reshape(b, s, h // 2, 2, 2 * d)
            o = o[..., 0, :] - lam * o[..., 1, :]
            scale = self.param("subln", nn.initializers.ones, (2 * d,),
                               jnp.float32)
            o = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True) + c.eps)
            o = (o * scale * (1.0 - init)).astype(c.dtype)
        out = _biased(c.d_model, c.dtype, "o_proj")(o.reshape(b, s, h * d))
        return out, (k, v), lam


class GatedMemory(nn.Module):
    """``(u, m) -> W_out(silu(W_in u) * m)``."""

    cfg: SambaYConfig

    @nn.compact
    def __call__(self, u, m):
        c = self.cfg
        with jax.named_scope("gmu"):
            gate = nn.silu(_dense(c.d_inner, c.dtype, "in_proj")(u))
            return _dense(c.d_model, c.dtype, "out_proj")(gate * m)


def _mixer_part(block: "SambaYBlock", x, shared):
    """``(x, what this block reads) -> (x, what it hands on, lam)``."""
    u, kind = block.mixer_norm(x), block.kind
    keep, lam = None, None
    if kind in ("mamba", "mamba_memory"):
        y, scanned = block.mixer(u)
        if kind == "mamba_memory":
            keep = scanned
    elif kind == "gmu":
        y = block.mixer(u, shared)
    else:
        y, kv, lam = block.mixer(u, shared)
        if kind == "full_kv":
            keep = kv
    return x + y, keep, lam


def _mlp_part(block: "SambaYBlock", x):
    return x + block.mlp(block.mlp_norm(x))


class SambaYBlock(nn.Module):
    """One layer of ``kind``; each half rematerialised on its own, as
    `models.kimi_linear.DecoderBlock`, the mixer's keeping what the flash
    call names (`SAVE_FLASH`).  ``shared`` is what the kind reads:
    the memory ``m`` (``gmu``), ``(k, v)`` (``cross``), else None."""

    cfg: SambaYConfig
    kind: str
    index: int
    attn_fn: Callable

    def setup(self):
        c = self.cfg
        norm = lambda: nn.LayerNorm(epsilon=c.eps, dtype=c.dtype,
                                    param_dtype=jnp.float32)
        self.mixer_norm, self.mlp_norm = norm(), norm()
        if self.kind in ("mamba", "mamba_memory"):
            self.mixer = Mamba(c)
        elif self.kind == "gmu":
            self.mixer = GatedMemory(c)
        else:
            window = c.window if self.kind == "swa" else None
            self.mixer = DiffAttention(
                c, self.index,
                lambda q, k, v: self.attn_fn(q, k, v, window=window),
                "swa" if self.kind == "swa" else "full_attn")
        self.mlp = SwiGLU(c.d_ff, c.dtype)

    def __call__(self, x, shared=None):
        x, keep, lam = nn.remat(_mixer_part, policy=SAVE_FLASH)(
            self, x, shared)
        return nn.remat(_mlp_part)(self, x), keep, lam


def dense_window_attention(q, k, v, *, window=None):
    """The default ``attn``: `dense_attention`, causal, with the band as a
    mask on the scores."""
    if window is None:
        return dense_attention(q, k, v, causal=True)
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        * q.shape[-1] ** -0.5
    age = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    scores = jnp.where((age >= 0) & (age < window), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(scores, axis=-1).astype(v.dtype), v)


class SambaYLM(nn.Module):
    """``__call__(tokens, positions=None) -> (logits [B, S, V] f32, lam
    [attention layers] f32)``.  ``attn(q, k, v, window=)`` is causal
    attention scaled by ``head_dim ** -0.5``."""

    cfg: SambaYConfig
    attn: Callable = dense_window_attention

    @nn.compact
    def __call__(self, tokens, positions=None):
        del positions   # no position encoding in any layer
        c = self.cfg
        embed = nn.Embed(c.vocab_size, c.d_model, dtype=c.dtype,
                         param_dtype=jnp.float32,
                         embedding_init=nn.initializers.normal(1.0),
                         name="tok_embed")
        x = embed(tokens)
        shared = {"gmu": None, "cross": None}
        lams = []
        for i, (kind, index) in enumerate(c.layers):
            x, keep, lam = SambaYBlock(c, kind, index, self.attn,
                                       name=f"block_{i}")(
                x, shared.get(kind))
            if kind == "mamba_memory":
                shared["gmu"] = keep
            elif kind == "full_kv":
                shared["cross"] = keep
            if lam is not None:
                lams.append(lam)
        with jax.named_scope("head_loss"):
            x = nn.LayerNorm(epsilon=c.eps, dtype=c.dtype,
                             param_dtype=jnp.float32, name="final_norm")(x)
            logits = embed.attend(x).astype(jnp.float32)
        return logits, jnp.stack(lams) if lams else jnp.zeros((0,))


def sambay_aux(model: SambaYLM) -> dict:
    """The aux tree `make_sambay_loss` threads through the step."""
    return {"counters": {"diff_lambda": np.zeros(
        (model.cfg.n_attention,), np.float32)}}


def make_sambay_loss(model: SambaYLM):
    """Next-token cross-entropy as ``loss_fn(params, aux, batch) -> (loss,
    new_aux)``; ``new_aux["counters"]["diff_lambda"]`` is each attention
    layer's ``lam`` at this step."""
    from ..utils.flatten import unflatten_params

    def loss_fn(params_named, aux, batch):
        del aux
        logits, lam = model.apply(
            {"params": unflatten_params(params_named)}, batch["tokens"],
            batch["positions"])
        with jax.named_scope("head_loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, batch["targets"][..., None],
                                     axis=-1)[..., 0]
            loss = -jnp.mean(ll)
        return loss, {"counters": {
            "diff_lambda": jax.lax.stop_gradient(lam)}}

    return loss_fn
