"""EvaByte (``evabyte``): a tokenizer-free decoder over bytes whose
attention is EVA (`ops.eva_attention`: exact softmax inside a window of
bytes and over one learnt summary a 16-byte chunk of everything before the
window, one normaliser) and whose head predicts the next ``n_pred_heads``
bytes at once.

Every block is pre-norm, ``h = x + Attn(RMSNorm(x)); out = h +
SwiGLU(RMSNorm(h))``, no bias anywhere.  The residual stream ``x`` is **f32**
(the embedding is read in f32 and every half-block's output is added into it
in f32); matrix products take ``dtype`` inputs; norms, softmax statistics,
the join of the two key sets, the logits and the loss are f32.  ``RMSNorm(x)
= x / sqrt(mean(x^2) + eps) (1 + g)`` with ``g`` starting at 0.

**Attention.**  ``n_heads`` heads of ``head_dim`` are *held* here:
``W_q, W_k, W_v: [d_model, n_heads head_dim]``, ``W_o: [n_heads head_dim,
d_model]``, and ``n_heads head_dim`` need not be ``d_model`` — a chip that
shares each layer with others by heads holds some of them, and what the
absent heads would have added to ``W_o``'s sum is left out.  q and k are
rotated over all of ``head_dim`` (`models.kimi_linear.rotate`, split halves,
``rope_theta``) before the summaries are made; ``phi`` and ``mu`` (``[n_heads,
head_dim]``, one pair a head a layer) are the summaries' pooling query and
the offset of the key summary.

**Head.**  One ``[d_model, n_pred_heads vocab_size]`` map on the final norm;
head ``i`` is scored against byte ``t + 1 + i``: the batch's ``targets``
(byte ``t + 1``) shifted left by ``i``, a row's last ``i`` positions having no
such byte and being left out; the loss is the mean over heads of each head's
mean cross-entropy (`multi_byte_losses`).

Each half of each block is rematerialised on its own (`nn.remat`).  Scopes
for the device trace (`jax.named_scope`): ``rope`` (both rotations),
``eva_summary``, ``eva_attn`` and inside it ``eva_local`` (from
`ops.eva_attention`), ``head_loss``.  `make_evabyte_loss` is an aux-style
loss for `MPI_PS.compile_step(loss, has_aux=True, aux=evabyte_aux(model))`;
under ``aux["counters"]`` leave the step ``eva_remote_mass`` (``[n_layers]``:
the share of the normaliser the summaries hold, `ops.eva_attention`) and
``mbp_loss`` (``[n_pred_heads]``: each head's loss).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.eva_attention import eva_attention
from .kimi_linear import rotate
from .moe import SwiGLU, bias_free_dense as _dense


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    """The sizes of one EvaByte model (or one chip's share of one stage)."""

    vocab_size: int
    d_model: int
    d_ff: int
    n_layers: int
    n_heads: int                 # heads held here, not d_model / head_dim
    head_dim: int
    window: int
    chunk: int
    n_pred_heads: int = 8
    rope_theta: float = 100000.0
    eps: float = 1e-5
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.window % self.chunk:
            raise ValueError(f"a window of {self.window} bytes is not whole "
                             f"chunks of {self.chunk}")


class UnitOffsetRMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) (1 + g)`` in f32, handed on in ``dtype``."""

    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                       jnp.float32)
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
        return (x * (1.0 + g)).astype(self.dtype)


def _pooling_init(scale: float):
    """N(0, 1) clipped to +-1, times the softmax scale."""
    def init(key, shape, dtype=jnp.float32):
        return jnp.clip(jax.random.normal(key, shape, dtype), -1.0, 1.0) \
            * scale
    return init


class EvaAttention(nn.Module):
    """``(u [B, S, d_model], positions [B, S]) -> (W_o [o^1 .. o^H], remote
    mass)``; ``attn`` is `ops.eva_attention.eva_attention` with its ``impl``
    bound."""

    cfg: EvaByteConfig
    attn: Callable

    @nn.compact
    def __call__(self, u, positions):
        c = self.cfg
        b, s, _ = u.shape
        h, d = c.n_heads, c.head_dim
        heads = lambda name: _dense(h * d, c.dtype, name)(u).reshape(
            b, s, h, d)
        q, k, v = heads("q_proj"), heads("k_proj"), heads("v_proj")
        with jax.named_scope("rope"):
            q = rotate(q, positions, c.rope_theta)
            k = rotate(k, positions, c.rope_theta)
        scale = d ** -0.5
        phi = self.param("phi", _pooling_init(scale), (h, d), jnp.float32)
        mu = self.param("mu", _pooling_init(scale), (h, d), jnp.float32)
        o, mass = self.attn(q, k, v, phi, mu, window=c.window, chunk=c.chunk,
                            scale=scale)
        return _dense(c.d_model, c.dtype, "o_proj")(
            o.reshape(b, s, h * d)), mass


class MultiByteHead(nn.Module):
    """``y -> y W`` with ``W: [d_model, features]`` f32 read in ``dtype`` and
    the product left in f32 (the logits are never rounded to ``dtype``)."""

    features: int
    dtype: Any

    @nn.compact
    def __call__(self, y):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (y.shape[-1], self.features), jnp.float32)
        return jnp.dot(y, kernel.astype(self.dtype),
                       preferred_element_type=jnp.float32)


def _attn_part(block: "EvaByteBlock", x, positions):
    y, mass = block.attn(block.attn_norm(x), positions)
    return x + y.astype(jnp.float32), mass


def _mlp_part(block: "EvaByteBlock", x):
    return x + block.mlp(block.mlp_norm(x)).astype(jnp.float32)


class EvaByteBlock(nn.Module):
    """One layer; each half rematerialised on its own, as
    `models.kimi_linear.DecoderBlock`."""

    cfg: EvaByteConfig
    attn_fn: Callable

    def setup(self):
        c = self.cfg
        self.attn_norm = UnitOffsetRMSNorm(c.eps, c.dtype)
        self.mlp_norm = UnitOffsetRMSNorm(c.eps, c.dtype)
        self.attn = EvaAttention(c, self.attn_fn)
        self.mlp = SwiGLU(c.d_ff, c.dtype)

    def __call__(self, x, positions):
        x, mass = nn.remat(_attn_part)(self, x, positions)
        return nn.remat(_mlp_part)(self, x), mass


class EvaByteLM(nn.Module):
    """``__call__(tokens, positions=None) -> (logits [B, S, n_pred_heads, V]
    f32, remote mass [n_layers] f32)``.  ``attn(q, k, v, phi, mu, window=,
    chunk=, scale=)`` is `eva_attention`; the default is its plain form."""

    cfg: EvaByteConfig
    attn: Callable = functools.partial(eva_attention, impl="dense")

    @nn.compact
    def __call__(self, tokens, positions=None):
        c = self.cfg
        b, s = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                         (b, s))
        x = nn.Embed(c.vocab_size, c.d_model, dtype=jnp.float32,
                     param_dtype=jnp.float32,
                     embedding_init=nn.initializers.normal(1.0),
                     name="tok_embed")(tokens)
        masses = []
        for i in range(c.n_layers):
            x, mass = EvaByteBlock(c, self.attn, name=f"block_{i}")(
                x, positions)
            masses.append(mass)
        with jax.named_scope("head_loss"):
            y = UnitOffsetRMSNorm(c.eps, c.dtype, name="final_norm")(x)
            logits = MultiByteHead(c.n_pred_heads * c.vocab_size, c.dtype,
                                   name="lm_head")(y)
        return logits.reshape(b, s, c.n_pred_heads, c.vocab_size), \
            jnp.stack(masses)


def multi_byte_losses(logits, targets):
    """``logits: [B, S, P, V]`` f32, ``targets: [B, S]`` (byte ``t + 1`` at
    position ``t``) -> ``[P]``: head ``i``'s mean cross-entropy against byte
    ``t + 1 + i`` over the ``S - i`` positions of a row that have one."""
    b, s, p, _ = logits.shape
    ahead = jnp.arange(p)
    shifted = jnp.stack([jnp.roll(targets, -i, axis=1) for i in range(p)],
                        axis=-1)                                  # [B, S, P]
    has_one = jnp.arange(s)[:, None] < s - ahead[None, :]         # [S, P]
    ll = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                             shifted[..., None], axis=-1)[..., 0]
    return -jnp.sum(ll * has_one, axis=(0, 1)) / (b * (s - ahead))


def evabyte_aux(model: EvaByteLM) -> dict:
    """The aux tree `make_evabyte_loss` threads through the step."""
    c = model.cfg
    return {"counters": {
        "eva_remote_mass": np.zeros((c.n_layers,), np.float32),
        "mbp_loss": np.zeros((c.n_pred_heads,), np.float32)}}


def make_evabyte_loss(model: EvaByteLM):
    """``loss_fn(params, aux, batch) -> (mean over heads of each head's
    cross-entropy, new_aux)`` from the `lm_batch` dict alone;
    ``new_aux["counters"]``: ``eva_remote_mass`` and ``mbp_loss``."""
    from ..utils.flatten import unflatten_params

    def loss_fn(params_named, aux, batch):
        del aux
        logits, mass = model.apply(
            {"params": unflatten_params(params_named)}, batch["tokens"],
            batch["positions"])
        with jax.named_scope("head_loss"):
            per_head = multi_byte_losses(logits, batch["targets"])
            loss = jnp.mean(per_head)
        return loss, {"counters": jax.lax.stop_gradient({
            "eva_remote_mass": mass, "mbp_loss": per_head})}

    return loss_fn
