"""Kimi-Linear: a decoder that mixes linear attention (KDA) and latent
attention (MLA, no position encoding) layers over a mixture-of-experts MLP.

Every block is ``x += Attn(RMSNorm(x)); x += MLP(RMSNorm(x))``, every
projection bias-free, parameters f32 and matrix products in ``dtype``.  Which
layers are which is a list each (1-indexed, as the published configuration
has them): ``kda_layers`` / the rest MLA, and the first ``first_k_dense``
layers a dense SwiGLU, the rest `models.moe.ShareOfExperts`.  There is no
position embedding anywhere; `positions` is accepted and not read, so that
the model takes the `lm_batch` contract.  The head is untied.

* **KDA** (`KDAttention`): ``q, k, v = SiLU(causal depthwise conv(W x))``,
  q and k L2-normalised per head and q scaled by ``d_k^-1/2``; the log-decay
  of each channel ``g = -exp(A_log) * softplus(W_f2 W_f1 x + dt_bias)``,
  the write strength ``beta = sigmoid(W_b x)``; the recurrence itself is
  the ``kda`` callable (`ops.kda.kda_attention`: Pallas kernels on the chip
  at widths that are whole lane tiles, `ops.kda.kda_chunked` elsewhere);
  the output is ``W_o [RMSNorm(o) * sigmoid(W_g2
  W_g1 x)]``.
* **MLA** (`LatentAttention`): q of ``nope + rope`` columns a head; keys and
  values from one 512-wide latent (RMSNorm'd) plus ``rope`` columns shared by
  all heads, to which **no rotation is applied** here (``rope_theta=None``;
  `models.glm_moe` sets it, and a low-rank q, on the same class); causal
  softmax attention with a q / k width (192) other than the v width (128),
  through the ``attn`` callable (`ops.flash_attention.flash_attention` on
  the chip).

Each half of each block is rematerialised on its own (`nn.remat`).  The device trace can
split a step by layer kind: `jax.named_scope` goes round ``kda`` (the
recurrence, apart from its projections), ``mla`` (the attention call),
``moe`` (router, grouping, expert products, combine, shared expert) and
``head_loss`` (final norm, head, cross-entropy).

`make_kimi_loss` is an aux-style loss for `MPI_PS.compile_step(loss,
has_aux=True, aux=kimi_aux(model))`: the expert load of each MoE layer
leaves the step under ``aux["counters"]`` (see `MPI_PS.step`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.kda import kda_attention
from ..parallel.ring_attention import dense_attention
from .moe import ShareOfExperts, SwiGLU, bias_free_dense as _dense


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log A`` with ``A`` uniform in [1, 16), one a head."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step drawn log-uniformly from [1e-3, 0.1)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, np.log(1e-3),
                                    np.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def causal_conv_silu(x, kernel, bias=None):
    """Depthwise causal convolution over the sequence, then SiLU:
    ``y_t = sum_i kernel[i] * x_{t - (K - 1) + i}`` (``+ bias``, a channel,
    where the layer has one).  ``x: [B, S, C]``, ``kernel: [K, C]``."""
    taps = kernel.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    s = x.shape[1]
    y = sum(padded[:, i:i + s] * kernel[i].astype(x.dtype)
            for i in range(taps))
    if bias is not None:
        y = y + bias.astype(x.dtype)
    return nn.silu(y)


class KDAttention(nn.Module):
    d_model: int
    n_heads: int
    head_dim: int
    conv_size: int
    gate_rank: int
    eps: float
    dtype: jnp.dtype
    kda: Callable = kda_attention

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        h, dk = self.n_heads, self.head_dim
        width = h * dk
        heads = lambda y: y.reshape(b, s, h, dk)

        def conv_proj(name):
            kernel = self.param(
                name + "_conv", nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (self.conv_size, width), jnp.float32)
            return heads(causal_conv_silu(
                _dense(width, self.dtype, name + "_proj")(x), kernel))

        def unit(y):
            y = y.astype(jnp.float32)
            return y * jax.lax.rsqrt(
                jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)

        q, k, v = conv_proj("q"), conv_proj("k"), conv_proj("v")
        q = (unit(q) * dk ** -0.5).astype(self.dtype)
        k = unit(k).astype(self.dtype)
        a_log = self.param("A_log", _a_log_init, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (width,), jnp.float32)
        decay_in = _dense(width, self.dtype, "f_b")(
            _dense(self.gate_rank, self.dtype, "f_a")(x))
        g = -jnp.exp(a_log)[:, None] * heads(jax.nn.softplus(
            decay_in.astype(jnp.float32) + dt_bias))
        beta = jax.nn.sigmoid(
            _dense(h, self.dtype, "b_proj")(x).astype(jnp.float32))
        with jax.named_scope("kda"):
            o = self.kda(q, k, v, g, beta)
        o = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype,
                       param_dtype=jnp.float32, name="o_norm")(o)
        gate = _dense(width, self.dtype, "g_b")(
            _dense(self.gate_rank, self.dtype, "g_a")(x))
        o = o.reshape(b, s, width) * jax.nn.sigmoid(gate)
        return _dense(self.d_model, self.dtype, "o_proj")(o)


def rotate(x, positions, theta: float):
    """Rotary position embedding over all of the last axis, pairing column
    ``i`` with column ``i + d/2`` (split halves): the pair turns by the angle
    ``pos * theta^(-2i/d)``.  ``x: [B, S, d]`` or ``[B, S, H, d]``,
    ``positions: [B, S]``; angles, sines and the products in f32."""
    half = x.shape[-1] // 2
    inv_freq = jnp.asarray(
        theta ** (-np.arange(half, dtype=np.float64) / half), jnp.float32)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = (y.astype(jnp.float32) for y in jnp.split(x, 2, axis=-1))
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention.  ``q_lora_rank`` None: one full-rank
    ``q_proj``; a rank: ``q_b_proj(RMSNorm(q_a_proj x))``.  ``rope_theta``
    None: no position enters (NoPE, `positions` unread); a base: the
    ``rope`` columns of q (a head) and the one shared rope key are rotated
    by `positions` (`rotate`), the key before it is broadcast to the heads."""

    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    eps: float
    dtype: jnp.dtype
    attn: Callable
    q_lora_rank: "int | None" = None
    rope_theta: "float | None" = None

    @nn.compact
    def __call__(self, x, positions=None):
        b, s, _ = x.shape
        h, nope, rope, dv = (self.n_heads, self.qk_nope_dim,
                             self.qk_rope_dim, self.v_dim)
        norm = lambda name: nn.RMSNorm(epsilon=self.eps, dtype=self.dtype,
                                       param_dtype=jnp.float32, name=name)
        if self.q_lora_rank is None:
            q = _dense(h * (nope + rope), self.dtype, "q_proj")(x)
        else:
            q = _dense(h * (nope + rope), self.dtype, "q_b_proj")(
                norm("q_a_norm")(
                    _dense(self.q_lora_rank, self.dtype, "q_a_proj")(x)))
        q = q.reshape(b, s, h, nope + rope)
        kv_a = _dense(self.kv_lora_rank + rope, self.dtype, "kv_a_proj")(x)
        latent, k_pe = kv_a[..., :self.kv_lora_rank], \
            kv_a[..., self.kv_lora_rank:]
        latent = norm("kv_a_norm")(latent)
        kv = _dense(h * (nope + dv), self.dtype, "kv_b_proj")(latent)
        kv = kv.reshape(b, s, h, nope + dv)
        if self.rope_theta is not None:
            with jax.named_scope("rope"):
                q = jnp.concatenate(
                    [q[..., :nope],
                     rotate(q[..., nope:], positions, self.rope_theta)],
                    axis=-1)
                k_pe = rotate(k_pe, positions, self.rope_theta)
        # The "rope" columns of k are one key shared by the heads.
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, rope))], axis=-1)
        with jax.named_scope("mla"):
            o = self.attn(q, k, kv[..., nope:])
        return _dense(self.d_model, self.dtype, "o_proj")(
            o.reshape(b, s, h * dv))


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The sizes of one Kimi-Linear model (or one chip's share of one)."""

    vocab_size: int
    d_model: int
    n_layers: int
    kda_layers: "tuple[int, ...]"      # 1-indexed; every other layer is MLA
    first_k_dense: int
    d_ff: int
    d_expert: int
    n_experts: int                     # published: the router's width
    experts_held: "tuple[int, ...]"
    top_k: int
    n_shared: int
    routed_scale: float
    n_heads: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    kda_heads: int
    kda_head_dim: int
    conv_size: int
    gate_rank: int = 128
    eps: float = 1e-5
    dtype: Any = jnp.float32
    q_lora_rank: "int | None" = None    # the MLA layers' q is full-rank
    rope_theta: "float | None" = None   # ... and they see no position

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense


def _attn_part(block: "DecoderBlock", x, positions):
    y = block.attn_norm(x)
    return x + (block.attn(y) if block.linear else block.attn(y, positions))


def _mlp_part(block: "DecoderBlock", x):
    y = block.mlp_norm(x)
    if block.dense:
        return x + block.mlp(y), None
    with jax.named_scope("moe"):
        y, load = block.moe(y)
    return x + y, load


class DecoderBlock(nn.Module):
    """``x += Attn(RMSNorm(x)); x += MLP(RMSNorm(x))``, each half
    rematerialised on its own: the backward pass holds the activations of
    one half of one block at a time, and the block's input.  The one block
    of `KimiLinearLM` and of `models.glm_moe.GlmMoeLM`: ``cfg`` is either
    model's configuration, read for the sizes of the kinds of layer this
    block is (the KDA sizes only where ``linear``)."""

    cfg: Any
    attn_fn: Callable
    linear: bool     # KDA, else MLA
    dense: bool      # a dense SwiGLU, else the expert layer
    kda_fn: Callable = kda_attention

    def setup(self):
        c = self.cfg
        norm = lambda: nn.RMSNorm(epsilon=c.eps, dtype=c.dtype,
                                  param_dtype=jnp.float32)
        self.attn_norm, self.mlp_norm = norm(), norm()
        if self.linear:
            self.attn = KDAttention(
                c.d_model, c.kda_heads, c.kda_head_dim, c.conv_size,
                c.gate_rank, c.eps, c.dtype, self.kda_fn)
        else:
            self.attn = LatentAttention(
                c.d_model, c.n_heads, c.kv_lora_rank, c.qk_nope_dim,
                c.qk_rope_dim, c.v_dim, c.eps, c.dtype, self.attn_fn,
                c.q_lora_rank, c.rope_theta)
        if self.dense:
            self.mlp = SwiGLU(c.d_ff, c.dtype)
        else:
            self.moe = ShareOfExperts(
                c.d_model, c.d_expert, c.n_experts, tuple(c.experts_held),
                c.top_k, c.routed_scale, c.d_expert * c.n_shared, c.dtype)

    def __call__(self, x, positions=None):
        x = nn.remat(_attn_part)(self, x, positions)
        return nn.remat(_mlp_part)(self, x)


class KimiLinearLM(nn.Module):
    """``__call__(tokens, positions=None) -> (logits [B, S, V] f32, load)``;
    ``load`` is ``[MoE layers, len(experts_held) + 1]`` (see
    `ShareOfExperts`)."""

    cfg: KimiLinearConfig
    attn: Callable = None              # default: causal dense attention
    kda: Callable = kda_attention      # the recurrence of the KDA layers

    @nn.compact
    def __call__(self, tokens, positions=None):
        del positions   # no position encoding in any layer
        c = self.cfg
        attn = self.attn
        if attn is None:
            attn = lambda q, k, v: dense_attention(q, k, v, causal=True)
        x = nn.Embed(c.vocab_size, c.d_model, dtype=c.dtype,
                     param_dtype=jnp.float32,
                     embedding_init=nn.initializers.normal(1.0),
                     name="tok_embed")(tokens)
        loads = []
        for i in range(c.n_layers):
            x, load = DecoderBlock(c, attn, linear=(i + 1) in c.kda_layers,
                                   dense=i < c.first_k_dense, kda_fn=self.kda,
                                   name=f"block_{i}")(x)
            if load is not None:
                loads.append(load)
        with jax.named_scope("head_loss"):
            x = nn.RMSNorm(epsilon=c.eps, dtype=c.dtype,
                           param_dtype=jnp.float32, name="final_norm")(x)
            logits = _dense(c.vocab_size, c.dtype, "lm_head")(x) \
                .astype(jnp.float32)
        return logits, jnp.stack(loads)


def kimi_aux(model: KimiLinearLM) -> dict:
    """The aux tree `make_kimi_loss` threads through the step."""
    c = model.cfg
    return {"counters": {"moe_load": np.zeros(
        (c.n_moe_layers, len(c.experts_held) + 1), np.float32)}}


def make_kimi_loss(model: KimiLinearLM):
    """Next-token cross-entropy as ``loss_fn(params, aux, batch) -> (loss,
    new_aux)``; ``new_aux["counters"]["moe_load"]`` is this step's expert
    load, per MoE layer."""
    from ..utils.flatten import unflatten_params

    def loss_fn(params_named, aux, batch):
        del aux
        logits, load = model.apply(
            {"params": unflatten_params(params_named)}, batch["tokens"],
            batch["positions"])
        with jax.named_scope("head_loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, batch["targets"][..., None],
                                     axis=-1)[..., 0]
            loss = -jnp.mean(ll)
        return loss, {"counters": {
            "moe_load": jax.lax.stop_gradient(load)}}

    return loss_fn
