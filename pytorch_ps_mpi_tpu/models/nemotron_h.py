"""Nemotron-H (``nemotron_h``; Nemotron-3-Nano): a hybrid decoder whose
every layer is **one part alone**, chosen by the layer's letter in
``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``E`` a mixture of
experts, ``*`` grouped-query attention.

Every block is ``x += Part(RMSNorm(x))``, the residual in ``dtype``
(``residual_in_fp32`` false), every projection bias-free, parameters f32
and matrix products in ``dtype``; after the last block ``RMSNorm`` and an
untied head.  No position enters anywhere (`positions` is accepted and not
read, so that the model takes the `lm_batch` contract): the family's
attention applies no rotation.

* ``M`` (`Mamba2Mixer`): ``[z | xBC | dt] = W_in u``; ``xBC = silu(conv(xBC)
  + b)`` (causal, depthwise, `models.kimi_linear.causal_conv_silu`); ``xBC``
  splits into ``x`` (``H`` heads of ``P``), ``B`` and ``C`` (``G`` groups of
  ``N``); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; ``y``
  the state-space duality scan of `ops.ssd.ssd` (``dt``, the decays and the
  state f32; head ``h`` reads group ``h // (H / G)``); ``out = W_out
  GroupGatedRMSNorm(y, z)``.
* ``E`` (`models.moe.ShareOfExperts` with ``act="relu2"``): sigmoid router
  over the published expert count, selection bias, top-k renormalised and
  scaled, experts and a shared expert ``down(relu(up x)^2)``.
* ``*`` (`GQAttention`): ``q`` of ``n_heads``, ``k`` and ``v`` of
  ``n_kv_heads`` heads, each kv head repeated over its ``n_heads /
  n_kv_heads`` query heads here, causal softmax through the ``attn``
  callable (`ops.flash_attention.flash_attention` on the chip).

Each block is rematerialised (`nn.remat`).  Scopes for the device trace
(`jax.named_scope`): ``mamba`` (the whole mixer: projections, convolution,
scan and gated norm), ``ssd`` inside it (the scan alone), ``moe`` (the expert
layer whole), ``attn`` (the attention layer whole), ``head_loss``.
`make_nemotron_loss` is an aux-style loss for `MPI_PS.compile_step(loss,
has_aux=True, aux=nemotron_aux(model))`: the expert load of each ``E`` layer
(``moe_load``) and the carried share of each ``M`` layer (``ssd_carry``,
`ops.ssd.carried_share`) leave the step under ``aux["counters"]``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.ssd import carried_share, ssd
from ..parallel.ring_attention import dense_attention
from .kimi_linear import _a_log_init, _dt_bias_init, causal_conv_silu
from .moe import ShareOfExperts, bias_free_dense as _dense

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The sizes of one Nemotron-H model (or one chip's share of one)."""

    vocab_size: int
    d_model: int
    pattern: str                       # a letter a layer: M, E or *
    d_expert: int
    d_shared: int
    n_experts: int                     # published: the router's width
    experts_held: "tuple[int, ...]"
    top_k: int
    routed_scale: float
    n_heads: int
    n_kv_heads: int
    head_dim: int
    mamba_heads: int
    mamba_head_dim: int
    n_groups: int
    d_state: int
    d_conv: int = 4
    chunk: int = 128
    eps: float = 1e-5
    dtype: Any = jnp.float32

    def __post_init__(self):
        if not set(self.pattern) <= set(KINDS):
            raise ValueError(f"layer letters {self.pattern!r}: know "
                             f"{''.join(KINDS)}")
        if self.mamba_heads % self.n_groups or self.n_heads % self.n_kv_heads:
            raise ValueError("groups divide the Mamba heads and kv heads "
                             "divide the query heads")

    @property
    def kinds(self) -> "tuple[str, ...]":
        return tuple(KINDS[letter] for letter in self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    def count(self, kind: str) -> int:
        return self.kinds.count(kind)


class GroupGatedRMSNorm(nn.Module):
    """``RMSNorm(y * silu(z)) * w`` with the statistics taken over groups of
    ``group`` channels (Mamba-2's gated norm, the gate before the norm), in
    f32, the result in ``dtype``."""

    group: int
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, y, z):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],),
                           jnp.float32)
        y = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
        g = y.reshape(*y.shape[:-1], -1, self.group)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                              + self.eps)
        return (g.reshape(y.shape) * scale).astype(self.dtype)


class Mamba2Mixer(nn.Module):
    """``u -> (W_out GroupGatedRMSNorm(y, z), carried share)``: Mamba-2
    around `ops.ssd.ssd`."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        b, s, _ = u.shape
        h, p, g, n = c.mamba_heads, c.mamba_head_dim, c.n_groups, c.d_state
        conv_dim = c.d_inner + 2 * g * n
        proj = _dense(c.d_inner + conv_dim + h, c.dtype, "in_proj")(u)
        z, xbc, dt = jnp.split(proj, [c.d_inner, c.d_inner + conv_dim],
                               axis=-1)
        kernel = self.param("conv", nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=0, out_axis=1),
            (c.d_conv, conv_dim), jnp.float32)
        conv_bias = self.param("conv_bias", nn.initializers.zeros,
                               (conv_dim,), jnp.float32)
        xbc = causal_conv_silu(xbc, kernel, conv_bias)
        x, bb, cc = jnp.split(xbc, [c.d_inner, c.d_inner + g * n], axis=-1)
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,), jnp.float32)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        a = -jnp.exp(self.param("A_log", _a_log_init, (h,), jnp.float32))
        skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        with jax.named_scope("ssd"):
            y = ssd(x.reshape(b, s, h, p), dt, a, bb.reshape(b, s, g, n),
                    cc.reshape(b, s, g, n), skip, chunk=c.chunk)
        y = GroupGatedRMSNorm(c.d_inner // g, c.eps, c.dtype, name="norm")(
            y.reshape(b, s, c.d_inner), z)
        carry = jax.lax.stop_gradient(carried_share(dt, a, chunk=c.chunk))
        return _dense(c.d_model, c.dtype, "out_proj")(y), carry


class GQAttention(nn.Module):
    """``u -> W_o attn(W_q u, W_k u, W_v u)``, ``n_kv_heads`` heads of keys
    and values, kv head ``i`` repeated over query heads ``i r`` to ``i r + r
    - 1`` (``r = n_heads / n_kv_heads``) before the call.  ``attn(q, k, v)``
    is causal attention over ``[B, S, H, D]`` scaled by ``D^-1/2``."""

    cfg: NemotronHConfig
    attn: Callable

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        b, s, _ = u.shape
        h, hk, d = c.n_heads, c.n_kv_heads, c.head_dim
        q = _dense(h * d, c.dtype, "q_proj")(u).reshape(b, s, h, d)
        k, v = (jnp.repeat(_dense(hk * d, c.dtype, name)(u).reshape(
            b, s, hk, d), h // hk, axis=2) for name in ("k_proj", "v_proj"))
        o = self.attn(q, k, v)
        return _dense(c.d_model, c.dtype, "o_proj")(o.reshape(b, s, h * d))


def _part(block: "NemotronHBlock", x):
    """``(x + Part(RMSNorm(x)), what the part counts)``, the part under the
    scope of its kind's name."""
    y = block.norm(x)
    with jax.named_scope(block.kind):
        out = block.mixer(y)
    y, counted = (out, None) if block.kind == "attn" else out
    return x + y, counted


class NemotronHBlock(nn.Module):
    """One layer of ``kind``, rematerialised whole: the backward pass holds
    one block's activations at a time, and the block's input."""

    cfg: NemotronHConfig
    kind: str
    attn_fn: Callable

    def setup(self):
        c = self.cfg
        self.norm = nn.RMSNorm(epsilon=c.eps, dtype=c.dtype,
                               param_dtype=jnp.float32)
        if self.kind == "mamba":
            self.mixer = Mamba2Mixer(c)
        elif self.kind == "moe":
            self.mixer = ShareOfExperts(
                c.d_model, c.d_expert, c.n_experts, tuple(c.experts_held),
                c.top_k, c.routed_scale, c.d_shared, c.dtype, act="relu2")
        else:
            self.mixer = GQAttention(c, self.attn_fn)

    def __call__(self, x):
        return nn.remat(_part)(self, x)


class NemotronHLM(nn.Module):
    """``__call__(tokens, positions=None) -> (logits [B, S, V] f32, load,
    carry)``: ``load`` is ``[E layers, len(experts_held) + 1]`` (see
    `ShareOfExperts`), ``carry`` ``[M layers]``."""

    cfg: NemotronHConfig
    attn: Callable = None              # default: causal dense attention

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
        counted = {"mamba": [], "moe": [], "attn": []}
        for i, kind in enumerate(c.kinds):
            x, count = NemotronHBlock(c, kind, attn, name=f"block_{i}")(x)
            counted[kind].append(count)
        with jax.named_scope("head_loss"):
            x = nn.RMSNorm(epsilon=c.eps, dtype=c.dtype,
                           param_dtype=jnp.float32, name="final_norm")(x)
            logits = _dense(c.vocab_size, c.dtype, "lm_head")(x) \
                .astype(jnp.float32)
        stack = lambda v, shape: jnp.stack(v) if v else jnp.zeros(shape)
        return (logits,
                stack(counted["moe"], (0, len(c.experts_held) + 1)),
                stack(counted["mamba"], (0,)))


def nemotron_aux(model: NemotronHLM) -> dict:
    """The aux tree `make_nemotron_loss` threads through the step."""
    c = model.cfg
    return {"counters": {
        "moe_load": np.zeros((c.count("moe"), len(c.experts_held) + 1),
                             np.float32),
        "ssd_carry": np.zeros((c.count("mamba"),), np.float32)}}


def make_nemotron_loss(model: NemotronHLM):
    """Next-token cross-entropy as ``loss_fn(params, aux, batch) -> (loss,
    new_aux)``; ``new_aux["counters"]``: ``moe_load`` (each expert layer's
    load) and ``ssd_carry`` (each Mamba layer's carried share)."""
    from ..utils.flatten import unflatten_params

    def loss_fn(params_named, aux, batch):
        del aux
        logits, load, carry = model.apply(
            {"params": unflatten_params(params_named)}, batch["tokens"],
            batch["positions"])
        with jax.named_scope("head_loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, batch["targets"][..., None],
                                     axis=-1)[..., 0]
            loss = -jnp.mean(ll)
        return loss, {"counters": jax.lax.stop_gradient(
            {"moe_load": load, "ssd_carry": carry})}

    return loss_fn
