"""GLM-4.7-Flash (``glm4_moe_lite``): a decoder of rotary latent-attention
layers over a mixture of experts, with a multi-token-prediction module.

Every block is `models.kimi_linear.DecoderBlock` (``x += Attn(RMSNorm(x));
x += MLP(RMSNorm(x))``, each half rematerialised), every projection
bias-free, parameters f32 and matrix products in ``dtype``:

* **Attention**: `models.kimi_linear.LatentAttention` with a low-rank q
  (``q_b(RMSNorm(q_a x))``) and rotary positions: the ``rope`` columns of q
  and the one rope key the heads share are rotated by `positions` (base
  ``rope_theta``, split halves, f32); q / k are ``nope + rope`` wide and v
  ``v_dim`` wide, through the ``attn`` callable
  (`ops.flash_attention.flash_attention` on the chip).
* **MLP**: the first ``first_k_dense`` layers a dense `SwiGLU`, the rest
  `models.moe.ShareOfExperts` (sigmoid router over the published expert
  count, selection bias, top-k renormalised and scaled, a shared expert).
* **Multi-token prediction** (`MTPModule`, DeepSeek-V3 section 2.2): at
  position ``i`` the main model's last hidden state ``h_i`` (before
  ``final_norm``) and the embedding of the next token ``t_{i+1}`` go through
  ``W_eh [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)]``, one more block at the
  same positions and a norm of its own, then **the main model's head**;
  ``Emb`` is **the main model's embedding**.  It predicts ``t_{i+2}``.

`make_glm_loss` is an aux-style loss for `MPI_PS.compile_step(loss,
has_aux=True, aux=glm_aux(model))`: ``loss_main + mtp_weight * loss_mtp``
from the `lm_batch` dict alone (``t_{i+1}`` is ``targets[i]``, ``t_{i+2}``
is ``targets[i+1]``, and a row's last position, which has none, carries no
MTP loss).  Both partial losses and the expert load of every expert layer
(the MTP block's last) leave the step under ``aux["counters"]``.

Scopes for the device trace: ``mla`` and ``rope`` (inside
`LatentAttention`), ``moe``, ``head_loss`` (final norm, head and
cross-entropy of the main model) and ``mtp`` (everything of the module:
``eh_proj``, its block, its norm, its pass through the head and its
cross-entropy — so the module's attention is under ``mla`` and ``mtp``, its
expert layer under ``moe`` and ``mtp``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.ring_attention import dense_attention
from .kimi_linear import DecoderBlock
from .moe import bias_free_dense as _dense


@dataclasses.dataclass(frozen=True)
class GlmMoeConfig:
    """The sizes of one GLM-MoE model (or one chip's share of one)."""

    vocab_size: int
    d_model: int
    n_layers: int                      # main layers; the MTP block is extra
    first_k_dense: int
    d_ff: int
    d_expert: int
    n_experts: int                     # published: the router's width
    experts_held: "tuple[int, ...]"
    top_k: int
    n_shared: int
    routed_scale: float
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    rope_theta: float
    n_mtp: int = 1                     # multi-token-prediction modules: 0, 1
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense + self.n_mtp


class MTPModule(nn.Module):
    """``(Emb(t_{i+1}), h_i, positions) -> (hidden state before the head,
    the block's expert load)``."""

    cfg: GlmMoeConfig
    attn_fn: Callable

    @nn.compact
    def __call__(self, next_embedded, hidden, positions):
        c = self.cfg
        norm = lambda name: nn.RMSNorm(epsilon=c.eps, dtype=c.dtype,
                                       param_dtype=jnp.float32, name=name)
        x = _dense(c.d_model, c.dtype, "eh_proj")(jnp.concatenate(
            [norm("enorm")(next_embedded), norm("hnorm")(hidden)], axis=-1))
        x, load = DecoderBlock(c, self.attn_fn, linear=False, dense=False,
                               name="block")(x, positions)
        return norm("final_norm")(x), load


class GlmMoeLM(nn.Module):
    """``__call__(tokens, positions, next_tokens) -> (logits, mtp_logits,
    load)``: both logits ``[B, S, V]`` f32 through the one ``lm_head``
    (``mtp_logits`` None without an MTP module); ``load`` is ``[expert
    layers, len(experts_held) + 1]``, the MTP block's row last."""

    cfg: GlmMoeConfig
    attn: Callable = None              # default: causal dense attention

    @nn.compact
    def __call__(self, tokens, positions, next_tokens=None):
        c = self.cfg
        attn = self.attn
        if attn is None:   # scaled by the q / k width, nope + rope
            attn = lambda q, k, v: dense_attention(q, k, v, causal=True)
        embed = nn.Embed(c.vocab_size, c.d_model, dtype=c.dtype,
                         param_dtype=jnp.float32,
                         embedding_init=nn.initializers.normal(1.0),
                         name="tok_embed")
        head = _dense(c.vocab_size, c.dtype, "lm_head")
        x = embed(tokens)
        loads = []
        for i in range(c.n_layers):
            x, load = DecoderBlock(c, attn, linear=False,
                                   dense=i < c.first_k_dense,
                                   name=f"block_{i}")(x, positions)
            if load is not None:
                loads.append(load)
        with jax.named_scope("head_loss"):
            logits = head(nn.RMSNorm(
                epsilon=c.eps, dtype=c.dtype, param_dtype=jnp.float32,
                name="final_norm")(x)).astype(jnp.float32)
        mtp_logits = None
        if c.n_mtp:
            with jax.named_scope("mtp"):
                y, load = MTPModule(c, attn, name="mtp")(
                    embed(next_tokens), x, positions)
                mtp_logits = head(y).astype(jnp.float32)
            loads.append(load)
        return logits, mtp_logits, jnp.stack(loads)


def glm_aux(model: GlmMoeLM) -> dict:
    """The aux tree `make_glm_loss` threads through the step."""
    c = model.cfg
    zero = np.zeros((), np.float32)
    return {"counters": {
        "moe_load": np.zeros((c.n_moe_layers, len(c.experts_held) + 1),
                             np.float32),
        "loss_main": zero, "loss_mtp": zero}}


def _log_likelihood(logits, targets):
    return jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               targets[..., None], axis=-1)[..., 0]


def make_glm_loss(model: GlmMoeLM, mtp_weight: float = 0.3):
    """``loss_fn(params, aux, batch) -> (loss_main + mtp_weight * loss_mtp,
    new_aux)``.  ``loss_main`` is the next-token cross-entropy over every
    position; ``loss_mtp`` the cross-entropy of the MTP module's prediction
    of the token after next over the positions that have one (all but a
    row's last).  ``new_aux["counters"]``: ``moe_load``, ``loss_main``,
    ``loss_mtp``."""
    from ..utils.flatten import unflatten_params

    def loss_fn(params_named, aux, batch):
        del aux
        targets = batch["targets"]
        logits, mtp_logits, load = model.apply(
            {"params": unflatten_params(params_named)}, batch["tokens"],
            batch["positions"], targets)
        with jax.named_scope("head_loss"):
            loss_main = -jnp.mean(_log_likelihood(logits, targets))
        loss, loss_mtp = loss_main, jnp.zeros((), jnp.float32)
        if mtp_logits is not None:
            with jax.named_scope("mtp"):
                # position i predicts targets[i + 1]; the last has none
                after_next = jnp.roll(targets, -1, axis=1)
                has_one = jnp.arange(targets.shape[1]) < targets.shape[1] - 1
                ll = _log_likelihood(mtp_logits, after_next) * has_one
                loss_mtp = -jnp.sum(ll) / (ll.shape[0] * (ll.shape[1] - 1))
            loss = loss_main + mtp_weight * loss_mtp
        return loss, {"counters": jax.lax.stop_gradient({
            "moe_load": load, "loss_main": loss_main,
            "loss_mtp": loss_mtp})}

    return loss_fn
