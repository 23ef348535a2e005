"""Decoder-only transformer LM — the long-context model family.

The attention implementation is pluggable (``attn=``): `dense_attention` for
single-device / batch-only parallelism, or `ring_attention` bound to a mesh
axis for sequence parallelism — everything else in the block (QKV/out
projections, MLP, LayerNorm, embeddings) is position-local, so the same
module runs unchanged inside a ``(dp, sp)``-sharded SPMD step: shard the
sequence dim, pass sequence-sharded ``positions``, and attention is the only
op that communicates.

**Tensor parallelism** (``tp_axis=``) shards the *compute* Megatron-style:
Q/K/V projections are column-parallel (each tp rank owns a contiguous block
of heads), the output projection and the MLP's second matmul are
row-parallel with a closing ``psum``; the MLP's first matmul is
column-parallel.  Parameter *storage* stays replicated — the PS design
(reference constraint: model fits on one device, `README.md:5-8`) — so tp
divides MXU work and activation memory per device, not param memory.  Each
rank dynamic-slices its block out of the replicated kernel.

Gradient bookkeeping (why this composes with the PS optimizer unchanged):
inside the step every rank's loss value is replicated, and the transpose of
the row-parallel ``psum`` is itself a psum — so each rank's backward yields
cotangents scaled ×tp on every path through the tp region (sliced blocks
and replicated-compute params alike).  The PS layer's mean over non-data
mesh axes cancels that factor exactly; per-parameter gradients were
verified to match the dense model to float32 noise.

Pre-LN blocks, learned positional embeddings, bf16-friendly (params in f32,
matmuls honoring ``dtype`` so the MXU sees bf16).
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.ring_attention import dense_attention


class PDense(nn.Module):
    """Dense layer with optional tensor-parallel execution.

    ``mode=None``: plain ``x @ kernel + bias``.
    ``mode='column'``: returns only this tp rank's block of output features.
    ``mode='row'``: consumes this rank's input block, ``psum``s partials
    across tp, adds the (unsharded) bias once.
    Same parameter shapes/names in every mode — checkpoints and weight
    transfer are tp-degree-independent.
    """

    features: int
    dtype: jnp.dtype = jnp.float32
    use_bias: bool = True

    @nn.compact
    def __call__(self, x, *, tp_axis: str | None = None,
                 mode: str | None = None, in_features: int | None = None):
        d_in = in_features if in_features is not None else x.shape[-1]
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (d_in, self.features), jnp.float32)
        bias = (self.param("bias", nn.initializers.zeros, (self.features,),
                           jnp.float32) if self.use_bias else None)
        kernel = kernel.astype(self.dtype)
        x = x.astype(self.dtype)

        if tp_axis is None or mode is None:
            y = x @ kernel
            return y + bias.astype(self.dtype) if bias is not None else y

        t = lax.axis_index(tp_axis)
        n = lax.axis_size(tp_axis)
        if mode == "column":
            if self.features % n:
                raise ValueError(
                    f"features {self.features} not divisible by tp={n}")
            blk = self.features // n
            k = lax.dynamic_slice_in_dim(kernel, t * blk, blk, 1)
            y = x @ k
            if bias is not None:
                b = lax.dynamic_slice_in_dim(bias, t * blk, blk, 0)
                y = y + b.astype(self.dtype)
            return y
        if mode == "row":
            if d_in % n:
                raise ValueError(f"in_features {d_in} not divisible by tp={n}")
            blk = d_in // n
            k = lax.dynamic_slice_in_dim(kernel, t * blk, blk, 0)
            y = lax.psum(x @ k, tp_axis)
            # Bias is added once, post-psum (outside the tp region).
            return y + bias.astype(self.dtype) if bias is not None else y
        raise ValueError(f"unknown tp mode {mode!r}")


class Block(nn.Module):
    d_model: int
    n_heads: int
    d_ff: int
    dtype: jnp.dtype
    attn: Callable
    tp_axis: str | None = None
    moe_experts: int = 0           # >0 replaces the MLP with a MoE layer
    moe_capacity: float = 1.25
    ep_axis: str | None = None     # expert-parallel mesh axis

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        h = self.n_heads
        dh = self.d_model // h
        tp = self.tp_axis
        n = lax.axis_size(tp) if tp else 1
        if h % n:
            raise ValueError(f"n_heads {h} not divisible by tp={n}")
        h_local = h // n
        col = dict(tp_axis=tp, mode="column") if tp else {}
        row = dict(tp_axis=tp, mode="row") if tp else {}

        y = nn.LayerNorm(dtype=self.dtype)(x)
        # One fused QKV GEMM (3*d_model wide — keeps the MXU busy in dense
        # mode) whose columns are laid out per-head as [q|k|v] blocks, so a
        # contiguous column slice of whole heads — what tp 'column' mode
        # takes — stays self-contained.
        qkv = PDense(3 * self.d_model, self.dtype, name="qkv")(y, **col)
        qkv = qkv.reshape(b, s, h_local, 3, dh)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        y = self.attn(q, k, v)
        y = y.reshape(b, s, h_local * dh)
        # Row-parallel output projection closes the tp region with a psum.
        y = PDense(self.d_model, self.dtype, name="out")(
            y, in_features=self.d_model, **row)
        x = x + y

        y = nn.LayerNorm(dtype=self.dtype)(x)
        if self.moe_experts:
            from .moe import MoEMLP

            y, aux_loss = MoEMLP(self.d_model, self.d_ff, self.moe_experts,
                                 self.moe_capacity, self.dtype,
                                 self.ep_axis, name="moe")(y)
            self.sow("losses", "moe_aux", aux_loss)
        else:
            y = PDense(self.d_ff, self.dtype, name="fc1")(y, **col)
            y = nn.gelu(y)
            y = PDense(self.d_model, self.dtype, name="fc2")(
                y, in_features=self.d_ff, **row)
        return x + y


class TransformerLM(nn.Module):
    """``__call__(tokens, positions) -> logits``.

    ``positions`` are **global** position ids: under sequence parallelism
    each device sees only its sequence shard, so positions can't be derived
    from the local shape — the trainer computes them globally and shards
    them alongside the tokens.
    """

    vocab_size: int
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    max_len: int = 2048
    dtype: jnp.dtype = jnp.float32
    attn: Callable = None  # default: causal dense attention
    tp_axis: str | None = None  # tensor-parallel mesh axis (e.g. "tp")
    moe_experts: int = 0        # >0: MoE MLPs (Switch top-1)
    moe_capacity: float = 1.25
    ep_axis: str | None = None  # expert-parallel mesh axis (e.g. "ep")

    @nn.compact
    def __call__(self, tokens, positions=None):
        attn = self.attn
        if attn is None:
            attn = lambda q, k, v: dense_attention(q, k, v, causal=True)
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None, :]
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     name="tok_embed")(tokens)
        x = x + nn.Embed(self.max_len, self.d_model, dtype=self.dtype,
                         name="pos_embed")(positions)
        for i in range(self.n_layers):
            x = Block(self.d_model, self.n_heads, self.d_ff, self.dtype,
                      attn, self.tp_axis, self.moe_experts,
                      self.moe_capacity, self.ep_axis,
                      name=f"block_{i}")(x)
        # The scope the other language models give their final norm, head
        # and cross-entropy (`make_lm_loss` has the other half).
        with jax.named_scope("head_loss"):
            x = nn.LayerNorm(dtype=jnp.float32)(x)
            return nn.Dense(self.vocab_size, dtype=jnp.float32,
                            name="lm_head")(x)


def build_lm(model: TransformerLM, seq_len: int, seed: int = 0):
    """Init → flat named params (PS-API shape), like `models.build_model`."""
    from ..utils.flatten import named_params

    tokens = jnp.zeros((1, seq_len), jnp.int32)
    variables = model.init(jax.random.PRNGKey(seed), tokens)
    return named_params(variables["params"])


def make_lm_loss(model: TransformerLM, *, aux_weight: float = 0.01):
    """Next-token cross-entropy.  ``batch``: ``tokens``/``targets``/
    ``positions``, all ``[B, S]`` — targets pre-shifted *before* any sequence
    sharding, so the shard boundary needs no halo exchange.  MoE models add
    ``aux_weight`` × the Switch load-balance losses sown by each block."""
    from ..utils.flatten import unflatten_params

    moe = bool(getattr(model, "moe_experts", 0))

    def loss_fn(params_named, batch):
        variables = {"params": unflatten_params(params_named)}
        if moe:
            logits, extras = model.apply(
                variables, batch["tokens"], batch["positions"],
                mutable=["losses"])
        else:
            logits = model.apply(variables, batch["tokens"],
                                 batch["positions"])
        with jax.named_scope("head_loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, batch["targets"][..., None],
                                     axis=-1)[..., 0]
            loss = -jnp.mean(ll)
        if moe:
            aux = sum(jax.tree.leaves(extras["losses"]))
            loss = loss + aux_weight * aux
        return loss

    return loss_fn


def lm_batch(tokens: "jnp.ndarray"):
    """Build the {tokens, targets, positions} dict from raw token rows
    ``[B, S+1]`` (global, pre-sharding)."""
    import numpy as np

    tokens = np.asarray(tokens)
    b, s1 = tokens.shape
    return {
        "tokens": tokens[:, :-1].astype(np.int32),
        "targets": tokens[:, 1:].astype(np.int32),
        "positions": np.broadcast_to(np.arange(s1 - 1, dtype=np.int32),
                                     (b, s1 - 1)).copy(),
    }
