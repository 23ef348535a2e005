"""Mixture-of-experts layers: two, for two purposes.

* `MoEMLP` — **Switch top-1 with a capacity and dropped tokens**, two-layer
  GELU experts, an all-to-all over an ``ep`` mesh axis.  It is what the
  expert-parallel tests (`tests/test_moe.py`) and `TransformerLM(moe_experts=
  ...)` drive: small, static shapes, tokens past capacity ride the residual.
  No published model is run through it.
* `ShareOfExperts` — **the share-aware dropless layer real models use**
  (sigmoid router over the published expert count, selection bias, top-k,
  renormalised and scaled, SwiGLU or relu² experts, a shared expert).  It
  is told which experts it holds, routes over all of them, computes its own
  experts' part of the result for the tokens routed to them and drops
  none, whatever the imbalance.  `models.kimi_linear` and `models.glm_moe`
  run on it with SwiGLU experts, `models.nemotron_h` with relu² ones.  Its plan over the ``T * k`` assignments (the count an
  expert, the weights in sorted order) compares, sums and sorts: XLA's TPU
  gather and scatter take scalars one after another.

`MoEMLP`, in detail.  The reference explores ``Ialltoallv`` as a transport primitive
(`/root/reference/test_mpi.py:11-25`) but never builds on it; this layer is
where all-to-all genuinely belongs on TPU: tokens shard over the ``ep`` mesh
axis, each rank owns a slice of the experts, and `lax.all_to_all` carries
each token to its expert's rank and back over ICI.

Static-shape dispatch (XLA-friendly — no data-dependent shapes):

1. top-1 router picks an expert per token; gate = that expert's softmax prob;
2. every expert gets a fixed **capacity** ``C = ceil(T * capacity_factor /
   E)`` slots; a token's slot is its position among same-expert tokens
   (one-hot cumsum), tokens past capacity are *dropped* — they pass through
   on the residual branch only (standard Switch behavior);
3. tokens scatter into a ``[E, C, d]`` dispatch buffer, ride all_to_all to
   their expert's rank, run that expert's 2-layer MLP, ride back, and
   combine scaled by the gate.

Gradient semantics: ``ep`` is a **data** axis (tokens shard over it), so it
belongs in the PS optimizer's ``axis`` tuple — expert-slice gradients live
only on the owning rank and the cross-rank **psum** assembles them; router
and non-expert params get the usual data-parallel sum.  Aux load-balancing
loss (Switch eq. 4) is returned for the trainer to add.
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class MoEMLP(nn.Module):
    """Drop-in MLP replacement: ``[B, S, d] -> ([B, S, d], aux_loss)``.

    ``ep_axis=None`` runs all experts locally (dense MoE); with an axis name
    it must divide ``n_experts`` and the call must be inside ``shard_map``
    with tokens sharded over that axis.
    """

    d_model: int
    d_ff: int
    n_experts: int
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.float32
    ep_axis: str | None = None

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        E = self.n_experts
        T = b * s
        toks = x.reshape(T, d)

        # --- routing (replicated-compute params: plain data-parallel grads)
        wr = self.param("router", nn.initializers.lecun_normal(),
                        (d, E), jnp.float32)
        logits = toks.astype(jnp.float32) @ wr
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)                 # [T]
        gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]

        # Switch load-balance aux loss: E * sum_e (frac_tokens_e * frac_prob_e)
        onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)   # [T, E]
        frac_tokens = onehot.mean(axis=0)
        frac_probs = probs.mean(axis=0)
        aux_loss = E * jnp.sum(frac_tokens * frac_probs)

        # --- capacity + slot assignment (static shapes)
        C = max(1, math.ceil(T * self.capacity_factor / E))
        pos = (jnp.cumsum(onehot, axis=0) - 1.0)            # [T, E]
        pos = jnp.sum(pos * onehot, axis=1)                 # [T] slot in expert
        keep = (pos < C).astype(jnp.float32)
        slot = (expert * C + pos.astype(jnp.int32)).astype(jnp.int32)
        slot = jnp.where(keep > 0, slot, E * C)             # dropped -> bin E*C

        dispatch = jnp.zeros((E * C + 1, d), toks.dtype).at[slot].add(
            (toks * keep[:, None]).astype(toks.dtype))
        dispatch = dispatch[:E * C].reshape(E, C, d)

        # --- expert parameters (replicated storage; sliced per ep rank)
        k1 = self.param("w1", nn.initializers.lecun_normal(),
                        (E, d, self.d_ff), jnp.float32)
        b1 = self.param("b1", nn.initializers.zeros, (E, self.d_ff),
                        jnp.float32)
        k2 = self.param("w2", nn.initializers.lecun_normal(),
                        (E, self.d_ff, d), jnp.float32)
        b2 = self.param("b2", nn.initializers.zeros, (E, d), jnp.float32)

        if self.ep_axis is None:
            y = self._ffn(dispatch, k1, b1, k2, b2)          # [E, C, d]
        else:
            n = lax.axis_size(self.ep_axis)
            if E % n:
                raise ValueError(
                    f"n_experts {E} not divisible by ep={n}")
            e_loc = E // n
            r = lax.axis_index(self.ep_axis)
            # Send: chunk j of my dispatch buffer goes to rank j (owner of
            # experts [j*e_loc, (j+1)*e_loc)).  Receive: my experts' tokens
            # from every rank, [n, e_loc, C, d].
            inbound = lax.all_to_all(
                dispatch.reshape(n, e_loc, C, d), self.ep_axis,
                split_axis=0, concat_axis=0, tiled=False)
            # [n, e_loc, C, d] -> per-expert token blocks [e_loc, n*C, d]
            inbound = inbound.transpose(1, 0, 2, 3).reshape(e_loc, n * C, d)
            k1r = lax.dynamic_slice_in_dim(k1, r * e_loc, e_loc, 0)
            b1r = lax.dynamic_slice_in_dim(b1, r * e_loc, e_loc, 0)
            k2r = lax.dynamic_slice_in_dim(k2, r * e_loc, e_loc, 0)
            b2r = lax.dynamic_slice_in_dim(b2, r * e_loc, e_loc, 0)
            y = self._ffn(inbound, k1r, b1r, k2r, b2r)       # [e_loc, n*C, d]
            # Return path: inverse shuffle back to the token-owning ranks.
            y = y.reshape(e_loc, n, C, d).transpose(1, 0, 2, 3)  # [n,e_loc,C,d]
            y = lax.all_to_all(y, self.ep_axis, split_axis=0,
                               concat_axis=0, tiled=False)
            y = y.reshape(E, C, d)

        # --- combine: gather each token's slot, scale by gate; dropped
        # tokens contribute zero (residual-only).
        y = jnp.concatenate([y.reshape(E * C, d),
                             jnp.zeros((1, d), y.dtype)], axis=0)
        out = y[slot] * (gate * keep)[:, None].astype(y.dtype)
        return out.reshape(b, s, d).astype(x.dtype), aux_loss

    def _ffn(self, xs, k1, b1, k2, b2):
        """Per-expert 2-layer MLP: ``xs [E', Tc, d]`` with expert-major
        params — one batched einsum pair keeps the MXU busy."""
        h = jnp.einsum("etd,edf->etf", xs.astype(self.dtype),
                       k1.astype(self.dtype)) + b1[:, None].astype(self.dtype)
        h = nn.gelu(h)
        return (jnp.einsum("etf,efd->etd", h, k2.astype(self.dtype))
                + b2[:, None].astype(self.dtype))


# -- the share-aware dropless layer ------------------------------------------

BLOCK_ROWS = 512    # assignments a step of the grouped sweep, all of one expert
# From a sweep on the v5e (my chip run, PR 28): one `ShareOfExperts` layer
# forward and backward in bf16 at 16,384 tokens, d 2304, experts of 1024, 8
# of 256 held, top-8, as block rows -> ms.  With 2.9 % of the assignments
# here (the seeded routing, ~480 an expert): 128 25.9, 256 24.2, **512
# 24.3**, 1024 25.3.  With 10.8 % here (where the cell's routing drifts
# to, ~1,760 an expert): 128 44.3, 256 37.6, **512 34.7**, 1024 33.0.
# Again in PR 36 (my chip runs; the sweep alone, `routed_here` forward and
# backward, ms at the two cells' shapes and at the shares they reach).
# Kimi's, 7 % / 13 % here (9,127 / 17,006): 256 21.1 / 31.6, 384 20.5 / 28.7,
# **512 20.1 / 28.4**, 768 19.4 / 25.2, 1024 22.7 / 30.2.  GLM's (8,192
# tokens, d 2048, experts of 1536, 8 of 64, top-4), 15 % / 25 % (4,939 /
# 8,177): 256 12.8 / 16.7, 384 9.3 / 12.2, **512 10.0 / 11.3**, 768 8.0 /
# 12.7, 1024 9.6 / 11.9.  What wins follows how each expert's count falls
# into its blocks, which drifts inside a run: 512 stays.
# The same sweep as grouped Pallas kernels over windows of W consecutive
# sorted rows, whichever experts they belong to (ISSUE 36: megablox's `gmm`,
# then a `gmm` of this repo's with one product a tile, and a `tgmm` that
# kept an expert's f32 gradient tile in VMEM over its rows), best tiles of a
# sweep over (tm, tk, tn): Kimi's W 512 19.6 / 28.7, 1024 19.3 / 28.0, 2048
# 19.5 / 28.0, 4096 21.1 / 29.6, 8192 23.5 / 31.9; GLM's 512 11.2 / 14.7,
# 1024 11.5 / 14.7, 2048 11.5 / 13.9, 4096 11.7 / 12.9, 8192 12.1 / 13.3: no
# better than the block loop alone, and worse inside the cells (GLM's step
# 366.8 -> 375.3 ms), so it is not here.  Why: PERF.md section 6, PR 36 (XLA's
# products on a 512-row block run at 175 TFLOP/s, the kernels at 80-138; a
# block's intermediates stay on the chip, a window's go through HBM; the
# scatter-add costs twice as much a row at 4,096 rows as at 512).


def route_top_k(x, router, select_bias, *, top_k: int, scale: float):
    """Sigmoid scores over every expert in f32, the top ``top_k`` of
    ``score + select_bias`` chosen (the bias moves the choice only, and so
    receives no gradient), their scores renormalised to sum to one and
    multiplied by ``scale``.  ``x: [T, d]`` -> ``(chosen [T, k] int32,
    weight [T, k] f32)``."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(scores + select_bias.astype(jnp.float32), top_k)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True) * scale
    return chosen.astype(jnp.int32), weight


def _block_of(i, plan, weight):
    """Block ``i`` of the grouped sweep: which held expert, where its rows
    start in the sorted assignment list, which of them are real, their
    tokens and their routing weights (token 0 at weight 0 where not real)."""
    expert = jnp.searchsorted(plan["block_end"], i, side="right") \
        .astype(jnp.int32)
    within = (i - (plan["block_end"][expert] - plan["blocks"][expert])) \
        * BLOCK_ROWS
    row0 = plan["start"][expert] + within
    valid = within + jnp.arange(BLOCK_ROWS) < plan["count"][expert]
    rows = lambda a: jnp.where(
        valid, lax.dynamic_slice(a, (row0,), (BLOCK_ROWS,)), 0)
    return expert, row0, valid, rows(plan["token"]), rows(weight)


ACTIVATIONS = ("swiglu", "relu2")


def _expert_block(xs, w_in, w_out, expert, act):
    """One block's expert products: ``(the activation's inputs, hidden,
    out)``.  ``"swiglu"``: ``w_in`` is ``[gate | up]``, hidden ``silu(gate)
    * up``; ``"relu2"``: ``w_in`` is ``up``, hidden ``relu(up)^2``."""
    pre = jnp.matmul(xs, w_in[expert], preferred_element_type=jnp.float32)
    if act == "swiglu":
        gate, up = jnp.split(pre, 2, axis=-1)
        pre, hidden = (gate, up), jax.nn.silu(gate) * up
    else:
        pre, hidden = (pre,), jnp.square(jax.nn.relu(pre))
    hidden = hidden.astype(xs.dtype)
    out = jnp.matmul(hidden, w_out[expert],
                     preferred_element_type=jnp.float32)
    return pre, hidden, out


def _activation_grad(act, pre, dhidden):
    """``d hidden / d (xs w_in)`` applied to ``dhidden``, in f32."""
    if act == "swiglu":
        gate, up = pre
        sig = jax.nn.sigmoid(gate)
        dgate = dhidden * up * sig * (1.0 + gate * (1.0 - sig))
        return jnp.concatenate([dgate, dhidden * gate * sig], axis=-1)
    return dhidden * 2.0 * jax.nn.relu(pre[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _grouped_experts(x, w_gate, w_up, w_down, weight, plan, act):
    """``sum over the assignments routed here of weight * E_e(x_token)`` as
    ``[T, d]`` in f32, ``E_e`` expert ``e`` under ``act`` (`_expert_block`;
    ``w_gate`` None for ``"relu2"``).  The assignments come sorted by held
    expert (``plan``); the sweep takes one block of `BLOCK_ROWS` of them at a
    time, all of one expert, under a loop whose trip count is the number of
    blocks the routing actually filled — so the matrix products follow the
    tokens that came here, not the worst case, and nothing has a capacity
    to overflow.

    The weights are read in ``x``'s type, ``[gate | up]`` side by side, and
    the reading is made in here, from the parameters themselves: the
    backward makes its own and hands each parameter's gradient back in one
    pass over its f32 accumulator, rounded to ``x``'s type as the transpose
    of that reading rounds it.  (Made outside, the concatenation and the
    cast were transposed by JAX in two more passes over ``[held, d, 2 f]``
    in f32, and their results kept for the backward: 0.6 ms a layer and
    228 MB of `glm47-flash-sync-1chip`'s peak; my chip runs, PR 36.)"""
    return _grouped_fwd(x, w_gate, w_up, w_down, weight, plan, act)[0]


def _read_weights(x, w_gate, w_up, w_down):
    w_in = w_up if w_gate is None else jnp.concatenate([w_gate, w_up],
                                                       axis=-1)
    return w_in.astype(x.dtype), w_down.astype(x.dtype)


def _grouped_fwd(x, w_gate, w_up, w_down, weight, plan, act):
    w_in, w_out = _read_weights(x, w_gate, w_up, w_down)

    def body(i, y):
        expert, _, _, tokens, w = _block_of(i, plan, weight)
        out = _expert_block(x[tokens], w_in, w_out, expert, act)[2]
        return y.at[tokens].add(out * w[:, None])

    y = lax.fori_loop(0, plan["n_blocks"], body,
                      jnp.zeros(x.shape, jnp.float32))
    return y, (x, w_gate, w_up, w_down, weight, plan)


def _grouped_bwd(act, res, dy):
    x, w_gate, w_up, w_down, weight, plan = res
    w_in, w_out = _read_weights(x, w_gate, w_up, w_down)
    dy = dy.astype(jnp.float32)

    def body(i, acc):
        dx, dw_in, dw_out, dweight = acc
        expert, row0, valid, tokens, w = _block_of(i, plan, weight)
        xs = x[tokens]
        pre, hidden, out = _expert_block(xs, w_in, w_out, expert, act)
        dys = dy[tokens]
        dweight = lax.dynamic_update_slice(
            dweight, jnp.where(valid, jnp.sum(out * dys, axis=-1), 0.0),
            (row0,))
        dout = (dys * w[:, None]).astype(x.dtype)
        dw_out = dw_out.at[expert].add(jnp.matmul(
            hidden.T, dout, preferred_element_type=jnp.float32))
        dhidden = jnp.matmul(dout, w_out[expert].T,
                             preferred_element_type=jnp.float32)
        dpre = _activation_grad(act, pre, dhidden).astype(x.dtype)
        dw_in = dw_in.at[expert].add(jnp.matmul(
            xs.T, dpre, preferred_element_type=jnp.float32))
        dxs = jnp.matmul(dpre, w_in[expert].T,
                         preferred_element_type=jnp.float32)
        dx = dx.at[tokens].add(jnp.where(valid[:, None], dxs, 0.0))
        return dx, dw_in, dw_out, dweight

    dx, dw_in, dw_out, dweight = lax.fori_loop(
        0, plan["n_blocks"], body,
        (jnp.zeros(x.shape, jnp.float32),
         jnp.zeros(w_in.shape, jnp.float32),
         jnp.zeros(w_out.shape, jnp.float32),
         jnp.zeros(weight.shape, jnp.float32)))
    as_read = lambda g, w: g.astype(x.dtype).astype(w.dtype)
    no_grad = jax.tree.map(
        lambda a: np.zeros(a.shape, jax.dtypes.float0), plan)
    dx = dx.astype(x.dtype)
    if w_gate is None:
        d_gate, d_up = None, as_read(dw_in, w_up)
    else:
        f = w_gate.shape[-1]
        d_gate = as_read(dw_in[..., :f], w_gate)
        d_up = as_read(dw_in[..., f:], w_up)
    return dx, d_gate, d_up, as_read(dw_out, w_down), dweight, no_grad


_grouped_experts.defvjp(_grouped_fwd, _grouped_bwd)


@jax.custom_vjp
def _sorted_by(where, weight):
    """``(order, weight[order])`` for ``order = argsort(where, stable)``, as
    one sort that carries both along, and its transpose as the sort that
    undoes it: no gather of ``T * k`` scalars and no scatter-add behind it
    (0.23 and 0.29 ms at 32,768 on the v5e, where a sort takes 0.02; my
    chip runs, PR 36)."""
    return _sorted_by_fwd(where, weight)[0]


def _sorted_by_fwd(where, weight):
    index = lax.iota(jnp.int32, where.shape[0])
    _, order, sorted_weight = lax.sort((where, index, weight), num_keys=1,
                                       is_stable=True)
    return (order, sorted_weight), order


def _sorted_by_bwd(order, grads):
    _, back = lax.sort((order, grads[1]), num_keys=1)
    return np.zeros(order.shape, jax.dtypes.float0), back


_sorted_by.defvjp(_sorted_by_fwd, _sorted_by_bwd)


def routed_here(x, chosen, weight, w_gate, w_up, w_down, *, n_experts: int,
                held: "tuple[int, ...]", act: str = "swiglu"):
    """The held experts' part of the layer: ``sum_{e chosen and held} w_e *
    E_e(x)`` for ``x: [T, d]`` (``E_e`` under ``act``, `_expert_block`),
    and the load: assignments on each held expert, then their sum.  Sorts the ``T * k`` assignments by held expert
    (those on experts held elsewhere go last and are never visited)."""
    top_k = chosen.shape[1]
    n_held = len(held)
    local = np.full((n_experts,), n_held, np.int32)
    local[list(held)] = np.arange(n_held, dtype=np.int32)
    where = jnp.asarray(local)[chosen.reshape(-1)]          # [T * k]
    order, sorted_weight = _sorted_by(where, weight.reshape(-1))
    count = jnp.sum(where[:, None] == jnp.arange(n_held)[None, :], axis=0,
                    dtype=jnp.int32)      # a scatter-add of ones is serial
    blocks = -(-count // BLOCK_ROWS)
    pad = lambda a: jnp.pad(a, (0, BLOCK_ROWS))   # a slice may run past
    plan = {
        "token": pad((order // top_k).astype(jnp.int32)),
        "count": count, "start": jnp.cumsum(count) - count,
        "blocks": blocks, "block_end": jnp.cumsum(blocks),
        "n_blocks": jnp.sum(blocks),
    }
    y = _grouped_experts(x, w_gate, w_up, w_down, pad(sorted_weight), plan,
                         act)
    load = jnp.concatenate([count, jnp.sum(count, keepdims=True)])
    return y, load.astype(jnp.float32)


class ShareOfExperts(nn.Module):
    """One chip's share of a mixture-of-experts layer: ``[B, S, d] ->
    ([B, S, d], load)``.

    ``n_experts`` is the published count and the router's width; ``held``
    lists the experts whose weights live here (the only expert weights the
    layer has).  Every token is routed over all ``n_experts``; the result
    is ``sum_{e chosen and held} w_e E_e(x) + E_shared(x)``, every expert
    ``E`` a `SwiGLU` (``act="swiglu"``) or a `ReluSquaredMLP` (``"relu2"``,
    no ``w_gate``).
    What the experts held elsewhere would add is left out: on one chip
    there is no exchange, and nothing here stands in for the absent chips.
    ``load`` is ``[len(held) + 1]`` in f32: the assignments that landed on
    each held expert, and their sum (of ``T * top_k`` made)."""

    d_model: int
    d_expert: int
    n_experts: int
    held: "tuple[int, ...]"
    top_k: int
    scale: float = 1.0
    d_shared: int = 0            # width of the shared expert, 0 for none
    dtype: jnp.dtype = jnp.float32
    act: str = "swiglu"          # one of `ACTIVATIONS`

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        n_held, f = len(self.held), self.d_expert
        toks = x.reshape(b * s, d).astype(self.dtype)
        init = nn.initializers.lecun_normal()
        router = self.param("router", init, (d, self.n_experts), jnp.float32)
        # Trained checkpoints carry a bias that balances the load; a seeded
        # one has to be non-zero to move any choice at all.
        bias = self.param("e_score_correction_bias",
                          nn.initializers.normal(0.02),
                          (self.n_experts,), jnp.float32)
        expert_init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                                   batch_axis=(0,))
        if self.act not in ACTIVATIONS:
            raise ValueError(f"expert activation {self.act!r}: know "
                             f"{ACTIVATIONS}")
        w_gate = None if self.act == "relu2" else self.param(
            "w_gate", expert_init, (n_held, d, f), jnp.float32)
        w_up = self.param("w_up", expert_init, (n_held, d, f), jnp.float32)
        w_down = self.param("w_down", expert_init, (n_held, f, d),
                            jnp.float32)
        chosen, weight = route_top_k(toks, router, bias, top_k=self.top_k,
                                     scale=self.scale)
        y, load = routed_here(
            toks, chosen, weight, w_gate, w_up, w_down,
            n_experts=self.n_experts, held=tuple(self.held), act=self.act)
        y = y.astype(self.dtype)
        if self.d_shared:
            shared = SwiGLU if self.act == "swiglu" else ReluSquaredMLP
            y = y + shared(self.d_shared, self.dtype, name="shared")(toks)
        return y.reshape(b, s, d).astype(x.dtype), load


class SwiGLU(nn.Module):
    """``down(silu(gate(x)) * up(x))``, bias-free, f32 parameters read in
    ``dtype``."""

    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        hidden = nn.silu(bias_free_dense(self.width, self.dtype, "gate")(x)) \
            * bias_free_dense(self.width, self.dtype, "up")(x)
        return bias_free_dense(x.shape[-1], self.dtype, "down")(hidden)


class ReluSquaredMLP(nn.Module):
    """``down(relu(up(x))^2)``, bias-free, f32 parameters read in
    ``dtype``."""

    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        hidden = jnp.square(nn.relu(
            bias_free_dense(self.width, self.dtype, "up")(x)))
        return bias_free_dense(x.shape[-1], self.dtype, "down")(hidden)


def bias_free_dense(features: int, dtype, name: str) -> nn.Dense:
    """A projection as these models have them: no bias, f32 parameters read
    in ``dtype``."""
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=jnp.float32, name=name)
