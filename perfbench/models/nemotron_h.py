"""The `nemotron_h` family: Nemotron-3-Nano (a hybrid of Mamba-2 layers,
relu² expert layers with a shared expert, and grouped-query attention
without positions, one part a layer) through the program's
`models.nemotron_h.NemotronHLM`, with its shape formulas and its plain
reference.

What is the program's: the model, the loss, the chunked state-space duality
scan (`ops/ssd.py`), the flash attention kernels and the grouped expert
layer.  What is the benchmark's: the sizes (from the configuration file),
the FLOP and byte formulas, and `reference_loss`: `jax.numpy` in the
precision of the parameters it is given (f32 in the check) that reads the
same parameter tree and is given the same share of the experts — **the
recurrence token by token** (never the chunked form, so that the check
compares two algorithms), dense causal attention over blocks of queries
with each kv head gathered for its query heads, one masked pass over every
token per held expert, the head over blocks of tokens; no kernel, nothing
from the program's `ops/` or `models/`.  The helpers the other references
already have (`_rms_norm`, `_blocked`, `causal_attention`,
`_log_likelihood`) are imported from them.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from perfbench.models.glm_moe import _log_likelihood
from perfbench.models.kimi_linear import Family as _KimiFamily
from perfbench.models.kimi_linear import _blocked, _rms_norm, causal_attention

UNIT = "tokens"
SSD_SCOPE, MAMBA_SCOPE, MOE_SCOPE = "ssd", "mamba", "moe"
LETTERS = {"M": "mamba", "E": "moe", "*": "attn"}


def sizes(config: dict, rehearse: bool) -> dict:
    """The sizes as they are run: `config` with, in a rehearsal, its
    `rehearsal` group laid over it.  The layers are the first
    ``num_layers`` letters of the published pattern."""
    c = dict(config, **(config["rehearsal"] if rehearse else {}))
    return {
        "d_model": c["hidden_size"],
        "pattern": c["hybrid_override_pattern"][:c["num_layers"]],
        "d_expert": c["moe_intermediate_size"],
        "d_shared": c["n_shared_experts"]
        * c["moe_shared_expert_intermediate_size"],
        "n_experts": c["n_routed_experts_published"],
        "experts_held": tuple(c["experts_held"]),
        "top_k": c["num_experts_per_tok"],
        "routed_scale": c["routed_scaling_factor"],
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "mamba_heads": c["mamba_num_heads"],
        "mamba_head_dim": c["mamba_head_dim"],
        "n_groups": c["n_groups"], "d_state": c["ssm_state_size"],
        "d_conv": c["conv_kernel"], "chunk": c["chunk_size"],
        "vocab_size": c["vocab_size"], "eps": c["layer_norm_epsilon"],
    }


# -- shape formulas -----------------------------------------------------------


def _kinds(s: dict) -> list:
    return [LETTERS[letter] for letter in s["pattern"]]


def block_params(s: dict) -> dict:
    """Parameters of each kind of part, counted from the shapes."""
    d, h = s["d_model"], s["mamba_heads"]
    d_inner = h * s["mamba_head_dim"]
    conv = d_inner + 2 * s["n_groups"] * s["d_state"]
    mamba_matmul = d * (d_inner + conv + h) + s["d_conv"] * conv \
        + d_inner * d
    attn = d * (s["n_heads"] + 2 * s["n_kv_heads"]) * s["head_dim"] \
        + s["n_heads"] * s["head_dim"] * d
    expert, shared = 2 * d * s["d_expert"], 2 * d * s["d_shared"]
    return {
        "mamba_matmul": mamba_matmul,
        # + the convolution's bias, dt_bias, A_log, D and the gated norm
        "mamba": mamba_matmul + conv + 3 * h + d_inner,
        "attn_matmul": attn, "attn": attn,
        "expert": expert, "shared": shared, "router": d * s["n_experts"],
        "moe": len(s["experts_held"]) * expert + shared
        + d * s["n_experts"] + s["n_experts"],
        "norm": d,
        "vocab": 2 * s["vocab_size"] * d,
    }


def total_params(s: dict) -> int:
    """Every parameter the chip holds and the optimizer updates: embedding,
    head and final norm once, and each layer's part and norm by its kind."""
    p = block_params(s)
    return p["vocab"] + s["d_model"] + sum(p[kind] + p["norm"]
                                           for kind in _kinds(s))


def matmul_params(s: dict, routed: "float | None" = None) -> float:
    """Parameters that sit in a multiply-accumulate once per token (the
    depthwise convolution's taps among them).  `routed` is the expert
    products a token an expert layer makes here; left out, their expected
    number: `top_k` assignments over `n_experts` experts of which
    `len(experts_held)` are here, 6 * 8 / 128 = 0.375 at the published
    sizes.  Embedding lookups, norms, biases, ``A_log``, ``D`` do none."""
    p = block_params(s)
    if routed is None:
        routed = s["top_k"] * len(s["experts_held"]) / s["n_experts"]
    per_kind = {"mamba": p["mamba_matmul"], "attn": p["attn_matmul"],
                "moe": p["router"] + p["shared"] + routed * p["expert"]}
    return float(s["d_model"] * s["vocab_size"]) \
        + sum(per_kind[kind] for kind in _kinds(s))


def ssd_flops_per_token(s: dict) -> float:
    """The chunked scan's matrix products, one token of one layer, forward,
    at a chunk of ``Q`` tokens: ``C B^T`` a group (``2 Q N G``), the masked
    product a head (``2 Q P H``), the chunk's own state (``2 N P H``) and
    the output from the state the chunk starts with (``2 N P H``).  The
    full ``Q x Q`` squares, as the chunked form computes them."""
    q, n, g = s["chunk"], s["d_state"], s["n_groups"]
    p, h = s["mamba_head_dim"], s["mamba_heads"]
    return 2.0 * q * n * g + 2.0 * q * p * h + 4.0 * n * p * h


def flops_per_sample(s: dict, seq_len: int,
                     routed: "float | None" = None) -> float:
    """FLOPs one token needs, forward and backward: 6 per matmul parameter
    (`routed`: see `matmul_params`); attention at 3.0 times its forward
    (``QK^T`` and ``PV`` a head wide each, over the causal half of the
    square, for ``n_heads`` heads), as `perfbench/models/sambay.py` counts
    it; the scan's products at 3.0 times their forward.  No rematerialised
    forward is counted."""
    kinds = _kinds(s)
    attn = 3.0 * 2 * s["n_heads"] * 2 * s["head_dim"] * seq_len / 2.0
    return 6.0 * matmul_params(s, routed) + attn * kinds.count("attn") \
        + 3.0 * ssd_flops_per_token(s) * kinds.count("mamba")


def ssd_work(s: dict, batch: int, seq_len: int) -> dict:
    """Work of the chunked scan of one step on one chip, every Mamba layer:
    its products' FLOPs (`ssd_flops_per_token`, forward, and twice that
    backward), and the bytes it has to move: forward it reads x (bf16), dt
    (f32), B and C (bf16), writes y (f32) and the state each chunk starts
    from (f32); backward it reads those inputs, dy (f32) and the chunk
    states and writes dx (bf16), ddt (f32), dB and dC (bf16); A and D and
    their gradients once.  Projections, convolution and the gated norm are
    not part of it."""
    tokens = batch * seq_len
    h, p = s["mamba_heads"], s["mamba_head_dim"]
    wide, heads = tokens * h * p, tokens * h
    groups = tokens * s["n_groups"] * s["d_state"]
    states = batch * (seq_len // s["chunk"]) * h * p * s["d_state"] * 4
    inputs = wide * 2 + heads * 4 + 2 * groups * 2
    forward = inputs + wide * 4 + states + h * 8
    backward = inputs + wide * 4 + states + inputs + h * 16
    layers = _kinds(s).count("mamba")
    return {"flops": layers * 3.0 * ssd_flops_per_token(s) * tokens,
            "bytes": layers * float(forward + backward), "scope": SSD_SCOPE}


# -- the family ---------------------------------------------------------------


class Family:
    unit = UNIT

    def __init__(self, config: dict, cell: dict, *, impl: str,
                 rehearse: bool):
        from pytorch_ps_mpi_tpu.models.nemotron_h import (NemotronHConfig,
                                                          NemotronHLM,
                                                          nemotron_aux)
        from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention

        self.s = s = sizes(config, rehearse)
        self.seq_len = cell["seq_len"]
        self.samples_per_row = self.seq_len
        self.tokens_per_step = cell["rows_per_chip"] * self.seq_len  # a chip
        shape = {f.name: s[f.name]
                 for f in dataclasses.fields(NemotronHConfig) if f.name in s}
        cfg = NemotronHConfig(**shape,
                              dtype=jnp.dtype(config["compute_dtype"]))
        self.model = NemotronHLM(cfg, attn=functools.partial(
            flash_attention, causal=True, scale=s["head_dim"] ** -0.5,
            impl=impl))
        # The shapes do not depend on the attention: initialise densely.
        self._init_model = NemotronHLM(NemotronHConfig(**shape))
        self.shapes = {"seq_len": self.seq_len,
                       "vocab_size": s["vocab_size"]}
        self.aux = nemotron_aux(self.model)

    def init_params(self, seed: int) -> "dict[str, jax.Array]":
        """All parameters in one jitted call from the seed, f32 as they are
        trained; the initialising forward is one chunk long and dense."""
        from pytorch_ps_mpi_tpu.utils.flatten import named_params

        def init(key):
            tokens = jnp.zeros((1, self.s["chunk"]), jnp.int32)
            return named_params(self._init_model.init(key, tokens)["params"])

        return jax.jit(init)(jax.random.PRNGKey(seed))

    def sync_loss(self):
        from pytorch_ps_mpi_tpu.models.nemotron_h import make_nemotron_loss
        return make_nemotron_loss(self.model), True

    def check_pair(self, mode: str):
        """(system loss, reference loss), both ``f(params, batch)``."""
        loss_aux, aux = self.sync_loss()[0], self.aux
        return (lambda p, b: loss_aux(p, aux, b)[0],
                functools.partial(reference_loss, self.s))

    def flops_per_sample(self) -> float:
        """With the expert products the steps counted, where they logged
        their load; with their expected number, 0.375 a token, before any
        step has run."""
        return flops_per_sample(self.s, self.seq_len, self._routed_counted())

    # Assignments on held experts per token and expert layer, mean over
    # every step this process logged: `moe_load` means here what it means
    # in Kimi-Linear's cell, and so does its reader.
    _routed_counted = _KimiFamily._routed_counted

    def kernel_work(self, rows_per_chip: int) -> dict:
        return {"ssd": ssd_work(self.s, rows_per_chip, self.seq_len)}


def build(config: dict, cell: dict, *, impl: str, rehearse: bool) -> Family:
    return Family(config, cell, impl=impl, rehearse=rehearse)


# -- the plain reference ------------------------------------------------------

TOKEN_BLOCK = 64     # tokens whose states are recomputed together


def ssd_recurrence(x, dt, a, b_in, c_out):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t`` per
    head, one token at a time from ``S_0 = 0``; head ``h`` reads group ``h
    // (H / G)`` of ``B`` and ``C``.  ``x: [B, S, H, P]``, ``dt: [B, S,
    H]``, ``a: [H]``, ``b_in, c_out: [B, S, G, N]`` -> ``[B, S, H, P]``.  A
    scan over blocks of tokens of a scan over tokens, the outer body
    rematerialised, so that the backward pass holds one state a block and
    not one a token."""
    rows, s, h, p = x.shape
    g, n_state = b_in.shape[2], b_in.shape[3]
    k = h // g
    n, block = _blocked(s, TOKEN_BLOCK)
    pad = n * block - s

    def steps(y):       # [B, S, ...] -> [n, block, B, ...]; zeros do nothing
        y = jnp.pad(y, ((0, 0), (0, pad)) + ((0, 0),) * (y.ndim - 2))
        return jnp.moveaxis(y, 1, 0).reshape(n, block, rows, *y.shape[2:])

    decay_rate = a.reshape(g, k)

    def token(state, inp):          # state [B, G, K, P, N]
        x_t, dt_t, b_t, c_t = inp
        x_t, dt_t = x_t.reshape(rows, g, k, p), dt_t.reshape(rows, g, k)
        state = jnp.exp(dt_t * decay_rate)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] \
            * b_t[:, :, None, None, :]
        y_t = jnp.sum(state * c_t[:, :, None, None, :], axis=-1)
        return state, y_t.reshape(rows, h, p)

    @jax.checkpoint
    def many(state, xs):
        return jax.lax.scan(token, state, xs)

    _, y = jax.lax.scan(many, jnp.zeros((rows, g, k, p, n_state), x.dtype),
                        tuple(steps(v) for v in (x, dt, b_in, c_out)))
    return jnp.moveaxis(y.reshape(n * block, rows, h, p)[:s], 0, 1)


def _mamba_layer(s, p, u):
    b, t, _ = u.shape
    h, hp, g, n = (s["mamba_heads"], s["mamba_head_dim"], s["n_groups"],
                   s["d_state"])
    d_inner = h * hp
    conv = d_inner + 2 * g * n
    proj = u @ p["in_proj/kernel"]
    z, xbc, dt = (proj[..., :d_inner], proj[..., d_inner:d_inner + conv],
                  proj[..., d_inner + conv:])
    taps = p["conv"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, i:i + t] * p["conv"][i]
                          for i in range(taps)) + p["conv_bias"])
    x = xbc[..., :d_inner].reshape(b, t, h, hp)
    b_in = xbc[..., d_inner:d_inner + g * n].reshape(b, t, g, n)
    c_out = xbc[..., d_inner + g * n:].reshape(b, t, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd_recurrence(x, dt, -jnp.exp(p["A_log"]), b_in, c_out) \
        + p["D"][:, None] * x
    y = y.reshape(b, t, d_inner) * jax.nn.silu(z)
    grouped = y.reshape(b, t, g, d_inner // g)
    y = _rms_norm(grouped, 1.0, s["eps"]).reshape(b, t, d_inner)
    return (y * p["norm/scale"]) @ p["out_proj/kernel"]


def _relu2(x, up, down):
    return jnp.square(jax.nn.relu(x @ up)) @ down


def _moe_layer(s, p, x):
    """The held experts' part of the layer and the shared expert: a pass
    over every token per held expert, weighted by the token's routing
    weight on that expert (zero where it was not chosen)."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(scores + p["e_score_correction_bias"],
                              s["top_k"])
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True) \
        * s["routed_scale"]
    y = _relu2(x, p["shared/up/kernel"], p["shared/down/kernel"])

    @jax.checkpoint
    def one_expert(y, held):
        expert, up, down = held
        on_it = jnp.sum(jnp.where(chosen == expert, weight, 0.0), axis=-1)
        return y + on_it[..., None] * _relu2(x, up, down), None

    y, _ = jax.lax.scan(one_expert, y, (
        jnp.asarray(s["experts_held"], jnp.int32), p["w_up"], p["w_down"]))
    return y


def _attention_layer(s, p, x):
    b, t, _ = x.shape
    h, hk, d = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    q = (x @ p["q_proj/kernel"]).reshape(b, t, h, d)
    kv_of_q = jnp.arange(h) // (h // hk)
    k = (x @ p["k_proj/kernel"]).reshape(b, t, hk, d)[:, :, kv_of_q]
    v = (x @ p["v_proj/kernel"]).reshape(b, t, hk, d)[:, :, kv_of_q]
    o = causal_attention(q, k, v, d ** -0.5)
    return o.reshape(b, t, h * d) @ p["o_proj/kernel"]


_PART = {"mamba": _mamba_layer, "moe": _moe_layer, "attn": _attention_layer}


def reference_loss(s: dict, params: dict, batch: dict):
    """Nemotron-H's forward and next-token cross-entropy in `jax.numpy`, in
    the parameters' own precision, from the program's parameter tree.  Each
    block is rematerialised, so that one block's activations exist at a
    time."""
    def part(prefix):
        return {n[len(prefix):]: v for n, v in params.items()
                if n.startswith(prefix)}

    def block(x, p, kind):
        mixer = {n[6:]: v for n, v in p.items() if n.startswith("mixer/")}
        return x + _PART[kind](s, mixer,
                               _rms_norm(x, p["norm/scale"], s["eps"]))

    x = params["tok_embed/embedding"][batch["tokens"]]
    for i, kind in enumerate(_kinds(s)):
        x = jax.checkpoint(block, static_argnums=(2,))(
            x, part(f"block_{i}/"), kind)
    x = _rms_norm(x, params["final_norm/scale"], s["eps"])
    return -jnp.mean(_log_likelihood(x, params["lm_head/kernel"],
                                     batch["targets"]))
