"""The `kimi_linear` family: Kimi-Linear (KDA linear attention, NoPE latent
attention, a mixture of experts with a shared expert) through the program's
`models.kimi_linear.KimiLinearLM`, with its shape formulas and its plain
reference.

What is the program's: the model, the loss, the chunked KDA recurrence, the
flash attention kernels and the grouped expert layer.  What is the
benchmark's: the sizes (from the configuration file), the FLOP and byte
formulas, and `reference_loss`: f32 `jax.numpy` that reads the same
parameter tree and is given the same share of the experts — the recurrence
token by token, dense causal attention over blocks of queries, one masked
pass over every token per held expert; no kernel, no chunked form, no sorting, nothing from
the program's `ops/`.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

UNIT = "tokens"
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")
KDA_SCOPE, MOE_SCOPE = "kda", "moe"
GATE_RANK = 128     # not in config.json: the modeling code's low-rank gates


def sizes(config: dict, rehearse: bool) -> dict:
    """The sizes as they are run: `config` with, in a rehearsal, its
    `rehearsal` group laid over it."""
    c = dict(config, **(config["rehearsal"] if rehearse else {}))
    linear = c["linear_attn_config"]
    return {
        "d_model": c["hidden_size"], "n_layers": c["num_layers"],
        "kda_layers": tuple(linear["kda_layers"]),
        "mla_layers": tuple(linear["full_attn_layers"]),
        "first_k_dense": c["first_k_dense_replace"],
        "d_ff": c["intermediate_size"],
        "d_expert": c["moe_intermediate_size"],
        "n_experts": c["num_experts_published"],
        "experts_held": tuple(c["experts_held"]),
        "top_k": c["num_experts_per_token"],
        "n_shared": c["num_shared_experts"],
        "routed_scale": c["routed_scaling_factor"],
        "n_heads": c["num_attention_heads"],
        "kv_lora_rank": c["kv_lora_rank"],
        "qk_nope_dim": c["qk_nope_head_dim"],
        "qk_rope_dim": c["qk_rope_head_dim"], "v_dim": c["v_head_dim"],
        "kda_heads": linear["num_heads"], "kda_head_dim": linear["head_dim"],
        "conv_size": linear["short_conv_kernel_size"],
        "gate_rank": c.get("gate_rank", GATE_RANK),
        "vocab_size": c["vocab_size"], "eps": c["rms_norm_eps"],
    }


# -- shape formulas -----------------------------------------------------------


def block_params(s: dict) -> dict:
    """Parameters of each kind of part, counted from the shapes."""
    d, w = s["d_model"], s["kda_heads"] * s["kda_head_dim"]
    h, r = s["n_heads"], s["gate_rank"]
    qk = s["qk_nope_dim"] + s["qk_rope_dim"]
    kda_matmul = 4 * d * w + 3 * w * s["conv_size"] \
        + 2 * (d * r + r * w) + d * s["kda_heads"]
    mla_matmul = d * h * qk + d * (s["kv_lora_rank"] + s["qk_rope_dim"]) \
        + s["kv_lora_rank"] * h * (s["qk_nope_dim"] + s["v_dim"]) \
        + h * s["v_dim"] * d
    expert = 3 * d * s["d_expert"]
    return {
        "kda_matmul": kda_matmul,
        # + A_log a head, dt_bias a channel, the output norm's scale
        "kda": kda_matmul + s["kda_heads"] + w + s["kda_head_dim"],
        "mla_matmul": mla_matmul, "mla": mla_matmul + s["kv_lora_rank"],
        "dense_mlp": 3 * d * s["d_ff"],
        "expert": expert, "router": d * s["n_experts"],
        "moe": len(s["experts_held"]) * expert + s["n_shared"] * expert
        + d * s["n_experts"] + s["n_experts"],
        "norms": 2 * d,
        "vocab": 2 * s["vocab_size"] * d,
    }


def total_params(s: dict) -> int:
    """Every parameter the chip holds and the optimizer updates."""
    p = block_params(s)
    n = p["vocab"] + s["d_model"]       # embedding, head, final norm
    for i in range(s["n_layers"]):
        n += p["kda"] if (i + 1) in s["kda_layers"] else p["mla"]
        n += p["dense_mlp"] if i < s["first_k_dense"] else p["moe"]
        n += p["norms"]
    return n


def matmul_params(s: dict, routed: "float | None" = None) -> float:
    """Parameters that sit in a multiply-accumulate once per token.
    `routed` is the expert products a token a MoE layer makes here; left
    out, their expected number: each token makes `top_k` assignments over
    `n_experts` experts of which `len(experts_held)` are here, so 8 * 8 /
    256 = 0.25 at the published sizes.  Embedding lookups, norms and biases
    do none."""
    p = block_params(s)
    if routed is None:
        routed = s["top_k"] * len(s["experts_held"]) / s["n_experts"]
    n = float(s["d_model"] * s["vocab_size"])       # the head
    for i in range(s["n_layers"]):
        n += p["kda_matmul"] if (i + 1) in s["kda_layers"] \
            else p["mla_matmul"]
        n += p["dense_mlp"] if i < s["first_k_dense"] else (
            p["router"] + (s["n_shared"] + routed) * p["expert"])
    return n


def kda_flops_per_token(s: dict) -> float:
    """The recurrence as it is defined, one token of one layer, forward:
    per head the decay of the state (d_k * d_v), S^T k, the rank-one write
    and S^T q (2 * d_k * d_v each): 7 * d_k * d_v."""
    return 7.0 * s["kda_heads"] * s["kda_head_dim"] ** 2


def flops_per_sample(s: dict, seq_len: int,
                     routed: "float | None" = None) -> float:
    """Forward and backward FLOPs one token needs: 6 per matmul parameter
    (`routed`: see `matmul_params`);
    causal attention in the MLA layers (QK^T over 192 columns and PV over
    128, half of the dense square under the mask, three times for forward
    and backward); the KDA recurrence three times its forward.  No
    recomputation is counted."""
    h = s["n_heads"]
    attn = 3.0 * seq_len * h * (s["qk_nope_dim"] + s["qk_rope_dim"]
                                + s["v_dim"]) * len(s["mla_layers"])
    kda = 3.0 * kda_flops_per_token(s) * len(s["kda_layers"])
    return 6.0 * matmul_params(s, routed) + attn + kda


def kda_work(s: dict, batch: int, seq_len: int) -> dict:
    """Least work of the KDA recurrence of one step on one chip, all KDA
    layers, whatever implements it: the token-by-token FLOPs (forward, and
    twice that backward), and the bytes an implementation that keeps the
    state on the chip has to move: forward it reads q, k, v (bf16), the
    log-decay (f32, a channel) and beta (f32, a head) and writes o; backward
    it reads those and do again and writes dq, dk, dv (bf16), dg and dbeta
    (f32).  Projections, convolutions and gates are not part of it."""
    tokens = batch * seq_len
    width = s["kda_heads"] * s["kda_head_dim"]
    wide, per_head = tokens * width, tokens * s["kda_heads"]
    forward = wide * (3 * 2 + 4 + 2) + per_head * 4
    backward = forward + wide * (3 * 2 + 4) + per_head * 4
    layers = len(s["kda_layers"])
    return {"flops": layers * 3.0 * kda_flops_per_token(s) * tokens,
            "bytes": layers * float(forward + backward), "scope": KDA_SCOPE}


def flash_work(s: dict, batch: int, seq_len: int) -> dict:
    """Least work of the flash kernels of one step on one chip, causal, all
    MLA layers, with a q / k width D (192) other than the v width Dv (128).
    A matrix product over the causal half of the square costs B * H * S * S
    * width FLOPs: forward QK^T (D) and PV (Dv); backward the scores again
    (D), dP (Dv), dV (Dv), dQ (D), dK (D).  Bytes in bf16: forward reads q,
    k, v and writes o and the row statistics; backward reads q, k, v, o,
    do and the statistics and writes dq, dk, dv."""
    h = s["n_heads"]
    d, dv = s["qk_nope_dim"] + s["qk_rope_dim"], s["v_dim"]
    square = float(batch) * h * seq_len * seq_len
    wide, narrow = (batch * seq_len * h * w * 2 for w in (d, dv))
    stats = batch * h * seq_len * 4
    layers = len(s["mla_layers"])
    return {"flops": layers * square * (4 * d + 3 * dv),
            "bytes": layers * float(6 * wide + 6 * narrow + 2 * stats),
            "kernels": FLASH_KERNELS,
            "match": tuple(f'"kernel":"{k}"' for k in FLASH_KERNELS)}


# -- the family ---------------------------------------------------------------


class Family:
    unit = UNIT

    def __init__(self, config: dict, cell: dict, *, impl: str,
                 rehearse: bool):
        from pytorch_ps_mpi_tpu.models.kimi_linear import (KimiLinearConfig,
                                                           KimiLinearLM,
                                                           kimi_aux)
        from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention

        self.s = s = sizes(config, rehearse)
        self.seq_len = cell["seq_len"]
        self.samples_per_row = self.seq_len
        self.tokens_per_step = cell["rows_per_chip"] * self.seq_len  # a chip
        shape = {f.name: s[f.name]
                 for f in dataclasses.fields(KimiLinearConfig) if f.name in s}
        cfg = KimiLinearConfig(
            **shape, dtype=jnp.dtype(config["compute_dtype"]))
        scale = (s["qk_nope_dim"] + s["qk_rope_dim"]) ** -0.5
        self.model = KimiLinearLM(cfg, attn=functools.partial(
            flash_attention, causal=True, scale=scale, impl=impl))
        # The shapes do not depend on the attention: initialise densely.
        self._init_model = KimiLinearLM(KimiLinearConfig(**shape))
        self.shapes = {"seq_len": self.seq_len,
                       "vocab_size": s["vocab_size"]}
        self.aux = kimi_aux(self.model)

    def init_params(self, seed: int) -> "dict[str, jax.Array]":
        """All parameters in one jitted call from the seed, f32 as they are
        trained; the initialising forward is short and dense."""
        from pytorch_ps_mpi_tpu.utils.flatten import named_params

        def init(key):
            tokens = jnp.zeros((1, 8), jnp.int32)
            return named_params(self._init_model.init(key, tokens)["params"])

        return jax.jit(init)(jax.random.PRNGKey(seed))

    def sync_loss(self):
        from pytorch_ps_mpi_tpu.models.kimi_linear import make_kimi_loss
        return make_kimi_loss(self.model), True

    def check_pair(self, mode: str):
        """(system loss, reference loss), both ``f(params, batch)``."""
        loss_aux, aux = self.sync_loss()[0], self.aux
        return (lambda p, b: loss_aux(p, aux, b)[0],
                functools.partial(reference_loss, self.s))

    def flops_per_sample(self) -> float:
        """With the expert products the steps counted, where they logged
        their load (the routing drifts towards the held experts while the
        cell trains, and the dropless layer's work follows it); with their
        expected number, 0.25 a token, before any step has run."""
        return flops_per_sample(self.s, self.seq_len, self._routed_counted())

    def _routed_counted(self) -> "float | None":
        """Assignments on held experts per token and MoE layer, mean over
        every step this process logged (`utils.timing.counter_log()`)."""
        try:
            from pytorch_ps_mpi_tpu.utils.timing import counter_log
        except ImportError:
            return None
        loads = [r["values"]["moe_load"] for r in counter_log().records()
                 if "moe_load" in r["values"]]
        if not loads:
            return None
        here = [x[..., -1].mean() for x in jax.device_get(loads)]
        return float(sum(here) / len(here)) / self.tokens_per_step

    def kernel_work(self, rows_per_chip: int) -> dict:
        return {"flash": flash_work(self.s, rows_per_chip, self.seq_len),
                "kda": kda_work(self.s, rows_per_chip, self.seq_len)}


def build(config: dict, cell: dict, *, impl: str, rehearse: bool) -> Family:
    return Family(config, cell, impl=impl, rehearse=rehearse)


# -- the plain reference ------------------------------------------------------

TOKEN_BLOCK = 64     # tokens whose states are recomputed together
QUERY_BLOCK = 512    # queries whose score rows exist together


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _blocked(n: int, block: int) -> "tuple[int, int]":
    """(blocks, block length) covering n with equal blocks."""
    block = min(block, n)
    return -(-n // block), block


def kda_recurrence(q, k, v, g, beta):
    """The recurrence as the model states it, one token at a time.  ``q, k,
    g: [B, S, H, Dk]``, ``v: [B, S, H, Dv]``, ``beta: [B, S, H]`` ->
    ``[B, S, H, Dv]``.  A scan over blocks of tokens of a scan over tokens,
    the outer body rematerialised, so that the backward pass holds one state
    a block and not one a token."""
    b, s, h, dk = q.shape
    n, block = _blocked(s, TOKEN_BLOCK)
    pad = n * block - s

    def steps(x):       # [B, S, ...] -> [n, block, B, ...]; zeros do nothing
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x, 1, 0).reshape(n, block, b, *x.shape[2:])

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[..., None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + beta_t[..., None, None] * k_t[..., None] \
            * (v_t - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def many(state, xs):
        return jax.lax.scan(token, state, xs)

    _, out = jax.lax.scan(many, jnp.zeros((b, h, dk, v.shape[-1]), q.dtype),
                          tuple(steps(x) for x in (q, k, v, g, beta)))
    out = out.reshape(n * block, b, h, v.shape[-1])[:s]
    return jnp.moveaxis(out, 0, 1)


def causal_attention(q, k, v, scale):
    """Dense causal softmax attention, ``[B, S, H, D]`` in, a block of
    queries at a time against every key."""
    b, s, h, _ = q.shape
    n, block = _blocked(s, QUERY_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, n * block - s), (0, 0), (0, 0)))
    q = jnp.moveaxis(q.reshape(b, n, block, h, -1), 1, 0)
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def rows(args):
        q_blk, first = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) * scale
        visible = key_pos[None, :] <= (first + jnp.arange(block))[:, None]
        scores = jnp.where(visible, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(rows, (q, jnp.arange(n) * block))
    return jnp.moveaxis(out, 0, 1).reshape(b, n * block, h, -1)[:, :s]


def _kda_layer(s, p, x):
    b, t, _ = x.shape
    h, dk = s["kda_heads"], s["kda_head_dim"]
    heads = lambda y: y.reshape(b, t, h, dk)

    def conv_silu(y, kernel):
        taps = kernel.shape[0]
        padded = jnp.pad(y, ((0, 0), (taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(padded[:, i:i + t] * kernel[i]
                               for i in range(taps)))

    unit = lambda y: y * jax.lax.rsqrt(
        jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)
    q, k, v = (heads(conv_silu(x @ p[f"{n}_proj/kernel"], p[f"{n}_conv"]))
               for n in "qkv")
    q, k = unit(q) * dk ** -0.5, unit(k)
    g = -jnp.exp(p["A_log"])[:, None] * heads(jax.nn.softplus(
        x @ p["f_a/kernel"] @ p["f_b/kernel"] + p["dt_bias"]))
    beta = jax.nn.sigmoid(x @ p["b_proj/kernel"])
    o = _rms_norm(kda_recurrence(q, k, v, g, beta), p["o_norm/scale"],
                  s["eps"])
    gate = jax.nn.sigmoid(x @ p["g_a/kernel"] @ p["g_b/kernel"])
    return (o.reshape(b, t, h * dk) * gate) @ p["o_proj/kernel"]


def _mla_layer(s, p, x):
    b, t, _ = x.shape
    h, nope, rope, dv = (s["n_heads"], s["qk_nope_dim"], s["qk_rope_dim"],
                         s["v_dim"])
    q = (x @ p["q_proj/kernel"]).reshape(b, t, h, nope + rope)
    kv_a = x @ p["kv_a_proj/kernel"]
    latent = _rms_norm(kv_a[..., :s["kv_lora_rank"]], p["kv_a_norm/scale"],
                       s["eps"])
    kv = (latent @ p["kv_b_proj/kernel"]).reshape(b, t, h, nope + dv)
    k_pe = jnp.broadcast_to(kv_a[:, :, None, s["kv_lora_rank"]:],
                            (b, t, h, rope))    # shared, and not rotated
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    o = causal_attention(q, k, kv[..., nope:], (nope + rope) ** -0.5)
    return o.reshape(b, t, h * dv) @ p["o_proj/kernel"]


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
    y = _swiglu(x, p["shared/gate/kernel"], p["shared/up/kernel"],
                p["shared/down/kernel"]) if s["n_shared"] else \
        jnp.zeros_like(x)
    if not s["experts_held"]:
        return y

    @jax.checkpoint
    def one_expert(y, held):
        expert, gate, up, down = held
        on_it = jnp.sum(jnp.where(chosen == expert, weight, 0.0), axis=-1)
        return y + on_it[..., None] * _swiglu(x, gate, up, down), None

    # One loop body for all held experts (a `lax.scan` over their stacked
    # weights, rematerialised), so that the compiled check stays small
    # enough to be cached and holds one expert's activations at a time.
    y, _ = jax.lax.scan(one_expert, y, (
        jnp.asarray(s["experts_held"], jnp.int32), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y


def reference_loss(s: dict, params: dict, batch: dict):
    """Kimi-Linear's forward and next-token cross-entropy in f32
    `jax.numpy`, from the program's parameter tree.  Each block is
    rematerialised, so that one block's activations exist at a time."""
    def part(prefix):
        return {n[len(prefix):]: v for n, v in params.items()
                if n.startswith(prefix)}

    x = params["tok_embed/embedding"][batch["tokens"]]
    for i in range(s["n_layers"]):
        linear, dense = (i + 1) in s["kda_layers"], i < s["first_k_dense"]

        def block(x, p, linear=linear, dense=dense):
            y = _rms_norm(x, p["attn_norm/scale"], s["eps"])
            attn = {n[5:]: v for n, v in p.items() if n.startswith("attn/")}
            x = x + (_kda_layer if linear else _mla_layer)(s, attn, y)
            y = _rms_norm(x, p["mlp_norm/scale"], s["eps"])
            if dense:
                return x + _swiglu(y, p["mlp/gate/kernel"],
                                   p["mlp/up/kernel"], p["mlp/down/kernel"])
            moe = {n[4:]: v for n, v in p.items() if n.startswith("moe/")}
            return x + _moe_layer(s, moe, y)

        x = jax.checkpoint(block)(x, part(f"block_{i}/"))
    x = _rms_norm(x, params["final_norm/scale"], s["eps"])
    logp = jax.nn.log_softmax(x @ params["lm_head/kernel"], axis=-1)
    ll = jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1)
    return -jnp.mean(ll)
