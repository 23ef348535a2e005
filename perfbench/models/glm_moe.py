"""The `glm_moe` family: GLM-4.7-Flash (rotary latent attention with a
low-rank q, a mixture of experts with a shared expert, a multi-token-
prediction module that shares embedding and head) through the program's
`models.glm_moe.GlmMoeLM`, with its shape formulas and its plain reference.

What is the program's: the model, the loss (main + weighted MTP), the
rotation, the flash attention kernels and the grouped expert layer.  What
is the benchmark's: the sizes (from the configuration file), the FLOP and
byte formulas, and `reference_loss`: f32 `jax.numpy` that reads the same
parameter tree and is given the same share of the experts — dense causal
attention over blocks of queries, one masked pass over every token per held
expert, the head over blocks of tokens; no kernel, nothing from the
program's `ops/` or `models/`.  The low-rank q, the rotation and the MTP
module are written here from the equations; the helpers that Kimi-Linear's
reference already has (`_rms_norm`, `_swiglu`, `causal_attention`,
`_moe_layer`) are imported from it.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.models.kimi_linear import FLASH_KERNELS
from perfbench.models.kimi_linear import Family as _KimiFamily
from perfbench.models.kimi_linear import (_blocked, _moe_layer, _rms_norm,
                                          _swiglu, causal_attention)

UNIT = "tokens"
ROPE_SCOPE, MTP_SCOPE = "rope", "mtp"


def sizes(config: dict, rehearse: bool) -> dict:
    """The sizes as they are run: `config` with, in a rehearsal, its
    `rehearsal` group laid over it."""
    c = dict(config, **(config["rehearsal"] if rehearse else {}))
    return {
        "d_model": c["hidden_size"], "n_layers": c["num_layers"],
        "n_mtp": c["num_nextn_predict_layers"],
        "first_k_dense": c["first_k_dense_replace"],
        "d_ff": c["intermediate_size"],
        "d_expert": c["moe_intermediate_size"],
        "n_experts": c["n_routed_experts_published"],
        "experts_held": tuple(c["experts_held"]),
        "top_k": c["num_experts_per_tok"],
        "n_shared": c["n_shared_experts"],
        "routed_scale": c["routed_scaling_factor"],
        "n_heads": c["num_attention_heads"],
        "q_lora_rank": c["q_lora_rank"], "kv_lora_rank": c["kv_lora_rank"],
        "qk_nope_dim": c["qk_nope_head_dim"],
        "qk_rope_dim": c["qk_rope_head_dim"], "v_dim": c["v_head_dim"],
        "rope_theta": float(c["rope_theta"]),
        "vocab_size": c["vocab_size"], "eps": c["rms_norm_eps"],
    }


# -- shape formulas -----------------------------------------------------------


def block_params(s: dict) -> dict:
    """Parameters of each kind of part, counted from the shapes."""
    d, h = s["d_model"], s["n_heads"]
    qk = s["qk_nope_dim"] + s["qk_rope_dim"]
    mla_matmul = d * s["q_lora_rank"] + s["q_lora_rank"] * h * qk \
        + d * (s["kv_lora_rank"] + s["qk_rope_dim"]) \
        + s["kv_lora_rank"] * h * (s["qk_nope_dim"] + s["v_dim"]) \
        + h * s["v_dim"] * d
    expert = 3 * d * s["d_expert"]
    return {
        "mla_matmul": mla_matmul,
        # + the norms of the two latents
        "mla": mla_matmul + s["q_lora_rank"] + s["kv_lora_rank"],
        "dense_mlp": 3 * d * s["d_ff"],
        "expert": expert, "router": d * s["n_experts"],
        "moe": len(s["experts_held"]) * expert + s["n_shared"] * expert
        + d * s["n_experts"] + s["n_experts"],
        "norms": 2 * d,
        "eh_proj": 2 * d * d,
        "vocab": 2 * s["vocab_size"] * d,
    }


def total_params(s: dict) -> int:
    """Every parameter the chip holds and the optimizer updates: embedding,
    head and final norm once, the main layers, and an MTP module of
    ``eh_proj``, one expert block and three norms."""
    p = block_params(s)
    n = p["vocab"] + s["d_model"]
    for i in range(s["n_layers"]):
        n += p["mla"] + p["norms"]
        n += p["dense_mlp"] if i < s["first_k_dense"] else p["moe"]
    n += s["n_mtp"] * (p["eh_proj"] + p["mla"] + p["moe"] + p["norms"]
                       + 3 * s["d_model"])
    return n


def expert_layers(s: dict) -> int:
    """Expert layers a step runs: the main model's, then the MTP block's."""
    return s["n_layers"] - s["first_k_dense"] + s["n_mtp"]


def attention_layers(s: dict) -> int:
    return s["n_layers"] + s["n_mtp"]


def matmul_params(s: dict, routed: "float | None" = None) -> float:
    """Parameters that sit in a multiply-accumulate once per token, the
    head once per use (the MTP module passes through it again).  `routed`
    is the expert products a token an expert layer makes here; left out,
    their expected number: `top_k` assignments over `n_experts` experts of
    which `len(experts_held)` are here, 4 * 8 / 64 = 0.5 at the published
    sizes.  Embedding lookups, norms and biases do none."""
    p = block_params(s)
    if routed is None:
        routed = s["top_k"] * len(s["experts_held"]) / s["n_experts"]
    moe = p["router"] + (s["n_shared"] + routed) * p["expert"]
    head = float(s["d_model"] * s["vocab_size"])
    n = head + attention_layers(s) * p["mla_matmul"] \
        + s["first_k_dense"] * p["dense_mlp"] + expert_layers(s) * moe
    return n + s["n_mtp"] * (p["eh_proj"] + head)


def flops_per_sample(s: dict, seq_len: int,
                     routed: "float | None" = None) -> float:
    """FLOPs one token needs, forward and backward: 6 per matmul parameter
    (`routed`: see `matmul_params`), and causal attention in every MLA layer
    at 3.5 times its forward — QK^T over `nope + rope` columns and PV over
    `v_dim`, half of the dense square under the mask, forward; the backward's
    five products of the same sizes (the scores again, dP, dV, dQ, dK) count
    as what a backward that never holds the square needs, as `flash_work`
    counts them.  No rematerialised forward is counted."""
    width = s["qk_nope_dim"] + s["qk_rope_dim"] + s["v_dim"]
    attn = 3.5 * seq_len * s["n_heads"] * width * attention_layers(s)
    return 6.0 * matmul_params(s, routed) + attn


def flash_work(s: dict, batch: int, seq_len: int) -> dict:
    """Least work of the flash kernels of one step on one chip, causal, every
    MLA layer (the MTP block's too), at a q / k width D and a v width Dv
    (256 / 256 as published) — counted from the shape, whatever tiles run
    it.  A matrix product over the causal half of the square costs B * H *
    S * S * width FLOPs: forward QK^T (D) and PV (Dv); backward the scores
    again (D), dP (Dv), dV (Dv), dQ (D), dK (D).  Bytes in bf16: forward
    reads q, k, v and writes o and the row statistics; backward reads q, k,
    v, o, do and the statistics and writes dq, dk, dv."""
    h = s["n_heads"]
    d, dv = s["qk_nope_dim"] + s["qk_rope_dim"], s["v_dim"]
    square = float(batch) * h * seq_len * seq_len
    wide, narrow = (batch * seq_len * h * w * 2 for w in (d, dv))
    stats = batch * h * seq_len * 4
    layers = attention_layers(s)
    return {"flops": layers * square * (4 * d + 3 * dv),
            "bytes": layers * float(6 * wide + 6 * narrow + 2 * stats),
            "kernels": FLASH_KERNELS,
            "match": tuple(f'"kernel":"{k}"' for k in FLASH_KERNELS)}


# -- the family ---------------------------------------------------------------


class Family:
    unit = UNIT

    def __init__(self, config: dict, cell: dict, *, impl: str,
                 rehearse: bool):
        from pytorch_ps_mpi_tpu.models.glm_moe import (GlmMoeConfig,
                                                       GlmMoeLM, glm_aux)
        from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention

        self.s = s = sizes(config, rehearse)
        self.seq_len = cell["seq_len"]
        self.samples_per_row = self.seq_len
        self.tokens_per_step = cell["rows_per_chip"] * self.seq_len  # a chip
        # not in config.json: see the configuration's `assumed`
        self.mtp_weight = cell["mtp_loss_weight"]
        shape = {f.name: s[f.name]
                 for f in dataclasses.fields(GlmMoeConfig) if f.name in s}
        cfg = GlmMoeConfig(**shape, dtype=jnp.dtype(config["compute_dtype"]))
        scale = (s["qk_nope_dim"] + s["qk_rope_dim"]) ** -0.5
        self.model = GlmMoeLM(cfg, attn=functools.partial(
            flash_attention, causal=True, scale=scale, impl=impl))
        # The shapes do not depend on the attention: initialise densely.
        self._init_model = GlmMoeLM(GlmMoeConfig(**shape))
        self.shapes = {"seq_len": self.seq_len,
                       "vocab_size": s["vocab_size"]}
        self.aux = glm_aux(self.model)

    def init_params(self, seed: int) -> "dict[str, jax.Array]":
        """All parameters in one jitted call from the seed, f32 as they are
        trained; the initialising forward is short and dense."""
        from pytorch_ps_mpi_tpu.utils.flatten import named_params

        def init(key):
            tokens = jnp.zeros((1, 8), jnp.int32)
            return named_params(self._init_model.init(
                key, tokens, tokens, tokens)["params"])

        return jax.jit(init)(jax.random.PRNGKey(seed))

    def sync_loss(self):
        from pytorch_ps_mpi_tpu.models.glm_moe import make_glm_loss
        return make_glm_loss(self.model, self.mtp_weight), True

    def check_pair(self, mode: str):
        """(system loss, reference loss), both ``f(params, batch)``."""
        loss_aux, aux = self.sync_loss()[0], self.aux
        return (lambda p, b: loss_aux(p, aux, b)[0],
                functools.partial(reference_loss, self.s,
                                  mtp_weight=self.mtp_weight))

    def flops_per_sample(self) -> float:
        """With the expert products the steps counted, where they logged
        their load (the routing drifts towards the held experts while the
        cell trains, and the dropless layer's work follows it); with their
        expected number, 0.5 a token, before any step has run."""
        return flops_per_sample(self.s, self.seq_len, self._routed_counted())

    # Assignments on held experts per token and expert layer, mean over
    # every step this process logged: `moe_load` means here what it means
    # in Kimi-Linear's cell, and so does its reader.
    _routed_counted = _KimiFamily._routed_counted

    def kernel_work(self, rows_per_chip: int) -> dict:
        return {"flash": flash_work(self.s, rows_per_chip, self.seq_len)}


def build(config: dict, cell: dict, *, impl: str, rehearse: bool) -> Family:
    return Family(config, cell, impl=impl, rehearse=rehearse)


# -- the plain reference ------------------------------------------------------

HEAD_BLOCK = 1024    # tokens whose logits exist together


def rope(x, positions, theta: float):
    """``RoPE(x, pos)`` on the last axis (width d, pairs of columns ``i``
    and ``i + d/2``): the pair ``(a, b)`` becomes ``(a cos t - b sin t,
    b cos t + a sin t)`` with ``t = pos * theta^(-2i/d)``, the angle in f32
    and the products in ``x``'s own precision.  ``x: [B, S, ..., d]``,
    ``positions: [B, S]``."""
    d = x.shape[-1]
    freq = jnp.asarray(np.power(theta, -np.arange(0, d, 2) / d), jnp.float32)
    t = positions.astype(jnp.float32)[..., None] * freq        # [B, S, d/2]
    t = jnp.expand_dims(t, tuple(range(2, x.ndim - 1)))
    cos, sin = jnp.cos(t).astype(x.dtype), jnp.sin(t).astype(x.dtype)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mla_layer(s, p, x, positions):
    b, t, _ = x.shape
    h, nope, dv = s["n_heads"], s["qk_nope_dim"], s["v_dim"]
    r = s["qk_rope_dim"]
    c_q = _rms_norm(x @ p["q_a_proj/kernel"], p["q_a_norm/scale"], s["eps"])
    q = (c_q @ p["q_b_proj/kernel"]).reshape(b, t, h, nope + r)
    kv_a = x @ p["kv_a_proj/kernel"]
    c_kv = _rms_norm(kv_a[..., :s["kv_lora_rank"]], p["kv_a_norm/scale"],
                     s["eps"])
    kv = (c_kv @ p["kv_b_proj/kernel"]).reshape(b, t, h, nope + dv)
    q_r = rope(q[..., nope:], positions, s["rope_theta"])
    k_r = rope(kv_a[..., s["kv_lora_rank"]:], positions, s["rope_theta"])
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate(        # one rotated key, the same for every head
        [kv[..., :nope], jnp.broadcast_to(k_r[:, :, None], (b, t, h, r))],
        axis=-1)
    o = causal_attention(q, k, kv[..., nope:], (nope + r) ** -0.5)
    return o.reshape(b, t, h * dv) @ p["o_proj/kernel"]


def _block(s, p, x, positions, dense: bool):
    def part(prefix):
        return {n[len(prefix):]: v for n, v in p.items()
                if n.startswith(prefix)}

    x = x + _mla_layer(s, part("attn/"),
                       _rms_norm(x, p["attn_norm/scale"], s["eps"]),
                       positions)
    y = _rms_norm(x, p["mlp_norm/scale"], s["eps"])
    if dense:
        return x + _swiglu(y, p["mlp/gate/kernel"], p["mlp/up/kernel"],
                           p["mlp/down/kernel"])
    return x + _moe_layer(s, part("moe/"), y)


def _log_likelihood(x, head, targets):
    """``log softmax(x @ head)[target]`` per token, a block of tokens at a
    time so that the ``[tokens, vocabulary]`` logits never exist whole.
    ``x: [B, S, d]``, ``targets: [B, S]`` -> ``[B, S]``."""
    b, t, d = x.shape
    n, block = _blocked(b * t, HEAD_BLOCK)
    pad = n * block - b * t
    xs = jnp.pad(x.reshape(b * t, d), ((0, pad), (0, 0)))
    ys = jnp.pad(targets.reshape(b * t), (0, pad))

    @jax.checkpoint
    def rows(args):
        x_blk, y_blk = args
        logp = jax.nn.log_softmax(x_blk @ head, axis=-1)
        return jnp.take_along_axis(logp, y_blk[:, None], axis=-1)[:, 0]

    ll = jax.lax.map(rows, (xs.reshape(n, block, d), ys.reshape(n, block)))
    return ll.reshape(n * block)[:b * t].reshape(b, t)


def reference_losses(s: dict, params: dict, batch: dict):
    """``(loss_main, loss_mtp)`` of GLM-4.7-Flash in f32 `jax.numpy`, from
    the program's parameter tree.  Each block is rematerialised, so that
    one block's activations exist at a time."""
    def part(prefix):
        return {n[len(prefix):]: v for n, v in params.items()
                if n.startswith(prefix)}

    tokens, targets, pos = (batch[k] for k in ("tokens", "targets",
                                               "positions"))
    embedding, head = params["tok_embed/embedding"], params["lm_head/kernel"]
    block = jax.checkpoint(functools.partial(_block, s),
                           static_argnums=(3,))
    h = embedding[tokens]
    for i in range(s["n_layers"]):
        h = block(part(f"block_{i}/"), h, pos, i < s["first_k_dense"])
    loss_main = -jnp.mean(_log_likelihood(
        _rms_norm(h, params["final_norm/scale"], s["eps"]), head, targets))
    if not s["n_mtp"]:
        return loss_main, jnp.zeros((), jnp.float32)
    # Depth 1: at position i the state h_i (before the final norm) and the
    # embedding of t_{i+1} = targets[i] predict t_{i+2} = targets[i+1]; the
    # last position of a row has no such target.
    m = part("mtp/")
    joined = jnp.concatenate(
        [_rms_norm(embedding[targets], m["enorm/scale"], s["eps"]),
         _rms_norm(h, m["hnorm/scale"], s["eps"])], axis=-1)
    h2 = block({n[6:]: v for n, v in m.items() if n.startswith("block/")},
               joined @ m["eh_proj/kernel"], pos, False)
    ll = _log_likelihood(_rms_norm(h2, m["final_norm/scale"], s["eps"]),
                         head, jnp.roll(targets, -1, axis=1))
    return loss_main, -jnp.mean(ll[:, :-1])


def reference_loss(s: dict, params: dict, batch: dict, mtp_weight: float):
    loss_main, loss_mtp = reference_losses(s, params, batch)
    return loss_main + mtp_weight * loss_mtp
