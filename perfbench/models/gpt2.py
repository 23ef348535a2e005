"""The `gpt2` family: GPT-2 (Radford et al. 2019) through the program's
`models.transformer.TransformerLM`, with its shape formulas and its plain
reference.

What is the program's: the model, the loss and the flash attention kernels.
What is the benchmark's: the sizes (from the configuration file), the FLOP
and byte formulas, and `reference_loss`, a straightforward f32 `jax.numpy`
forward with dense attention that reads the same parameter tree.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
import numpy as np

UNIT = "tokens"
FLASH_KERNELS = ("_fwd_kernel", "_bwd_dkdv_kernel", "_bwd_dq_kernel")
# The trace names a Pallas kernel's event by its HLO custom call and carries
# no kernel name (`kernel_metadata={}`: the program's `pallas_call`s give no
# `name=`).  The flash kernels are the only Mosaic calls of this model's
# step (72 a step: 24 layers x 3), so they are found by the call's target.
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


def sizes(config: dict, rehearse: bool) -> dict:
    s = {k: config[k] for k in ("n_embd", "n_layer", "n_head", "n_positions",
                                "vocab_size", "layer_norm_epsilon")}
    s["n_inner"] = config.get("n_inner") or 4 * s["n_embd"]
    if rehearse:
        s.update(config["rehearsal"])
    return s


def matmul_params(s: dict) -> int:
    """Parameters that sit in a matrix multiplication once per token: the
    four projections of each block and the output head.  Embedding lookups,
    biases and LayerNorms do no multiply-accumulate per parameter."""
    d, f = s["n_embd"], s["n_inner"]
    return s["n_layer"] * (3 * d * d + d * d + 2 * d * f) \
        + d * s["vocab_size"]


def flops_per_sample(s: dict, seq_len: int) -> float:
    """Forward and backward FLOPs one token needs: 6 per matmul parameter,
    plus causal attention (QK^T and PV, 2 * S * d each when dense, half of
    that under the causal mask, three times for forward and backward).
    Recomputation inside the flash backward is not counted."""
    attn = 3 * 2 * seq_len * s["n_embd"] * s["n_layer"]
    return 6.0 * matmul_params(s) + attn


def flash_work(s: dict, batch: int, seq_len: int) -> dict:
    """Least work of the flash kernels of one step on one chip, for causal
    attention over ``[B, S, H, D]`` in bf16, all layers: forward 2 matmuls,
    backward 5 (one recomputation of the scores is part of the algorithm),
    each 2*B*H*S*S*D FLOPs dense and half under the mask.  Bytes: the
    forward reads q, k, v and writes o and the row statistics; the backward
    reads q, k, v, o, do and the statistics and writes dq, dk, dv."""
    h, dh = s["n_head"], s["n_embd"] // s["n_head"]
    unit = 2.0 * batch * h * seq_len * seq_len * dh / 2.0
    tensor = batch * seq_len * h * dh * 2
    stats = batch * h * seq_len * 4
    layers = s["n_layer"]
    return {"flops": layers * 7.0 * unit,
            "bytes": layers * float(4 * tensor + stats + 8 * tensor + stats),
            "kernels": FLASH_KERNELS, "match": (MOSAIC_CALL,)}


class Family:
    unit = UNIT

    def __init__(self, config: dict, cell: dict, *, impl: str,
                 rehearse: bool):
        from pytorch_ps_mpi_tpu.models.transformer import TransformerLM
        from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention

        self.s = sizes(config, rehearse)
        self.seq_len = min(cell["seq_len"], self.s["n_positions"])
        self.samples_per_row = self.seq_len
        dtype = jnp.dtype(config["compute_dtype"])
        kw = dict(vocab_size=self.s["vocab_size"], d_model=self.s["n_embd"],
                  n_heads=self.s["n_head"], n_layers=self.s["n_layer"],
                  d_ff=self.s["n_inner"], max_len=self.s["n_positions"],
                  dtype=dtype)
        self.model = TransformerLM(**kw, attn=functools.partial(
            flash_attention, causal=True, impl=impl))
        self._init_model = TransformerLM(**kw)
        self.shapes = {"seq_len": self.seq_len,
                       "vocab_size": self.s["vocab_size"]}
        self.aux = None

    def init_params(self, seed: int) -> "dict[str, jax.Array]":
        """All parameters in one jitted call from the seed, f32 as they are
        trained.  The shapes do not depend on the attention used or on the
        sequence length, so the initialising forward is dense and short."""
        from pytorch_ps_mpi_tpu.utils.flatten import named_params

        def init(key):
            tokens = jnp.zeros((1, 8), jnp.int32)
            return named_params(self._init_model.init(key, tokens)["params"])

        return jax.jit(init)(jax.random.PRNGKey(seed))

    def sync_loss(self):
        from pytorch_ps_mpi_tpu.models.transformer import make_lm_loss
        return make_lm_loss(self.model), False

    def async_loss(self):
        return self.sync_loss()[0]

    def check_pair(self, mode: str):
        """(system loss, reference loss), both ``f(params, batch)``."""
        return self.sync_loss()[0], functools.partial(reference_loss, self.s)

    def flops_per_sample(self) -> float:
        return flops_per_sample(self.s, self.seq_len)

    def kernel_work(self, rows_per_chip: int) -> dict:
        return {"flash": flash_work(self.s, rows_per_chip, self.seq_len)}


def build(config: dict, cell: dict, *, impl: str, rehearse: bool) -> Family:
    return Family(config, cell, impl=impl, rehearse=rehearse)


# -- the plain reference ------------------------------------------------------


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def reference_loss(s: dict, params: dict, batch: dict):
    """GPT-2's forward and next-token cross-entropy in f32 `jax.numpy`:
    pre-LN blocks, learned positions, dense causal softmax attention,
    `gelu_new`.  Reads the program's parameter tree, so it shares the
    layout of the fused QKV projection (per head: q | k | v) and the
    untied, biased head — departures from the published model that the
    configuration file lists."""
    p = params
    eps = s["layer_norm_epsilon"]
    h = s["n_head"]
    tokens, targets, positions = (batch["tokens"], batch["targets"],
                                  batch["positions"])
    b, t = tokens.shape
    x = p["tok_embed/embedding"][tokens] + p["pos_embed/embedding"][positions]
    dh = x.shape[-1] // h
    mask = jnp.tril(jnp.ones((t, t), bool))

    def block(x, bp):
        y = _layer_norm(x, bp["LayerNorm_0/scale"], bp["LayerNorm_0/bias"],
                        eps)
        qkv = y @ bp["qkv/kernel"] + bp["qkv/bias"]
        qkv = qkv.reshape(b, t, h, 3, dh)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
        scores = jnp.where(mask, scores, -jnp.inf)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + att.reshape(b, t, h * dh) @ bp["out/kernel"] + bp["out/bias"]
        y = _layer_norm(x, bp["LayerNorm_1/scale"], bp["LayerNorm_1/bias"],
                        eps)
        y = _gelu_new(y @ bp["fc1/kernel"] + bp["fc1/bias"])
        return x + y @ bp["fc2/kernel"] + bp["fc2/bias"], None

    # The blocks are one loop over their stacked parameters, rematerialised
    # per block: the same arithmetic as 24 blocks written out, but the
    # program stays small (it is compiled and cached beside the step it
    # vouches for) and the dense f32 score matrices of 24 layers never sit
    # in memory together.
    suffixes = sorted(n[len("block_0/"):] for n in p if n.startswith("block_0/"))
    stacked = {sfx: jnp.stack([p[f"block_{i}/{sfx}"]
                               for i in range(s["n_layer"])])
               for sfx in suffixes}
    x, _ = jax.lax.scan(jax.checkpoint(block), x, stacked)
    x = _layer_norm(x, p["LayerNorm_0/scale"], p["LayerNorm_0/bias"], eps)
    logits = x @ p["lm_head/kernel"] + p["lm_head/bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)
