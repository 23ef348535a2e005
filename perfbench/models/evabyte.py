"""The `evabyte` family: EvaByte (a byte-level decoder whose attention is EVA
— exact softmax inside a window of bytes and over one learnt summary a
16-byte chunk of every earlier window, one normaliser — and whose head
predicts the next eight bytes) through the program's
`models.evabyte.EvaByteLM`, with its shape formulas and its plain reference.

What is the program's: the model, the loss over eight shifted targets, the
rotation, the chunk summaries, the flash kernels over the windows with their
row statistics and the join.  What is the benchmark's: the sizes (from the
configuration file), the FLOP and byte formulas, and `reference_loss`:
`jax.numpy` in the precision of the parameters it is given (f32 in the
check) that reads the same parameter tree and the same share of the heads —
the summaries from the equations, one masked softmax a block of queries
over the bytes and the summaries side by side with the masks built from
``t // window`` and ``c`` directly, eight plain cross-entropies; no kernel,
no join of partial softmaxes, no `nn.remat`, nothing from the program's
`ops/` or `models/`.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from perfbench.models.glm_moe import rope
from perfbench.models.kimi_linear import _blocked, _swiglu

UNIT = "tokens"
ATTN_SCOPE, LOCAL_SCOPE, SUMMARY_SCOPE = "eva_attn", "eva_local", \
    "eva_summary"


def sizes(config: dict, rehearse: bool) -> dict:
    """The sizes as they are run: `config` with, in a rehearsal, its
    `rehearsal` group laid over it.  ``n_heads`` is the heads held here."""
    c = dict(config, **(config["rehearsal"] if rehearse else {}))
    return {
        "d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
        "n_layers": c["num_layers"], "n_heads": c["num_attention_heads"],
        "head_dim": c["head_dim"], "window": c["window_size"],
        "chunk": c["chunk_size"], "n_pred_heads": c["num_pred_heads"],
        "vocab_size": c["vocab_size"], "rope_theta": c["rope_theta"],
        "eps": c["rms_norm_eps"],
    }


# -- shape formulas -----------------------------------------------------------


def layer_params(s: dict) -> dict:
    """Parameters of one layer's parts, counted from the shapes."""
    d, held = s["d_model"], s["n_heads"] * s["head_dim"]
    return {"attn_matmul": 4 * d * held,       # W_q, W_k, W_v, W_o
            "pooling": 2 * held,               # phi and mu, a head each
            "mlp": 3 * d * s["d_ff"],
            "norms": 2 * d}


def head_params(s: dict) -> int:
    return s["d_model"] * s["n_pred_heads"] * s["vocab_size"]


def total_params(s: dict) -> int:
    """Every parameter the chip holds and the optimizer updates."""
    return s["n_layers"] * sum(layer_params(s).values()) \
        + s["vocab_size"] * s["d_model"] + head_params(s) + s["d_model"]


def matmul_params(s: dict) -> int:
    """Parameters that sit in a multiply-accumulate once per token: the
    projections, the MLP and the eight heads; the lookup, the norms, ``phi``
    and ``mu`` do none."""
    p = layer_params(s)
    return s["n_layers"] * (p["attn_matmul"] + p["mlp"]) + head_params(s)


def attended_pairs(seq_len: int, window: int, chunk: int) -> "tuple[int, int]":
    """``(local, remote)`` pairs a head of ``seq_len`` rows keeps: a byte
    with the bytes of its own window up to itself, and with one summary a
    chunk of every earlier window."""
    local = remote = 0
    for w, start in enumerate(range(0, seq_len, window)):
        rows = min(window, seq_len - start)
        local += rows * (rows + 1) // 2
        remote += rows * w * (window // chunk)
    return local, remote


def flops_per_sample(s: dict, seq_len: int) -> float:
    """FLOPs one byte needs, forward and backward: 6 per matmul parameter;
    attention at 3.0 times its forward (the convention of `gpt2`,
    `kimi_linear` and `sambay`), the forward being ``QK^T`` and ``PV``, each
    a head's width, for the heads held, over the local and the summaries'
    pairs.  The pooling and the rematerialised forward are not counted."""
    pairs = sum(attended_pairs(seq_len, s["window"], s["chunk"]))
    per_pair = 2.0 * s["n_heads"] * 2 * s["head_dim"]
    return 6.0 * matmul_params(s) \
        + 3.0 * s["n_layers"] * per_pair * pairs / seq_len


def _attention_work(s: dict, batch: int, seq_len: int, remote: bool) -> dict:
    """Least work of the attention of one step, all layers, forward and
    backward, at the true widths (128 needs no padding), whatever
    implements it.  Seven matrix products of ``2 pairs width`` FLOPs a head:
    forward ``QK^T`` and ``PV``; backward the scores again, dP, dV, dQ, dK.
    Bytes in bf16 for one pass that keeps the scores on the chip: forward
    reads q, k, v and writes o and the row statistics; backward reads q, k,
    v, o, do, the statistics and their cotangent and writes dq, dk, dv; with
    the summaries (1 / chunk of k and v) read twice and their gradients
    written once."""
    h, d = s["n_heads"], s["head_dim"]
    local, far = attended_pairs(seq_len, s["window"], s["chunk"])
    pairs = float(batch) * h * (local + far * remote)
    array = batch * seq_len * h * d * 2
    stats = batch * seq_len * h * 4
    summaries = 2 * array // s["chunk"] * remote
    return {"flops": s["n_layers"] * 2.0 * pairs * 7 * d,
            "bytes": s["n_layers"] * float(12 * array + 3 * stats
                                           + 3 * summaries)}


def eva_attn_work(s: dict, batch: int, seq_len: int) -> dict:
    """Both key sets and the join: the `eva_attn` scope."""
    return dict(_attention_work(s, batch, seq_len, True), scope=ATTN_SCOPE)


def eva_local_flash_work(s: dict, batch: int, seq_len: int) -> dict:
    """The windows' own bytes alone: the flash calls, the `eva_local`
    scope."""
    return dict(_attention_work(s, batch, seq_len, False), scope=LOCAL_SCOPE)


def eva_summary_work(s: dict, batch: int, seq_len: int) -> dict:
    """The pooling: a key's score against ``phi`` (2 d FLOPs) and its part
    of the two weighted sums (2 d each), three times that with the backward;
    it reads k and v and writes the summaries, and the backward reads k, v
    and the summaries' gradients and writes dk and dv (bf16; the summaries
    are 1 / chunk of that).  Memory-bound."""
    array = batch * seq_len * s["n_heads"] * s["head_dim"]
    small = 2 * array // s["chunk"]
    return {"flops": s["n_layers"] * 3.0 * 6 * array,
            "bytes": s["n_layers"] * 2.0 * (6 * array + 2 * small),
            "scope": SUMMARY_SCOPE}


# -- the family ---------------------------------------------------------------


class Family:
    unit = UNIT

    def __init__(self, config: dict, cell: dict, *, impl: str,
                 rehearse: bool):
        from pytorch_ps_mpi_tpu.models.evabyte import (EvaByteConfig,
                                                       EvaByteLM, evabyte_aux)
        from pytorch_ps_mpi_tpu.ops.eva_attention import eva_attention

        self.s = s = sizes(config, rehearse)
        self.seq_len = cell["seq_len"]
        self.samples_per_row = self.seq_len
        self.tokens_per_step = cell["rows_per_chip"] * self.seq_len  # a chip
        shape = {f.name: s[f.name]
                 for f in dataclasses.fields(EvaByteConfig) if f.name in s}
        cfg = EvaByteConfig(**shape, dtype=jnp.dtype(config["compute_dtype"]))
        self.model = EvaByteLM(cfg, attn=functools.partial(eva_attention,
                                                           impl=impl))
        # The shapes do not depend on the attention: initialise with the
        # plain form on one chunk of bytes.
        self._init_model = EvaByteLM(EvaByteConfig(**shape))
        self.shapes = {"seq_len": self.seq_len,
                       "vocab_size": s["vocab_size"]}
        self.aux = evabyte_aux(self.model)

    def init_params(self, seed: int) -> "dict[str, jax.Array]":
        """All parameters in one jitted call from the seed, f32 as they are
        trained."""
        from pytorch_ps_mpi_tpu.utils.flatten import named_params

        def init(key):
            tokens = jnp.zeros((1, self.s["chunk"]), jnp.int32)
            return named_params(self._init_model.init(key, tokens)["params"])

        return jax.jit(init)(jax.random.PRNGKey(seed))

    def sync_loss(self):
        from pytorch_ps_mpi_tpu.models.evabyte import make_evabyte_loss
        return make_evabyte_loss(self.model), True

    def check_pair(self, mode: str):
        """(system loss, reference loss), both ``f(params, batch)``."""
        loss_aux, aux = self.sync_loss()[0], self.aux
        return (lambda p, b: loss_aux(p, aux, b)[0],
                functools.partial(reference_loss, self.s))

    def flops_per_sample(self) -> float:
        return flops_per_sample(self.s, self.seq_len)

    def kernel_work(self, rows_per_chip: int) -> dict:
        work = (self.s, rows_per_chip, self.seq_len)
        return {"eva_attn": eva_attn_work(*work),
                "eva_local_flash": eva_local_flash_work(*work),
                "eva_summary": eva_summary_work(*work)}


def build(config: dict, cell: dict, *, impl: str, rehearse: bool) -> Family:
    return Family(config, cell, impl=impl, rehearse=rehearse)


# -- the plain reference ------------------------------------------------------

QUERY_BLOCK = 128    # queries whose score rows exist together


def _rms_norm(x, g, eps):
    """``x / sqrt(mean(x^2) + eps) (1 + g)``."""
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1 + g)


def summaries(k, v, phi, mu, chunk: int):
    """``k~_c = sum_j pi_j k_j + mu``, ``v~_c = sum_j pi_j v_j`` with ``pi =
    softmax_{j in c}(d^-1/2 phi . k_j)``.  ``k, v: [B, S, H, D]``, ``phi, mu:
    [H, D]`` -> two ``[B, S / chunk, H, D]``."""
    b, s, h, d = k.shape
    k = k.reshape(b, s // chunk, chunk, h, d)
    v = v.reshape(b, s // chunk, chunk, h, d)
    pi = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", k, phi) * d ** -0.5,
                        axis=2)
    return jnp.einsum("bnch,bnchd->bnhd", pi, k) + mu, \
        jnp.einsum("bnch,bnchd->bnhd", pi, v)


def eva_softmax(q, k, v, k_sum, v_sum, window: int, chunk: int):
    """One softmax a query over ``L(t) = {j : j // window = t // window, j
    <= t}`` and ``R(t) = {c : (c + 1) chunk <= (t // window) window}``, a
    block of queries at a time against every byte and every summary.
    ``q, k, v: [B, S, H, D]`` -> ``[B, S, H, D]``."""
    b, s, h, d = q.shape
    n, block = _blocked(s, QUERY_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, n * block - s), (0, 0), (0, 0)))
    q = jnp.moveaxis(q.reshape(b, n, block, h, d), 1, 0)
    keys = jnp.concatenate([k, k_sum], axis=1)
    values = jnp.concatenate([v, v_sum], axis=1)
    byte, last = jnp.arange(s), (jnp.arange(k_sum.shape[1]) + 1) * chunk

    @jax.checkpoint
    def rows(args):
        q_blk, first = args
        t = jnp.minimum(first + jnp.arange(block), s - 1)[:, None]
        local = (byte[None, :] // window == t // window) & (byte[None, :] <= t)
        remote = last[None, :] <= (t // window) * window
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, keys) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(
            jnp.concatenate([local, remote], axis=1), scores, -jnp.inf),
            axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, values)

    out = jax.lax.map(rows, (q, jnp.arange(n) * block))
    return jnp.moveaxis(out, 0, 1).reshape(b, n * block, h, d)[:, :s]


def _attention_layer(s, p, u, positions):
    b, t, _ = u.shape
    h, d = s["n_heads"], s["head_dim"]
    q, k, v = ((u @ p[f"{name}_proj/kernel"]).reshape(b, t, h, d)
               for name in "qkv")
    q = rope(q, positions, s["rope_theta"])
    k = rope(k, positions, s["rope_theta"])
    k_sum, v_sum = summaries(k, v, p["phi"], p["mu"], s["chunk"])
    o = eva_softmax(q, k, v, k_sum, v_sum, s["window"], s["chunk"])
    return o.reshape(b, t, h * d) @ p["o_proj/kernel"]


def _block(s, p, x, positions):
    attn = {n[5:]: v for n, v in p.items() if n.startswith("attn/")}
    x = x + _attention_layer(
        s, attn, _rms_norm(x, p["attn_norm/scale"], s["eps"]), positions)
    u = _rms_norm(x, p["mlp_norm/scale"], s["eps"])
    return x + _swiglu(u, p["mlp/gate/kernel"], p["mlp/up/kernel"],
                       p["mlp/down/kernel"])


def reference_losses(s: dict, params: dict, batch: dict):
    """``[n_pred_heads]``: each head's mean cross-entropy, head ``i``
    against byte ``t + 1 + i`` (``targets`` from column ``i`` on), over the
    positions of a row that have one; in the parameters' own precision,
    from the program's parameter tree.  Each block is rematerialised so
    that one block's activations exist at a time."""
    x = params["tok_embed/embedding"][batch["tokens"]]
    for i in range(s["n_layers"]):
        prefix = f"block_{i}/"
        x = jax.checkpoint(functools.partial(_block, s))(
            {n[len(prefix):]: v for n, v in params.items()
             if n.startswith(prefix)}, x, batch["positions"])
    y = _rms_norm(x, params["final_norm/scale"], s["eps"])
    targets, vocab, length = batch["targets"], s["vocab_size"], x.shape[1]
    losses = []
    for i in range(s["n_pred_heads"]):
        head = params["lm_head/kernel"][:, i * vocab:(i + 1) * vocab]
        logp = jax.nn.log_softmax(y[:, :length - i] @ head, axis=-1)
        losses.append(-jnp.mean(jnp.take_along_axis(
            logp, targets[:, i:, None], axis=-1)))
    return jnp.stack(losses)


def reference_loss(s: dict, params: dict, batch: dict):
    return jnp.mean(reference_losses(s, params, batch))
