"""The `sambay` family: Phi-4-mini-flash-reasoning (SambaY with differential
attention: Mamba-1 scans, window and full attention, a cross-decoder of
gated memory units and cross-attention over one layer's keys, values and
scan output) through the program's `models.sambay.SambaYLM`, with its shape
formulas and its plain reference.

What is the program's: the model, the loss, the blocked scan, the flash
attention kernels with their window and the two-call differential
combination.  What is the benchmark's: the sizes (from the configuration
file), the FLOP and byte formulas, and `reference_loss`: `jax.numpy` in the
precision of the parameters it is given (f32 in the check) that reads the
same parameter tree — the state-space recurrence token by token, dense
band-masked softmax attention over blocks of queries with the differential
combination written as ``(A1 - lam A2) V``, the head over blocks of tokens;
no kernel, no blocked scan, nothing from the program's `ops/` or `models/`.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from perfbench.models.glm_moe import _log_likelihood
from perfbench.models.kimi_linear import _blocked, _swiglu

UNIT = "tokens"
SSM_SCOPE, SWA_SCOPE, FULL_SCOPE = "ssm", "swa", "full_attn"
DIFF_SCOPE, GMU_SCOPE = "diff", "gmu"
ATTENTION_KINDS = ("swa", "full_kv", "cross")


def layer_kind(index: int, published_layers: int, mb_per_layer: int) -> str:
    """The kind of published layer ``index``, by the modeling code's rule:
    ``use_mamba = index % mb_per_layer == 0``; the second half is the
    cross-decoder, whose first layer is the Mamba that leaves the memory,
    whose second the full attention that leaves keys and values, and whose
    others read those (``yoco_mb``, ``yoco_kv``, ``yoco_cross``)."""
    half = published_layers // 2
    scans = index % mb_per_layer == 0
    if index < half:
        return "mamba" if scans else "swa"
    if index == half:
        return "mamba_memory"
    if index == half + 1:
        return "full_kv"
    return "gmu" if scans else "cross"


def sizes(config: dict, rehearse: bool) -> dict:
    """The sizes as they are run: `config` with, in a rehearsal, its
    `rehearsal` group laid over it."""
    c = dict(config, **(config["rehearsal"] if rehearse else {}))
    d = c["hidden_size"]
    return {
        "d_model": d, "d_ff": c["intermediate_size"],
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"],
        "head_dim": d // c["num_attention_heads"],
        "window": c["sliding_window"],
        "layers": tuple(
            (layer_kind(i, c["num_hidden_layers"],
                        c["mb_per_layer"]), i) for i in c["layers_kept"]),
        "d_inner": c["mamba_expand"] * d, "d_state": c["mamba_d_state"],
        "d_conv": c["mamba_d_conv"], "dt_rank": c["mamba_dt_rank"],
        "vocab_size": c["vocab_size"], "eps": c["layer_norm_eps"],
    }


# -- shape formulas -----------------------------------------------------------


def _kinds(s: dict) -> list:
    return [kind for kind, _ in s["layers"]]


def block_params(s: dict) -> dict:
    """Parameters of each kind of part, counted from the shapes."""
    d, di, n, r = s["d_model"], s["d_inner"], s["d_state"], s["dt_rank"]
    h, hk, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    mamba_matmul = d * 2 * di + s["d_conv"] * di + di * (r + 2 * n) \
        + r * di + di * d
    attn_matmul = d * (h + 2 * hk) * hd + h * hd * d
    cross_matmul = d * h * hd + h * hd * d
    # four lambda vectors a head wide and the 2-heads-wide norm's scale
    diff = 4 * hd + 2 * hd
    return {
        "mamba_matmul": mamba_matmul,
        # + the convolution's bias, dt's bias, A_log and D
        "mamba": mamba_matmul + di + di + di * n + di,
        "attn_matmul": attn_matmul,
        "attn": attn_matmul + (h + 2 * hk) * hd + d + diff,
        "cross_matmul": cross_matmul,
        "cross": cross_matmul + h * hd + d + diff,
        "gmu_matmul": 2 * d * di, "gmu": 2 * d * di,
        "mlp": 3 * d * s["d_ff"],
        "norms": 4 * d,             # two LayerNorms, scale and bias
        "vocab": s["vocab_size"] * d,
    }


_PART_OF = {"mamba": "mamba", "mamba_memory": "mamba", "swa": "attn",
            "full_kv": "attn", "gmu": "gmu", "cross": "cross"}


def total_params(s: dict) -> int:
    """Every parameter the chip holds and the optimizer updates: the tied
    embedding once, the final norm, and each layer by its kind."""
    p = block_params(s)
    n = p["vocab"] + 2 * s["d_model"]
    for kind in _kinds(s):
        n += p[_PART_OF[kind]] + p["mlp"] + p["norms"]
    return n


def matmul_params(s: dict) -> int:
    """Parameters that sit in a multiply-accumulate once per token, the
    head (the embedding's transpose) once; the lookup, norms, biases,
    ``A_log``, ``D`` and the lambdas do none."""
    p = block_params(s)
    n = p["vocab"]
    for kind in _kinds(s):
        n += p[_PART_OF[kind] + "_matmul"] + p["mlp"]
    return n


def attended_pairs(seq_len: int, window: "int | None") -> float:
    """(query, key) pairs a head of ``seq_len`` rows keeps: half the square
    under the causal mask; under a window the band ``S W - W^2 / 2``."""
    if window is None or window >= seq_len:
        return seq_len * seq_len / 2.0
    return seq_len * float(window) - window * window / 2.0


def ssm_flops_per_token(s: dict) -> float:
    """The recurrence as it is defined, one token of one layer, forward: a
    state entry's ``dt A``, its exponential, the decay, ``(dt x) B``, the
    sum, and its multiply-add into ``y``: 7 a state entry."""
    return 7.0 * s["d_inner"] * s["d_state"]


def flops_per_sample(s: dict, seq_len: int) -> float:
    """FLOPs one token needs, forward and backward: 6 per matmul parameter;
    attention at 3.0 times its forward (Kimi-Linear's and GPT-2's
    convention; GLM-4.7-Flash counts 3.5), the forward being ``QK^T`` over
    a head's width and ``PV`` over twice that, for ``n_heads`` heads, over
    the pairs the mask keeps — the band and not the square in the window
    layers; the scan at 3 times its forward.  No rematerialised forward is
    counted."""
    kinds = _kinds(s)
    per_pair = 2.0 * s["n_heads"] * 3 * s["head_dim"]     # D + 2 D wide
    pairs = sum(attended_pairs(seq_len, s["window"] if k == "swa" else None)
                for k in kinds if k in ATTENTION_KINDS)
    scans = sum(k in ("mamba", "mamba_memory") for k in kinds)
    return 6.0 * matmul_params(s) + 3.0 * per_pair * pairs / seq_len \
        + 3.0 * ssm_flops_per_token(s) * scans


def ssm_work(s: dict, batch: int, seq_len: int) -> dict:
    """Least work of the selective scan of one step on one chip, both Mamba
    layers, whatever implements it: the token-by-token FLOPs (forward, and
    twice that backward), and the bytes of an implementation that keeps the
    state on the chip: forward it reads x (bf16), dt (f32), B and C (f32)
    and writes y (bf16); backward it reads those and dy again and writes dx
    (bf16), ddt (f32), dB and dC (f32); A, D and their gradients once.
    Projections, the convolution and the gate are not part of it."""
    tokens = batch * seq_len
    wide, narrow = tokens * s["d_inner"], tokens * s["d_state"]
    small = s["d_inner"] * (s["d_state"] + 1) * 4
    forward = wide * (2 + 4 + 2) + narrow * 8 + small
    backward = wide * (2 + 4 + 2) + narrow * 8 + wide * (2 + 4) \
        + narrow * 8 + 2 * small
    layers = sum(k in ("mamba", "mamba_memory") for k in _kinds(s))
    return {"flops": layers * 3.0 * ssm_flops_per_token(s) * tokens,
            "bytes": layers * float(forward + backward), "scope": SSM_SCOPE}


def _flash_work(s: dict, batch: int, seq_len: int, kinds: tuple,
                window: "int | None", scope: str) -> dict:
    """Least work of the attention calls of the layers of ``kinds``: at the
    true widths (q / k a head wide, v two heads wide, no lane padding), over
    the pairs the mask keeps.  A matrix product over those pairs costs 2 *
    pairs * width FLOPs a head: forward ``QK^T`` (D) and ``PV`` (2 D);
    backward the scores again (D), dP (2 D), dV (2 D), dQ (D), dK (D).
    Bytes in bf16 with each kv head read once, not once a query head:
    forward reads q, k, v and writes o and the row statistics; backward
    reads q, k, v, o, do and the statistics and writes dq, dk, dv."""
    h, hk, d = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    layers = sum(k in kinds for k in _kinds(s))
    pairs = float(batch) * h * attended_pairs(seq_len, window)
    tokens = batch * seq_len
    q, kv, o = tokens * h * d * 2, tokens * hk * d * 2, tokens * h * 2 * d * 2
    stats = tokens * h * 4
    return {"flops": layers * 2.0 * pairs * (4 * d + 3 * 2 * d),
            "bytes": layers * float(3 * q + 6 * kv + 3 * o + 2 * stats),
            "scope": scope}


def swa_flash_work(s: dict, batch: int, seq_len: int) -> dict:
    return _flash_work(s, batch, seq_len, ("swa",), s["window"], SWA_SCOPE)


def full_flash_work(s: dict, batch: int, seq_len: int) -> dict:
    return _flash_work(s, batch, seq_len, ("full_kv", "cross"), None,
                       FULL_SCOPE)


# -- the family ---------------------------------------------------------------


class Family:
    unit = UNIT

    def __init__(self, config: dict, cell: dict, *, impl: str,
                 rehearse: bool):
        from pytorch_ps_mpi_tpu.models.sambay import (SambaYConfig, SambaYLM,
                                                      sambay_aux)
        from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention

        self.s = s = sizes(config, rehearse)
        self.seq_len = cell["seq_len"]
        self.samples_per_row = self.seq_len
        self.tokens_per_step = cell["rows_per_chip"] * self.seq_len  # a chip
        shape = {f.name: s[f.name]
                 for f in dataclasses.fields(SambaYConfig) if f.name in s}
        cfg = SambaYConfig(**shape, dtype=jnp.dtype(config["compute_dtype"]))
        self.model = SambaYLM(cfg, attn=functools.partial(
            flash_attention, causal=True, scale=s["head_dim"] ** -0.5,
            impl=impl))
        # The shapes do not depend on the attention: initialise densely.
        self._init_model = SambaYLM(SambaYConfig(**shape))
        self.shapes = {"seq_len": self.seq_len,
                       "vocab_size": s["vocab_size"]}
        self.aux = sambay_aux(self.model)

    def init_params(self, seed: int) -> "dict[str, jax.Array]":
        """All parameters in one jitted call from the seed, f32 as they are
        trained; the initialising forward is short and dense."""
        from pytorch_ps_mpi_tpu.utils.flatten import named_params

        def init(key):
            tokens = jnp.zeros((1, 8), jnp.int32)
            return named_params(self._init_model.init(key, tokens)["params"])

        return jax.jit(init)(jax.random.PRNGKey(seed))

    def sync_loss(self):
        from pytorch_ps_mpi_tpu.models.sambay import make_sambay_loss
        return make_sambay_loss(self.model), True

    def check_pair(self, mode: str):
        """(system loss, reference loss), both ``f(params, batch)``."""
        loss_aux, aux = self.sync_loss()[0], self.aux
        return (lambda p, b: loss_aux(p, aux, b)[0],
                functools.partial(reference_loss, self.s))

    def flops_per_sample(self) -> float:
        return flops_per_sample(self.s, self.seq_len)

    def kernel_work(self, rows_per_chip: int) -> dict:
        work = (self.s, rows_per_chip, self.seq_len)
        return {"ssm": ssm_work(*work), "swa_flash": swa_flash_work(*work),
                "full_flash": full_flash_work(*work)}


def build(config: dict, cell: dict, *, impl: str, rehearse: bool) -> Family:
    return Family(config, cell, impl=impl, rehearse=rehearse)


# -- the plain reference ------------------------------------------------------

TOKEN_BLOCK = 64     # tokens whose states are recomputed together
QUERY_BLOCK = 128    # queries whose score rows exist together


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def ssm_recurrence(x, dt, a, b_in, c_out):
    """``h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t^T; y_t = h_t C_t``, one
    token at a time from ``h_0 = 0``.  ``x, dt: [B, S, d_inner]``, ``a:
    [d_inner, N]``, ``b_in, c_out: [B, S, N]`` -> ``[B, S, d_inner]``.  A
    scan over blocks of tokens of a scan over tokens, the outer body
    rematerialised, so that the backward pass holds one state a block and
    not one a token."""
    rows, s, _ = x.shape
    n, block = _blocked(s, TOKEN_BLOCK)
    pad = n * block - s

    def steps(y):       # [B, S, ...] -> [n, block, B, ...]; zeros do nothing
        y = jnp.pad(y, ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(y, 1, 0).reshape(n, block, rows, y.shape[-1])

    def token(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    @jax.checkpoint
    def many(h, xs):
        return jax.lax.scan(token, h, xs)

    _, y = jax.lax.scan(many, jnp.zeros((rows, *a.shape), x.dtype),
                        tuple(steps(v) for v in (x, dt, b_in, c_out)))
    return jnp.moveaxis(y.reshape(n * block, rows, -1)[:s], 0, 1)


def _mamba_layer(s, p, u):
    """``(the layer's output, the scan's output y with the D skip)``."""
    n, r, t = s["d_state"], s["dt_rank"], u.shape[1]
    x, z = jnp.split(u @ p["in_proj/kernel"], 2, axis=-1)
    taps = p["conv"].shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(padded[:, i:i + t] * p["conv"][i]
                        for i in range(taps)) + p["conv_bias"])
    dbc = x @ p["x_proj/kernel"]
    dt = jax.nn.softplus(dbc[..., :r] @ p["dt_proj/kernel"] + p["dt_bias"])
    y = ssm_recurrence(x, dt, -jnp.exp(p["A_log"]), dbc[..., r:r + n],
                       dbc[..., r + n:]) + p["D"] * x
    return (y * jax.nn.silu(z)) @ p["out_proj/kernel"], y


def differential_attention(q, k, v, lam, window):
    """``(A1 - lam A2) V`` a query pair, a block of queries at a time
    against every key.  ``q: [B, S, H, D]``, ``k, v: [B, S, Hk, D]``; query
    pair ``p`` is heads ``(2p, 2p + 1)``, its kv pair ``g = p // (H / Hk)``
    is kv heads ``(2g, 2g + 1)`` and ``V = [v_2g; v_2g+1]``.  ``window``
    None: causal; else key ``j`` is seen from ``i`` while ``0 <= i - j <
    window``.  -> ``[B, S, H / 2, 2 D]``."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    pairs, group = h // 2, h // hk
    kv_pair = jnp.arange(pairs) // group                    # g of pair p
    # k head of q head 2p + r: 2 g + r
    k_of_q = (2 * kv_pair[:, None] + jnp.arange(2)[None, :]).reshape(h)
    k_sel = k[:, :, k_of_q]                                 # [B, S, H, D]
    v_sel = v.reshape(b, s, hk // 2, 2 * d)[:, :, kv_pair]  # [B, S, P, 2D]
    n, block = _blocked(s, QUERY_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, n * block - s), (0, 0), (0, 0)))
    q = jnp.moveaxis(q.reshape(b, n, block, h, d), 1, 0)
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def rows(args):
        q_blk, first = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_sel) * d ** -0.5
        age = (first + jnp.arange(block))[:, None] - key_pos[None, :]
        visible = age >= 0
        if window is not None:
            visible &= age < window
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        probs = probs.reshape(b, pairs, 2, block, s)
        both = probs[:, :, 0] - lam.astype(probs.dtype) * probs[:, :, 1]
        return jnp.einsum("bpqk,bkpd->bqpd", both, v_sel)

    out = jax.lax.map(rows, (q, jnp.arange(n) * block))
    return jnp.moveaxis(out, 0, 1).reshape(b, n * block, pairs, 2 * d)[:, :s]


def _attention_layer(s, p, u, index, window, kv=None):
    """``(the layer's output, (k, v))``; ``kv`` given: the cross layer."""
    b, t, _ = u.shape
    h, hk, d = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    if kv is None:
        qkv = u @ p["qkv_proj/kernel"] + p["qkv_proj/bias"]
        q = qkv[..., :h * d]
        k = qkv[..., h * d:(h + hk) * d].reshape(b, t, hk, d)
        v = qkv[..., (h + hk) * d:].reshape(b, t, hk, d)
    else:
        q = u @ p["q_proj/kernel"] + p["q_proj/bias"]
        k, v = kv
    init = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init
    o = differential_attention(q.reshape(b, t, h, d), k, v, lam, window)
    o = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True) + s["eps"])
    o = o * p["subln"] * (1.0 - init)
    return o.reshape(b, t, h * d) @ p["o_proj/kernel"] + p["o_proj/bias"], \
        (k, v)


def _block(s, p, x, shared, kind: str, index: int):
    """``(x, what this layer hands on)``."""
    mixer = {n[6:]: v for n, v in p.items() if n.startswith("mixer/")}
    u = _layer_norm(x, p["mixer_norm/scale"], p["mixer_norm/bias"], s["eps"])
    keep = None
    if kind in ("mamba", "mamba_memory"):
        y, keep = _mamba_layer(s, mixer, u)
    elif kind == "gmu":
        y = (jax.nn.silu(u @ mixer["in_proj/kernel"]) * shared) \
            @ mixer["out_proj/kernel"]
    else:
        y, keep = _attention_layer(
            s, mixer, u, index, s["window"] if kind == "swa" else None,
            shared if kind == "cross" else None)
    x = x + y
    u = _layer_norm(x, p["mlp_norm/scale"], p["mlp_norm/bias"], s["eps"])
    return x + _swiglu(u, p["mlp/gate/kernel"], p["mlp/up/kernel"],
                       p["mlp/down/kernel"]), keep


def reference_loss(s: dict, params: dict, batch: dict):
    """SambaY's forward and next-token cross-entropy in `jax.numpy`, in the
    parameters' own precision, from the program's parameter tree.  Each
    block is rematerialised, so that one block's activations exist at a
    time; the memory and the keys and values that cross blocks are
    arguments and results of those blocks."""
    def part(prefix):
        return {n[len(prefix):]: v for n, v in params.items()
                if n.startswith(prefix)}

    embedding = params["tok_embed/embedding"]
    x = embedding[batch["tokens"]]
    memory = kv = None
    for i, (kind, index) in enumerate(s["layers"]):
        shared = {"gmu": memory, "cross": kv}.get(kind)
        x, keep = jax.checkpoint(
            functools.partial(_block, s, kind=kind, index=index))(
                part(f"block_{i}/"), x, shared)
        if kind == "mamba_memory":
            memory = keep
        elif kind == "full_kv":
            kv = keep
    x = _layer_norm(x, params["final_norm/scale"], params["final_norm/bias"],
                    s["eps"])
    return -jnp.mean(_log_likelihood(x, embedding.T, batch["targets"]))
