"""Training CLI — ``python -m pytorch_ps_mpi_tpu.train``.

The reference has no train.py (SURVEY §0); its implied L4 loop is
``loss.backward(); opt.step()`` under ``mpirun``.  Here the same ladder runs
on a TPU mesh with no launcher: the mesh IS the world (BASELINE north star:
"train.py runs on a TPU pod with no mpirun and no GPU").

Examples::

    python -m pytorch_ps_mpi_tpu.train --model mlp --dataset mnist --steps 50
    python -m pytorch_ps_mpi_tpu.train --model resnet18 --dataset cifar10 \
        --codec topk --optim adam --batch-size 256 --steps 100
    python -m pytorch_ps_mpi_tpu.train --model transformer --seq-len 256 \
        --sp 4 --steps 100                       # sequence-parallel LM
    python -m pytorch_ps_mpi_tpu.train --model lenet --save ckpt.psz
    python -m pytorch_ps_mpi_tpu.train --model lenet --resume ckpt.psz
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time

import numpy as np

import jax

# Exit code for a preemption-triggered graceful shutdown (EX_TEMPFAIL:
# "transient failure, retry"): the in-flight step finished and a RESUMABLE
# checkpoint was written — a supervisor should relaunch with --resume.
# Distinct from 130 (SIGINT without a graceful window: a SECOND signal
# while the first's checkpoint was still being handled).
PREEMPTED_EXIT_CODE = 75


class _PreemptionHandler:
    """Signal-safe preemption latch for SIGTERM/SIGINT.

    The handler only sets a flag — no I/O, no checkpointing inside the
    (async-signal) handler context.  The training loop polls the flag at
    its step boundary, finishes the in-flight step, writes an atomic
    RESUMABLE checkpoint, and exits `PREEMPTED_EXIT_CODE`.  A second
    signal means "now": it raises KeyboardInterrupt, falling through to
    the legacy best-effort save + exit 130.  Installed only on the main
    thread (CPython restriction); elsewhere the latch stays inert and
    signals keep their default behavior."""

    _SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self):
        self.flagged: "int | None" = None
        self._prev: dict = {}

    def __enter__(self):
        if threading.current_thread() is threading.main_thread():
            for s in self._SIGNALS:
                self._prev[s] = signal.signal(s, self._handle)
        return self

    def _handle(self, signum, frame):
        del frame
        if self.flagged is not None:
            raise KeyboardInterrupt
        self.flagged = signum

    def __exit__(self, *exc):
        for s, h in self._prev.items():
            signal.signal(s, h)
        return False


def build(args):
    import jax.numpy as jnp
    from .data.datasets import (synthetic_cifar10, synthetic_imagenet,
                                synthetic_mnist)
    from .models import (LeNet5, build_model, make_classifier_loss,
                         init_mlp, mlp_loss_fn, resnet18, resnet50)

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    if args.dataset == "mnist":
        x, y = synthetic_mnist(args.n_examples)
        shape = (1, 28, 28, 1)
    elif args.dataset == "cifar10":
        x, y = synthetic_cifar10(args.n_examples)
        shape = (1, 32, 32, 3)
    elif args.dataset == "imagenet":
        x, y = synthetic_imagenet(max(args.n_examples, args.batch_size))
        shape = (1, 224, 224, 3)
    else:
        raise SystemExit(f"unknown dataset {args.dataset}")

    if args.model == "mlp":
        d = int(np.prod(x.shape[1:]))
        params = init_mlp(np.random.RandomState(args.seed), (d, 128, 10))
        return params, {}, mlp_loss_fn, False, (x, y), None
    if args.model == "lenet":
        model = LeNet5(dtype=dtype)
    elif args.model == "resnet18":
        model = resnet18(num_classes=10, small_inputs=(args.dataset != "imagenet"),
                         dtype=dtype)
    elif args.model == "resnet50":
        model = resnet50(num_classes=(1000 if args.dataset == "imagenet" else 10),
                         small_inputs=(args.dataset != "imagenet"), dtype=dtype)
    else:
        raise SystemExit(f"unknown model {args.model}")
    params, aux = build_model(model, shape, seed=args.seed)
    loss_fn, has_aux = make_classifier_loss(model, has_aux=bool(aux))
    return params, aux, loss_fn, has_aux, (x, y), model


def ps_kwargs_from_args(args) -> dict:
    """The MPI_PS feature kwargs shared by every optimizer construction
    site (dense/sp/tp, ep, pp, vision) — one place, so a new knob reaches
    all of them."""
    return dict(zero=args.zero, clip_norm=args.clip_norm,
                skip_nonfinite=args.skip_nonfinite,
                error_feedback=args.error_feedback,
                ema_decay=args.ema_decay, bucket_mb=args.bucket_mb,
                decompose_allreduce=args.decompose_allreduce,
                sync_mode=args.sync_mode,
                overlap_reducer=args.overlap_reducer,
                consensus_every=args.sdc_check_every,
                consensus_policy=args.sdc_policy)


def hyper_from_args(args) -> dict:
    lr = args.lr
    schedule = getattr(args, "lr_schedule", "constant")
    if schedule != "constant":
        from .optim import schedules
        warmup = args.warmup_steps
        if schedule == "cosine":
            lr = schedules.cosine(args.lr, args.steps, warmup_steps=warmup,
                                  final_lr=args.lr_final)
        elif schedule == "linear-warmup":
            lr = schedules.linear_warmup(args.lr,
                                         warmup or max(args.steps // 10, 1))
        elif schedule == "step":
            lr = schedules.step_decay(args.lr,
                                      max(args.steps // 3, 1))
        else:  # pragma: no cover - argparse choices guard this
            raise SystemExit(f"unknown --lr-schedule {schedule}")
    return ({"lr": lr, "momentum": args.momentum}
            if args.optim == "sgd" else {"lr": lr})


def _resolve_fill_deadline(args) -> float:
    """--fill-deadline's effective value: the flag (already validated to
    require --quorum), or 0.05 s when --quorum is set without it, or 0.0
    (inert) on quorum-less runs."""
    if args.fill_deadline is not None:
        return args.fill_deadline
    return 0.05 if args.quorum is not None else 0.0


def _resolve_group_deadline(args) -> float:
    """`_resolve_fill_deadline` for the hierarchy's GROUP level."""
    if args.group_fill_deadline is not None:
        return args.group_fill_deadline
    return 0.05 if args.group_quorum is not None else 0.0


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="mlp",
                   choices=["mlp", "lenet", "resnet18", "resnet50",
                            "transformer"])
    p.add_argument("--dataset", default=None,
                   choices=["mnist", "cifar10", "imagenet", "lm"],
                   help="default: mnist (lm for --model transformer)")
    p.add_argument("--optim", default="sgd",
                   choices=["sgd", "adam", "adamw"])
    p.add_argument("--codec", default="identity",
                   choices=["identity", "bf16", "topk", "topk_approx",
                            "quantize", "sign", "blockq"])
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--lr-schedule", default="constant",
                   choices=["constant", "cosine", "linear-warmup", "step"],
                   help="lr schedule over the optimizer step count "
                        "(compiled into the update; resume-aligned)")
    p.add_argument("--warmup-steps", type=int, default=0,
                   help="warmup steps for --lr-schedule cosine / "
                        "linear-warmup")
    p.add_argument("--lr-final", type=float, default=0.0,
                   help="final lr for --lr-schedule cosine")
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--n-examples", type=int, default=4096)
    p.add_argument("--n-devices", type=int, default=None)
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--summary", action="store_true",
                   help="print the per-phase timing summary at the end")
    p.add_argument("--accum-steps", type=int, default=1, metavar="K",
                   help="gradient accumulation: split each rank's batch "
                        "shard into K sequential microbatches (1/K the "
                        "activation memory)")
    p.add_argument("--error-feedback", action="store_true",
                   help="error-feedback compression (EF-SGD): each rank "
                        "carries the residual its lossy codec dropped and "
                        "folds it into the next encode - makes aggressive "
                        "topk/sign compression converge (needs a lossy "
                        "--codec)")
    p.add_argument("--eval-every", type=int, default=0, metavar="N",
                   help="evaluate top-1 accuracy every N steps (and at the "
                        "end) on --eval-examples examples; uses the EMA "
                        "weights when --ema-decay is set.  The data here "
                        "is synthetic, so this is an in-sample accuracy")
    p.add_argument("--eval-examples", type=int, default=512)
    p.add_argument("--ema-decay", type=float, default=None, metavar="D",
                   help="maintain an EMA of the weights inside the step "
                        "(ema = D*ema + (1-D)*params); checkpointed, "
                        "exposed as opt.ema_params")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize activations in the backward pass "
                        "(jax.checkpoint): ~1/depth the activation memory "
                        "for one extra forward of compute")
    p.add_argument("--clip-norm", type=float, default=None, metavar="C",
                   help="global-norm gradient clipping of the summed "
                        "gradient before the update")
    p.add_argument("--skip-nonfinite", action="store_true",
                   help="skip updates (world-consensus) when any rank's "
                        "gradient contains NaN/inf instead of corrupting "
                        "the parameters")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO-style sharded optimizer state: each rank "
                        "owns 1/world of momentum/Adam moments; gradients "
                        "reduce-scatter, updated params all-gather")
    p.add_argument("--bucket-mb", type=float, default=4.0, metavar="MB",
                   help="gradient-exchange bucket size: same-dtype code "
                        "leaves concatenate into <=MB MiB flat buckets, "
                        "one collective each (0 = one collective per "
                        "parameter, the reference's per-param lowering)")
    p.add_argument("--sync-mode", default=None,
                   choices=["post", "bucketed", "overlap"],
                   help="when the cross-rank gradient sum runs: 'post' = "
                        "after backward, per-parameter collectives; "
                        "'bucketed' = after backward, flat bucketed "
                        "transfers (default when --bucket-mb > 0); "
                        "'overlap' = each bucket's collective is issued "
                        "INSIDE the backward pass via per-bucket "
                        "custom-vjp hooks (--bucket-mb 0 auto-tunes the "
                        "bucket size, parallel.overlap.auto_bucket_bytes)")
    p.add_argument("--overlap-reducer", default="rs_ag",
                   choices=["rs_ag", "psum"],
                   help="--sync-mode overlap, identity codec: lower each "
                        "bucket as reduce-scatter+all-gather (survives "
                        "XLA's all-reduce combiner, the TPU overlap "
                        "lowering) or as one all-reduce per bucket")
    p.add_argument("--decompose-allreduce", action="store_true",
                   help="lower each identity-codec gradient bucket as "
                        "reduce-scatter + all-gather instead of one "
                        "all-reduce: same sum, but XLA's combiner can't "
                        "merge the buckets into one end-of-backward op, "
                        "so the exchange overlaps backward compute")
    p.add_argument("--async-ps", action="store_true",
                   help="AsySG-InCon async PS (quota'd updates, "
                        "inconsistent reads) instead of the sync step")
    p.add_argument("--staleness-weighting", action="store_true",
                   help="async PS (--async-ps or --serve): damp each "
                        "gradient by 1/(1+staleness) before the quota sum "
                        "(staleness-aware AsySG)")
    p.add_argument("--quota", type=int, default=None,
                   help="async PS: gradients consumed per update "
                        "(default: number of workers)")
    p.add_argument("--async-bucket-bytes", type=int, default=None,
                   metavar="N",
                   help="multihost worker (--connect): stream each "
                        "gradient as per-bucket GRAD frames (protocol "
                        "v11) instead of one whole-tree frame — bucket "
                        "k ships while later buckets still compute, and "
                        "the PS decodes bucket b while b+1 is on the "
                        "wire.  N = target bucket payload bytes; 0 "
                        "auto-tunes from the payload and the world size "
                        "(parallel.overlap.auto_bucket_bytes)")
    p.add_argument("--fused-encode", action="store_true",
                   help="with --async-bucket-bytes: compile the "
                        "per-bucket codec encode INTO the grad program "
                        "(one jitted backward+encode step; Pallas "
                        "kernels for blockq) instead of encoding each "
                        "bucket at the host boundary")
    p.add_argument("--max-staleness", type=int, default=None, metavar="S",
                   help="async PS: drop (and count) gradients more than S "
                        "versions stale instead of applying them — bounds "
                        "the divergence unbounded staleness causes after "
                        "faults")
    p.add_argument("--aggregate", default="mean",
                   choices=["mean", "trimmed_mean", "median", "norm_clip"],
                   help="async PS gradient reducer: 'mean' (the legacy "
                        "staleness-weighted sum), coordinate-wise "
                        "'trimmed_mean' (drop --trim-k extremes per side) "
                        "or 'median', or 'norm_clip' (clip each "
                        "contribution to the rolling median norm) — the "
                        "Byzantine-robust rules; see ops/robust.py")
    p.add_argument("--trim-k", type=int, default=None, metavar="K",
                   help="--aggregate trimmed_mean: contributions trimmed "
                        "per side per coordinate (default 1, clamped so "
                        "at least one survives)")
    p.add_argument("--quorum", type=int, default=None, metavar="Q",
                   help="async PS straggler tolerance: once Q gradients "
                        "are in and --fill-deadline has expired, the "
                        "update proceeds with the contributors it has "
                        "(renormalized) instead of stalling on the "
                        "slowest rank")
    p.add_argument("--fill-deadline", type=float, default=None, metavar="S",
                   help="--quorum: seconds from FILL START a quorate "
                        "fill waits for stragglers before closing short "
                        "(default 0.05 when --quorum is set; refused "
                        "without it — a fill with no quorum never "
                        "closes short, so the flag would be silently "
                        "inert)")
    p.add_argument("--anomaly-z", type=float, default=None, metavar="Z",
                   help="async PS per-rank anomaly quarantine: rolling "
                        "robust z-score of each rank's gradient norm; "
                        "ranks persistently past Z are down-weighted, "
                        "then quarantined (reversible; surfaced in "
                        "fault_stats)")
    p.add_argument("--adaptive-deadline", action="store_true",
                   help="derive the quorum fill-deadline from the live "
                        "per-rank latency p95 (x1.5 margin), clamped to "
                        "the configured --fill-deadline / "
                        "--group-fill-deadline as a CEILING: a fast "
                        "fleet closes short fills at its own pace "
                        "(counted deadline_adapted) while a uniformly-"
                        "slow fleet uses the whole ceiling instead of "
                        "tripping spurious short fills (needs a quorum "
                        "at the level it applies to)")
    p.add_argument("--latency-weighting", action="store_true",
                   help="heterogeneous-fleet admission: contributions "
                        "from ranks persistently slower than the fleet "
                        "median are down-weighted by their latency-EMA "
                        "ratio (floored at 0.25) instead of every fill "
                        "stalling to keep them at parity (counted "
                        "latency_weighted; applies at every PS level)")
    p.add_argument("--aggregators", type=int, default=0, metavar="G",
                   help="hierarchical aggregation (--serve): run G "
                        "group-local aggregators in this process between "
                        "the workers and the root PS/fleet — each group "
                        "fills under its OWN --group-* policy, "
                        "pre-reduces, and forwards ONE AGGR frame per "
                        "fill, so the root consumes G frames instead of "
                        "W raw gradients (straggler/Byzantine tolerance "
                        "scales sub-linearly with fleet size); "
                        "aggregator ports are printed as 'aggregators "
                        "on ports ...'")
    p.add_argument("--group-size", type=int, default=0, metavar="N",
                   help="--aggregators: each group's fill target (its "
                        "quota of worker gradients per forward); "
                        "required with --aggregators")
    p.add_argument("--group-aggregate", default="mean",
                   choices=["mean", "trimmed_mean", "median", "norm_clip"],
                   help="--aggregators: the GROUP-level reducer (the "
                        "containment layer: a Byzantine rank is trimmed/"
                        "clipped inside its group before the root ever "
                        "sees the frame)")
    p.add_argument("--group-trim-k", type=int, default=None, metavar="K",
                   help="--aggregators: per-side trim for "
                        "--group-aggregate trimmed_mean")
    p.add_argument("--group-quorum", type=int, default=None, metavar="Q",
                   help="--aggregators: group-level straggler quorum — a "
                        "slow rank costs its GROUP a deadline, never the "
                        "whole fleet")
    p.add_argument("--group-fill-deadline", type=float, default=None,
                   metavar="S",
                   help="--aggregators: the group fill deadline (default "
                        "0.05 when --group-quorum is set)")
    p.add_argument("--group-anomaly-z", type=float, default=None,
                   metavar="Z",
                   help="--aggregators: group-level anomaly quarantine "
                        "— the group scoreboard contains a Byzantine "
                        "rank without the root ever scoring it")
    p.add_argument("--group", type=int, default=None, metavar="G",
                   help="--connect --fallback: this worker's group id "
                        "(carried in the direct-fallback HELO so the "
                        "root's groups view names which group lost it; "
                        "default 0)")
    p.add_argument("--fallback", default=None, metavar="HOST:PORT[,...]",
                   help="--connect (to an aggregator): the ROOT "
                        "endpoint(s) this worker fails over to when its "
                        "aggregator dies un-restorably — bounded redial "
                        "first, then a direct root connection (counted "
                        "agg_failovers worker-side, direct_fallbacks at "
                        "the root)")
    p.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                   help="--serve: atomic auto-checkpoint to --save every N "
                        "updates; a killed PS restarts with --resume and "
                        "surviving workers reconnect")
    p.add_argument("--credit-window", type=int, default=0, metavar="N",
                   help="async PS flow control (protocol v8): on a "
                        "serve role, the credit window the PS "
                        "advertises in PSA/PARM/ACKR replies (and its "
                        "net-queue bound; 0 = auto, max(2*quota, 8)); "
                        "on --async-ps, the bounded gradient-queue "
                        "capacity; on --connect, a sender-side CAP on "
                        "the adopted window.  Senders at zero credits "
                        "stall-then-shed data frames oldest-first "
                        "(counted credits_stalled / shed_data_frames) "
                        "— control frames (heartbeats) never shed")
    p.add_argument("--op-deadline", type=float, default=None, metavar="S",
                   help="unified per-operation transport budget "
                        "(transport.Deadline): each pull/replication "
                        "round trip must finish within S seconds or it "
                        "counts deadline_expired and heals through the "
                        "normal reconnect ladder (multihost roles: "
                        "--serve / --connect)")
    p.add_argument("--reconnect-retries", type=int, default=30, metavar="R",
                   help="--connect: redial attempts (exponential backoff + "
                        "jitter, ~50s total at the default) after a lost "
                        "PS connection before the worker gives up cleanly "
                        "— sized so workers survive a supervised PS "
                        "relaunch (process start + compile); raise it for "
                        "slower restarts")
    p.add_argument("--chaos", default=None, metavar="JSON",
                   help="fault-injection plan (utils.faults.FaultPlan as "
                        "JSON) applied to this process's role: --serve "
                        "honors kill_ps_at, --connect honors "
                        "kill_worker_at/nonfinite_at/wire faults.  "
                        "Deterministic under the plan's seed; for chaos "
                        "testing only")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel degree (transformer only): "
                        "builds a (dp, sp) mesh with ring attention")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel degree (transformer only): "
                        "Megatron-style head/MLP compute sharding")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel degree (transformer only): "
                        "layers split into pp stages, microbatched "
                        "activations ride a ppermute ring (GPipe)")
    p.add_argument("--pp-microbatches", type=int, default=None, metavar="M",
                   help="microbatch count for --pp (default: pp); larger M "
                        "shrinks the pipeline bubble")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="transformer only: replace MLPs with a Switch-style "
                        "top-1 MoE of N experts")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel degree (needs --moe-experts): "
                        "tokens ride all_to_all to their expert's rank")
    p.add_argument("--attn", default="dense", choices=["dense", "flash"],
                   help="transformer attention: XLA dense or the Pallas "
                        "flash kernel (O(S*128) memory; on a CPU run — "
                        "JAX_PLATFORMS=cpu / --force-cpu-devices — the "
                        "kernel runs under the Pallas interpreter)")
    p.add_argument("--sp-attn", default="ring", choices=["ring", "ulysses"],
                   help="sequence-parallel strategy for --sp: 'ring' "
                        "rotates K/V with a streaming softmax (O(S/N) "
                        "memory/device); 'ulysses' all_to_all-reshards to "
                        "head sharding and runs full-sequence attention "
                        "(composes with --attn flash; needs heads %% sp "
                        "== 0)")
    p.add_argument("--seq-len", type=int, default=128,
                   help="transformer sequence length")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--save", default=None, metavar="PATH",
                   help="write a checkpoint at the end of the run")
    p.add_argument("--save-every", type=int, default=0, metavar="N",
                   help="also checkpoint every N steps (needs --save); "
                        "periodic saves go to step-tagged siblings "
                        "(ckpt.stepNNNNNNNN.psz) under keep-last-K "
                        "retention (--keep-checkpoints)")
    p.add_argument("--keep-checkpoints", type=int, default=3, metavar="K",
                   help="retention for --save-every: keep the newest K "
                        "step-tagged checkpoints (the newest and any "
                        "RESUMABLE-marked preemption checkpoint are never "
                        "deleted)")
    p.add_argument("--resume", default=None, metavar="PATH",
                   help="restore optimizer state before training; a "
                        "missing PATH resolves to its newest step-tagged "
                        "sibling (what a preempted --save-every run "
                        "leaves behind)")
    p.add_argument("--resume-min-step", type=int, default=None, metavar="S",
                   help="refuse to resume from a checkpoint recording a "
                        "step below S (guards against silently rewinding "
                        "onto a stale retention survivor)")
    p.add_argument("--sdc-check-every", type=int, default=0, metavar="K",
                   help="replica-consensus SDC guard: every K steps, "
                        "fingerprint the parameter tree per data-parallel "
                        "replica and compare — replicas must be bitwise "
                        "identical, so any mismatch is silent data "
                        "corruption or a desync bug (0 = off; sync PS "
                        "only)")
    p.add_argument("--sdc-policy", default="abort",
                   choices=["abort", "rebroadcast"],
                   help="on SDC-guard mismatch: 'abort' raises (fail "
                        "stop), 'rebroadcast' restores consensus from "
                        "replica 0's copy and keeps training")
    p.add_argument("--guard-spike-mad", type=float, default=0.0, metavar="M",
                   help="rollback-on-divergence: flag a step whose loss "
                        "exceeds the rolling median by M robust sigmas "
                        "(median+MAD window) and roll back to the last "
                        "good checkpoint (0 = off; needs --save; sync "
                        "image/MLP path)")
    p.add_argument("--guard-nonfinite-streak", type=int, default=0,
                   metavar="N",
                   help="rollback-on-divergence: roll back after N "
                        "consecutive non-finite losses (0 = off; needs "
                        "--save; sync image/MLP path)")
    p.add_argument("--guard-window", type=int, default=64, metavar="W",
                   help="rolling window for the loss-spike detector")
    p.add_argument("--rollback-lr-scale", type=float, default=1.0,
                   metavar="S",
                   help="multiply the learning rate by S on each rollback "
                        "(e.g. 0.5 halves it) before resuming")
    p.add_argument("--max-rollbacks", type=int, default=3, metavar="R",
                   help="disable the divergence guard (loudly) after R "
                        "rollbacks instead of looping forever")
    p.add_argument("--trace-dir", default=None, metavar="DIR",
                   help="write a jax.profiler trace of the run "
                        "(view in TensorBoard/Perfetto)")
    p.add_argument("--serve", type=int, default=None, metavar="PORT",
                   help="multi-host async PS: run the parameter-server "
                        "process on PORT (0 = auto); workers connect with "
                        "--connect.  Serves --steps updates, quota --quota.")
    p.add_argument("--shards", type=int, default=1, metavar="K",
                   help="sharded PS fleet: --serve runs K PS shards "
                        "(shard k on PORT+k, all ephemeral when PORT=0), "
                        "the parameter tree partitioned by "
                        "--partition-rules (size-balanced greedy without "
                        "them); --connect with a single HOST:PORT expands "
                        "to the K consecutive ports (or list all "
                        "endpoints comma-separated) and runs the worker "
                        "through a shard router with one fleet-wide rank "
                        "and per-shard versions")
    p.add_argument("--replicas", type=int, default=0, metavar="R",
                   help="--serve --shards K: hot-standby replication — "
                        "each PS shard streams applied updates to its "
                        "own standby (R=1; full-state REPL frames every "
                        "update), and a shard killed mid-run is PROMOTED "
                        "onto its old port with ZERO checkpoint rewind "
                        "instead of restored from a checkpoint (works "
                        "with --checkpoint-every 0)")
    p.add_argument("--snapshot-every", type=int, default=0, metavar="N",
                   help="--serve --shards K: coordinated fleet snapshots "
                        "— roughly every N updates the supervisor "
                        "injects SNAP markers so every shard checkpoints "
                        "at ONE agreed cut, then writes the "
                        "ckpt.fleet.json manifest (per-shard path + "
                        "version + sha256) that --resume verifies; "
                        "needs --save")
    p.add_argument("--partition-rules", default=None, metavar="JSON",
                   help="--serve --shards K: ordered [[regex, shard], "
                        "...] leaf->shard rules (first re.search match "
                        "wins; unmatched leaves fall to the size-"
                        "balanced greedy).  PS-side only: workers fetch "
                        "the resulting plan from shard 0 at connect "
                        "time, so the two sides cannot disagree")
    p.add_argument("--token", default=None, metavar="SECRET",
                   help="multi-host admission token: --serve refuses "
                        "connections whose HELO doesn't carry the same "
                        "secret (connection-local NOAU refusal)")
    p.add_argument("--connect", default=None, metavar="HOST:PORT",
                   help="multi-host async PS: run a worker process against "
                        "the server at HOST:PORT (launch one per host)")
    p.add_argument("--subscribe", default=None, metavar="HOST:PORT[,...]",
                   help="serve tier (v10): run a READER — a versioned "
                        "snapshot subscription against the PS at "
                        "HOST:PORT (comma-separated endpoints, or a "
                        "single one with --shards K expanding to "
                        "PORT..PORT+K-1, subscribe the whole fleet).  "
                        "Polls --steps conditional reads: full snapshot "
                        "first, then delta frames on version advance "
                        "with head-only 'unchanged' short-circuits; "
                        "READ-class end to end, so this role can never "
                        "stall training traffic")
    p.add_argument("--infer-serve", action="store_true",
                   help="--subscribe --model transformer: run the "
                        "continuous-batching inference front-end on the "
                        "subscription — submits --steps synthetic LM "
                        "requests through the bounded admission queue, "
                        "hot-swapping params as versions advance, and "
                        "reports per-request p50/p95 latency and the "
                        "typed-shed counters")
    p.add_argument("--read-window", type=int, default=0, metavar="N",
                   help="--serve roles: the READ-class credit budget — "
                        "at most N full-payload snapshot reads per "
                        "served-version advance (0 = auto, "
                        "max(4, quota)); an exhausted budget sheds "
                        "reads head-only (counted read_shed) so a "
                        "reader flood degrades READERS, never training")
    p.add_argument("--wire-codec", choices=("identity", "bf16", "int8"),
                   default="identity",
                   help="--serve roles: compress the parameter wire "
                        "(PARM pulls, DELT snapshots, REPL replication) "
                        "with a host-side codec — each served version "
                        "is encoded once and fanned out to every "
                        "reader; frames carry the codec id so readers "
                        "decode without configuration (optimizer state "
                        "stays f32 server-side, only the wire is lossy)")
    p.add_argument("--delta-parm", action="store_true",
                   help="--serve roles: answer SUBS polls with a sparse "
                        "delta against the reader's presented version "
                        "when it sits in the server's recent-version "
                        "ring (full snapshot on ring miss, after "
                        "load_state_dict, and after any redial — the "
                        "forced-full failover rule)")
    p.add_argument("--force-cpu-devices", type=int, default=None, metavar="N",
                   help="simulate an N-device mesh on CPU (the mpirun -n N "
                        "analogue for development without a TPU slice)")
    args = p.parse_args(argv)

    if args.force_cpu_devices:
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.force_cpu_devices}")
        jax.config.update("jax_platforms", "cpu")

    from .utils.compile_cache import configure_compile_cache
    configure_compile_cache()

    if args.trace_dir:
        from .utils.timing import trace

        with trace(args.trace_dir):
            return _dispatch(args)
    return _dispatch(args)


def _dispatch(args):
    # Refuse, don't drop: these flags only act on the transformer path.
    if args.pp_microbatches is not None and args.pp <= 1:
        raise SystemExit("--pp-microbatches needs --pp > 1")
    if args.pp > 1 and args.model != "transformer":
        raise SystemExit("--pp applies to --model transformer only")
    if args.sp_attn != "ring" and args.sp <= 1:
        raise SystemExit(f"--sp-attn {args.sp_attn} needs --sp > 1")
    if args.eval_every and (args.model == "transformer" or args.async_ps
                            or args.serve is not None or args.connect):
        raise SystemExit("--eval-every supports the sync image/MLP path "
                         "only (the LM paths report loss; dropping the "
                         "flag silently would be worse than refusing)")
    if (args.staleness_weighting and not args.async_ps
            and args.serve is None and not args.connect):
        raise SystemExit("--staleness-weighting applies to the async PS "
                         "(--async-ps or --serve); the sync step has no "
                         "staleness to weight")
    on_async = args.async_ps or args.serve is not None or bool(args.connect)
    if args.sdc_check_every and on_async:
        raise SystemExit("--sdc-check-every is the sync PS's replica-"
                         "consensus guard; the async PS keeps canonical "
                         "state on one device — there are no replicas to "
                         "compare")
    guard_on = bool(args.guard_spike_mad or args.guard_nonfinite_streak)
    if guard_on:
        if on_async:
            raise SystemExit("--guard-spike-mad / --guard-nonfinite-streak "
                             "(rollback-on-divergence) apply to the sync "
                             "trainer only")
        if args.model == "transformer":
            raise SystemExit("the divergence guard supports the sync "
                             "image/MLP path only for now (the LM loop's "
                             "data replay is rng-draw based; refusing "
                             "beats a rollback that cannot rewind its "
                             "data stream)")
        if not args.save:
            raise SystemExit("the divergence guard rolls back to the last "
                             "good checkpoint: set --save (and ideally "
                             "--save-every) so one exists")
    if args.chaos and not on_async:
        # The sync trainer honors the sync faults (preempt / loss spike /
        # replica corruption); async-role faults on a sync run would be
        # silently dead flags, which is worse than refusing.
        from .utils.faults import FaultPlan
        plan = FaultPlan.from_json(args.chaos)
        if plan.any_async_faults() or not plan.any_sync_faults():
            raise SystemExit(
                "--chaos on the sync trainer honors preempt_at_step / "
                "spike_at_step / sdc_at_step only; kill/NaN/wire faults "
                "apply to the async roles (--serve / --connect / "
                "--async-ps)")
    # --- serve tier (ISSUE 14): reader / inference roles --------------------
    if args.subscribe:
        if args.serve is not None or args.connect:
            raise SystemExit("--subscribe / --serve / --connect are "
                             "mutually exclusive roles (one process is "
                             "the PS, a training worker, or a reader)")
        if args.async_ps:
            raise SystemExit("--subscribe reads a MULTIHOST PS over "
                             "TCP; --async-ps runs entirely in-process "
                             "with no server to subscribe to")
    if args.infer_serve:
        if not args.subscribe:
            raise SystemExit("--infer-serve runs the continuous-"
                             "batching inference front-end ON a "
                             "snapshot subscription: set --subscribe "
                             "HOST:PORT (the sync and worker paths "
                             "have no subscription to serve from)")
        if args.model != "transformer":
            raise SystemExit("--infer-serve drives the in-tree "
                             "transformer LM: set --model transformer "
                             "(the subscribed parameter tree must "
                             "match the model the front-end applies)")
    if args.read_window:
        if args.read_window < 0:
            raise SystemExit(f"--read-window must be >= 0, got "
                             f"{args.read_window}")
        if args.serve is None:
            raise SystemExit("--read-window is the PS-side READ credit "
                             "budget (--serve roles advertise it in "
                             "DELT replies); on a worker, reader, sync "
                             "or in-process role it would be silently "
                             "inert, which is worse than refusing")
    if args.wire_codec != "identity" and args.serve is None:
        raise SystemExit("--wire-codec is the PS-side wire compression "
                         "knob (--serve roles stamp the codec id into "
                         "every PARM/DELT/REPL frame; readers decode "
                         "from the frame byte, not from flags); on a "
                         "worker, reader, sync or in-process role it "
                         "would be silently inert, which is worse than "
                         "refusing")
    if args.delta_parm and args.serve is None:
        raise SystemExit("--delta-parm is the PS-side delta-snapshot "
                         "knob (--serve roles keep the recent-version "
                         "ring that deltas are diffed against); on a "
                         "worker, reader, sync or in-process role it "
                         "would be silently inert, which is worse than "
                         "refusing")
    if args.subscribe:
        return run_subscribe(args)
    if args.model == "transformer":
        if args.dataset not in (None, "lm"):
            raise SystemExit(
                f"--model transformer trains on the 'lm' dataset, "
                f"not {args.dataset!r}")
        if args.async_ps or args.serve is not None or args.connect:
            if args.sp > 1 or args.tp > 1 or args.pp > 1 or args.ep > 1:
                raise SystemExit("async transformer runs dense per worker "
                                 "(no --sp/--tp/--pp/--ep: each async "
                                 "worker is a single device; "
                                 "--moe-experts runs all experts locally "
                                 "— the sparse per-expert gradients ride "
                                 "the codecs and the PS/aggregator tier)")
        else:
            return run_transformer(args)
    if args.dataset == "lm" and args.model != "transformer":
        raise SystemExit("--dataset lm requires --model transformer")
    if args.dataset is None:
        args.dataset = "mnist"
    if args.serve is not None and args.connect:
        raise SystemExit("--serve and --connect are mutually exclusive "
                         "(one process is either the PS or a worker)")
    if args.shards < 1:
        raise SystemExit(f"--shards must be >= 1, got {args.shards}")
    if args.shards > 1 and args.serve is None and not args.connect:
        raise SystemExit("--shards is the sharded PS FLEET degree: it "
                         "applies to the multihost roles (--serve runs K "
                         "shards, --connect routes across them); the "
                         "sync and --async-ps paths have no server to "
                         "shard")
    if args.partition_rules is not None and (args.serve is None
                                             or args.shards < 2):
        raise SystemExit("--partition-rules is PS-side and sharded-only "
                         "(--serve --shards K with K >= 2): workers "
                         "fetch the resulting plan from shard 0 at "
                         "connect time, and a single PS has nothing to "
                         "partition — anywhere else the flag would be "
                         "silently inert, which is worse than refusing")
    on_fleet_ps = args.serve is not None and args.shards > 1
    if args.replicas:
        if args.replicas != 1:
            raise SystemExit(f"--replicas supports 0 or 1 (one hot "
                             f"standby per shard), got {args.replicas}")
        if not on_fleet_ps:
            raise SystemExit("--replicas is the PS FLEET's hot-standby "
                             "degree (--serve --shards K): only the "
                             "fleet supervisor can promote a standby — "
                             "anywhere else the flag would be silently "
                             "inert, which is worse than refusing")
    if args.snapshot_every:
        if not on_fleet_ps:
            raise SystemExit("--snapshot-every is the PS FLEET's "
                             "coordinated-snapshot cadence (--serve "
                             "--shards K): a single PS's auto-checkpoint "
                             "IS its consistent cut (--checkpoint-every) "
                             "— anywhere else the flag would be silently "
                             "inert, which is worse than refusing")
        if not args.save:
            raise SystemExit("--snapshot-every needs --save PATH for the "
                             "per-shard cut checkpoints and the "
                             "ckpt.fleet.json manifest")
    on_hier_ps = args.serve is not None and args.aggregators > 0
    if args.aggregators:
        if args.aggregators < 1:
            raise SystemExit(
                f"--aggregators must be >= 1, got {args.aggregators}")
        if args.serve is None:
            raise SystemExit("--aggregators is the hierarchical-"
                             "aggregation tier of the PS process "
                             "(--serve): it spawns the group-local "
                             "aggregators next to the root — workers "
                             "connect to the printed aggregator ports")
        if args.group_size < 1:
            raise SystemExit("--aggregators needs --group-size N (each "
                             "group's fill target); without it the tier "
                             "has no quota to fill")
    group_flags = (args.group_aggregate != "mean"
                   or args.group_trim_k is not None
                   or args.group_quorum is not None
                   or args.group_fill_deadline is not None
                   or args.group_anomaly_z is not None)
    if group_flags and not args.aggregators:
        raise SystemExit("--group-aggregate / --group-trim-k / "
                         "--group-quorum / --group-fill-deadline / "
                         "--group-anomaly-z configure the GROUP level of "
                         "a hierarchy (--serve --aggregators G); without "
                         "one they would be silently inert, which is "
                         "worse than refusing")
    if (args.group_fill_deadline is not None
            and args.group_quorum is None):
        raise SystemExit("--group-fill-deadline only takes effect with "
                         "--group-quorum (a fill without one never "
                         "closes short)")
    if args.fallback and not args.connect:
        raise SystemExit("--fallback is the worker-side failover target "
                         "(--connect to an aggregator, falling back to "
                         "the root): on any other role it would be "
                         "silently inert")
    if args.fallback and "," in args.connect:
        raise SystemExit("--fallback needs --connect to name ONE "
                         "aggregator endpoint (the fallback list itself "
                         "may be comma-separated for a sharded root)")
    if args.group is not None and not args.fallback:
        raise SystemExit("--group tags a failover-capable hierarchy "
                         "worker's direct-fallback HELO (--connect AGG "
                         "--fallback ROOT); without --fallback it would "
                         "be silently inert, which is worse than "
                         "refusing")
    if args.adaptive_deadline:
        if not on_async:
            raise SystemExit("--adaptive-deadline tunes the async PS's "
                             "quorum fill-deadline; the sync step has "
                             "no fills")
        if args.connect:
            raise SystemExit("--adaptive-deadline is PS-side: set it on "
                             "the --serve process")
        if args.quorum is None and not (args.aggregators
                                        and args.group_quorum is not None):
            raise SystemExit("--adaptive-deadline adapts a QUORUM "
                             "deadline: set --quorum (root level) "
                             "and/or --group-quorum (group level), or "
                             "drop the flag (it would be silently "
                             "inert)")
    if args.latency_weighting:
        if not on_async:
            raise SystemExit("--latency-weighting is async-PS admission "
                             "(contribution weights from the latency "
                             "EMA); the sync step admits no per-rank "
                             "contributions")
        if args.connect:
            raise SystemExit("--latency-weighting is PS-side: set it on "
                             "the --serve process")
    if args.chaos:
        # kill_shard_at names a FLEET shard; on any role without a fleet
        # (plain --serve, --connect workers, --async-ps) it would be a
        # silently dead flag — the chaos run would test nothing.  The
        # inverse holds too: kill_ps_at on a fleet names no shard and
        # shard_view would drop it.
        from .utils.faults import FaultPlan
        probe = FaultPlan.from_json(args.chaos)
        on_fleet = args.serve is not None and args.shards > 1
        if probe.kill_shard_at and not on_fleet:
            raise SystemExit("--chaos kill_shard_at applies to the "
                             "sharded PS fleet (--serve --shards K); on "
                             "this role it would be silently inert — "
                             "use kill_ps_at for a single PS")
        if probe.kill_ps_at is not None and on_fleet:
            raise SystemExit("--chaos kill_ps_at is ambiguous for a "
                             "sharded fleet (which shard?) and would be "
                             "silently dropped — use kill_shard_at="
                             "{shard: update}")
        on_router = bool(args.connect) and (args.shards > 1
                                            or "," in args.connect)
        if probe.partition_links and not on_router:
            raise SystemExit("--chaos partition_links names (worker, "
                             "shard) links of a FLEET worker (--connect "
                             "through the shard router); on this role "
                             "the partition would be silently inert — "
                             "which is worse than refusing")
        if probe.any_agg_faults() and not on_hier_ps:
            raise SystemExit("--chaos kill_agg_at / slow_agg / "
                             "byzantine_agg name GROUP AGGREGATORS of a "
                             "hierarchy (--serve --aggregators G); on "
                             "this role they would be silently inert — "
                             "which is worse than refusing")
        if (probe.any_overload_worker_faults()
                and not (args.connect or args.async_ps)):
            # flood_rank / burst_at flood the gradient-PUSHING loop; a
            # role with no push loop (--serve, the sync trainer) would
            # carry them as silently dead flags.
            raise SystemExit("--chaos flood_rank / burst_at are "
                             "worker-side overload injectors (--connect "
                             "or --async-ps push loops); on this role "
                             "they would be silently inert — which is "
                             "worse than refusing")
        if (probe.slow_consumer > 0
                and args.serve is None and not args.async_ps):
            raise SystemExit("--chaos slow_consumer throttles the PS "
                             "CONSUMER loop (--serve or --async-ps); on "
                             "this role it would be silently inert — "
                             "which is worse than refusing")
    if args.zero and (args.async_ps or args.serve is not None
                      or args.connect):
        raise SystemExit("--zero applies to the sync PS only: the async "
                         "PS keeps canonical state on one device, so "
                         "there is no replicated state to shard")
    if ((args.accum_steps > 1
         or args.clip_norm is not None or args.error_feedback
         or args.ema_decay is not None or args.remat
         or args.sync_mode is not None)
            and (args.async_ps or args.serve is not None or args.connect)):
        raise SystemExit("--accum-steps / --clip-norm / "
                         "--error-feedback / --ema-decay / --sync-mode / "
                         "--remat apply to "
                         "the sync PS only; the async paths do not support "
                         "them yet (dropping the flag silently would be "
                         "worse than refusing)")
    if (args.max_staleness is not None and not args.async_ps
            and args.serve is None and not args.connect):
        raise SystemExit("--max-staleness applies to the async PS "
                         "(--async-ps or --serve); the sync step consumes "
                         "no stale gradients")
    if args.credit_window:
        if args.credit_window < 0:
            raise SystemExit(f"--credit-window must be >= 0, got "
                             f"{args.credit_window}")
        if not on_async:
            raise SystemExit("--credit-window is the async PS's bounded-"
                             "queue / flow-control window (--serve / "
                             "--connect / --async-ps); the sync step's "
                             "collective sum has no gradient queue to "
                             "bound — dropping the flag silently would "
                             "be worse than refusing")
    if args.op_deadline is not None:
        if args.op_deadline <= 0:
            raise SystemExit(f"--op-deadline must be > 0, got "
                             f"{args.op_deadline}")
        if args.serve is None and not args.connect:
            raise SystemExit("--op-deadline budgets MULTIHOST transport "
                             "operations (--serve / --connect round "
                             "trips); the sync and --async-ps paths run "
                             "no transport ops — the flag would be "
                             "silently inert, which is worse than "
                             "refusing")
    # --- bucket-streamed async gradients (ISSUE 15, protocol v11) -----------
    if args.async_bucket_bytes is not None:
        if args.async_bucket_bytes < 0:
            raise SystemExit(f"--async-bucket-bytes must be >= 0 "
                             f"(0 = auto), got {args.async_bucket_bytes}")
        if not args.connect:
            raise SystemExit("--async-bucket-bytes is the MULTIHOST "
                             "worker's gradient-streaming knob "
                             "(--connect): the sync step has no wire, "
                             "the PS side assembles whatever bucket "
                             "plan its workers chose, and the "
                             "in-process --async-ps path moves device "
                             "arrays, not frames — anywhere else the "
                             "flag would be silently inert, which is "
                             "worse than refusing")
        if args.fallback:
            raise SystemExit("--async-bucket-bytes does not compose "
                             "with the hierarchy failover worker "
                             "(--fallback) yet — the GroupWorker's "
                             "direct-root failover re-compiles the "
                             "whole-tree step; drop one of the flags")
        if args.shards > 1 or "," in args.connect:
            raise SystemExit("--async-bucket-bytes does not compose "
                             "with the shard router (--connect to a "
                             "fleet) yet — the router already splits "
                             "every gradient per shard slice; drop one "
                             "of the flags")
    if args.fused_encode and args.async_bucket_bytes is None:
        raise SystemExit("--fused-encode fuses the PER-BUCKET encode "
                         "into the grad program — it needs "
                         "--async-bucket-bytes (0 auto-tunes); without "
                         "a bucket plan it would be silently inert, "
                         "which is worse than refusing")
    robust_flags = (args.aggregate != "mean" or args.trim_k is not None
                    or args.quorum is not None
                    or args.fill_deadline is not None
                    or args.anomaly_z is not None)
    if robust_flags and not args.async_ps and args.serve is None \
            and not args.connect:
        raise SystemExit("--aggregate / --trim-k / --quorum / "
                         "--fill-deadline / --anomaly-z "
                         "are async-PS admission/aggregation knobs "
                         "(--async-ps or --serve); the sync step reduces "
                         "with its collective sum")
    if args.trim_k is not None and args.aggregate != "trimmed_mean":
        raise SystemExit("--trim-k only applies to "
                         "--aggregate trimmed_mean")
    if (args.fill_deadline is not None and args.quorum is None
            and not args.connect):
        # (--connect gets the PS-side refusal below instead.)
        raise SystemExit("--fill-deadline only takes effect with --quorum "
                         "(a fill without one never closes short); set "
                         "--quorum or drop the flag (it would be silently "
                         "inert, which is worse than refusing)")
    if args.checkpoint_every:
        if args.serve is None:
            raise SystemExit("--checkpoint-every is the --serve path's "
                             "auto-checkpoint cadence (the sync loop uses "
                             "--save-every)")
        if not args.save:
            raise SystemExit("--checkpoint-every needs --save PATH for the "
                             "checkpoint file")
    if args.connect and (args.skip_nonfinite
                         or args.max_staleness is not None or robust_flags):
        raise SystemExit("--skip-nonfinite / --max-staleness / --aggregate "
                         "/ --trim-k / --quorum / --fill-deadline / "
                         "--anomaly-z are PS-side "
                         "admission knobs: set them on the --serve process "
                         "(dropping them silently here would be worse than "
                         "refusing)")
    if args.serve is not None or args.connect:
        return run_multihost(args)
    if args.async_ps:
        return run_async(args)

    from . import MPI_PS
    from .data.loader import DataLoader
    from .parallel.mesh import make_ps_mesh

    mesh = make_ps_mesh(args.n_devices)
    world = mesh.shape["ps"]
    print(f"mesh: {world} x {mesh.devices.flat[0].platform}",
          file=sys.stderr)
    if args.batch_size % world:
        raise SystemExit(f"--batch-size {args.batch_size} must divide by "
                         f"the {world}-device world")

    params, aux, loss_fn, has_aux, (x, y), model = build(args)
    hyper = hyper_from_args(args)
    opt = MPI_PS(list(params.items()), optim=args.optim, code=args.codec,
                 mesh=mesh, **ps_kwargs_from_args(args), **hyper)
    opt.compile_step(loss_fn, has_aux=has_aux, aux=aux,
                     accum_steps=args.accum_steps,
                     remat=args.remat)

    start, extra = _restore(args, opt)
    step = start
    # The resumable loader replaces the old per-epoch `batches(seed=step)`
    # stream: its (epoch, batch_index) position rides in every checkpoint's
    # `extra`, so a resumed (or rolled-back) run replays the SAME batch
    # sequence bitwise instead of reshuffling from the resume step.
    loader = DataLoader({"x": x, "y": y}, batch_size=args.batch_size,
                        seed=args.seed, epochs=None)
    if extra and extra.get("loader"):
        loader.load_state_dict(extra["loader"])
    plan = _sync_fault_plan(args)
    guard = _make_guard(args)
    fired: set = set()  # single-shot chaos injections survive rollbacks
    # Maps opt.steps_completed (monotonic applied updates, rollbacks
    # included) back to the loop's logical step, for the second-signal
    # KeyboardInterrupt path.
    applied_offset = start

    t_start = time.perf_counter()
    with _PreemptionHandler() as preempt:
        data_iter = iter(loader)
        try:
            while step < args.steps:
                _chaos_before_step(opt, plan, fired, step)
                b = _maybe_spike(plan, fired, step, next(data_iter))
                loss, data = opt.step(b)
                step += 1
                if step % 10 == 0 or step == 1:
                    print(f"step {step:5d}  loss {loss:.4f}  "
                          f"comm_wait {data['comm_wait']*1e3:.2f}ms",
                          file=sys.stderr)
                if preempt.flagged is not None:
                    _preempt_exit(args, opt, step, preempt.flagged,
                                  loader=loader)
                rolled = _maybe_rollback(args, opt, guard, loss, step,
                                         loader)
                if rolled is not None:
                    step = rolled
                    applied_offset = step - opt.steps_completed
                    data_iter.close()  # the old stream is now the future
                    data_iter = iter(loader)
                    continue
                if np.isfinite(loss):
                    # Never record a non-finite step as a "good"
                    # checkpoint: during a nonfinite-streak window (the
                    # guard waits for N in a row) a periodic save would
                    # persist already-NaN params, and the later rollback
                    # would restore exactly that poison.
                    _maybe_save(args, opt, step,
                                extra=_loop_extra(loader, opt))
                if args.eval_every and step % args.eval_every == 0:
                    _eval_and_log(args, opt, model, x, y, step)
        except KeyboardInterrupt:
            # Second signal (or an interrupt outside the latch): the
            # optimizer's own counter, not the loop's — an interrupt
            # landing inside step()'s blocking wait has already applied
            # update N+1 while the loop counter still says N (r4 advisor).
            _interrupted_exit(args, opt,
                              applied_offset + opt.steps_completed,
                              loader=loader)
    wall = time.perf_counter() - t_start
    if args.eval_every and step % args.eval_every:
        # Final eval only if the loop's cadence didn't just produce one.
        _eval_and_log(args, opt, model, x, y, step, final=True)
    steps_run = step - start
    imgs = args.batch_size * steps_run
    print(f"done: {steps_run} steps, {imgs/wall:.1f} images/sec "
          f"({imgs/wall/world:.1f}/device)", file=sys.stderr)
    _maybe_save(args, opt, step, final=True, extra=_loop_extra(loader, opt))
    from .utils.timing import format_fault_stats
    rendered = format_fault_stats(opt.fault_stats)
    if rendered != "clean":
        print("fault stats: " + rendered, file=sys.stderr)
    if args.summary:
        opt.print_summary()
    return opt


def _eval_and_log(args, opt, model, x, y, step, *, final=False) -> float:
    """Top-1 accuracy on the first --eval-examples examples, using the EMA
    weights when available (the evaluation-quality set).  ``model`` is the
    trained flax module from build() — the same object, so evaluation can
    never run a differently-configured architecture."""
    from .models import eval_accuracy, mlp_apply

    n = min(args.eval_examples, len(x))
    params = opt.ema_params if opt.ema_params is not None else opt.params
    which = "ema" if opt.ema_params is not None else "params"
    if model is None:  # mlp: plain-jax apply
        import jax.numpy as jnp
        logits = mlp_apply(jax.device_get(params),
                           jnp.asarray(x[:n].reshape(n, -1)))
        acc = float((jnp.argmax(logits, -1) == y[:n]).mean())
    else:
        bs = 256
        batches_iter = ({"x": x[i:i + bs], "y": y[i:i + bs]}
                        for i in range(0, n, bs))
        acc = eval_accuracy(model, params, opt.aux, batches_iter)
    tag = "final " if final else ""
    print(f"{tag}eval @ step {step}: top-1 {acc:.4f} ({which}, n={n})",
          file=sys.stderr)
    return acc


def _restore(args, opt) -> "tuple[int, dict | None]":
    """--resume: restore optimizer state.  Returns ``(start_step, extra)``
    — extra carries the loader position a resumed loop replays.  The path
    resolves to its newest step-tagged sibling when it doesn't exist
    itself (the shape a preempted --save-every run leaves), and a consumed
    RESUMABLE marker is cleared so retention GC can eventually reclaim the
    file."""
    if not args.resume:
        return 0, None
    from .utils import checkpoint
    path = checkpoint.latest_checkpoint(args.resume)
    if path is None:
        raise SystemExit(f"--resume {args.resume}: no checkpoint found "
                         f"(also looked for step-tagged siblings)")
    info = checkpoint.load_optimizer(path, opt,
                                     min_step=args.resume_min_step)
    checkpoint.clear_resumable(path)
    start = int(info.get("step") or 0)
    print(f"resumed from {path} at step {start}", file=sys.stderr)
    return start, info.get("extra")


def _loop_extra(loader, opt) -> dict:
    """Checkpoint ``extra`` for the sync loop: the loader position (so a
    resume replays the same batches) plus how many LR-rollback scalings
    are already baked into this state's float lr (so repeated rollbacks
    compound to S^k instead of re-applying S against the restored lr)."""
    return {"loader": loader.state_dict(),
            "lr_rollbacks": len([e for e in opt.fault_stats["rollbacks"]
                                 if e.get("restored_step") is not None])}


def _interrupted_exit(args, opt, step: int, loader=None):
    """Hard-interrupt courtesy (a SECOND signal, or Ctrl-C outside the
    preemption latch): persist progress best-effort (when --save is set)
    and exit with the conventional 130.  The loader position rides along
    when the loop has one — without it a resume would silently restart
    the data stream at epoch 0 while the step counter says N."""
    print(f"interrupted at step {step}", file=sys.stderr)
    _maybe_save(args, opt, step, final=True,
                extra=_loop_extra(loader, opt) if loader is not None
                else None)
    raise SystemExit(130)


def _preempt_exit(args, opt, step: int, signum: int, loader=None):
    """The signal-safe preemption path: the in-flight step has finished;
    write an atomic step-tagged checkpoint, mark it RESUMABLE (pinned
    against retention GC until a resume consumes it), and exit
    `PREEMPTED_EXIT_CODE` so a supervisor relaunches with --resume."""
    from .utils import checkpoint
    name = signal.Signals(signum).name
    print(f"{name} received: finished in-flight step {step}",
          file=sys.stderr)
    if args.save:
        path = (checkpoint.step_path(args.save, step) if args.save_every
                else args.save)
        extra = _loop_extra(loader, opt) if loader is not None else None
        checkpoint.save_optimizer(path, opt, step=step, extra=extra,
                                  raw_shards=hasattr(opt, "topology"))
        checkpoint.mark_resumable(path, {"step": step, "signal": name,
                                         "unix_time": time.time()})
        if args.save_every:
            checkpoint.gc_step_checkpoints(
                args.save, keep_last=args.keep_checkpoints)
        print(f"checkpoint -> {path} (step {step}, RESUMABLE)",
              file=sys.stderr)
    else:
        print("preempted with no --save: progress is lost",
              file=sys.stderr)
    raise SystemExit(PREEMPTED_EXIT_CODE)


def _maybe_save(args, opt, step: int, *, final: bool = False,
                extra: "dict | None" = None) -> None:
    if not args.save:
        return
    from .utils import checkpoint
    if final:
        checkpoint.save_optimizer(args.save, opt, step=step, extra=extra)
        print(f"checkpoint -> {args.save} (step {step})", file=sys.stderr)
    elif args.save_every and step % args.save_every == 0:
        # Periodic saves are step-tagged + keep-last-K GC'd, so
        # --save-every no longer grows without bound.  The sync loop
        # skips this call on a non-finite loss, so rollback's
        # latest-checkpoint target is always a finite-loss state.
        path = checkpoint.step_path(args.save, step)
        checkpoint.save_optimizer(path, opt, step=step, extra=extra)
        gone = checkpoint.gc_step_checkpoints(
            args.save, keep_last=args.keep_checkpoints)
        print(f"checkpoint -> {path} (step {step}"
              + (f", gc'd {len(gone)} old" if gone else "") + ")",
              file=sys.stderr)


def _sync_fault_plan(args):
    """The sync trainer's chaos plan (validated sync-only in _dispatch)."""
    if not args.chaos:
        return None
    from .utils.faults import FaultPlan
    return FaultPlan.from_json(args.chaos)


def _make_guard(args):
    if not (args.guard_spike_mad or args.guard_nonfinite_streak):
        return None
    from .utils.guardrails import DivergenceGuard
    return DivergenceGuard(window=args.guard_window,
                           spike_mad=args.guard_spike_mad,
                           nonfinite_streak=args.guard_nonfinite_streak)


def _chaos_before_step(opt, plan, fired: set, step: int) -> None:
    """Fire due single-shot sync chaos injections before step ``step+1``:
    a REAL SIGTERM to this process (preempt_at_step) and/or a replica
    parameter corruption (sdc_at_step).  ``fired`` keeps each one-shot
    across rollback replays."""
    if plan is None:
        return
    if plan.should_preempt(step) and "preempt" not in fired:
        fired.add("preempt")
        print(f"chaos: raising SIGTERM before step {step + 1}",
              file=sys.stderr)
        os.kill(os.getpid(), signal.SIGTERM)
    if plan.should_corrupt_replica(step) and "sdc" not in fired:
        fired.add("sdc")
        from .utils import faults
        leaf = faults.corrupt_replica(opt, plan.sdc_rank, plan.sdc_param)
        print(f"chaos: corrupted replica {plan.sdc_rank} of {leaf!r} "
              f"before step {step + 1}", file=sys.stderr)


def _maybe_spike(plan, fired: set, step: int, batch):
    """Loss-spike injection: scale the batch inputs AND (for integer
    labels) rotate them one class over, so every example is confidently
    wrong — the loss genuinely spikes and the saturated-softmax gradients
    genuinely wreck the parameters (scaling alone would saturate a well-
    trained classifier toward loss ~0, the opposite of a spike)."""
    if plan is None or not plan.should_spike(step) or "spike" in fired:
        return batch
    fired.add("spike")
    print(f"chaos: scaling batch x{plan.spike_scale:g} + rotating labels "
          f"at step {step + 1} (loss spike injection)", file=sys.stderr)
    batch = dict(batch)
    batch["x"] = np.asarray(batch["x"]) * plan.spike_scale
    y = batch.get("y")
    if y is not None and np.issubdtype(np.asarray(y).dtype, np.integer):
        y = np.asarray(y)
        batch["y"] = (y + 1) % (int(y.max()) + 1)
    return batch


def _maybe_rollback(args, opt, guard, loss, step: int, loader):
    """Feed the divergence guard; on a verdict, restore the last good
    checkpoint (and its loader position), optionally rescale LR, record
    the event in ``opt.fault_stats``, and return the restored step (the
    loop rewinds to it).  Returns None when training just continues."""
    if guard is None:
        return None
    why = guard.observe(loss)
    if why is None:
        return None
    from .utils import checkpoint
    events = opt.fault_stats["rollbacks"]
    last = checkpoint.latest_checkpoint(args.save)
    if last is None:
        print(f"divergence guard: {why} at step {step}, but no checkpoint "
              f"exists yet — continuing without rollback", file=sys.stderr)
        events.append({"step": step, "reason": why, "restored_step": None,
                       "skipped": "no checkpoint yet"})
        guard.reset()
        return None
    info = checkpoint.load_optimizer(last, opt)
    restored = int(info.get("step") or 0)
    extra = info.get("extra") or {}
    if loader is not None and extra.get("loader"):
        loader.load_state_dict(extra["loader"])
    if args.rollback_lr_scale != 1.0:
        if callable(opt.hyper["lr"]):
            # Schedule lr: the load kept the loop's CURRENT (already
            # k-times-wrapped) schedule, so one more wrap compounds.
            opt.rescale_lr(args.rollback_lr_scale)
        else:
            # Float lr: the load restored the CHECKPOINT's lr, which has
            # only the scalings baked in at its save time (recorded as
            # extra["lr_rollbacks"]).  Apply the difference so the k-th
            # rollback lands on lr * S^k, not lr * S.
            k = 1 + len([e for e in events
                         if e.get("restored_step") is not None])
            baked = int(extra.get("lr_rollbacks") or 0)
            if k > baked:
                opt.rescale_lr(args.rollback_lr_scale ** (k - baked))
    guard.reset()
    events.append({"step": step, "reason": why, "restored_step": restored,
                   "checkpoint": last,
                   "lr_scale": args.rollback_lr_scale,
                   "loss": float(loss)})
    print(f"divergence guard: {why} at step {step} — rolled back to "
          f"checkpoint step {restored}"
          + (f", lr x{args.rollback_lr_scale:g}"
             if args.rollback_lr_scale != 1.0 else ""), file=sys.stderr)
    if len([e for e in events if e.get("restored_step") is not None]) \
            >= args.max_rollbacks:
        guard.disabled = True
        print(f"divergence guard: {args.max_rollbacks} rollbacks reached "
              f"— guard disabled for the rest of the run", file=sys.stderr)
    return restored


def transformer_model(args):
    """The CLI's LM configuration — one definition shared by the sync,
    async, and multihost paths so their parameter trees always agree."""
    import jax.numpy as jnp
    from .models.transformer import TransformerLM

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    return TransformerLM(vocab_size=args.vocab, d_model=256, n_heads=8,
                         n_layers=4, d_ff=1024,
                         max_len=max(2048, args.seq_len), dtype=dtype,
                         moe_experts=args.moe_experts)


def _flash_attention():
    """`flash_attention` bound to the kernel implementation of the devices
    this run trains on: the Mosaic kernel on TPUs; on a CPU run (named by
    JAX_PLATFORMS=cpu / --force-cpu-devices) the Pallas interpreter."""
    import functools

    from .ops.flash_attention import flash_attention
    from .ops.pallas_kernels import impl_for_platform
    from .parallel.mesh import default_devices

    impl = impl_for_platform(default_devices()[0].platform, cpu="interpret")
    return functools.partial(flash_attention, impl=impl)


def _build_lm_async(args):
    """(params, loss_fn, toks) for the async/multihost transformer paths.
    Each worker is one device (no sp/tp/pp sharding), but ``--attn flash``
    threads through: the worker's jitted grad+encode program runs the
    Pallas kernel."""
    import functools

    from .data.datasets import synthetic_lm
    from .models.transformer import build_lm, make_lm_loss

    dense = transformer_model(args)
    params = build_lm(dense, seq_len=args.seq_len, seed=args.seed)
    model = dense
    if args.attn == "flash":
        model = dense.copy(
            attn=functools.partial(_flash_attention(), causal=True))
    toks = synthetic_lm(max(args.n_examples, args.batch_size),
                        seq_len=args.seq_len, vocab=args.vocab,
                        seed=args.seed)
    return params, make_lm_loss(model), toks


def run_transformer(args):
    """Transformer LM training with composable parallelism: --sp shards the
    sequence over a ring-attention axis, --tp shards head/MLP compute
    Megatron-style; batch shards over the remaining dp axis."""
    import functools

    from jax.sharding import PartitionSpec as P

    from . import MPI_PS
    from .data.datasets import synthetic_lm
    from .models.transformer import (TransformerLM, build_lm, lm_batch,
                                     make_lm_loss)
    from .parallel.mesh import (default_devices, make_dp_sp_mesh,
                                make_dp_sp_tp_mesh, make_dp_tp_mesh,
                                make_ps_mesh)
    from .parallel.ring_attention import ring_attention

    if args.seq_len % args.sp:
        raise SystemExit(f"--seq-len {args.seq_len} must divide by --sp {args.sp}")
    if args.ep > 1:
        if not args.moe_experts:
            raise SystemExit("--ep needs --moe-experts")
        if args.moe_experts % args.ep:
            raise SystemExit(
                f"--moe-experts {args.moe_experts} must divide by --ep {args.ep}")
        if args.sp > 1 or args.tp > 1:
            raise SystemExit("--ep composes with dp only (not --sp/--tp) "
                             "in this CLI")
    if args.pp > 1 and (args.sp > 1 or args.ep > 1 or args.moe_experts):
        raise SystemExit("--pp composes with dp and --tp only (not --sp/"
                         "--ep/MoE) in this CLI")
    shard = args.sp * args.tp * args.pp
    if args.n_devices and args.n_devices % (shard * args.ep):
        raise SystemExit(
            f"--n-devices {args.n_devices} must divide by --sp*--tp*--pp*--ep")

    dense = transformer_model(args)
    params = build_lm(dense, seq_len=args.seq_len, seed=args.seed)

    tp_axis = "tp" if args.tp > 1 else None
    if args.attn == "flash" and args.sp > 1 and args.sp_attn == "ring":
        raise SystemExit("--attn flash composes with dp/tp/ep or with "
                         "--sp-attn ulysses; --sp-attn ring uses its own "
                         "streaming softmax")
    flash = None
    if args.attn == "flash":
        flash = functools.partial(_flash_attention(), causal=True)
    if args.sp > 1 and args.sp_attn == "ulysses":
        from .parallel.ulysses import ulysses_attention
        inner = _flash_attention() if flash is not None else None
        ring = functools.partial(ulysses_attention, axis="sp", causal=True,
                                 inner=inner)
    elif args.sp > 1:
        ring = functools.partial(ring_attention, axis="sp", causal=True)
    else:
        ring = flash
    n_dev = args.n_devices
    dp = n_dev // shard if n_dev else None
    if args.ep > 1:
        from .parallel.mesh import make_dp_ep_mesh

        mesh = make_dp_ep_mesh(dp=n_dev // args.ep if n_dev else None,
                               ep=args.ep)
        model = dense.copy(ep_axis="ep", attn=ring)
        opt = MPI_PS(list(params.items()), optim=args.optim,
                     code=args.codec, mesh=mesh, axis=("ps", "ep"),
                     batch_spec=P(("ps", "ep")), **ps_kwargs_from_args(args),
                     **hyper_from_args(args))
        return _run_transformer_loop(args, opt, mesh, model)
    if args.pp > 1:
        from .models.pipelined import make_pipelined_lm_loss
        from .parallel.mesh import make_dp_pp_mesh

        if dense.n_layers % args.pp:
            raise SystemExit(f"{dense.n_layers} layers do not split into "
                             f"--pp {args.pp} stages")
        if args.tp > 1:
            from .parallel.mesh import make_dp_pp_tp_mesh

            mesh = make_dp_pp_tp_mesh(
                dp or len(default_devices()) // shard, args.pp, args.tp)
        else:
            mesh = make_dp_pp_mesh(dp=dp, pp=args.pp)
        model = dense.copy(attn=ring, tp_axis=tp_axis)
        opt = MPI_PS(list(params.items()), optim=args.optim,
                     code=args.codec, mesh=mesh, batch_spec=P("ps"),
                     **ps_kwargs_from_args(args),
                     **hyper_from_args(args))
        loss_fn = make_pipelined_lm_loss(model,
                                         n_micro=args.pp_microbatches)
        return _run_transformer_loop(args, opt, mesh, model,
                                     loss_fn=loss_fn)
    if args.sp > 1 and args.tp > 1:
        mesh = make_dp_sp_tp_mesh(dp or len(default_devices()) // shard,
                                  args.sp, args.tp)
        batch_spec = P("ps", "sp")
    elif args.sp > 1:
        mesh = make_dp_sp_mesh(dp=dp, sp=args.sp)
        batch_spec = P("ps", "sp")
    elif args.tp > 1:
        mesh = make_dp_tp_mesh(dp=dp, tp=args.tp)
        batch_spec = P("ps")
    else:
        mesh = make_ps_mesh(n_dev)
        batch_spec = None
    model = dense.copy(tp_axis=tp_axis, attn=ring)
    opt = MPI_PS(list(params.items()), optim=args.optim, code=args.codec,
                 mesh=mesh, batch_spec=batch_spec, **ps_kwargs_from_args(args),
                 **hyper_from_args(args))
    return _run_transformer_loop(args, opt, mesh, model)


def _run_transformer_loop(args, opt, mesh, model, loss_fn=None):
    from .data.datasets import synthetic_lm
    from .models.transformer import lm_batch, make_lm_loss

    dp = mesh.shape["ps"]
    data_shards = dp * mesh.shape.get("ep", 1)
    if args.batch_size % data_shards:
        raise SystemExit(
            f"--batch-size {args.batch_size} must divide by {data_shards} "
            f"data shards")
    print(f"mesh: dp={dp} sp={mesh.shape.get('sp', 1)} "
          f"tp={mesh.shape.get('tp', 1)} pp={mesh.shape.get('pp', 1)} "
          f"ep={mesh.shape.get('ep', 1)} x "
          f"{mesh.devices.flat[0].platform}", file=sys.stderr)

    opt.compile_step(loss_fn if loss_fn is not None else make_lm_loss(model),
                     accum_steps=args.accum_steps,
                     remat=args.remat)

    toks = synthetic_lm(max(args.n_examples, args.batch_size),
                        seq_len=args.seq_len, vocab=args.vocab,
                        seed=args.seed)
    start, _extra = _restore(args, opt)
    step = start
    plan = _sync_fault_plan(args)
    fired: set = set()
    t0 = time.perf_counter()
    rng = np.random.RandomState(args.seed)
    for _ in range(start):
        # Replay the index draws already consumed, so a resumed run
        # continues the data stream instead of re-training early batches.
        rng.randint(0, len(toks), size=args.batch_size)
    with _PreemptionHandler() as preempt:
        try:
            while step < args.steps:
                _chaos_before_step(opt, plan, fired, step)
                take = rng.randint(0, len(toks), size=args.batch_size)
                loss, data = opt.step(lm_batch(toks[take]))
                step += 1
                if step % 10 == 0 or step == 1:
                    print(f"step {step:5d}  loss {loss:.4f}  "
                          f"comm_wait {data['comm_wait']*1e3:.2f}ms",
                          file=sys.stderr)
                if preempt.flagged is not None:
                    _preempt_exit(args, opt, step, preempt.flagged)
                _maybe_save(args, opt, step)
        except KeyboardInterrupt:
            # Second signal / interrupt outside the latch: trust the
            # optimizer's applied-update counter, not the loop counter
            # (which lags when the interrupt lands inside step()'s
            # blocking wait).  The rng-replay on resume then replays
            # exactly the draws the applied updates consumed.
            _interrupted_exit(args, opt, start + opt.steps_completed)
    wall = time.perf_counter() - t0
    steps_run = step - start
    tok_s = args.batch_size * args.seq_len * steps_run / wall
    print(f"done: {steps_run} steps, {tok_s:,.0f} tokens/sec "
          f"({tok_s / mesh.size:,.0f}/device)", file=sys.stderr)
    _maybe_save(args, opt, step, final=True)
    if args.summary:
        opt.print_summary()
    return opt


def _shutdown(*roles) -> None:
    """End of a --serve role: let every connection handler DONE its peer
    and finish, drain the decode pools (`AsyncPSServer.join`), then close.
    The process must reach interpreter exit with no thread of ours inside
    a native or JAX call — that aborts with rc 134 after a clean run."""
    for role in roles:
        role.join()
        role.close()


def _serve_single(args, srv, loss_fn):
    """The plain --serve role on an already-constructed server (the
    caller shuts it down)."""
    srv.compile_step(loss_fn)
    start = 0
    if args.resume:
        start = srv.resume_from(args.resume)
        print(f"resumed from {args.resume} at step {start}",
              file=sys.stderr)
    updates = max(args.steps - start, 0)
    if updates == 0:
        print("nothing to do: checkpoint is already at "
              f"step {start} >= --steps {args.steps}", file=sys.stderr)
        return srv
    # Machine-parseable on stdout: launchers read the bound port from
    # here when --serve 0 asked for an ephemeral one.  Only the port is
    # printed — the bind address (0.0.0.0) is not a connectable host.
    print(f"serving on port {srv.address[1]}", flush=True)
    t0 = time.perf_counter()
    hist = srv.serve(steps=updates, log_every=10,
                     checkpoint_path=args.save,
                     checkpoint_every=args.checkpoint_every,
                     start_step=start)
    wall = time.perf_counter() - t0
    grads = hist["grads_consumed"]
    print(f"done: {updates} updates, {grads} grads, "
          f"{grads * args.batch_size / wall:.1f} images/sec, "
          f"mean staleness {np.mean(hist['staleness']):.2f}",
          file=sys.stderr)
    from .utils.timing import format_fault_stats
    rendered = format_fault_stats(hist["fault_stats"])
    if rendered != "clean":
        print("fault stats: " + rendered, file=sys.stderr)
    if args.save:
        # Through the server's own checkpoint path (not the generic
        # _maybe_save): it records the serving version counter, which
        # a later --resume needs for continuous staleness accounting.
        srv._auto_checkpoint(args.save, args.steps)
        print(f"checkpoint -> {args.save} (step {args.steps})",
              file=sys.stderr)
    if args.summary:
        srv.print_summary()
    return srv


def run_multihost(args):
    """Multi-host AsySG-InCon over TCP (`multihost_async`): the reference's
    multi-node deployment shape — one --serve process (rank 0 of
    `/root/reference/README.md:56-77`), any number of --connect workers."""
    from .async_ps import dataset_batch_fn, lm_batch_fn
    from .multihost_async import AsyncPSServer, AsyncPSWorker

    plan = None
    if args.chaos:
        from .utils.faults import FaultPlan
        plan = FaultPlan.from_json(args.chaos)

    if args.model == "transformer":
        params, loss_fn, toks = _build_lm_async(args)
        batch_fn = lm_batch_fn(toks, args.batch_size, seed=args.seed)
    else:
        params, aux, loss_fn, has_aux, (x, y), _model = build(args)
        if has_aux or aux:
            raise SystemExit(
                "multi-host async PS supports aux-free models (mlp, "
                "transformer)")
        batch_fn = dataset_batch_fn(x, y, args.batch_size, seed=args.seed)

    if args.serve is not None and args.aggregators:
        return _run_hier(args, params, loss_fn, plan)
    if args.serve is not None and args.shards > 1:
        return _run_fleet(args, params, loss_fn, plan)
    if args.serve is not None:
        srv = AsyncPSServer(list(params.items()), optim=args.optim,
                            code=args.codec, quota=args.quota or 1,
                            port=args.serve, host="0.0.0.0",
                            token=args.token,
                            staleness_weighting=args.staleness_weighting,
                            max_staleness=args.max_staleness,
                            skip_nonfinite=args.skip_nonfinite,
                            aggregate=args.aggregate, trim_k=args.trim_k,
                            quorum=args.quorum,
                            fill_deadline=_resolve_fill_deadline(args),
                            anomaly_z=args.anomaly_z,
                            adaptive_deadline=args.adaptive_deadline,
                            latency_weighting=args.latency_weighting,
                            credit_window=args.credit_window,
                            op_deadline=args.op_deadline,
                            read_window=args.read_window,
                            wire_codec=args.wire_codec,
                            delta_parm=args.delta_parm,
                            fault_plan=plan,
                            **hyper_from_args(args))
        try:
            return _serve_single(args, srv, loss_fn)
        finally:
            _shutdown(srv)

    endpoints = []
    for part in args.connect.split(","):
        host, _, port = part.rpartition(":")
        if not host or not port.isdigit():
            raise SystemExit(f"--connect wants HOST:PORT (comma-separated "
                             f"for a shard fleet), got {args.connect!r}")
        endpoints.append((host, int(port)))
    if args.fallback:
        return _run_group_worker(args, endpoints[0], loss_fn, batch_fn,
                                 plan)
    if args.shards > 1 and len(endpoints) == 1:
        # The --serve --shards convention: shard k listens on PORT+k.
        host, port = endpoints[0]
        endpoints = [(host, port + k) for k in range(args.shards)]
    if len(endpoints) > 1:
        return _run_shard_worker(args, endpoints, loss_fn, batch_fn, plan)
    (host, port), = endpoints
    # backoff_max=2.0 (vs the library's 1.0): CLI workers face real PS
    # relaunches (python start + jax import + compile), so the retry
    # budget must stretch over tens of seconds, not test-speed blips.
    worker = AsyncPSWorker(host, port, code=args.codec,
                           token=args.token, fault_plan=plan,
                           reconnect_retries=args.reconnect_retries,
                           op_deadline=args.op_deadline,
                           credit_cap=args.credit_window or None,
                           bucket_bytes=args.async_bucket_bytes,
                           fused_encode=args.fused_encode,
                           backoff_max=2.0)
    print(f"worker rank {worker.rank} connected to {args.connect}",
          file=sys.stderr)
    if args.async_bucket_bytes is not None:
        # Machine-parseable: harnesses assert the streaming mode engaged.
        print(f"bucket streaming on "
              f"({'fused' if args.fused_encode else 'host'} encode)",
              file=sys.stderr)
    # batch_fn already mixes the rank into its SeedSequence stream;
    # the plain seed is what guarantees per-worker disjointness.
    pushed = worker.run(loss_fn, batch_fn)
    if worker.reconnects:
        print(f"worker rank {worker.rank}: {worker.reconnects} "
              f"reconnect(s) to the PS", file=sys.stderr)
    from .utils.timing import format_fault_stats
    rendered = format_fault_stats(worker.fault_snapshot())
    if rendered != "clean":
        # The sender-side flow-control accounting (credit stalls, shed
        # data frames, blown op deadlines, injected overload) — the
        # counted degradation this worker's own transport performed.
        print(f"worker fault stats: {rendered}", file=sys.stderr)
    print(f"worker rank {worker.rank} done: {pushed} gradients pushed",
          file=sys.stderr)
    return worker


def run_subscribe(args):
    """--subscribe: the serve-tier READER role — a versioned snapshot
    subscription against a live PS (or fleet), optionally driving the
    continuous-batching inference front-end (--infer-serve)."""
    from .serve import FleetSubscriber, InferenceFrontend, Subscriber
    from .utils.timing import format_fault_stats

    endpoints = []
    for part in args.subscribe.split(","):
        host, _, port = part.rpartition(":")
        if not host or not port.isdigit():
            raise SystemExit(f"--subscribe wants HOST:PORT (comma-"
                             f"separated for a shard fleet), got "
                             f"{args.subscribe!r}")
        endpoints.append((host, int(port)))
    if args.shards > 1 and len(endpoints) == 1:
        host, port = endpoints[0]
        endpoints = [(host, port + k) for k in range(args.shards)]
    sub_kw = dict(token=args.token,
                  reconnect_retries=args.reconnect_retries,
                  op_deadline=args.op_deadline, backoff_max=2.0,
                  # The inference engine must keep its per-step latency
                  # bound while the PS is down: the swap poll gets one
                  # bounded dial probe per backoff window, never the
                  # full redial ladder inside the decode loop.
                  nonblock_heal=args.infer_serve)
    if len(endpoints) > 1:
        sub = FleetSubscriber(endpoints, **sub_kw)
    else:
        (host, port), = endpoints
        sub = Subscriber(host, port, **sub_kw)
    version, params = sub.snapshot()
    # Machine-parseable on stdout (like "serving on port N").
    print(f"subscribed at version {version}", flush=True)

    if args.infer_serve:
        model = transformer_model(args)
        fe = InferenceFrontend(
            model, params, params_source=sub,
            max_batch=4, buf_len=max(args.seq_len, 16) + 16,
            max_queue=16)
        from .data.datasets import synthetic_lm
        from .errors import InferShedError
        toks = synthetic_lm(max(args.steps, 1), seq_len=8,
                            vocab=args.vocab, seed=args.seed)
        handles = []
        for i in range(args.steps):
            try:
                handles.append(fe.submit(toks[i % len(toks)][:8],
                                         max_new=8))
            except InferShedError:
                pass  # counted infer_shed; the driver just moves on
            fe.step()
        fe.drain()
        stats = fe.stats()
        lat = stats.get("request_latency") or {}
        print(f"infer done: {len(handles)} served, "
              f"{stats['infer_shed']} shed, "
              f"p50 {lat.get('p50_s', 0):.4f}s "
              f"p95 {lat.get('p95_s', 0):.4f}s over {stats['steps']} "
              f"batch steps, {stats['param_swaps']} hot swaps",
              file=sys.stderr)
    else:
        updates = sub.run(interval=0.02, max_polls=args.steps)
        print(f"subscriber done: {updates} snapshot update(s) over "
              f"{args.steps} polls, final version {sub.version}",
              file=sys.stderr)
    rendered = format_fault_stats(sub.fault_snapshot())
    if rendered != "clean":
        print(f"subscriber fault stats: {rendered}", file=sys.stderr)
    sub.close()
    return sub


def _run_fleet(args, params, loss_fn, plan):
    """--serve --shards K: the sharded PS fleet (`shard.PSFleet`) — K
    `AsyncPSServer` shards on serve threads in this process, shard k on
    port PORT+k (all ephemeral when PORT=0), supervised: a shard killed
    by the chaos plan is restored from its own auto-checkpoint."""
    import json as _json

    from .shard import PSFleet

    rules = None
    if args.partition_rules:
        try:
            rules = _json.loads(args.partition_rules)
        except ValueError as exc:
            raise SystemExit(
                f"--partition-rules is not valid JSON: {exc}")
    fleet = PSFleet(list(params.items()), num_shards=args.shards,
                    quota=args.quota or 1, host="0.0.0.0",
                    ports=args.serve, rules=rules,
                    replicas=args.replicas,
                    optim=args.optim, code=args.codec, token=args.token,
                    staleness_weighting=args.staleness_weighting,
                    max_staleness=args.max_staleness,
                    skip_nonfinite=args.skip_nonfinite,
                    aggregate=args.aggregate, trim_k=args.trim_k,
                    quorum=args.quorum,
                    fill_deadline=_resolve_fill_deadline(args),
                    anomaly_z=args.anomaly_z,
                    adaptive_deadline=args.adaptive_deadline,
                    latency_weighting=args.latency_weighting,
                    credit_window=args.credit_window,
                    op_deadline=args.op_deadline,
                    read_window=args.read_window,
                    wire_codec=args.wire_codec,
                    delta_parm=args.delta_parm,
                    fault_plan=plan, **hyper_from_args(args))
    fleet.compile_step(loss_fn)
    if args.resume:
        starts = fleet.resume_from(args.resume)
        print(f"resumed fleet shards at steps {starts}", file=sys.stderr)
    # Machine-parseable on stdout, the fleet analogue of "serving on
    # port N": shard k's port at position k.
    print("serving on ports "
          + " ".join(str(p) for _, p in fleet.addresses), flush=True)
    t0 = time.perf_counter()
    try:
        hist = fleet.serve(steps=args.steps, log_every=10,
                           checkpoint_path=args.save,
                           checkpoint_every=args.checkpoint_every,
                           snapshot_every=args.snapshot_every)
    finally:
        _shutdown(fleet)
    wall = time.perf_counter() - t0
    print(f"done: {hist['updates_total']} shard-updates across "
          f"{args.shards} shards ({hist['updates_total'] / wall:.1f} "
          f"aggregate updates/sec), {hist['grads_consumed']} grad "
          f"slices", file=sys.stderr)
    from .utils.timing import format_fault_stats
    rendered = format_fault_stats(hist["fault_stats"])
    if rendered != "clean":
        print("fault stats: " + rendered, file=sys.stderr)
    if args.save:
        fleet.save_checkpoint(args.save, args.steps)
        print(f"checkpoint -> {args.save} (per-shard siblings, step "
              f"{args.steps})", file=sys.stderr)
    return fleet


def _run_hier(args, params, loss_fn, plan):
    """--serve --aggregators G --group-size N: hierarchical aggregation
    (`shard.hierarchy`) — the root PS (or --shards K fleet) serves on a
    thread while G group-local aggregators fill under their own
    --group-* policy and forward one AGGR frame per fill.  Workers
    connect to the printed aggregator ports (with --fallback naming the
    root for failover)."""
    import json as _json
    import threading as _threading

    from .multihost_async import AsyncPSServer
    from .shard import Hierarchy, PSFleet
    from .utils.timing import format_fault_stats

    root_kw = dict(optim=args.optim, code=args.codec, token=args.token,
                   staleness_weighting=args.staleness_weighting,
                   max_staleness=args.max_staleness,
                   skip_nonfinite=args.skip_nonfinite,
                   aggregate=args.aggregate, trim_k=args.trim_k,
                   quorum=args.quorum,
                   fill_deadline=_resolve_fill_deadline(args),
                   anomaly_z=args.anomaly_z,
                   adaptive_deadline=(args.adaptive_deadline
                                      and args.quorum is not None),
                   latency_weighting=args.latency_weighting,
                   credit_window=args.credit_window,
                   op_deadline=args.op_deadline,
                   read_window=args.read_window,
                   wire_codec=args.wire_codec,
                   delta_parm=args.delta_parm,
                   **hyper_from_args(args))
    quota = args.quota or args.aggregators
    if args.shards > 1:
        rules = None
        if args.partition_rules:
            try:
                rules = _json.loads(args.partition_rules)
            except ValueError as exc:
                raise SystemExit(
                    f"--partition-rules is not valid JSON: {exc}")
        root = PSFleet(list(params.items()), num_shards=args.shards,
                       quota=quota, host="0.0.0.0", ports=args.serve,
                       rules=rules, replicas=args.replicas,
                       fault_plan=plan, **root_kw)
    else:
        root = AsyncPSServer(list(params.items()), quota=quota,
                             host="0.0.0.0", port=args.serve,
                             fault_plan=plan, **root_kw)
    root.compile_step(loss_fn)
    start = 0
    if args.resume:
        if args.shards > 1:
            starts = root.resume_from(args.resume)
            start = min(starts)
            print(f"resumed fleet shards at steps {starts}",
                  file=sys.stderr)
        else:
            start = root.resume_from(args.resume)
            print(f"resumed from {args.resume} at step {start}",
                  file=sys.stderr)
    updates = max(args.steps - start, 0)
    root_out: dict = {}

    def serve_root():
        try:
            kw = dict(log_every=10, checkpoint_path=args.save,
                      checkpoint_every=args.checkpoint_every)
            if args.shards > 1:
                # The fleet supervisor owns per-shard resume points; it
                # wants the TOTAL step target.
                kw.update(steps=args.steps,
                          snapshot_every=args.snapshot_every)
            else:
                kw.update(steps=updates, start_step=start)
            root_out["hist"] = root.serve(**kw)
        except BaseException as exc:  # re-raised after the tier winds down
            root_out["error"] = exc

    root_thread = _threading.Thread(target=serve_root, daemon=True,
                                    name="hier-root")
    root_thread.start()
    if args.shards > 1:
        root_ports = [p for _, p in root.addresses]
        print("serving on ports "
              + " ".join(str(p) for p in root_ports), flush=True)
    else:
        root_ports = [root.address[1]]
        print(f"serving on port {root_ports[0]}", flush=True)
    upstream = [("127.0.0.1", p) for p in root_ports]
    hier = Hierarchy(list(params.items()), groups=args.aggregators,
                     group_size=args.group_size, upstream=upstream,
                     host="0.0.0.0", fault_plan=plan,
                     code=args.codec, token=args.token,
                     aggregate=args.group_aggregate,
                     trim_k=args.group_trim_k, quorum=args.group_quorum,
                     fill_deadline=_resolve_group_deadline(args),
                     anomaly_z=args.group_anomaly_z,
                     adaptive_deadline=(args.adaptive_deadline
                                        and args.group_quorum is not None),
                     latency_weighting=args.latency_weighting,
                     # Worker-level admission control belongs at the
                     # level that sees RAW gradients: a NaN (or stale)
                     # worker gradient dropped here costs ONE gradient;
                     # admitted, it poisons the group's pre-reduced
                     # frame and the root then drops the whole GROUP's
                     # contribution.
                     skip_nonfinite=args.skip_nonfinite,
                     max_staleness=args.max_staleness,
                     staleness_weighting=args.staleness_weighting,
                     credit_window=args.credit_window,
                     op_deadline=args.op_deadline)
    hier.compile()
    # Machine-parseable on stdout: group g's aggregator port at position
    # g — what the workers' --connect should name.
    print("aggregators on ports "
          + " ".join(str(p) for _, p in hier.addresses), flush=True)
    t0 = time.perf_counter()
    try:
        view = hier.serve(log_every=10)
        root_thread.join(timeout=600)
    finally:
        _shutdown(hier, root)
    if "error" in root_out:
        raise root_out["error"]
    hist = root_out.get("hist") or {}
    wall = time.perf_counter() - t0
    fs = dict(hist.get("fault_stats") or {})
    # The fleet view's "groups" section: the root's HELO-side view plus
    # each aggregator's full snapshot (the group-level scoreboard the
    # containment story is about).
    tier = view["fault_stats"]
    merged_groups = dict(fs.get("groups") or {})
    for g, snap in tier.get("groups", {}).items():
        entry = dict(merged_groups.get(g) or {})
        entry["aggregator"] = snap
        merged_groups[g] = entry
    fs["groups"] = merged_groups
    n_updates = len(hist.get("losses") or [])
    print(f"done: {n_updates} root updates, {view['fills_total']} group "
          f"fills across {args.aggregators} aggregators in {wall:.1f}s",
          file=sys.stderr)
    rendered = format_fault_stats(fs)
    if rendered != "clean":
        print("fault stats: " + rendered, file=sys.stderr)
    tier_rendered = format_fault_stats(tier)
    if tier_rendered != "clean":
        print("aggregator tier: " + tier_rendered, file=sys.stderr)
    if args.save:
        if args.shards > 1:
            root.save_checkpoint(args.save, args.steps)
        else:
            root._auto_checkpoint(args.save, args.steps)
        print(f"checkpoint -> {args.save} (step {args.steps})",
              file=sys.stderr)
    return root


def _run_group_worker(args, agg_endpoint, loss_fn, batch_fn, plan):
    """--connect AGG --fallback ROOT[,...]: a failover-capable hierarchy
    worker (`shard.hierarchy.GroupWorker`)."""
    from .shard import GroupWorker

    roots = []
    for part in args.fallback.split(","):
        host, _, port = part.rpartition(":")
        if not host or not port.isdigit():
            raise SystemExit(f"--fallback wants HOST:PORT[,...], got "
                             f"{args.fallback!r}")
        roots.append((host, int(port)))
    if args.shards > 1 and len(roots) == 1:
        host, port = roots[0]
        roots = [(host, port + k) for k in range(args.shards)]
    (h, p) = agg_endpoint
    group = args.group if args.group is not None else 0
    worker = GroupWorker(h, p, root_endpoints=roots, group=group,
                         code=args.codec, token=args.token,
                         fault_plan=plan,
                         reconnect_retries=args.reconnect_retries,
                         backoff_max=2.0)
    print(f"group {group} worker local rank {worker.rank} "
          f"connected to aggregator {h}:{p}", file=sys.stderr)
    pushed = worker.run(loss_fn, batch_fn)
    from .utils.timing import format_fault_stats
    rendered = format_fault_stats(worker.fault_stats)
    if rendered != "clean":
        print(f"worker fault stats: {rendered}", file=sys.stderr)
    print(f"group worker done: {pushed} gradients pushed",
          file=sys.stderr)
    return worker


def _run_shard_worker(args, endpoints, loss_fn, batch_fn, plan):
    """--connect with a K-shard fleet: one `shard.ShardRouter` — a
    single fleet-wide rank, one gradient computation per step, per-shard
    GRAD slices with per-shard versions."""
    from .shard import ShardRouter

    router = ShardRouter(endpoints, code=args.codec, token=args.token,
                         fault_plan=plan,
                         reconnect_retries=args.reconnect_retries,
                         op_deadline=args.op_deadline,
                         credit_cap=args.credit_window or None,
                         backoff_max=2.0)
    print(f"worker rank {router.rank} connected to "
          f"{len(endpoints)}-shard fleet at {endpoints[0][0]}",
          file=sys.stderr)
    pushed = router.run(loss_fn, batch_fn)
    if router.reconnects:
        print(f"worker rank {router.rank}: {router.reconnects} "
              f"reconnect(s) to the fleet", file=sys.stderr)
    print(f"worker rank {router.rank} done: {pushed} gradients pushed",
          file=sys.stderr)
    return router


def run_async(args):
    """AsySG-InCon training (`/root/reference/README.md:56-77`): host-driven
    workers on their own devices, PS updates after ``--quota`` grads."""
    from .async_ps import AsyncPS, dataset_batch_fn, lm_batch_fn

    if args.model == "transformer":
        params, loss_fn, toks = _build_lm_async(args)
        make_batch_fn = lambda seed: lm_batch_fn(
            toks, args.batch_size, seed=seed)
    else:
        params, aux, loss_fn, has_aux, (x, y), _model = build(args)
        if has_aux or aux:
            raise SystemExit(
                "--async-ps supports aux-free models (mlp, transformer)")
        make_batch_fn = lambda seed: dataset_batch_fn(
            x, y, args.batch_size, seed=seed)
    if args.save_every:
        raise SystemExit("--save-every is not supported with --async-ps "
                         "(updates run inside one opt.run call); use --save")
    hyper = hyper_from_args(args)
    from .parallel.mesh import default_devices
    devices = (default_devices()[:args.n_devices] if args.n_devices
               else None)
    plan = None
    if args.chaos:
        from .utils.faults import FaultPlan
        plan = FaultPlan.from_json(args.chaos)  # kill_ps_at applies here
    opt = AsyncPS(list(params.items()), optim=args.optim, code=args.codec,
                  quota=args.quota, devices=devices,
                  staleness_weighting=args.staleness_weighting,
                  max_staleness=args.max_staleness,
                  skip_nonfinite=args.skip_nonfinite,
                  aggregate=args.aggregate, trim_k=args.trim_k,
                  quorum=args.quorum,
                  fill_deadline=_resolve_fill_deadline(args),
                  anomaly_z=args.anomaly_z,
                  adaptive_deadline=args.adaptive_deadline,
                  latency_weighting=args.latency_weighting,
                  credit_window=args.credit_window,
                  fault_plan=plan, **hyper)
    print(f"async PS: {opt.num_workers} workers, quota {opt.quota}",
          file=sys.stderr)
    opt.compile_step(loss_fn)
    start, _extra = _restore(args, opt)
    updates = max(args.steps - start, 0)
    if updates == 0:
        print("nothing to do: checkpoint is already at "
              f"step {start} >= --steps {args.steps}", file=sys.stderr)
        return opt
    t0 = time.perf_counter()
    # Mix the resume point into the seed: async batch order is
    # quota-nondeterministic anyway, but a resumed run must draw *fresh*
    # batches, not re-train the stream the first run consumed.
    try:
        hist = opt.run(make_batch_fn(args.seed + start),
                       steps=updates, log_every=10)
    except KeyboardInterrupt:
        # The async run's update count isn't observable mid-flight from
        # here; save at the resume point — params/state reflect every
        # update applied so far, and the step counter stays conservative.
        _interrupted_exit(args, opt, start)
    wall = time.perf_counter() - t0
    grads = hist["grads_consumed"]
    print(f"done: {updates} updates, {grads} grads, "
          f"{grads * args.batch_size / wall:.1f} images/sec, "
          f"mean staleness {np.mean(hist['staleness']):.2f}", file=sys.stderr)
    _maybe_save(args, opt, start + updates, final=True)
    if args.summary:
        opt.print_summary()
    return opt


def cli_entry() -> None:
    """Console-script entry point (`ps-tpu-train`): like ``main()`` but
    discards the returned optimizer (setuptools treats a non-None return
    as an exit status)."""
    main()


if __name__ == "__main__":
    main()
