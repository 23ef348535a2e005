"""The readers of the sync step's phases on hand-made traces: a synthetic
`Trace`, and a compiled text registered under the step program's name (as
`test_perfbench_kimi.py` does for the model scopes)."""

import json
import os
import re

import pytest

from perfbench.layer_metrics import (_sync_phases, collective_ms_step,
                                     sync_bwd_after_exchange_start_pct,
                                     sync_bwd_ms_step, sync_exchange_ms_step,
                                     sync_fwd_ms_step, sync_head_loss_ms_step,
                                     sync_host_ms_p50,
                                     sync_remat_fused_ms_step,
                                     sync_remat_ms_step,
                                     sync_shard_batch_ms_p50,
                                     sync_unscoped_pct,
                                     sync_update_fused_ms_step,
                                     sync_update_ms_step)
from perfbench.trace_reduce import DeviceTrace, Op, Trace
from pytorch_ps_mpi_tpu.utils import timing

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
US = 1e-6
G = "jit(spmd_step)/shard_map/ps.grad"
T = G + "/transpose(jvp(ps.grad))"
TEXT = f"""
  %fusion.1 = f32[4]{{0}} fusion(%a), metadata={{op_name="{G}/jvp(block_0)/dot_general"}}
  %while.2 = (f32[4]) while(%t), metadata={{op_name="{G}/jvp(block_0)/kda/while"}}
  %fusion.3 = f32[4]{{0}} fusion(%a), metadata={{op_name="{G}/jvp(block_0)/kda/while/body/mul"}}
  %fusion.4 = f32[4]{{0}} fusion(%a), metadata={{op_name="{G}/jvp(head_loss)/lm_head/dot_general"}}
  %fusion.5 = f32[4]{{0}} fusion(%a), metadata={{op_name="{T}/head_loss/lm_head/dot_general"}}
  %fusion.6 = f32[4]{{0}} fusion(%a), metadata={{op_name="{T}/jvp()/checkpoint/rematted_computation/block_0/dot_general"}}
  %fusion.7 = f32[4]{{0}} fusion(%a), metadata={{op_name="{T}/jvp()/checkpoint/block_0/dot_general"}}
  %fusion.8 = f32[4]{{0}} fusion(%a), metadata={{op_name="jit(spmd_step)/shard_map/ps.exchange/concatenate"}}
  %all-reduce.9 = f32[4]{{0}} all-reduce(%a), metadata={{op_name="jit(spmd_step)/shard_map/ps.exchange/psum"}}
  %fusion.10 = f32[4]{{0}} fusion(%a), metadata={{op_name="jit(spmd_step)/shard_map/ps.update/sub"}}
  %fusion.11 = f32[4]{{0}} fusion(%a), metadata={{op_name="jit(spmd_step)/shard_map/convert_element_type"}}
  %copy.12 = f32[4]{{0}} copy(%a)
"""
# One step of 100 us on one chip, in order: forward 10 + a loop of 20 with a
# body event inside it + head 5; backward: head 5, remat 10, backward 15;
# packing 4, the sum 10, update 8; then 3 us under no scope and 2 us of an
# instruction the text says nothing of: 92 us busy.
STEP = [("fusion.1", 0, 10), ("while.2", 10, 30), ("fusion.3", 12, 28),
        ("fusion.4", 30, 35), ("fusion.5", 35, 40), ("fusion.6", 40, 50),
        ("fusion.7", 50, 65), ("fusion.8", 65, 69), ("all-reduce.9", 69, 79),
        ("fusion.10", 79, 87), ("fusion.11", 87, 90), ("copy.12", 90, 92)]


def ops(events, at=0.0, stretch=1.0):
    return [Op(n, (at + s * stretch) * US, (at + e * stretch) * US)
            for n, s, e in events]


@pytest.fixture
def program():
    timing.register_program(_sync_phases.PROGRAM, lambda: TEXT)
    yield
    timing.register_program(_sync_phases.PROGRAM, lambda: "")


def observed(devices, steps, window):
    trace = Trace(devices=[DeviceTrace(i, ops=o) for i, o in
                           enumerate(devices)], spans=[],
                  window=(window[0] * US, window[1] * US))
    return {"trace": trace, "result": {"trace_steps": steps}}


def test_the_text_classifies_as_the_readers_expect(program):
    phases = _sync_phases.instruction_phases()
    assert phases == {
        "fusion.1": "forward", "while.2": "forward", "fusion.3": "forward",
        "fusion.4": "forward", "fusion.5": "backward", "fusion.6": "remat",
        "fusion.7": "backward", "fusion.8": "exchange",
        "all-reduce.9": "exchange", "fusion.10": "update"}


def test_a_loop_and_its_body_are_counted_once_and_the_phases_add_up(program):
    obs = observed([ops(STEP) + ops(STEP, at=100)], 2, (0, 200))
    assert sync_fwd_ms_step.read(obs) == pytest.approx(1e3 * 35 * US)
    assert sync_remat_ms_step.read(obs) == pytest.approx(1e3 * 10 * US)
    assert sync_bwd_ms_step.read(obs) == pytest.approx(1e3 * 20 * US)
    assert sync_update_ms_step.read(obs) == pytest.approx(1e3 * 8 * US)
    assert sync_exchange_ms_step.read(obs) == pytest.approx(1e3 * 14 * US)
    # the head's forward and backward together, never added to the phases
    assert sync_head_loss_ms_step.read(obs) == pytest.approx(1e3 * 10 * US)
    # 5 of the 92 busy microseconds a step are under no phase
    assert sync_unscoped_pct.read(obs) == pytest.approx(100 * 5 / 92)
    phases = sum(r.read(obs) for r in (
        sync_fwd_ms_step, sync_remat_ms_step, sync_bwd_ms_step,
        sync_update_ms_step, sync_exchange_ms_step))
    busy = 1e3 * sum(obs["trace"].busy_s()) / 2
    assert phases == pytest.approx(busy * (1 - 5 / 92))


def test_the_window_clips_and_two_chips_are_averaged(program):
    slow = ops(STEP, stretch=2.0)           # the second chip at half speed
    obs = observed([ops(STEP), slow], 1, (0, 200))
    assert sync_fwd_ms_step.read(obs) == pytest.approx(1e3 * 52.5 * US)
    assert sync_update_ms_step.read(obs) == pytest.approx(1e3 * 12 * US)
    # the exchange on the chip where it is longest, as `collective_ms_step`
    assert sync_exchange_ms_step.read(obs) == pytest.approx(1e3 * 28 * US)
    assert collective_ms_step.read(obs) == pytest.approx(1e3 * 20 * US)
    assert sync_exchange_ms_step.read(obs) >= collective_ms_step.read(obs)
    # busy 92 + 184, unscoped 5 + 10: all chips together
    assert sync_unscoped_pct.read(obs) == pytest.approx(100 * 15 / 276)
    # a window that ends inside the forward's loop
    early = observed([ops(STEP)], 1, (5, 20))
    assert sync_fwd_ms_step.read(early) == pytest.approx(1e3 * 15 * US)
    assert sync_bwd_ms_step.read(early) is None


def test_an_asynchronous_pair_is_exchange_from_its_start_to_its_done():
    pair = TEXT.replace("%all-reduce.9 = f32[4]{0} all-reduce(", """%all-reduce-start.9 = f32[4]{0} all-reduce-start(%a), metadata={op_name="jit(spmd_step)/shard_map/ps.exchange/psum"}
  %all-reduce-done.9 = f32[4]{0} all-reduce-done(""")
    timing.register_program(_sync_phases.PROGRAM, lambda: pair)
    events = [e for e in STEP if e[0] != "all-reduce.9"]
    obs = observed([ops(events) + ops([("all-reduce-start.9", 69, 70),
                                       ("all-reduce-done.9", 78, 79)])],
                   1, (0, 100))
    assert sync_exchange_ms_step.read(obs) == pytest.approx(1e3 * 14 * US)
    assert collective_ms_step.read(obs) == pytest.approx(1e3 * 10 * US)
    timing.register_program(_sync_phases.PROGRAM, lambda: "")


def test_a_collective_that_lost_the_scope_is_not_exchange(program):
    """The metric reads the scope, not the opcode: `sync_exchange_ms_step`
    falls under `collective_ms_step` when XLA's passes drop `ps.exchange`
    from a collective, and that is how one would see it."""
    events = [e for e in STEP if e[0] != "all-reduce.9"]
    obs = observed([ops(events) + ops([("all-reduce.99", 69, 79)])], 1,
                   (0, 100))
    assert collective_ms_step.read(obs) == pytest.approx(1e3 * 10 * US)
    assert sync_exchange_ms_step.read(obs) == pytest.approx(1e3 * 4 * US)
    assert sync_bwd_after_exchange_start_pct.read(obs) is None


def test_backward_after_the_first_sum_is_0_when_the_sums_come_last(program):
    obs = observed([ops(STEP) + ops(STEP, at=100) + ops(STEP, at=200)], 3,
                   (0, 300))
    assert sync_bwd_after_exchange_start_pct.read(obs) == 0.0


def test_backward_after_the_first_sum_is_50_when_half_of_it_follows(program):
    # head's backward 5, remat 10, the first sum, backward 15, the second
    # sum: 15 of the 30 us of backward and remat lie after the first sum
    step = STEP[:6] + [("all-reduce.9", 50, 55), ("fusion.7", 55, 70),
                       ("all-reduce.9", 70, 75), ("fusion.10", 75, 83)]
    obs = observed([ops(step) + ops(step, at=100)], 2, (0, 200))
    assert sync_bwd_after_exchange_start_pct.read(obs) == pytest.approx(50.0)
    # the chip where the share is lowest decides
    both = observed([ops(step) + ops(step, at=100),
                     ops(STEP) + ops(STEP, at=100)], 2, (0, 200))
    assert sync_bwd_after_exchange_start_pct.read(both) == 0.0
    # an operation that recurs inside a loop cannot cut the steps: the first
    # one that runs once a step does
    looped = [("fusion.3", -3, -2), ("fusion.3", -2, -1)] + step
    obs = observed([ops(looped, at=3) + ops(looped, at=103)], 2, (0, 210))
    assert sync_bwd_after_exchange_start_pct.read(obs) == pytest.approx(50.0)
    # no collective in the trace (one chip): nothing to say
    alone = [e for e in STEP if e[0] != "all-reduce.9"]
    assert sync_bwd_after_exchange_start_pct.read(
        observed([ops(alone)], 1, (0, 100))) is None


DEVICE_READERS = (sync_fwd_ms_step, sync_remat_ms_step, sync_bwd_ms_step,
                  sync_update_ms_step, sync_head_loss_ms_step,
                  sync_unscoped_pct, sync_exchange_ms_step,
                  sync_bwd_after_exchange_start_pct,
                  sync_update_fused_ms_step, sync_remat_fused_ms_step)


@pytest.mark.parametrize("reader", DEVICE_READERS,
                         ids=lambda r: r.__name__.rsplit(".", 1)[-1])
def test_none_without_scopes_and_without_device_planes(reader, program):
    obs = observed([ops(STEP)], 1, (0, 100))
    assert reader.read(obs) is not None
    # the CPU rehearsal: a trace with no device plane, or no trace at all
    assert reader.read(observed([], 1, (0, 100))) is None
    assert reader.read({**obs, "trace": None}) is None
    assert reader.read({**obs, "result": {"trace_steps": 0}}) is None
    # the parent commit: a program with the models' scopes and none of the
    # step's (its `head_loss` is there, and still says nothing)
    timing.register_program(_sync_phases.PROGRAM, lambda: TEXT.replace(
        "/ps.grad", "").replace("ps.grad", "").replace("ps.", "x."))
    assert reader.read(obs) is None
    timing.register_program(_sync_phases.PROGRAM, lambda: "")
    assert reader.read(obs) is None


# What XLA fused into what: `fusion.5` and `fusion.7` are rooted in the
# backward; the first also holds the optimizer's rule, the second the rule and
# rematerialised work; `fusion.10` is the update with a backward convert in it.
FUSED = f"""
%fused_computation.5 (p: f32[4]) -> f32[4] {{
  %mul.50 = f32[4]{{0}} multiply(%p, %p), metadata={{op_name="jit(spmd_step)/shard_map/ps.update/mul"}}
  ROOT %dot.51 = f32[4]{{0}} dot(%p, %mul.50), metadata={{op_name="{T}/head_loss/lm_head/dot_general"}}
}}

%fused_computation.7 (p: f32[4]) -> f32[4] {{
  %exp.70 = f32[4]{{0}} exponential(%p), metadata={{op_name="{T}/jvp()/checkpoint/rematted_computation/block_0/exp"}}
  %sub.71 = f32[4]{{0}} subtract(%p, %exp.70), metadata={{op_name="jit(spmd_step)/shard_map/ps.update/sub"}}
  ROOT %dot.72 = f32[4]{{0}} dot(%p, %sub.71), metadata={{op_name="{T}/jvp()/checkpoint/block_0/dot_general"}}
}}

%fused_computation.10 (p: f32[4]) -> f32[4] {{
  %convert.100 = f32[4]{{0}} convert(%p), metadata={{op_name="{T}/jvp()/checkpoint/block_0/convert_element_type"}}
  ROOT %sub.101 = f32[4]{{0}} subtract(%p, %convert.100), metadata={{op_name="jit(spmd_step)/shard_map/ps.update/sub"}}
}}

ENTRY %main (a: f32[4]) -> f32[4] {{""" + re.sub(
    r"(%fusion\.(\d+) = f32\[4\]\{0\} fusion\(%a\)), ",
    r"\1, kind=kOutput, calls=%fused_computation.\2, ", TEXT) + "}\n"


def test_fused_elsewhere_is_the_other_bound_of_update_and_remat():
    timing.register_program(_sync_phases.PROGRAM, lambda: FUSED)
    fusions = timing.program_fusions(_sync_phases.PROGRAM)
    assert fusions["fusion.5"] == ("mul.50", "dot.51")
    assert fusions["fusion.7"] == ("exp.70", "sub.71", "dot.72")
    assert fusions["fusion.10"] == ("convert.100", "sub.101")
    assert fusions["fusion.1"] == ()        # calls a computation not shown
    obs = observed([ops(STEP), ops(STEP, stretch=2.0)], 1, (0, 200))
    # the roots' phases are what they were
    assert sync_update_ms_step.read(obs) == pytest.approx(1e3 * 12 * US)
    assert sync_bwd_ms_step.read(obs) == pytest.approx(1e3 * 30 * US)
    # update instructions ride in fusion.5 (5 us) and fusion.7 (15 us),
    # rematerialised ones in fusion.7; fusion.10 is the update's own
    assert sync_update_fused_ms_step.read(obs) == pytest.approx(
        1e3 * 30 * US)
    assert sync_remat_fused_ms_step.read(obs) == pytest.approx(
        1e3 * 22.5 * US)
    timing.register_program(_sync_phases.PROGRAM, lambda: "")


def test_fused_elsewhere_is_0_where_nothing_is_and_absent_without_the_phase(
        program):
    # `program`'s text shows no fused computation: every fusion is whole
    obs = observed([ops(STEP)], 1, (0, 100))
    assert sync_update_fused_ms_step.read(obs) == 0.0
    assert sync_remat_fused_ms_step.read(obs) == 0.0
    no_remat = TEXT.replace("rematted_computation/", "")
    timing.register_program(_sync_phases.PROGRAM, lambda: no_remat)
    assert sync_remat_fused_ms_step.read(obs) is None
    assert sync_update_fused_ms_step.read(obs) == 0.0


def test_remat_is_absent_where_the_loss_has_no_checkpoint(program):
    events = [e for e in STEP if e[0] != "fusion.6"]
    obs = observed([ops(events)], 1, (0, 100))
    assert sync_remat_ms_step.read(obs) is None
    assert sync_bwd_ms_step.read(obs) == pytest.approx(1e3 * 20 * US)


def test_span_readers_take_the_median_over_the_untraced_window():
    log = timing.span_log()
    log.clear()
    try:
        assert sync_host_ms_p50.read({"result": {"window": (0.0, 1e9)}}) \
            is None
        import time
        start = time.perf_counter()
        for ms in (1, 2, 9):
            with timing.span("sync.step"):
                with timing.span("sync.shard_batch"):
                    time.sleep(ms * 1e-3)
                time.sleep(1e-3)
        end = time.perf_counter()
        with timing.span("sync.step"):      # after the window: not counted
            time.sleep(20e-3)
        obs = {"result": {"window": (start, end)}}
        assert 2.0 <= sync_shard_batch_ms_p50.read(obs) < 3.0 + 2.0
        assert sync_host_ms_p50.read(obs) \
            >= sync_shard_batch_ms_p50.read(obs) + 1.0
        assert sync_host_ms_p50.read(obs) < 9.0
    finally:
        log.clear()


def test_the_entries_are_as_the_issue_wrote_them_and_two_more():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    sync6 = [w["name"] for w in bench["workloads"]
             if w["name"] != "resnet50-async-1chip"]
    lm5 = [c for c in sync6 if c != "resnet50-sync-1chip"]
    # listed where the loss has a checkpoint: the reader finds nothing else
    remat3 = ["kimi-linear-sync-1chip", "glm47-flash-sync-1chip",
              "phi4flash-sync-1chip"]
    want = {
        "sync_fwd_ms_step": ("ms", "lower", "device_trace", sync6),
        "sync_remat_ms_step": ("ms", "lower", "device_trace", remat3),
        "sync_bwd_ms_step": ("ms", "lower", "device_trace", sync6),
        "sync_update_ms_step": ("ms", "lower", "device_trace", sync6),
        "sync_head_loss_ms_step": ("ms", "lower", "device_trace", lm5),
        "sync_unscoped_pct": ("%", "lower", "device_trace", sync6),
        "sync_exchange_ms_step": ("ms", "lower", "device_trace",
                                  ["gpt2m-sync-dp4"]),
        "sync_bwd_after_exchange_start_pct": ("%", "higher", "device_trace",
                                              ["gpt2m-sync-dp4"]),
        "sync_host_ms_p50": ("ms", "lower", "program_span", sync6),
        "sync_shard_batch_ms_p50": ("ms", "lower", "program_span", sync6),
        # the review's: the other bound of the two phases XLA fuses away
        "sync_update_fused_ms_step": ("ms", "lower", "device_trace", sync6),
        "sync_remat_fused_ms_step": ("ms", "lower", "device_trace", remat3),
    }
    assert [m["name"] for m in bench["per_layer"]][-len(want):] == list(want)
    for name, (unit, better, source, cells) in want.items():
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"]) == (unit, better, source)
        assert sorted(m["workloads"]) == sorted(cells), name
        assert m["moves"] == "samples_per_s_chip"
        assert m["layer"] == ("gradient exchange" if "exchange" in name
                              else "sync step")
