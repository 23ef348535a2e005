"""Timing/profiling utils: metric-dict contract and profiler trace output."""

import threading
import time

import pytest

from pytorch_ps_mpi_tpu.utils.timing import (STEP_METRIC_KEYS, SpanLog,
                                             print_summary, span, span_log,
                                             trace)


def test_step_metric_keys_match_reference_contract():
    # The reference step() dict keys (/root/reference/ps.py:193 and SURVEY §5).
    for key in ("code_wait", "iallgather_prepare_time", "isend_time",
                "comm_wait", "decode_time", "optim_step_time", "msg_bytes",
                "packaged_bytes"):
        assert key in STEP_METRIC_KEYS


def test_print_summary_smoke(capsys):
    print_summary([{"comm_wait": 0.5, "msg_bytes": 10.0},
                   {"comm_wait": 1.5}])
    out = capsys.readouterr().out
    assert "comm_wait" in out and "mean=  1.0" in out.replace("1.000000", "1.0")


def test_trace_writes_profile_with_the_programs_spans(tmp_path):
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from perfbench.trace_reduce import find_xplane

    logdir = str(tmp_path / "trace")
    with trace(logdir):
        with span("toy-compute", update=3):
            jnp.arange(128.0).sum().block_until_ready()
    profile = ProfileData.from_file(find_xplane(logdir))
    names = [e.name for plane in profile.planes for line in plane.lines
             for e in line.events]
    assert any(n.startswith("ps:toy-compute") for n in names)


# -- span() and the span log -------------------------------------------------


@pytest.fixture
def log():
    """The process-wide log, emptied before and after."""
    span_log().clear()
    yield span_log()
    span_log().clear()


def test_span_records_its_fields_and_its_parent(log):
    with span("outer", update=7) as outer:
        with span("inner") as inner:
            time.sleep(0.01)
        inner.set(n=2, staleness=1.5)      # known only after the body ran
    inner_rec, outer_rec = log.records()   # the inner one closed first
    assert inner_rec["name"] == "inner" and outer_rec["name"] == "outer"
    assert outer_rec["update"] == 7
    assert inner_rec["n"] == 2 and inner_rec["staleness"] == 1.5
    assert outer_rec["parent"] is None
    assert inner_rec["parent"] == outer_rec["id"] != inner_rec["id"]
    for rec, s in ((inner_rec, inner), (outer_rec, outer)):
        assert rec["thread"] == threading.current_thread().name
        assert rec["start"] <= rec["end"]
        assert s.duration == rec["end"] - rec["start"]
        assert 0.0 <= rec["cpu"] <= s.duration + 1e-3
    assert outer_rec["start"] <= inner_rec["start"]
    assert inner_rec["end"] <= outer_rec["end"]
    assert inner.duration >= 0.01
    # asleep is off the CPU: the thread's own time is a small part of the wall
    assert inner_rec["cpu"] < 0.5 * inner.duration
    # the clock is perf_counter, the one the benchmark's window is on
    assert abs(outer_rec["end"] - time.perf_counter()) < 5.0


def test_cpu_clock_is_read_again_only_after_the_reuse_window(monkeypatch):
    """The thread's CPU clock is a system call: a read younger than
    `_CPU_READ_REUSE_S` is used again, an older one is not."""
    from pytorch_ps_mpi_tpu.utils import timing
    reads = iter([1.0, 2.0, 3.0])
    monkeypatch.setattr(timing.time, "thread_time", lambda: next(reads))
    mine, eps = timing._ThreadSpans(), timing._CPU_READ_REUSE_S
    assert mine.cpu(100.0) == 1.0
    assert mine.cpu(100.0 + 0.9 * eps) == 1.0       # young enough
    assert mine.cpu(100.0 + 1.1 * eps) == 2.0       # measured from the read
    assert mine.cpu(100.0 + 1.9 * eps) == 2.0       # ... not from the reuse
    assert mine.cpu(100.0 + 2.2 * eps) == 3.0


def test_span_closes_and_records_when_its_body_raises(log):
    with pytest.raises(KeyError):
        with span("outer"):
            with span("raises"):
                raise KeyError("x")
    with span("after"):
        pass
    by_name = {r["name"]: r for r in log.records()}
    assert by_name["raises"]["parent"] == by_name["outer"]["id"]
    assert by_name["after"]["parent"] is None     # the stack was unwound


def test_spans_of_two_threads_keep_separate_parents(log):
    inside = threading.Barrier(2, timeout=10)

    def body(tag):
        with span("top", tag=tag):
            inside.wait()       # both threads hold their `top` open at once
            with span("leaf", tag=tag):
                pass

    threads = [threading.Thread(target=body, args=(t,), name=f"span-test-{t}")
               for t in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    for tag in (0, 1):
        top, = [r for r in log.records(name="top") if r["tag"] == tag]
        leaf, = log.records(name="leaf", thread=f"span-test-{tag}")
        assert top["thread"] == leaf["thread"] == f"span-test-{tag}"
        assert leaf["parent"] == top["id"] and top["parent"] is None
    assert len({r["id"] for r in log.records()}) == 4


def test_records_filters_by_name_thread_and_time(log):
    with span("a"):
        pass
    with span("b"):
        pass
    a, b = log.records()
    assert [r["name"] for r in log.records(name="b")] == ["b"]
    assert log.records(thread="no-such-thread") == []
    assert log.records(since=b["start"]) == [b]
    assert log.records(until=a["end"]) == [a]
    assert log.records(since=a["start"], until=b["end"]) == [a, b]
    # copies: a reader cannot change the log
    a["name"] = "changed"
    assert log.records()[0]["name"] == "a"


def test_ring_drops_the_oldest_and_counts_them():
    ring = SpanLog(capacity=3)
    for i in range(5):
        ring._append({"name": "s", "thread": "t", "start": float(i),
                      "end": i + 0.5, "i": i})
    assert len(ring) == 3 and ring.dropped == 2
    assert [r["i"] for r in ring.records()] == [2, 3, 4]
    assert ring.dropped_until == 1.5        # the end of the last one dropped
    ring.clear()
    assert len(ring) == 0 and ring.dropped == 0 and ring.dropped_until is None


def test_log_is_bounded_under_appends_from_several_threads(log):
    import sys
    per_thread, n_threads = 2000, 8
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def body():
            for _ in range(per_thread):
                with span("stress"):
                    pass
        threads = [threading.Thread(target=body) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(before)
    records = log.records(name="stress")
    # nothing lost, nothing counted twice, every id its own
    assert len(records) + log.dropped == per_thread * n_threads
    assert len({r["id"] for r in records}) == len(records)
    assert all(r["parent"] is None for r in records)


# -- counters that leave a step unread, and scopes of compiled programs ------


def test_counter_log_is_bounded_and_filters_by_source():
    from pytorch_ps_mpi_tpu.utils.timing import CounterLog
    log = CounterLog(capacity=3)
    for i in range(5):
        log.append("a" if i % 2 else "b", i, {"n": i})
    assert len(log) == 3 and log.dropped == 2
    assert [r["step"] for r in log.records()] == [2, 3, 4]
    assert [r["values"]["n"] for r in log.records("b")] == [2, 4]
    log.clear()
    assert len(log) == 0 and log.dropped == 0


def test_program_scopes_reads_op_names_and_keeps_them():
    from pytorch_ps_mpi_tpu.utils import timing
    calls = []
    text = """
HloModule jit_step
%fused (p: f32[4]) -> f32[4] {
  ROOT %multiply.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(step)/kda/mul" source_file="x.py" source_line=3}
}
ENTRY %main {
  %fusion.7 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/transpose(jvp(kda))/while/body/mul"}
  dot.3 = f32[4,4]{1,0} dot(%a, %b), metadata={op_name="jit(step)/block_1/moe/dot_general"}
  %copy.1 = f32[4]{0} copy(%a)
}"""
    timing.register_program("test.program",
                            lambda: calls.append(1) or text)
    scopes = timing.program_scopes("test.program")
    assert scopes == {
        "multiply.1": "jit(step)/kda/mul",
        "fusion.7": "jit(step)/transpose(jvp(kda))/while/body/mul",
        "dot.3": "jit(step)/block_1/moe/dot_general"}
    assert timing.program_scopes("test.program") is scopes and calls == [1]
    assert timing.program_scopes("no.such.program") is None
    assert timing.in_scope(scopes["fusion.7"], "kda")
    assert timing.in_scope(scopes["multiply.1"], "kda")
    assert not timing.in_scope(scopes["dot.3"], "kda")
    assert timing.in_scope(scopes["dot.3"], "moe")
    assert not timing.in_scope("jit(step)/kdanot/mul", "kda")


def test_program_scopes_reads_a_pallas_call_printed_over_three_lines():
    """A Pallas call that carries `metadata=` has its ``kernel_metadata={``
    printed over three lines with the ``op_name`` on the last, and so has
    every read of its tuple; the computation's closing brace starts a line
    too and joins nothing."""
    from pytorch_ps_mpi_tpu.utils import timing
    text = """
ENTRY %main {
  %kda_fwd.2 = (bf16[1,256,256]{2,1,0}, f32[1,2,1,128,128]{4,3,2,1,0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"kernel":"kda_fwd"
}}, metadata={op_name="jit(step)/jvp(KDAttention)/kda/cond/branch_0_fun/kda_fwd/pallas_call" stack_frame_id=81}, backend_config={"custom_call_config":{"body":"TUzv"}}
  %pallas_call.13 = bf16[1,256,256]{2,1,0} get-tuple-element(%kda_fwd.2), index=0, frontend_attributes={kernel_metadata={
"kernel":"kda_fwd"
}}, metadata={op_name="jit(step)/jvp(KDAttention)/kda/cond/branch_0_fun/kda_fwd/pallas_call"}
  ROOT %add.1 = f32[4]{0} add(%a, %a), metadata={op_name="jit(step)/head_loss/add"}
}
%other {
  %copy.1 = f32[4]{0} copy(%a)
}"""
    timing.register_program("test.pallas", lambda: text)
    scopes = timing.program_scopes("test.pallas")
    assert sorted(scopes) == ["add.1", "kda_fwd.2", "pallas_call.13"]
    assert timing.in_scope(scopes["kda_fwd.2"], "kda")
    assert timing.in_scope(scopes["pallas_call.13"], "kda")
    assert scopes["add.1"] == "jit(step)/head_loss/add"
