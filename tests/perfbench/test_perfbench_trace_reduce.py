"""The reduction from a profiler trace to the per-layer numbers, checked on
a trace written by hand (every answer countable on paper) and on one small
trace recorded on a TPU v5e host (`fixtures/`)."""

import os

import pytest

from perfbench import trace_reduce as tr

US = 1_000_000  # picoseconds in a microsecond, the text proto's unit


def _event(meta: int, start_us: float, dur_us: float, stats=()) -> str:
    body = "".join(f' stats {{ metadata_id: {k} str_value: "{v}" }}'
                   for k, v in stats)
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_us * US)} "
            f"duration_ps: {int(dur_us * US)}{body} }}\n")


def _hand_trace():
    """One chip, a window of 100 us under two host spans.

        0        10       40 42     60      70 75    85      100
        |  idle   | fusion |s| fusion |  done | |flash|  idle  |
                           [--- all-reduce ---]
        [---- pb:dispatch 0..30 ----][---- pb:wait_ready 30..100 ----]
    """
    from jax.profiler import ProfileData
    names = {1: "fusion.1", 2: "all-reduce-start.1", 3: "fusion.2",
             4: "all-reduce-done.1", 5: "custom-call.7"}
    meta = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
        for k, v in names.items())
    ops = (_event(1, 10, 30, [(1, "convolution fusion")])
           + _event(2, 40, 2, [(1, "all-reduce")])
           + _event(3, 42, 18, [(1, "loop fusion")])
           + _event(4, 60, 10, [(1, "all-reduce")])
           + _event(5, 75, 10, [(1, "custom-call"),
                                (2, "jit(spmd_step)/pallas_call[name="
                                    "_fwd_kernel]")]))
    txt = f'''
    planes {{ id: 1 name: "/device:TPU:0"
      {meta}
      stat_metadata {{ key: 1 value {{ id: 1 name: "hlo_category" }} }}
      stat_metadata {{ key: 2 value {{ id: 2 name: "tf_op" }} }}
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
        {ops} }}
      lines {{ id: 2 name: "Steps" timestamp_ns: 0
        {_event(1, 0, 100)} }}
    }}
    planes {{ id: 2 name: "/host:CPU"
      event_metadata {{ key: 1 value {{ id: 1 name: "pb:dispatch" }} }}
      event_metadata {{ key: 2 value {{ id: 2 name: "pb:wait_ready" }} }}
      event_metadata {{ key: 3 value {{ id: 3 name: "PjitFunction(f)" }} }}
      lines {{ id: 1 name: "python3" timestamp_ns: 0
        {_event(1, 0, 30)}{_event(3, 1, 5)}{_event(2, 30, 70)} }}
    }}'''
    return tr.reduce_profile(ProfileData.from_text_proto(txt))


@pytest.fixture(scope="module")
def hand():
    return _hand_trace()


def test_hand_trace_window_and_spans(hand):
    assert [s[0] for s in hand.spans] == ["dispatch", "wait_ready"]
    assert hand.window == pytest.approx((0.0, 100e-6))
    assert len(hand.devices) == 1 and len(hand.devices[0].ops) == 5


def test_hand_trace_busy_and_idle(hand):
    # busy: 10..70 and 75..85 = 70 us of 100
    assert hand.busy_s() == pytest.approx([70e-6])
    assert hand.idle_pct() == pytest.approx([30.0])


def test_hand_trace_kernel_time(hand):
    assert hand.kernel_seconds(["_fwd_kernel"]) == pytest.approx([10e-6])
    assert hand.kernel_seconds(["_bwd_dq_kernel"]) == [0.0]


def test_hand_trace_exposed_collective(hand):
    # under way 40..70; fusion.2 hides 42..60; exposed 2 + 10 us
    assert hand.collective_s() == pytest.approx([30e-6])
    assert hand.collective_exposed_s() == pytest.approx([12e-6])


def test_hand_trace_gaps_go_to_the_span_that_covered_them(hand):
    gaps = hand.idle_gaps(hand.devices[0], top=3)
    assert [g[0] for g in gaps] == ["wait_ready", "dispatch", "wait_ready"]
    assert [g[1] for g in gaps] == pytest.approx([15e-6, 10e-6, 5e-6])


def test_hand_trace_top_ops_keep_category_and_name(hand):
    top = dict(hand.top_ops(10))
    assert top["convolution fusion:fusion.1"] == pytest.approx(30e-6)
    assert top["custom-call:custom-call.7"] == pytest.approx(10e-6)
    assert len(top) == 5


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 4), (6, 9)], [(3, 7)], [(0, 3), (7, 9)]),
    ([(0, 4)], [(0, 4)], []),
    ([(1, 2), (3, 4)], [(0, 10)], []),
])
def test_subtract(a, b, want):
    assert tr.subtract(a, b) == want


def test_union_merges_and_drops_empty():
    assert tr.union([(5, 6), (0, 2), (1, 3), (4, 4)]) == [(0, 3), (5, 6)]
    assert tr.total(tr.clip([(0, 3), (5, 6)], 2, 5.5)) == pytest.approx(1.5)


def test_trace_without_device_plane_has_no_devices():
    """A CPU trace (the rehearsal's) reduces to no device at all, so every
    device reader finds nothing and no number can appear under a device
    metric's name."""
    from jax.profiler import ProfileData
    txt = '''planes { id: 2 name: "/host:CPU"
      event_metadata { key: 1 value { id: 1 name: "pb:dispatch" } }
      lines { id: 1 name: "python3" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 } } }'''
    t = tr.reduce_profile(ProfileData.from_text_proto(txt))
    assert t.devices == [] and t.busy_s() == []


# -- a trace recorded on the chip ---------------------------------------------

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


@pytest.fixture(scope="module")
def one_chip():
    """Three runs of one small jitted program (a bf16 1024^3 matmul and a
    flash forward kernel at [1, 256, 2, 64]) on one TPU v5e chip, under the
    benchmark's spans `dispatch`, `next_batch` (a 2 ms sleep) and
    `wait_ready`; recorded in PR 22 (33 KB).  Each run takes the core
    16.99 us; the device's events read about 0.7 ms early against the host's
    spans, so the first run falls before the first span and two runs lie
    inside the window."""
    return tr.reduce_trace(
        os.path.join(FIXTURES, "v5e_1chip_flash_matmul.xplane.pb"))


def test_recorded_trace_layout(one_chip):
    assert [d.index for d in one_chip.devices] == [0]
    dev = one_chip.devices[0]
    assert len(dev.ops) == 24 and len(dev.async_ops) == 3   # 8 ops x 3 runs
    assert [s[0] for s in one_chip.spans] == [
        "dispatch", "next_batch", "wait_ready"] * 3
    assert one_chip.window_s == pytest.approx(9.7728e-3, rel=1e-4)
    # an event of the line is named by its whole HLO instruction
    kernel = next(o for o in dev.ops if "tpu_custom_call" in o.text)
    assert kernel.name == "body.1" and kernel.category == "custom-call"
    assert kernel.shape == "bf16[2,256,128]"


def test_recorded_trace_busy_idle_and_kernel_time(one_chip):
    # per run: copy 0.610 + pad 0.049 + kernel 2.946 + fusion 0.023 +
    # copy 0.475 + matmul fusion 12.590 us and two 0.01 us markers
    assert one_chip.busy_s() == pytest.approx([2 * 16.7045e-6], rel=1e-3)
    assert one_chip.idle_pct()[0] == pytest.approx(99.658, abs=1e-3)
    flash = one_chip.kernel_seconds(['custom_call_target="tpu_custom_call"'])
    assert flash == pytest.approx([2 * 2.94625e-6], rel=1e-3)
    assert one_chip.collective_s() == [0.0]            # one chip, no exchange
    top = one_chip.top_ops(2)
    assert top[0][0] == "fusion output:fusion bf16[1024,1024]"
    assert top[0][1] == pytest.approx(2 * 12.59e-6, rel=1e-3)
    assert top[1][0] == "custom-call:body.1 bf16[2,256,128]"


def test_recorded_trace_gaps_lie_under_the_sleep(one_chip):
    gaps = one_chip.idle_gaps(one_chip.devices[0], top=3)
    assert [g[0] for g in gaps] == ["next_batch"] * 3
    assert all(2e-3 < g[1] < 4.5e-3 for g in gaps)
    total_idle = one_chip.window_s - one_chip.busy_s()[0]
    assert sum(g[1] for g in gaps) <= total_idle


# -- the readers over the hand-written trace ----------------------------------


def _obs(trace):
    class Family:
        @staticmethod
        def kernel_work(rows):
            return {"flash": {"flops": 197e12 * 4e-6, "bytes": 819e9 * 1e-6,
                              "match": ("_fwd_kernel",)}}
    # an untraced window of 10 steps in 1 ms: 100 us a step; one traced step
    return {"trace": trace, "family": Family, "chips": 1,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "result": {"window": (5.0, 5.001), "attempted": 10,
                       "trace_steps": 1, "rows_per_chip": 8}}


@pytest.mark.parametrize("metric,want", [
    ("device_idle_pct", 30.0),            # 70 us busy of a 100 us step
    ("device_idle_worst_pct", 30.0),
    ("collective_ms_step", 0.030),
    ("collective_exposed_pct", 40.0),     # 12 of 30 us
    ("flash_ms_step", 0.010),
    ("flash_roofline_pct", 40.0),         # least 4 us (compute) of 10 us
])
def test_reader_over_the_hand_trace(hand, metric, want):
    import importlib
    reader = importlib.import_module(f"perfbench.layer_metrics.{metric}")
    assert reader.read(_obs(hand)) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "device_idle_pct", "device_idle_worst_pct", "collective_ms_step",
    "collective_exposed_pct", "flash_ms_step", "flash_roofline_pct"])
def test_reader_without_a_trace_returns_nothing(metric):
    import importlib
    reader = importlib.import_module(f"perfbench.layer_metrics.{metric}")
    assert reader.read(_obs(None)) is None


@pytest.fixture(scope="module")
def four_chips():
    """The same program over the four chips of a v5e host with a `psum` of
    the bf16 [1024, 1024] product behind it (PR 22, 76 KB): on every chip a
    synchronous `all-reduce` of ~40.8 us follows the 11.5 us matmul, and
    nothing runs beside it."""
    return tr.reduce_trace(
        os.path.join(FIXTURES, "v5e_4chip_allreduce.xplane.pb"))


def test_recorded_four_chip_trace_has_one_exposed_collective_a_run(four_chips):
    assert [d.index for d in four_chips.devices] == [0, 1, 2, 3]
    dev = four_chips.devices[0]
    reduces = [o for o in dev.ops if o.collective]
    assert [o.name for o in reduces] == ["psum.7"] * 3
    assert all(o.category == "all-reduce" for o in reduces)
    # two of the three runs lie inside the window of the host's spans
    assert four_chips.collective_s()[0] == pytest.approx(
        40.843e-6 + 40.787e-6, rel=1e-3)
    # synchronous: all of it is exposed, on every chip
    assert four_chips.collective_exposed_s() == pytest.approx(
        four_chips.collective_s())
    assert all(75e-6 < c < 85e-6 for c in four_chips.collective_s())


def test_recorded_four_chip_trace_busy_and_readers(four_chips):
    import importlib
    busy = four_chips.busy_s()
    assert len(busy) == 4 and all(105e-6 < b < 115e-6 for b in busy)
    assert busy[0] == pytest.approx(2 * 15.66e-6 + 81.63e-6, rel=1e-3)
    obs = _obs(four_chips)
    exposed = importlib.import_module(
        "perfbench.layer_metrics.collective_exposed_pct").read(obs)
    per_step = importlib.import_module(
        "perfbench.layer_metrics.collective_ms_step").read(obs)
    assert exposed == pytest.approx(100.0)
    assert per_step == pytest.approx(0.08163, rel=1e-3)   # the worst chip
    top = dict(four_chips.top_ops(3))
    assert "all-reduce:psum.7 bf16[1024,1024]" in top
