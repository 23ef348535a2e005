"""`flash_fwd_calls_step`: its reader over a trace written by hand (two
chips, events named by their whole HLO instruction as the chip's trace
names them), over a trace with no flash call, and its entry in
`BENCHMARK.json`, found by name."""

import json
import os

import pytest

from perfbench import trace_reduce as tr
from perfbench.layer_metrics import flash_fwd_calls_step as metric

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
US = 1_000_000  # picoseconds in a microsecond, the text proto's unit
META = 'frontend_attributes={kernel_metadata={\\"kernel\\":\\"flash_fwd\\"}}'
OPS = {
    1: f'%flash_fwd.7 = (bf16[40,8192,128]{{2,1,0}}, f32[40,8192,128]'
       f'{{2,1,0}}) custom-call(%p0, %p1, %p2), custom_call_target='
       f'\\"tpu_custom_call\\", {META}',
    2: f'%flash_fwd.10 = (bf16[40,8192,128]{{2,1,0}}, f32[40,8192,128]'
       f'{{2,1,0}}) custom-call(%p3, %p4, %p5), custom_call_target='
       f'\\"tpu_custom_call\\", {META}',
    # what reads the call's output: its name is in the text, the kernel's
    # metadata on the read too, and neither is a call of the kernel
    3: f'%pallas_call.13 = bf16[40,8192,128]{{2,1,0}} get-tuple-element('
       f'%flash_fwd.7), index=0, {META}',
    4: '%fusion.9 = bf16[1,8192,40,128]{3,2,1,0} fusion(%flash_fwd.7), '
       'kind=kLoop',
    5: '%flash_bwd_dkdv.4 = (bf16[40,8192,128]{2,1,0}) custom-call(%p6), '
       'custom_call_target=\\"tpu_custom_call\\"',
}


def _event(meta: int, start_us: float, dur_us: float) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_us * US)} "
            f"duration_ps: {int(dur_us * US)} }}\n")


def _plane(pid, chip, events):
    meta = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
        for k, v in OPS.items())
    ops = "".join(_event(*e) for e in events)
    return f'''planes {{ id: {pid} name: "/device:TPU:{chip}"
      {meta}
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
        {ops} }} }}
    '''


def _trace(chips):
    """Host spans from 10 to 210 us: two traced steps of 100 us.  Each
    chip's events are ``(op, start, duration)`` in microseconds."""
    from jax.profiler import ProfileData
    host = f'''planes {{ id: 99 name: "/host:CPU"
      event_metadata {{ key: 1 value {{ id: 1 name: "pb:dispatch" }} }}
      event_metadata {{ key: 2 value {{ id: 2 name: "pb:wait_ready" }} }}
      lines {{ id: 1 name: "python3" timestamp_ns: 0
        {_event(1, 10, 5)}{_event(2, 15, 95)}{_event(1, 110, 5)}\
{_event(2, 115, 95)} }} }}'''
    txt = "".join(_plane(i + 1, i, ev) for i, ev in enumerate(chips)) + host
    return tr.reduce_profile(ProfileData.from_text_proto(txt))


def _step(t0, fwd_calls):
    """One step from ``t0``: ``fwd_calls`` forward kernels (forward, then
    the rematerialised forward), each with its reads, then a backward."""
    events, t = [], t0
    for i in range(fwd_calls):
        events += [(1 + i % 2, t, 10), (3, t + 10, 0.01), (4, t + 11, 2)]
        t += 15
    return events + [(5, t, 20)]


def _obs(trace, steps=2):
    return {"trace": trace, "result": {"trace_steps": steps}}


@pytest.mark.parametrize("calls,want", [
    # a bare remat: forward and rematerialised forward, two layers
    ((4, 4), 4.0),
    # the kept output: once a layer
    ((2, 2), 2.0),
    # two chips that differ: the fullest
    ((3, 1), 3.0),
])
def test_it_counts_the_calls_a_step_on_the_fullest_chip(calls, want):
    a, b = calls
    chips = [_step(12, a) + _step(112, a),
             _step(12, b) + _step(112, b)]
    # a call that ends before the first span opens is not the window's
    chips[0] = [(1, 0, 5)] + chips[0]
    assert metric.read(_obs(_trace(chips))) == pytest.approx(want)


def test_a_call_the_device_clock_puts_across_the_window_edge_counts():
    """The device's events read about a millisecond early against the host
    spans: a call that starts before the first span and ends inside it is
    the first step's."""
    chips = [[(1, 5, 10)] + _step(20, 1)[1:] + _step(112, 2)]
    assert metric.read(_obs(_trace(chips))) == pytest.approx(1.5)


def test_it_reads_nothing_without_a_flash_call():
    no_flash = [[(4, 20, 5), (5, 30, 20), (4, 120, 5), (5, 130, 20)]]
    assert metric.read(_obs(_trace(no_flash))) is None
    assert metric.read(_obs(None)) is None
    assert metric.read(_obs(_trace([_step(12, 2)]), steps=0)) is None


def test_the_entry_is_found_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    m = by_name["flash_fwd_calls_step"]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
        == ("calls", "lower", "device_trace", "attention kernels",
            "samples_per_s_chip")
    assert sorted(m["workloads"]) == sorted([
        "gpt2m-sync-1chip", "gpt2m-sync-dp4", "kimi-linear-sync-1chip",
        "glm47-flash-sync-1chip", "phi4flash-sync-1chip",
        "evabyte-sync-1chip"])
    cells = {w["name"] for w in bench["workloads"]}
    assert set(m["workloads"]) <= cells
    # the layer is the one the other attention kernels' metrics name
    assert any(o["layer"] == m["layer"] for n, o in by_name.items()
               if n != m["name"])
