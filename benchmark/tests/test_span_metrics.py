"""The readers of the program's spans and kernel names against hand sums,
and nothing read from a trace without them."""

import os

import pytest

from benchmark import harness, span_metrics as sm
from benchmark import trace as tr

METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "metrics")
READERS = ("idle_fetch.als", "idle_dispatch.als", "fetches_per_step.als",
           "idle_fetch.pair", "idle_dispatch.pair", "sddmm_s.gat",
           "spmm_s.gat")


def reader(name):
    return harness.load_module(os.path.join(METRICS, name + ".py"),
                               "benchmark.metrics." + name).read


def _op(name, s, e, kind="custom_call"):
    return tr.Op(name, s, e, kind)


def _ctx(devices, host, units=2):
    return {"trace": tr.Trace(devices, host), "devices": devices,
            "lo": 0, "hi": 100, "units": units, "chips": len(devices)}


@pytest.fixture
def ctx():
    # Window [0, 100), two units. Device 0 runs 0-10, 20-40, 50-70 and
    # 90-100: idle 10-20 (middle 15, under cgStep:fetch), 40-50 (middle
    # 45, under cgStep:dispatch) and 70-90 (middle 80, under no leaf:
    # the op span alone). Device 1 is idle 0-100 but for 0-50: its gap
    # 50-100 has its middle 75 under no leaf.
    d0 = tr.Device("/device:TPU:0", [
        _op("sddmm_tiles.3", 0, 10), _op("sddmm_tiles.4", 5, 10),
        _op("spmm_tiles.4", 20, 40), _op("fusion.1", 50, 70, "compute"),
        _op("spmm_tiles.5", 90, 120)], [])
    d1 = tr.Device("/device:TPU:1", [_op("sddmm_tiles.3", 0, 50)], [])
    host = [("bench:window", 0, 100), ("cgStep", 0, 60),
            ("cgStep:dispatch", 0, 2), ("cgStep:fetch", 2, 30),
            ("cgStep", 30, 100), ("cgStep:dispatch", 30, 55),
            ("cgStep:fetch", 55, 60), ("cgStep:fetch", 99, 130),
            ("cgStep:fetch", 140, 150)]
    return _ctx([d0, d1], host)


def test_idle_by_the_leaf_open_at_each_gaps_middle(ctx):
    idle = sm.idle_by_leaf(ctx)
    # Device 0: fetch 10, dispatch 10, none 20; device 1: none 50.
    assert idle == {":fetch": 5.0, ":dispatch": 5.0, None: 35.0}
    assert reader("idle_fetch.als")(ctx) == pytest.approx(5.0)
    assert reader("idle_dispatch.als")(ctx) == pytest.approx(5.0)
    assert reader("idle_fetch.pair")(ctx) == pytest.approx(5.0)
    assert reader("idle_dispatch.pair")(ctx) == pytest.approx(5.0)


def test_fetches_start_in_the_window(ctx):
    # Fetches start at 2, 55 and 99 inside [0, 100); 140 is outside.
    assert reader("fetches_per_step.als")(ctx) == pytest.approx(3 / 2)


def test_kernel_seconds_by_name(ctx):
    # sddmm: device 0 union 0-10 (10), device 1 0-50 (50): mean 30 ns
    # over two units. spmm: device 0 20-40 and 90-100 in the window (30),
    # device 1 none: mean 15 ns over two units.
    assert reader("sddmm_s.gat")(ctx) == pytest.approx(15e-9)
    assert reader("spmm_s.gat")(ctx) == pytest.approx(7.5e-9)
    assert sm.kernel_s(ctx, "fused") is None


def test_nested_leaves_the_latest_started_wins():
    dev = tr.Device("/device:TPU:0", [_op("k", 0, 10), _op("k", 30, 100)], [])
    host = [("a:fetch", 0, 100), ("b:dispatch", 12, 28)]
    idle = sm.idle_by_leaf(_ctx([dev], host))
    assert idle == {":fetch": 0.0, ":dispatch": 20.0, None: 0.0}


def test_nothing_to_read_without_spans_or_names():
    dev = tr.Device("/device:TPU:0", [_op("_tile_call.3", 0, 50)], [])
    ctx = _ctx([dev], [("bench:window", 0, 100), ("bench:als:unit", 0, 99)])
    for name in READERS:
        assert reader(name)(ctx) is None, name
    idle = tr.Device("/device:TPU:0", [], [])
    ctx = _ctx([idle], [("cgStep:fetch", 0, 50)])
    assert reader("idle_fetch.als")(ctx) is None
    assert reader("fetches_per_step.als")(dict(ctx, units=0)) is None


def test_launch_offsets_match_spans_to_executions():
    dev = tr.Device(
        "/device:TPU:0",
        [_op("fused_tiles.2", 110, 150), _op("fused_tiles.2", 305, 340)],
        [("jit_one_iter", 100, 160), ("jit_slice", 170, 175),
         ("jit_one_iter", 300, 350)])
    t = tr.Trace([dev], [("cgStep:dispatch", 90, 95),
                         ("cgStep:dispatch", 290, 296)])
    got = sm.launch_offsets(t, 0, 1000, "cgStep:dispatch", "one_iter", dev)
    assert got == [20, 15]
    assert sm.launch_offsets(t, 0, 1000, "cgStep:dispatch", "nope", dev) == []


DATA = os.path.join(os.path.dirname(__file__), "data", "spans.xplane.pb")


def test_recorded_trace_with_program_names():
    """A trace recorded on a v5e (PR 23): one tiny ALS step (one CG
    iteration a half-step) and one tiny one-layer GAT forward under the
    benchmark's annotations, with the program's spans and kernel names."""
    import numpy as np
    from jax.profiler import ProfileData

    t = tr.load(DATA)
    lo, hi = t.window("bench:window")
    (dev,) = t.devices
    kernels = {o.name.split(".")[0] for o in dev.ops if o.kind == "custom_call"}
    assert kernels == {"fused_tiles", "sddmm_tiles", "spmm_tiles"}
    ctx = {"trace": t, "lo": lo, "hi": hi, "devices": [dev], "units": 1}
    # Two half-steps of 1 SpMM + 1 fused pair + 1 cgStep, and one layer.
    assert sm.fetches_per_step(ctx) == 7
    # Kernel seconds: the events' durations summed straight from the
    # file (one device, no overlaps).
    sums = dict.fromkeys(("sddmm", "spmm", "fused"), 0)
    busy = np.zeros(hi - lo, dtype=bool)
    for plane in ProfileData.from_file(DATA).planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    s = int(ev.start_ns) - lo
                    busy[max(s, 0):max(s + int(ev.duration_ns), 0)] = True
                    name = tr.short_name(ev.name)
                    for k in sums:
                        if name.startswith(k) and lo <= ev.start_ns < hi:
                            sums[k] += int(ev.duration_ns)
    assert sums == {"sddmm": 12434, "spmm": 49695, "fused": 89592}
    for k, v in sums.items():
        assert sm.kernel_s(ctx, k) == pytest.approx(v / 1e9)
    # Every idle nanosecond goes to exactly one of the three.
    idle = sm.idle_by_leaf(ctx)
    assert sum(idle.values()) == int((~busy).sum()) == 33363102
    assert idle[":fetch"] > idle[None] > idle[":dispatch"]
    # This capture's device clock reads 0.80-0.82 ms behind the host's:
    # each cgStep program starts before the span that launched it.
    assert sm.launch_offsets(t, lo, hi, "cgStep:dispatch", "jit_one_iter",
                             dev) == [-800645, -817113]
