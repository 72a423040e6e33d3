"""The trace reduction: busy time as a union of operation intervals, idle
gaps, and each gap's attribution to what the host was doing, on small
hand-made timelines and on a trace recorded on a TPU v5e."""

from pathlib import Path

import pytest

from bench import trace as T

FIXTURE = Path(__file__).parent / "data" / "hnp-n256-3-programs.xplane.pb"


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (40, 45), (50, 55)]
    assert T.union(iv) == [(0, 20), (30, 45), (50, 55)]
    assert T.busy_ns(iv, 0, 100) == 20 + 15 + 5
    assert T.busy_ns(iv, 8, 52) == 12 + 15 + 2
    assert T.gaps(iv, -5, 60) == [(-5, 0), (20, 30), (45, 50), (55, 60)]


def test_self_times_subtract_nested_events():
    evs = [("loop", 0, 100), ("a", 10, 30), ("b", 40, 50), ("c", 200, 210)]
    assert dict(T.self_times(evs)) == {"loop": 70, "a": 20, "b": 10, "c": 10}


def test_attribute_goes_to_innermost_host_event():
    thread = [("window", 0, 100), ("call", 10, 60), ("sync", 20, 40),
              ("sleep", 70, 90)]
    holes = [(15, 25), (35, 65), (80, 95)]
    got = T.attribute(thread, holes)
    assert got == {"call": 5 + 20, "sync": 5 + 5, "window": 5 + 5,
                   "sleep": 10}
    assert sum(got.values()) == sum(hi - lo for lo, hi in holes)


def test_clock_offset_from_paired_launches():
    modules = [("m", 105, 110), ("m", 207, 215)]
    launches = [("launch", 100, 101), ("launch", 200, 201)]
    assert T.clock_offset_ns(modules, launches) == 5
    assert T.clock_offset_ns(modules, launches[:1]) == 0.0


def test_names():
    assert T.module_name("jit_serve_step(1234)") == "jit_serve_step"
    assert T.op_name("%fusion.3 = bf16[8]{0} fusion(%x)") == "fusion.3"


def _synthetic():
    host = {"python3": [("bench.traced", 0, 1000), ("bench.program", 10, 400),
                        (T.LAUNCH_EVENT, 20, 30), ("wait", 100, 390),
                        ("bench.program", 500, 990), (T.LAUNCH_EVENT, 510, 520),
                        ("wait", 600, 980)]}
    dev = {"XLA Modules": [("jit_f(7)", 25, 95), ("jit_f(7)", 525, 595)],
           "XLA Ops": [("%a = f32[] add()", 25, 60), ("%b = f32[] mul()", 60, 95),
                       ("%a = f32[] add()", 525, 560), ("%b = f32[] mul()", 560, 595)]}
    return T.Trace(devices={0: dev}, host=host)


def test_reduce_synthetic():
    r = T.reduce(_synthetic(), "bench.traced", min_gap_ns=0)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(140e-9)
    assert [m[0] for m in r.modules] == ["jit_f", "jit_f"]
    assert dict(r.device_ops) == pytest.approx({"a": 70e-9, "b": 70e-9})
    gaps = dict(r.idle_gaps)
    assert sum(gaps.values()) == pytest.approx(860e-9)
    assert gaps["wait"] == pytest.approx((390 - 100 + 980 - 600) * 1e-9)


def test_reduce_recorded_tpu_trace():
    """Three hnp programs at n=256 under the benchmark's span names, traced
    on one TPU v5e."""
    r = T.reduce(T.load(str(FIXTURE)), "bench.traced")
    assert 0 < r.busy_s < r.window_s
    assert 0 < r.idle_share < 1
    assert r.modules, "program executions on the device"
    assert all(r.modules[i][1] <= r.modules[i + 1][1] for i in range(len(r.modules) - 1))
    assert r.device_ops and all(t > 0 for _, t in r.device_ops)
    assert sum(r.idle_by_host.values()) == pytest.approx(r.window_s - r.busy_s)
    assert len(r.idle_gaps) <= T.TOP_N
    names = [m[0] for m in r.modules]
    assert names.count("jit_pallas_gemm") == 2 * 3, names
