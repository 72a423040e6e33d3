"""The program's marks in a trace: the ``serve.*`` host spans of a served
batch, the scopes of its compiled step's ops, their reduction
(``bench.spans``) and the readers built on it."""

import dataclasses
import re
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, spans
from bench import trace as T
from bench.tests import tiny

FIXTURE = Path(__file__).parent / "data" / "hnp-n256-3-programs.xplane.pb"
MAX_NEW = 4
DECODE_SPANS = ("serve.decode.wait", "serve.decode.copy", "serve.decode.pick",
                "serve.decode.dispatch")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One smoke yi-6b batch under the profiler: its result and the host
    thread that holds its spans."""
    import jax

    from repro.launch.serve import serve_batch

    log_dir = tmp_path_factory.mktemp("profile")
    # Without Python frames in the trace: the spans are all this reads.
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        res = serve_batch("yi-6b", [[1, 2, 3], [4, 5, 6]], smoke=True,
                          max_new_tokens=MAX_NEW)
    trace = T.load(T.find_xplane(str(log_dir)))
    threads = [evs for evs in trace.host.values()
               if any(n.startswith("serve.") for n, _, _ in evs)]
    assert len(threads) == 1, "every serve.* span on one thread"
    return res, [ev for ev in threads[0] if ev[0].startswith("serve.")]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_serve_batch_spans_nest_on_one_thread(served):
    _, evs = served
    names = [n for n, _, _ in evs]
    for name in ("serve.batch", "serve.compile", "serve.compile.cache",
                 "serve.compile.step", "serve.prefill", "serve.prefill.wait",
                 "serve.decode.stack"):
        assert names.count(name) == 1, name
    for name in DECODE_SPANS:
        assert names.count(name) == MAX_NEW, name
    one = {n: ev for ev in evs for n in [ev[0]]}
    batch = one["serve.batch"]
    assert all(_inside(ev, batch) for ev in evs)
    assert _inside(one["serve.compile.cache"], one["serve.compile"])
    assert _inside(one["serve.compile.step"], one["serve.compile"])
    assert _inside(one["serve.prefill.wait"], one["serve.prefill"])
    # Spans on one thread nest: any two are disjoint or one holds the other.
    for a in evs:
        for b in evs:
            assert a[2] <= b[1] or b[2] <= a[1] or _inside(a, b) or _inside(b, a)
    # Each token: wait, copy, pick, dispatch, in that order, after prefill.
    decode = [ev for ev in evs if ev[0] in DECODE_SPANS]
    assert [n for n, _, _ in decode] == list(DECODE_SPANS) * MAX_NEW
    assert all(ev[1] >= one["serve.prefill"][2] for ev in decode)
    assert one["serve.decode.stack"][1] >= decode[-1][2]


def _body(hlo: str) -> str:
    """The text of the while loop's body computation (the layer scan)."""
    body = re.search(r"\bwhile\(.*?body=%?([\w.\-]+)", hlo).group(1)
    m = re.search(r"^%?" + re.escape(body) + r" .*?\{\n(.*?)^\}", hlo, re.M | re.S)
    return m.group(1)


def test_step_ops_map_to_scopes(served):
    from repro.models import SCOPES

    res, _ = served
    hlo = res.step.as_text()
    scope_of = spans.op_scopes(hlo, SCOPES)
    body = []  # (name, opcode, op_name) of each op of the layer scan's body
    for line in _body(hlo).splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = \S+ ([\w\-]+)\(", line)
        path = re.search(r'op_name="([^"]*)"', line)
        if m and path:
            body.append((m.group(1), m.group(2), path.group(1)))
    # Every dot, fusion and kernel of the layer's code has a scope.  Not
    # counted: the scan's own slicing, stacking and counter, whose op_name
    # ends at the loop body, and ops that carry no op_name (a CPU pass
    # drops it from the reduce-windows it makes).
    layer = [(n, op) for n, op, path in body
             if op in ("dot", "fusion", "custom-call")
             and not re.search(r"/while/body/[^/]+$", path)]
    assert len(layer) > 10
    assert all(scope_of[n] in SCOPES for n, _ in layer), [
        (n, op) for n, op in layer if scope_of[n] not in SCOPES]
    # The cache update and the attention math are attention's.
    assert any(scope_of[n] == "attention" for n, op, _ in body
               if "dynamic-update-slice" in (op, n.split("_fusion")[0].split("bitcast_")[-1]))
    qk = [n for n, op, path in body if op == "dot" and "->" in path]
    assert len(qk) == 2 and all(scope_of[n] == "attention" for n in qk)
    assert set(scope_of.values()) >= set(SCOPES)


def test_op_scopes_innermost_name():
    hlo = "\n".join([
        'ENTRY %main {',
        '  %dot.1 = f32[2] dot(%a, %b), metadata={op_name="jit(f)/head/dot_general"}',
        '  ROOT %fusion.2 = f32[2] fusion(%x), metadata={op_name="jit(f)/attention/mlp/add"}',
        '  %copy.3 = f32[2] copy(%y)',
        '  %add.4 = f32[2] add(%x, %y), metadata={op_name="jit(f)/while/body/add"}',
        '}'])
    got = spans.op_scopes(hlo, ("attention", "mlp", "head"))
    assert got == {"dot.1": "head", "fusion.2": "mlp", "copy.3": None, "add.4": None}
    assert spans.op_paths(hlo)["add.4"] == "jit(f)/while/body/add"
    assert spans.op_paths(hlo)["copy.3"] is None


def _synthetic():
    """A batch of two serve steps, each two ops, with program spans."""
    host = {"python3": [
        ("bench.traced", 0, 1000), ("bench.batch", 0, 1000),
        ("serve.batch", 10, 990), ("serve.compile", 10, 100),
        (T.LAUNCH_EVENT, 112, 115), ("serve.decode.wait", 120, 300),
        ("serve.decode.copy", 300, 340), ("serve.decode.pick", 340, 350),
        ("serve.decode.dispatch", 350, 400), (T.LAUNCH_EVENT, 372, 375),
        ("serve.decode.wait", 400, 600), ("serve.decode.copy", 600, 660),
        ("serve.decode.stack", 700, 900)]}
    dev = {"XLA Modules": [("jit_serve_step(9)", 112, 280), ("jit_serve_step(9)", 372, 580)],
           "XLA Ops": [("%fusion.1 = bf16[] fusion()", 112, 200),
                       ("%dot.2 = bf16[] dot()", 200, 280),
                       ("%fusion.1 = bf16[] fusion()", 372, 500),
                       ("%dot.2 = bf16[] dot()", 500, 580)]}
    return T.Trace(devices={0: dev}, host=host)


def test_spans_reduce_synthetic():
    r = spans.reduce(_synthetic(), "bench.traced")
    assert [n for n, _, _ in r.spans][:2] == ["serve.batch", "serve.compile"]
    assert len(r.spans) == 9
    assert r.ops == [("fusion.1", 112, pytest.approx(88e-9)),
                     ("dot.2", 200, pytest.approx(80e-9)),
                     ("fusion.1", 372, pytest.approx(128e-9)),
                     ("dot.2", 500, pytest.approx(80e-9))]
    # Idle: 0-10 and 990-1000 outside, 10-100 compile, 100-112 batch,
    # 280-300 wait, 300-340 copy, 340-350 pick, 350-372 dispatch,
    # 580-600 wait, 600-660 copy, 660-700 and 900-990 batch, 700-900 stack.
    assert r.idle_by_span == pytest.approx({
        spans.OUTSIDE: 20e-9, "serve.compile": 90e-9, "serve.batch": 142e-9,
        "serve.decode.wait": 40e-9, "serve.decode.copy": 100e-9,
        "serve.decode.pick": 10e-9, "serve.decode.dispatch": 22e-9,
        "serve.decode.stack": 200e-9})
    assert sum(r.idle_by_span.values()) == pytest.approx(r.window_s - r.busy_s)
    # The fields bench.trace.reduce gives are its own.
    base = T.reduce(_synthetic(), "bench.traced")
    assert (r.window_s, r.busy_s, r.modules, r.device_ops, r.idle_by_host) == (
        base.window_s, base.busy_s, base.modules, base.device_ops, base.idle_by_host)


def test_step_scope_split():
    ops = [("fusion.1", 112, 88e-9), ("dot.2", 200, 80e-9),
           ("fusion.1", 372, 128e-9), ("dot.2", 500, 80e-9), ("dot.2", 700, 1.0)]
    steps = [("jit_serve_step", 112, 280), ("jit_serve_step", 372, 580)]
    got = spans.step_scope_s(ops, steps, {"fusion.1": "attention", "dot.2": "mlp"})
    assert got == [{"attention": 88e-9, "mlp": 80e-9},
                   {"attention": 128e-9, "mlp": 80e-9}]
    assert spans.step_scope_s(ops, steps, {})[0] == {None: 168e-9}


class _Step:
    """A compiled step's stand-in: its HLO text."""

    def as_text(self):
        return "\n".join([
            '%fusion.1 = bf16[] fusion(), metadata={op_name="jit(serve_step)/while/body/attention/mul"}',
            '%dot.2 = bf16[] dot(), metadata={op_name="jit(serve_step)/while/body/mlp/dot_general"}'])


def _ctx(trace, step=None):
    return SimpleNamespace(trace=trace, traced=[{"wall_s": 1e-6, "step": step}])


@pytest.mark.parametrize("reader,want", [
    ("serve_compile_ms", 90e-6),
    ("serve_copy_ms", 50e-6),          # median of 40 and 60 ns
    ("serve_pick_ms", 10e-6),
    ("serve_stack_ms", 200e-6),
])
def test_span_readers_synthetic(reader, want):
    read = harness.load_module(harness.BENCH / "metrics" / f"{reader}.py").read
    assert read(_ctx(spans.reduce(_synthetic(), "bench.traced"))) == pytest.approx(want)
    # A trace reduced without the program's spans, as bench.trace gives it.
    assert read(_ctx(T.reduce(_synthetic(), "bench.traced"))) is None


def test_attention_reader_synthetic():
    read = harness.load_module(harness.BENCH / "metrics" / "serve_attention_ms.py").read
    r = spans.reduce(_synthetic(), "bench.traced")
    assert read(_ctx(r, _Step())) == pytest.approx(1e3 * (88e-9 + 128e-9) / 2)
    assert read(_ctx(r)) is None, "no compiled step on the record"
    assert read(_ctx(T.reduce(_synthetic(), "bench.traced"), _Step())) is None


def test_trace_serve_summary_synthetic():
    """trace_serve.py's summary of a reduced trace and its record."""
    tool = harness.load_module(harness.BENCH / "trace_serve.py")
    r = spans.reduce(_synthetic(), "bench.traced")
    record = {"wall_s": 1e-6, "step": _Step()}
    cell = dataclasses.replace(harness.resolve(tiny.SERVE_CELL), per_layer=[])
    out = tool.summarize(cell, r, record, {})
    assert set(out["metrics"]) == set(tool.READERS)
    assert all(v is not None for v in out["metrics"].values())
    assert out["steps"] == 2
    assert out["scopes_ms"] == pytest.approx({"attention": 108e-6, "mlp": 80e-6})
    assert out["top_ops_ms"][0] == [
        "fusion.1", pytest.approx(108e-6), "attention",
        "jit(serve_step)/while/body/attention/mul"]
    assert out["spans_ms"]["serve.decode.wait"] == [2, pytest.approx(190e-6)]
    assert out["step_device_ms"] == pytest.approx(188e-6)


def test_reduce_recorded_tpu_trace_numbers():
    """bench.trace.reduce reads the same numbers on the recorded v5e trace,
    and bench.spans.reduce keeps them and adds its own."""
    trace = T.load(str(FIXTURE))
    r = T.reduce(trace, "bench.traced")
    assert r.window_s == pytest.approx(0.017976502)
    assert r.busy_s == pytest.approx(2.6872e-05)
    assert len(r.modules) == 12
    assert r.modules[0] == ("jit_pallas_gemm", 50362548.0, 50366016.0)
    assert r.device_ops[0] == ("pallas_gemm.1", pytest.approx(2.1105e-05))
    assert r.idle_gaps[0] == ("$array.py:631 _value", pytest.approx(0.003216424))
    assert len(r.idle_by_host) == 495

    s = spans.reduce(trace, "bench.traced", prefix="bench.")
    assert (s.window_s, s.busy_s, s.modules, s.device_ops, s.idle_gaps) == (
        r.window_s, r.busy_s, r.modules, r.device_ops, r.idle_gaps)
    assert len(s.ops) == 12
    assert sum(t for _, _, t in s.ops) == pytest.approx(r.busy_s)
    assert {n for n, _, _ in s.spans} <= {"bench.traced", "bench.program"}
    assert sum(s.idle_by_span.values()) == pytest.approx(r.window_s - r.busy_s)
