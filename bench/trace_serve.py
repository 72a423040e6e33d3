"""Trace one batch of a serving cell and print what the program's own spans
and scopes show.

    python3 bench/trace_serve.py --workload <name> --seed <n>

Run from the root of a checkout, on a TPU (no TPU: exit 1, no result).
Set-up as in a run of ``bench/run.py`` (weights from the seed, warm-up of
every shape), then the cell's next batch under the profiler, inside the
benchmark's ``bench.traced`` and ``bench.batch`` spans, as a traced run
traces its first batch.  The trace is reduced by ``bench.spans``.  The last
line of standard output is one JSON object:

* ``metrics``: the cell's per-layer metrics, and the readers in
  ``READERS``, which read the program's spans and scopes;
* ``spans_ms``: each ``serve.*`` span's count and median duration;
* ``scopes_ms``: the serve step's device self time by scope
  (``repro.models.SCOPES``; ``unscoped`` for ops traced under none, such
  as the layer scan's own slicing and stacking; ``no op_name`` for ops the
  compiler adds), in milliseconds per step; ``top_ops_ms``, the ops that
  take most of it, each with its scope and ``op_name``; and
  ``step_device_ms``, the median step's device time;
* ``idle_by_span_s``: the device's idle time by innermost program span;
* ``batch_wall_s``, ``window_s``, ``busy_s``: the traced batch's host time,
  its window in the trace and the device's busy time in it.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

READERS = ("serve_attention_ms", "serve_compile_ms", "serve_copy_ms",
           "serve_pick_ms", "serve_stack_ms")
STEP = "jit_serve_step"
TOP_OPS = 20


def _per_step_ms(split) -> dict:
    """Per-execution seconds by key -> milliseconds per execution."""
    total = {}
    for s in split:
        for k, t in s.items():
            total[k] = total.get(k, 0.0) + t
    return {k: 1e3 * t / len(split) for k, t in total.items()}


def summarize(cell, reduced, record: dict, peak: dict) -> dict:
    """The JSON object, from a reduced trace of one batch and its record
    (which holds the compiled ``step``)."""
    from bench import spans, stats
    from repro.models import SCOPES

    ctx = SimpleNamespace(trace=reduced, traced=[record], records=[record],
                          config=cell.config, traffic=cell.traffic,
                          counts=cell.counts(), peak=peak)
    names = [m["name"] for m in cell.per_layer] + list(READERS)
    metrics = {n: cell.reader(n).read(ctx) for n in names}

    steps = [m for m in reduced.modules if m[0] == STEP]
    hlo = record["step"].as_text()
    paths, scope_of = spans.op_paths(hlo), spans.op_scopes(hlo, SCOPES)
    label = {n: scope_of[n] or ("unscoped" if path else "no op_name")
             for n, path in paths.items()}
    scopes = _per_step_ms(spans.step_scope_s(reduced.ops, steps, label))
    by_op = _per_step_ms(spans.step_scope_s(reduced.ops, steps,
                                            {n: n for n in paths}))
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP_OPS]
    span_names = sorted({n for n, _, _ in reduced.spans})
    return {
        "metrics": metrics,
        "spans_ms": {n: [len(reduced.span_s(n)), spans.median_span_ms(reduced, n)]
                     for n in span_names},
        "scopes_ms": dict(sorted(scopes.items(), key=lambda kv: -kv[1])),
        "top_ops_ms": [[n, t, label.get(n), paths.get(n)] for n, t in top],
        "steps": len(steps),
        "idle_by_span_s": dict(sorted(reduced.idle_by_span.items(),
                                      key=lambda kv: -kv[1])),
        "batch_wall_s": record["wall_s"],
        "window_s": reduced.window_s,
        "busy_s": reduced.busy_s,
        "step_device_ms": (stats.median([e - s for _, s, e in steps]) * 1e-6
                           if steps else None),
    }


def trace_batch(cell, seed: int, require_tpu: bool = True) -> dict:
    import jax

    from bench import harness, spans
    from bench import trace as tr

    devs = harness.devices_for(cell, require_tpu)
    harness.use_compile_cache()

    class Keeping(cell.adapter().Adapter):
        """The cell's adapter, keeping the last batch's ``ServeResult``."""

        def _serve(self, prompts, max_new):
            self.last = super()._serve(prompts, max_new)
            return self.last

    ad = Keeping(cell.config_name, cell.config, cell.traffic, seed)
    ad.setup()
    print(f"trace_serve: {cell.name} seed {seed}: set-up "
          f"{time.perf_counter() - T0:.3f} s", file=sys.stderr, flush=True)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation(f"bench.{ad.unit}"):
                record = ad.call()
        jax.profiler.stop_trace()
        reduced = spans.reduce(tr.load(tr.find_xplane(trace_dir)),
                               harness.WINDOW_SPAN, devices=[d.id for d in devs])
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    record["step"] = ad.last.step
    return summarize(cell, reduced, record, harness.peak_of(devs[0].device_kind))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()

    from bench import harness

    out = trace_batch(harness.resolve(args.workload), args.seed)
    print(json.dumps(dict(workload=args.workload, seed=args.seed, **out)),
          flush=True)


if __name__ == "__main__":
    main()
