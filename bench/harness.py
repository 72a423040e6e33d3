"""Run one cell of the benchmark once and build its result line.

Everything a cell needs is found by name: the cell's entry in
``BENCHMARK.json`` names its configuration and traffic mix;
``bench/configs/<config>.json`` holds the configuration, its limits and the
name of its adapter module under ``bench/adapters/``;
``bench/traffic/<traffic>.json`` holds the traffic's parameters, and may
hold ``limits`` of its own, which take the place of the configuration's;
``bench/counts/<config>.py`` computes operations and bytes from shapes;
``bench/metrics/<metric>.py`` reduces a traced run to one per-layer number.

A metric named ``<quantity>.<variant>`` is the quantity ``<quantity>`` in
the cells it lists, under a bound of its own: its value is the adapter's
``<quantity>``, and its reader ``metrics/<metric>.py`` where that file
exists, else ``metrics/<quantity>.py``.

A run: set-up (import, device, weights and operands from the seed, warm-up
of every shape), then a closed loop of units of work (batches, programs)
until ``seconds`` have passed; the window ends when the unit running then
returns.  With ``trace`` the first ``trace_units`` units run under the
profiler.  After the window the device's peak memory is read, the
program's state freed, and a sample of the window's results compared with
the plain reference.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WINDOW_SPAN = "bench.traced"
NOT_FINITE = 1e300


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path) -> ModuleType:
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One cell and everything it resolves to."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def limits(self) -> dict:
        return self.traffic.get("limits", self.config["limits"])

    def adapter(self):
        return load_module(BENCH / "adapters" / f"{self.config['adapter']}.py")

    def counts(self):
        return load_module(BENCH / "counts" / f"{self.config_name}.py")

    def reader(self, metric: str):
        path = BENCH / "metrics" / f"{metric}.py"
        if not path.is_file():
            path = BENCH / "metrics" / f"{quantity(metric)}.py"
        return load_module(path)


def quantity(metric: str) -> str:
    """What a metric measures: its name up to the first dot."""
    return metric.split(".", 1)[0]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str) -> Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[w["config"]]
    return Cell(
        name=workload,
        chips=w["chips"],
        config_name=w["config"],
        config=json.loads((ROOT / entry["file"]).read_text()),
        traffic=json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
    )


def use_compile_cache() -> None:
    """JAX's persistent compile cache at ``<checkout>/.jax_cache``, a fixed
    path, unless ``JAX_COMPILATION_CACHE_DIR`` names another."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))


def devices_for(cell: Cell, require_tpu: bool):
    """The cell's devices; with ``require_tpu``, a TPU with enough chips or
    an error (never the CPU)."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < cell.chips:
        raise SystemExit(f"bench: {cell.name} needs {cell.chips} chips, "
                         f"JAX found {len(devs)}")
    return devs[:cell.chips]


def _peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             require_tpu: bool = True, adapter=None) -> dict:
    """One run of ``cell``; ``t0`` is the process's start on the
    ``perf_counter`` clock, where set-up begins.  ``adapter`` replaces the
    configuration's adapter class (tests)."""
    import jax

    devs = devices_for(cell, require_tpu)
    use_compile_cache()
    ad = (adapter or cell.adapter().Adapter)(cell.config_name, cell.config,
                                             cell.traffic, seed)
    ad.setup()
    setup_s = time.perf_counter() - t0
    log(f"bench: {cell.name} seed {seed}: set-up {setup_s:.3f} s on "
        f"{devs[0].device_kind} x{len(devs)}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    trace_units = cell.traffic["trace_units"] if trace else 0
    records: List[dict] = []
    start = time.perf_counter()
    deadline = start + seconds
    while True:
        i = len(records)
        if i == 0 and trace_units:
            jax.profiler.start_trace(trace_dir)
            window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            window.__enter__()
        with jax.profiler.TraceAnnotation(f"bench.{ad.unit}"):
            records.append(ad.call())
        if len(records) == trace_units:
            window.__exit__(None, None, None)
            jax.profiler.stop_trace()
        if time.perf_counter() >= deadline and len(records) >= trace_units:
            break
    window_s = time.perf_counter() - start
    memory_peak = _peak_bytes(devs)
    log(f"bench: window {window_s:.3f} s, {len(records)} {ad.unit}s")

    metrics: Dict[str, dict] = {}
    if not trace:
        values = dict(ad.end_to_end(records, window_s), setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[quantity(m["name"])],
                                  "unit": m["unit"]}
    ad.free()

    readings = ad.readings(records)
    limits = cell.limits
    # A reading that is not finite fails, and is printed as a number JSON holds.
    checks = {k: {"value": readings[k] if math.isfinite(readings[k]) else NOT_FINITE,
                  "limit": limits[k]} for k in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": ad.attempted(records),
              "failed": 0, "metrics": metrics, "device": device}
    if trace:
        from bench import trace as tr

        reduced = tr.reduce(tr.load(tr.find_xplane(trace_dir)), WINDOW_SPAN,
                            devices=[d.id for d in devs])
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = SimpleNamespace(
            trace=reduced, traced=records[:trace_units], records=records,
            config=cell.config, traffic=cell.traffic, counts=cell.counts(),
            peak=peak_of(devs[0].device_kind))
        for m in cell.per_layer:
            value: Optional[float] = cell.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["breakdown"] = {"device_ops": [list(x) for x in reduced.device_ops],
                               "idle_gaps": [list(x) for x in reduced.idle_gaps]}
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    result["checks"] = checks
    return result


def peak_of(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]
