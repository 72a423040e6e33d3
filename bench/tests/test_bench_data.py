"""Every cell and metric of BENCHMARK.json resolves to its files, and the
benchmark refuses to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from bench import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
PER_LAYER = [m["name"] for m in SPEC["per_layer"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = harness.resolve(name)
    assert hasattr(cell.adapter(), "Adapter")
    assert cell.limits and cell.config["source"]
    assert "trace_units" in cell.traffic
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
        assert m["moves"] in reported
    assert callable(cell.counts().step_flops)


@pytest.mark.parametrize("name", PER_LAYER)
def test_per_layer_metric_resolves(name):
    """Each per-layer metric has a reader, and every cell it lists reports
    the end-to-end metric it moves."""
    metric = next(m for m in SPEC["per_layer"] if m["name"] == name)
    assert metric["workloads"]
    for cell_name in metric["workloads"]:
        cell = harness.resolve(cell_name)
        assert callable(cell.reader(name).read)
        assert metric["moves"] in {m["name"] for m in cell.end_to_end}


def test_variant_names_share_their_quantity():
    assert harness.quantity("serve_tok_s.decode") == "serve_tok_s"
    assert harness.quantity("setup_s") == "setup_s"
    cell = harness.resolve(CELLS[0])
    own = cell.reader("device_idle.decode")
    assert own.__file__.endswith("device_idle.py")


def test_traffic_limits_take_the_place_of_the_configurations():
    cell = harness.resolve(CELLS[0])
    assert cell.limits == cell.config["limits"]
    cell.traffic = dict(cell.traffic, limits={"token_gap": 0.5})
    assert cell.limits == {"token_gap": 0.5}


def test_names_and_files():
    paths = SPEC["paths"]
    for c in SPEC["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        assert (harness.ROOT / c["file"]).is_file()
        assert c["reduced"] == json.loads((harness.ROOT / c["file"]).read_text())["reduced"]
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(0.01 <= m["bound"] <= 0.25 for m in SPEC["end_to_end"])


def test_yi6b_counts_match_published_sizes():
    cell = harness.resolve("yi6b.decode-heavy")
    counts = cell.counts()
    assert counts.weight_bytes(cell.config) == 12_122_071_040
    assert counts.kv_bytes_per_token(cell.config) == 65_536


def test_peaks_known_kind_only():
    assert harness.peak_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peak_of("cpu")


def test_run_without_tpu_fails_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
