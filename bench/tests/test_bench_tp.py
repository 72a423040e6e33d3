"""The tensor-parallel cell at a size the CPU holds: a Qwen2-shaped model with
random Q/K/V biases, served through ``serve_batch_tp.Adapter`` on a
``(data=1, model=4)`` mesh of four host devices, checked against the plain
reference ``reference/qwen2.py``; the collectives of its compiled step; and
the counts of the published configuration.

Forcing four host devices has to happen before JAX starts, so the served
runs are made in a subprocess (as ``tests/test_sharding.py`` does)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import harness

CELL = "qwen2-72b.tp4-decode"

# Every number in the subprocess's one JSON line.
SCRIPT = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [{root!r}, {src!r}]
    import dataclasses, json, time
    from types import SimpleNamespace
    import jax
    from bench import harness
    from bench.metrics import serve_collective_mb
    from repro.roofline.hlo_parse import analyze_module

    real = harness.resolve({cell!r})
    config = dict(real.config, hidden_size=128, intermediate_size=256,
                  num_hidden_layers=2, num_attention_heads=8,
                  num_key_value_heads=4, head_dim=16, vocab_size=512)
    traffic = dict(real.traffic, batch=4, prompt_len=6, max_new_tokens=5,
                   cache_len=16, check_requests=3, check_rows=2)
    adapter = real.adapter()
    out = {{}}

    # float32: the program against the reference, the control, and the
    # reference with its biases zeroed.
    f32 = dict(config, torch_dtype="float32")
    ad = adapter.Adapter("tiny-tp-f32", f32, traffic, 5)
    ad.setup()
    records = [ad.call() for _ in range(2)]
    wq = ad.weights["stack"]["mixer"]["wq"]
    out["wq_shards"] = [s.data.shape[-1] for s in wq.addressable_shards]
    out["steps_on_records"] = ["step" in r for r in records]
    out["program"] = ad.readings(records)
    out["control"] = ad.readings(records, control=True)
    biased = ad.weights
    ad.weights = jax.tree_util.tree_map_with_path(
        lambda p, a: a * 0 if jax.tree_util.keystr(p).endswith(
            ("['bq']", "['bk']", "['bv']")) else a, biased)
    out["no_bias"] = ad.readings(records)
    ad.weights = biased
    out["collective_mb"] = {{2: serve_collective_mb.read(
        SimpleNamespace(traced=records[:1]))}}
    for layers in (3, 4):
        deeper = adapter.Adapter(f"tiny-tp-f32-{{layers}}",
                                 dict(f32, num_hidden_layers=layers), traffic, 5)
        deeper.setup()
        out["collective_mb"][layers] = analyze_module(
            deeper.last.step.as_text()).collective_bytes / 1e6

    # bfloat16, as the cell serves it, through the harness with its limits.
    cell = dataclasses.replace(real, config=config, traffic=traffic,
                               config_name="tiny-tp")
    res = harness.run_cell(cell, 7, 0.5, False, time.perf_counter(),
                           require_tpu=False)
    out["run"] = {{k: res[k] for k in ("correct", "checks", "metrics", "device")}}
    print(json.dumps(out))
    """
)

# Both sides compute in float32; they differ only in the order of their
# sums (partial sums over four devices, token by token through the cache
# against one dense causal pass), which moves logits of order 1 by about
# 1e-6.  A change to the math, such as a missing bias, moves them by far
# more.
F32_TOL = 1e-3


@pytest.fixture(scope="module")
def tp_run():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    script = SCRIPT.format(root=str(harness.ROOT), src=str(harness.ROOT / "src"),
                           cell=CELL)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_weights_land_split_by_column(tp_run):
    assert tp_run["wq_shards"] == [8 * 16 // 4] * 4


def test_step_is_on_the_first_record_only(tp_run):
    assert tp_run["steps_on_records"] == [True, False]


def test_program_matches_qwen2_reference_in_float32(tp_run):
    assert tp_run["program"]["token_gap"] == 0.0, tp_run["program"]
    assert tp_run["program"]["logit_err"] < F32_TOL, tp_run["program"]


@pytest.mark.parametrize("reading", ["control", "no_bias"])
def test_departures_fail_the_check(tp_run, reading):
    """The fp8 control, and the reference without the Q/K/V biases, depart
    from the program by more than the float32 tolerance, and by far more
    than the program does."""
    got = tp_run[reading]["logit_err"]
    assert got > F32_TOL and got > 30 * tp_run["program"]["logit_err"], tp_run


def test_sound_tp_run_is_correct_and_reports_its_metrics(tp_run):
    run = tp_run["run"]
    assert run["correct"], run["checks"]
    assert run["device"]["count"] == 4
    assert set(run["metrics"]) == {m["name"] for m in harness.resolve(CELL).end_to_end}
    assert all(m["value"] > 0 for m in run["metrics"].values())


def test_collective_mb_grows_by_the_scan_trip_count(tp_run):
    """Positive, and linear in the layer count: each layer of the scan's
    body adds the same bytes."""
    mb = {int(k): v for k, v in tp_run["collective_mb"].items()}
    per_layer = mb[3] - mb[2]
    assert mb[2] > 0 and per_layer > 0, mb
    assert mb[4] - mb[2] == pytest.approx(2 * per_layer, rel=1e-9), mb


def test_counts_give_the_published_parameters():
    """At 80 layers, the configuration's counts give Qwen2-72B's 72.71 B
    parameters, 70.21 B outside the embeddings (arXiv:2407.10671)."""
    cell = harness.resolve(CELL)
    counts, config = cell.counts(), dict(cell.config, num_hidden_layers=80)
    total = counts.params(config)
    assert total == 72_706_203_648
    embeddings = 2 * config["vocab_size"] * config["hidden_size"]
    assert round((total - embeddings) / 1e9, 2) == 70.21
    assert counts.weight_bytes(config) == 2 * total / 4


def test_collective_bytes_of_a_loop_and_an_async_pair():
    """``hlo_parse`` on a hand-written module, as the reader uses it: a sync
    all-reduce in the entry, an async all-gather pair counted once by its
    -done, and an all-reduce in a loop body that runs 3 times by its
    condition's bound."""
    from repro.roofline.hlo_parse import analyze_module

    hlo = textwrap.dedent(
        """
        %cond (p: (s32[], f32[8])) -> pred[] {
          %c = s32[] constant(3)
          ROOT %lt = pred[] compare(%x, %c), direction=LT
        }

        %body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
          %ar = f32[8]{0} all-reduce(%y), replica_groups={}, to_apply=%add
          ROOT %t = (s32[], f32[8]) tuple(%i, %ar)
        }

        ENTRY %main (a: bf16[16,4]) -> bf16[16,16] {
          %ar0 = bf16[16,4]{1,0} all-reduce(%a), to_apply=%add
          %ags = (bf16[16,4]{1,0}, bf16[16,16]{1,0}) all-gather-start(%ar0), dimensions={1}
          %agd = bf16[16,16]{1,0} all-gather-done(%ags)
          %w = (s32[], f32[8]) while(%init), condition=%cond, body=%body
          ROOT %r = bf16[16,16]{1,0} copy(%agd)
        }
        """
    )
    assert analyze_module(hlo).collective_bytes == (
        16 * 4 * 2 + 16 * 16 * 2 + 3 * 8 * 4)


def test_weights_are_drawn_without_a_constant_part():
    """Every matrix of the bfloat16 weights has mean zero within the noise
    of its draw.  Normals drawn in bfloat16 have mean -0.0118 (their
    uniform is rounded to 8 bits), which at Qwen2's widths makes the
    residual stream grow along the all-ones direction and the reference
    ill-conditioned; the adapter draws in float32 and rounds once."""
    import jax.numpy as jnp
    import numpy as np

    from bench.adapters import serve_batch_tp

    config = dict(harness.resolve(CELL).config, hidden_size=512,
                  intermediate_size=2048, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2,
                  head_dim=128, vocab_size=2048)
    weights = serve_batch_tp.make_weights(config, 2147400001, None)
    ffn = weights["stack"]["ffn"]
    for name in ("w_gate", "w_up", "w_down"):
        w = np.asarray(ffn[name].astype(jnp.float32), np.float64).ravel()
        assert ffn[name].dtype == jnp.bfloat16
        z = abs(w.mean()) / w.std() * np.sqrt(w.size)
        assert z < 6, (name, w.mean() / w.std(), z)
