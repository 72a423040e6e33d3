"""A run with the timed path broken underneath reads ``correct`` false.

Each test skips the harness's look for a chip and drives the rest of a run
at a small size on the CPU, with the cell's own limits, once for each fault
the cell can have.  One chip has no exchange between chips to leave out."""

import pytest

from bench.tests import tiny


def test_sound_run_is_correct_and_reports_its_metrics():
    cell = tiny.serve_cell()
    result = tiny.run(cell)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def _unchanged_state(model):
    def step(params, cache, tokens, cache_index):
        logits, _ = model.decode_step(params, cache, tokens, cache_index)
        return logits, cache
    return step


def _half_batch(model):
    def step(params, cache, tokens, cache_index):
        logits, cache = model.decode_step(params, cache, tokens, cache_index)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:half]), cache
    return step


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_serve_step_fault(monkeypatch, fault):
    import repro.launch.serve as serve

    monkeypatch.setattr(serve, "make_serve_step", fault)
    result = tiny.run(tiny.serve_cell())
    assert not result["correct"], result["checks"]


def test_serve_token_altered(monkeypatch):
    import repro.launch.serve as serve

    real = serve._run_decode

    def altered(*args, **kwargs):
        out, seen, cache, t = real(*args, **kwargs)
        out = out.copy()
        out[0, out.shape[1] // 2] = (out[0, out.shape[1] // 2] + 1) % seen.shape[-1]
        return out, seen, cache, t

    monkeypatch.setattr(serve, "_run_decode", altered)
    result = tiny.run(tiny.serve_cell())
    assert not result["correct"], result["checks"]
