"""The plain reference agrees with the system at small sizes on the CPU:
the float32 Llama-style forward with the served logits of ``serve_batch``."""

from bench.tests import tiny


def test_llama_reference_matches_serve_batch_in_float32():
    """Prefill and decode through the cache, in float32, give the logits of
    one dense causal pass of the reference."""
    cell = tiny.serve_cell()
    config = dict(cell.config, torch_dtype="float32")
    ad = cell.adapter().Adapter("tiny-f32", config, cell.traffic, 5)
    ad.setup()
    records = [ad.call() for _ in range(2)]
    got = ad.readings(records)
    assert got["token_gap"] == 0.0
    assert got["logit_err"] < 1e-3, got


def test_llama_reference_fp8_control_departs():
    """The control rounds every matrix product's operands to float8."""
    cell = tiny.serve_cell()
    ad = cell.adapter().Adapter(cell.config_name, cell.config, cell.traffic, 6)
    ad.setup()
    records = [ad.call()]
    program = ad.readings(records)
    control = ad.readings(records, control=True)
    assert control["logit_err"] > 3 * program["logit_err"]
