"""A cell at a size the CPU holds: the published configuration with small
widths, and short traffic, driven through the harness as a run is."""

import json

from bench import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
SERVE_CELL = "yi6b.decode-heavy"


def serve_cell() -> harness.Cell:
    real = harness.resolve(SERVE_CELL)
    config = dict(real.config, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, vocab_size=256)
    traffic = dict(real.traffic, batch=4, prompt_len=6, max_new_tokens=5,
                   cache_len=16, check_requests=3, check_rows=2)
    return harness.Cell(
        name=SERVE_CELL, chips=1, config_name=f"{SERVE_CELL}-tiny",
        config=config, traffic=traffic,
        end_to_end=[m for m in SPEC["end_to_end"] if harness._applies(m, SERVE_CELL)],
        per_layer=[])


def run(cell: harness.Cell, seed: int = 3, seconds: float = 0.5, adapter=None) -> dict:
    import time

    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            require_tpu=False, adapter=adapter)
