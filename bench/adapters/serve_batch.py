"""Adapter for served decoder models: closed-loop batches through
``repro.launch.serve.serve_batch``.

One unit of work is one batch: ``batch`` prompts of ``prompt_len`` tokens
drawn from the seed, served greedily for ``max_new_tokens`` tokens from a
``cache_len``-slot cache, the next batch sent when the last has returned.
The weights are made by the benchmark on the device from the seed, in the
type they are served in, and handed to the program; the reference reads the
same arrays.

``correct`` compares, for a sample of the requests served in the window
drawn from the seed, every served token and the logits it was picked from
with the float32 reference run over the prompt and the served tokens:

* ``token_gap``: the widest gap by which a served token's reference logit
  lies below the reference's best logit at that position;
* ``logit_err``: the largest absolute difference between a logit the
  program served from and the reference's.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import stats
from bench.reference import llama

PROGRAM_NAME = "bench-{}"


def arch_config(name: str, config: dict):
    """The program's ``ArchConfig`` for a configuration file's published
    sizes (Hugging Face ``config.json`` names)."""
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=PROGRAM_NAME.format(name),
        family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=config["torch_dtype"],
    )


def weight_shapes(config: dict) -> Dict:
    """Shapes of the weights, stacked over layers, in the layout the program
    and the reference both read."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, f, v = config["head_dim"], config["intermediate_size"], config["vocab_size"]
    return {
        "embed": (v, d),
        "head": (d, v),
        "final_norm": {"scale": (d,)},
        "stack": {
            "norm1": {"scale": (n, d)},
            "norm2": {"scale": (n, d)},
            "mixer": {"wq": (n, d, hq * hd), "wk": (n, d, hkv * hd),
                      "wv": (n, d, hkv * hd), "wo": (n, hq * hd, d)},
            "ffn": {"w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d)},
        },
    }


def prng_key(seed: int):
    """A threefry key holding all 64 bits of ``seed``."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def make_weights(config: dict, seed: int):
    """Weights drawn from ``seed`` on the device, in one jitted call, in the
    configuration's type.  Matrices are N(0, 1/fan_in) (the embedding
    N(0, 1/d)), norm scales 1 + N(0, 0.1^2)."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(config["torch_dtype"])
    shapes = weight_shapes(config)
    leaves, tree = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda s: isinstance(s, tuple))[0]]

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, shape, path in zip(keys, leaves, paths):
            z = jax.random.normal(k, shape, dtype)
            if "scale" in path:
                out.append(1 + z * jnp.asarray(0.1, dtype))
            else:
                fan_in = shape[-1] if "embed" in path else shape[-2]
                out.append(z * jnp.asarray(fan_in ** -0.5, dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(build)(prng_key(seed))


class Adapter:
    unit = "batch"

    def __init__(self, name: str, config: dict, traffic: dict, seed: int):
        self.name, self.config, self.traffic, self.seed = name, config, traffic, seed
        self.batch = traffic["batch"]
        self.prompt_len = traffic["prompt_len"]
        self.max_new = traffic["max_new_tokens"]
        self.cache_len = traffic["cache_len"]
        self.prompt_rng = np.random.default_rng([seed, 0])
        self.sample_rng = np.random.default_rng([seed, 1])
        self.sample: List = []   # reservoir of (prompt, tokens, logits)
        self.served = 0

    # ---- set-up ----------------------------------------------------------
    def setup(self) -> None:
        import jax

        from repro.configs.registry import get_arch, register
        from repro.models import build_model

        arch = arch_config(self.name, self.config)
        try:
            get_arch(arch.name)
        except KeyError:
            register(arch)
        self.arch = arch
        self.weights = make_weights(self.config, self.seed)
        want = jax.eval_shape(build_model(arch).init_params, prng_key(0))
        layout = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
        got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), self.weights)
        if layout != got:
            raise ValueError(f"weight layout differs from the program's: "
                             f"{layout} vs {got}")
        jax.block_until_ready(self.weights)
        # Every shape of the window: the step's shapes depend on the batch
        # and the cache length, not on the prompt length.
        self._serve(np.ones((self.batch, 1), np.int64), 2)

    def _serve(self, prompts: np.ndarray, max_new: int):
        from repro.launch.serve import serve_batch

        return serve_batch(
            self.arch.name, prompts.tolist(), smoke=False,
            max_new_tokens=max_new, cache_len=self.cache_len,
            params=self.weights,
        )

    # ---- the window ------------------------------------------------------
    def call(self) -> dict:
        v = self.config["vocab_size"]
        prompts = self.prompt_rng.integers(1, v, (self.batch, self.prompt_len))
        t0 = time.perf_counter()
        res = self._serve(prompts, self.max_new)
        wall = time.perf_counter() - t0
        keep = self.traffic["check_requests"]
        for row in range(self.batch):
            self.served += 1
            item = (prompts[row], res.tokens[row], res.logits[row])
            if len(self.sample) < keep:
                self.sample.append(item)
            else:
                j = int(self.sample_rng.integers(0, self.served))
                if j < keep:
                    self.sample[j] = item
        return {"wall_s": wall, "decode_s": res.decode_s,
                "requests": self.batch, "tokens": self.batch * self.max_new}

    def attempted(self, records) -> int:
        return sum(r["requests"] for r in records)

    def end_to_end(self, records, window_s: float) -> Dict[str, float]:
        ttft, tpot = [], []
        for r in records:
            ttft += [r["wall_s"] - r["decode_s"]] * r["requests"]
            tpot += [r["decode_s"] / self.max_new * 1e3] * r["requests"]
        return {
            "serve_tok_s": sum(r["tokens"] for r in records) / window_s,
            "serve_ttft_p50_s": stats.median(ttft),
            "serve_tpot_p50_ms": stats.median(tpot),
        }

    # ---- correct ---------------------------------------------------------
    def readings(self, records=(), control: bool = False) -> Dict[str, float]:
        """The compared numbers for the sampled requests; with ``control``,
        those of the float8 reference put in the program's place."""
        import jax.numpy as jnp

        spec = llama.spec_of(self.config)
        seqs = np.stack([np.concatenate([p, t[:-1]]) for p, t, _ in self.sample])
        first = self.prompt_len - 1
        rows = self.traffic["check_rows"]
        ref = llama.logits(self.weights, seqs, spec, first=first, rows=rows)
        best = ref.max(-1)

        def gap(tokens):
            picked = jnp.take_along_axis(ref, jnp.asarray(tokens)[..., None], -1)
            return float(jnp.max(best - picked[..., 0]))

        if control:
            got = llama.logits(self.weights, seqs, spec, first=first, rows=rows,
                               precision="fp8")
            tokens = jnp.argmax(got, -1)
        else:
            got = jnp.asarray(np.stack([lg for _, _, lg in self.sample]))
            tokens = np.stack([t for _, t, _ in self.sample])
        return {"token_gap": gap(tokens),
                "logit_err": float(jnp.max(jnp.abs(got - ref)))}

    def free(self) -> None:
        """Nothing of the program outlives a ``serve_batch`` call but the
        weights, which the reference reads."""
