"""Adapter for decoder models served tensor-parallel over several chips:
``serve_batch.Adapter``'s closed-loop batches through
``repro.launch.serve.serve_batch(..., mesh=...)``.

The mesh is ``(data=1, model=tensor_parallel)`` over the first
``tensor_parallel`` devices.  The weights are drawn from the seed on the
devices, straight into the layout the program gives them (``param_pspecs``
of the program's own parameter shapes), so no device ever holds more of a
split matrix than its share; the Q/K/V biases are drawn too, not left at
zero, so that the check covers them.  ``correct`` is decided as in
``serve_batch.py``, against the float32 Qwen2 reference
(``bench/reference/qwen2.py``), which reads the same sharded arrays.

The first record of a run carries ``step``, the compiled step its batch ran
on, for the readers that read the program's collectives and scopes.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from bench.adapters import serve_batch
from bench.reference import qwen2

BIAS_STD = 0.1


def arch_config(name: str, config: dict):
    return dataclasses.replace(serve_batch.arch_config(name, config),
                               qkv_bias=bool(config["qkv_bias"]))


def weight_shapes(config: dict) -> Dict:
    """``serve_batch.weight_shapes`` with the Q/K/V biases, stacked."""
    shapes = serve_batch.weight_shapes(config)
    n, hd = config["num_hidden_layers"], config["head_dim"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    shapes["stack"]["mixer"].update(bq=(n, hq * hd), bk=(n, hkv * hd),
                                    bv=(n, hkv * hd))
    return shapes


def make_weights(config: dict, seed: int, shardings):
    """Weights drawn from ``seed`` into ``shardings`` (a tree matching
    :func:`weight_shapes`), in one jitted call, in the configuration's type.
    Matrices N(0, 1/fan_in), the embedding N(0, 1/d), norm scales
    1 + N(0, 0.1^2), biases N(0, 0.1^2).

    Each value is drawn in float32 and rounded to the configuration's type
    once.  ``jax.random.normal`` drawn in bfloat16 takes 128 values with a
    mean of -0.0118 (the uniform it starts from is rounded to 8 bits), so
    every matrix would carry a constant part; through a layer of Qwen2's
    widths it multiplies the all-ones direction of the residual stream by
    about 0.0118 * sqrt(fan_in) per matrix (1.07 at 8192, 2.03 at 29568),
    that direction grows from layer to layer, attention turns sharp, and
    the logits of some positions move by several units under a change of
    the input far below bfloat16's rounding: no bfloat16 program could
    match the reference there."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(config["torch_dtype"])
    shapes = weight_shapes(config)
    is_shape = lambda s: isinstance(s, tuple)  # noqa: E731
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_shape)

    def build(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, shape) in zip(keys, flat):
            name = jax.tree_util.keystr(path)
            z = jax.random.normal(k, shape, jnp.float32)
            if "scale" in name:
                z = 1 + 0.1 * z
            elif name.endswith(("['bq']", "['bk']", "['bv']")):
                z = BIAS_STD * z
            else:
                fan_in = shape[-1] if "embed" in name else shape[-2]
                z = fan_in ** -0.5 * z
            out.append(z.astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(build, out_shardings=shardings)(serve_batch.prng_key(seed))


class Adapter(serve_batch.Adapter):

    def setup(self) -> None:
        import jax

        from repro.configs.registry import get_arch, register
        from repro.models import build_model
        from repro.sharding import named, param_pspecs

        arch = arch_config(self.name, self.config)
        try:
            get_arch(arch.name)
        except KeyError:
            register(arch)
        self.arch = arch
        tp = self.config["tensor_parallel"]
        self.mesh = jax.make_mesh(
            (1, tp), ("data", "model"), devices=jax.devices()[:tp],
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        want = jax.eval_shape(build_model(arch).init_params,
                              serve_batch.prng_key(0))
        shardings = named(self.mesh, param_pspecs(want, self.mesh))
        self.weights = make_weights(self.config, self.seed, shardings)
        layout = jax.tree_util.tree_map(
            lambda a, s: (a.shape, a.dtype, s), want, shardings)
        got = jax.tree_util.tree_map(
            lambda a: (a.shape, a.dtype, a.sharding), self.weights)
        if layout != got:
            raise ValueError(f"weight layout differs from the program's: "
                             f"{layout} vs {got}")
        jax.block_until_ready(self.weights)
        # Every shape of the window: the step's shapes depend on the batch
        # and the cache length, not on the prompt length.
        self._serve(np.ones((self.batch, 1), np.int64), 2)

    def _serve(self, prompts: np.ndarray, max_new: int):
        from repro.launch.serve import serve_batch as serve

        self.last = serve(
            self.arch.name, prompts.tolist(), smoke=False,
            max_new_tokens=max_new, cache_len=self.cache_len,
            params=self.weights, mesh=self.mesh,
        )
        return self.last

    def call(self) -> dict:
        first = self.served == 0
        record = super().call()
        if first:
            record["step"] = self.last.step
        return record

    def readings(self, records=(), control: bool = False) -> Dict[str, float]:
        """The compared numbers for the sampled requests; with ``control``,
        those of the float8 reference put in the program's place."""
        import jax.numpy as jnp

        spec = qwen2.spec_of(self.config)
        seqs = np.stack([np.concatenate([p, t[:-1]]) for p, t, _ in self.sample])
        first = self.prompt_len - 1
        rows = self.traffic["check_rows"]
        ref = qwen2.logits(self.weights, seqs, spec, first=first, rows=rows)
        best = ref.max(-1)

        def gap(tokens):
            picked = jnp.take_along_axis(ref, jnp.asarray(tokens)[..., None], -1)
            return float(jnp.max(best - picked[..., 0]))

        if control:
            got = qwen2.logits(self.weights, seqs, spec, first=first, rows=rows,
                               precision="fp8")
            tokens = jnp.argmax(got, -1)
        else:
            got = jnp.asarray(np.stack([lg for _, _, lg in self.sample]))
            tokens = np.stack([t for _, t, _ in self.sample])
        return {"token_gap": gap(tokens),
                "logit_err": float(jnp.max(jnp.abs(got - ref)))}
