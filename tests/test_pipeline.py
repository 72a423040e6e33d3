"""GPipe pipeline parallelism: schedule == sequential composition, fwd+bwd."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

PIPE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.launch.pipeline import pipeline_apply

    S, D, B, M = 4, 16, 8, 4
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {"w": jax.random.normal(k1, (S, D, D)) * 0.3,
              "b": jax.random.normal(k2, (S, D)) * 0.1}
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

    def stage_fn(p, xmb):
        return jax.nn.gelu(xmb @ p["w"] + p["b"])

    def sequential(params, x):
        for i in range(S):
            x = stage_fn(jax.tree_util.tree_map(lambda a: a[i], params), x)
        return x

    want = sequential(params, x)
    with mesh:
        got = jax.jit(
            lambda p, x: pipeline_apply(p, x, stage_fn, mesh,
                                        num_microbatches=M)
        )(params, x)
    fwd_err = float(jnp.max(jnp.abs(want - got)))

    def loss_pipe(p):
        with mesh:
            return jnp.sum(pipeline_apply(p, x, stage_fn, mesh,
                                          num_microbatches=M) ** 2)

    def loss_seq(p):
        return jnp.sum(sequential(p, x) ** 2)

    with mesh:
        g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    g_seq = jax.grad(loss_seq)(params)
    g_err = max(
        float(jnp.max(jnp.abs(g_pipe["w"] - g_seq["w"]))),
        float(jnp.max(jnp.abs(g_pipe["b"] - g_seq["b"]))),
    )
    print(json.dumps({"fwd_err": fwd_err, "grad_err": g_err}))
    """
)


def test_gpipe_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c", PIPE], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["fwd_err"] < 1e-5, rec
    assert rec["grad_err"] < 1e-4, rec
