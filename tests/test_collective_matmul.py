"""Ring collective matmul == all_gather + matmul (the overlap primitive)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.compat import shard_map
    from repro.sharding.collective_matmul import ring_ag_matmul

    N, B, S, D, F = 4, 2, 16, 8, 12
    mesh = jax.make_mesh((N,), ("model",), axis_types=(AxisType.Auto,))
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, D))
    w = jax.random.normal(jax.random.PRNGKey(1), (D, F))

    def local(x_shard, w_loc):
        return ring_ag_matmul(x_shard, w_loc, "model")

    fn = shard_map(local, mesh=mesh,
                       in_specs=(P(None, "model", None), P(None, "model")),
                       out_specs=P(None, None, "model"),
                       check_vma=False)
    with mesh:
        got = fn(x, w)
    want = jnp.einsum("bsd,df->bsf", x, w)
    err = float(jnp.max(jnp.abs(got - want)))

    # differentiability (the TP backward path)
    def loss(x):
        with mesh:
            return jnp.sum(fn(x, w) ** 2)
    g = jax.grad(loss)(x)
    g_want = jax.grad(lambda x: jnp.sum(jnp.einsum("bsd,df->bsf", x, w) ** 2))(x)
    gerr = float(jnp.max(jnp.abs(g - g_want)))
    print(json.dumps({"err": err, "gerr": gerr}))
    """
)


def test_ring_ag_matmul_matches_gather_matmul():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["err"] < 1e-4, rec
    assert rec["gerr"] < 1e-3, rec
