"""Pipeline parallelism: shard_map+ppermute GPipe vs sequential reference.
Runs in a subprocess with 4 host devices (the main test process must keep
the default 1-device platform)."""
import os
import subprocess
import sys
import textwrap

import numpy as np

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.parallel.pipeline import pipeline_forward, sequential_reference

    mesh = make_mesh((4,), ("pipe",))
    n_stages, n_micro, bm, d = 4, 8, 2, 16
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (n_stages, d, d)) * 0.2,
              "b": jnp.zeros((n_stages, d))}
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, bm, d))

    def stage(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    out = pipeline_forward(stage, params, x, mesh)
    want = sequential_reference(stage, params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    print("PIPELINE_OK")
""")


def test_pipeline_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
