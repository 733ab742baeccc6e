"""Hierarchical compressed gradient reduction: multi-device shard_map test
(subprocess with 8 host devices arranged as pod=2 x data=4)."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.optim.hierarchical import hierarchical_grad_reduce

    mesh = make_mesh((2, 4), ("pod", "data"))
    key = jax.random.PRNGKey(0)
    n, dim = 8, 64
    gs = jax.random.normal(key, (n, dim))          # one grad per shard

    def step(g, err):
        return hierarchical_grad_reduce(g, err)

    f = jax.jit(jax.shard_map(step, mesh=mesh,
                              in_specs=(P(("pod", "data")),
                                        P(("pod", "data"))),
                              out_specs=(P(("pod", "data")),
                                         P(("pod", "data"))),
                              check_vma=False))

    # exact reference: fleet mean
    exact = jnp.broadcast_to(gs.mean(0, keepdims=True), gs.shape)

    # (a) uncompressed path == exact
    f0 = jax.jit(jax.shard_map(
        lambda g, e: hierarchical_grad_reduce(g, e, compress=False),
        mesh=mesh, in_specs=(P(("pod", "data")), P(("pod", "data"))),
        out_specs=(P(("pod", "data")), P(("pod", "data"))),
        check_vma=False))
    out0, _ = f0(gs.reshape(n, dim), jnp.zeros((n, dim)))
    np.testing.assert_allclose(np.asarray(out0), np.asarray(exact),
                               rtol=1e-5, atol=1e-6)
    print("UNCOMPRESSED_OK")

    # (b) compressed + error feedback: telescoping sum converges to the
    # exact gradient sum over repeated steps with a FIXED gradient
    err = jnp.zeros((n, dim))
    acc = jnp.zeros((n, dim))
    for _ in range(30):
        dec, err = f(gs, err)
        acc = acc + dec
    mean_step = acc / 30
    rel = float(jnp.linalg.norm(mean_step - exact)
                / jnp.linalg.norm(exact))
    assert rel < 0.05, rel
    print("COMPRESSED_OK", rel)
""")


def test_hierarchical_reduce_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "UNCOMPRESSED_OK" in r.stdout, r.stdout + r.stderr
    assert "COMPRESSED_OK" in r.stdout, r.stdout + r.stderr
