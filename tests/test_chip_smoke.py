"""chip_smoke.py at smoke size on the CPU.

The script proves the served path on a TPU at full width; here its phases
run on the smoke config (kernels in interpret mode) so the script cannot
rot between chip runs, and the script itself must refuse the CPU.
"""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SMOKE = dict(n_slots=4, max_seq=64, prompt_bucket=32, page_size=8,
             n_requests=4, prompt_range=(8, 32), max_new=4)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from repro import configs
    dense, dsg = mod.serving_configs(
        configs.get_smoke_config(mod.ARCH))
    params = mod.init_params(dense, 0)
    return mod, mod.Sizes(**SMOKE), dense, dsg, params


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_kernel_phase_matches_references(smoke):
    mod, sizes, _, dsg_cfg, _ = smoke
    rec = mod.kernel_phase(dsg_cfg, sizes, 0)
    for name in mod.KERNELS:
        assert rec[f"{name}_rel_err"] <= mod.TOL


def test_dense_serving_phase(smoke):
    mod, sizes, dense, _, params = smoke
    rec = mod.serving_phase(dense, params, None, sizes, 0)
    assert rec["requests_ok"] == sizes.n_requests
    assert rec["tokens"] == sizes.n_requests * sizes.max_new
    assert min(rec["reference_agreement"]) == 1.0


def test_dsg_serving_phase(smoke):
    from repro.serving.dsg_runtime import DSGServingConfig
    mod, sizes, _, dsg_cfg, params = smoke
    dsg = mod.init_dsg_state(dsg_cfg, params, 0)
    rec = mod.serving_phase(dsg_cfg, params, dsg, sizes, 0,
                            DSGServingConfig(threshold="topk"))
    assert rec["requests_ok"] == sizes.n_requests


REPLICAS = textwrap.dedent("""
    import importlib.util, sys
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from repro import configs
    dense, _ = mod.serving_configs(configs.get_smoke_config(mod.ARCH))
    sizes = mod.Sizes(**eval(sys.argv[2]))
    rec = mod.replica_phase(dense, mod.init_params(dense, 0), sizes, 0,
                            n_replicas=2)
    assert rec["streams_equal"] and rec["requests_ok"] == sizes.n_requests
    print("REPLICAS_OK")
""")


def test_replica_phase_places_each_replica_on_its_device():
    """Two host devices stand in for chips: each threaded replica's
    params, KV pool and prefill template live on its own device, and the
    streams match a single replica's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", REPLICAS,
                        str(ROOT / "chip_smoke.py"), repr(SMOKE)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert "REPLICAS_OK" in r.stdout, r.stderr[-4000:]
