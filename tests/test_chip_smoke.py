"""chip_smoke.py's phases at reduced size on the CPU, and the compile-cache
helper the launchers share.

The phases run under the ``interpret`` dispatch backend, so every packed
layer goes through the Pallas kernels in the interpreter and the same
dispatch and logits checks as on the chip apply.  The platform check lives
in ``main`` only: on this host it must refuse to run.
"""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.kernels import registry
from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]
REDUCED = ("--engine", "--arch", "llama3.2-1b", "--reduced",
           "--requests", "3", "--prompt-len", "32", "--gen", "4",
           "--max-slots", "2", "--page-size", "16", "--arrival-rate", "4")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "tc.json"))
    registry.set_backend_override("interpret")
    yield _load_smoke()
    registry.set_backend_override(None)


@pytest.mark.parametrize("phase", ["dense", "tiled_csc", "tiled_csc_int8",
                                   "block_csr"])
def test_phase_reduced_interpret(smoke, phase):
    rep = smoke.run_phase(phase, smoke.PHASES[phase], base_flags=REDUCED,
                          backend="interpret")
    assert rep["phase"] == phase and rep["generated_tokens"] > 0
    counts = rep["kernel_dispatch"]
    if phase == "dense":
        assert counts == {}
        return
    assert counts and all(k.split("[")[0] in smoke.PALLAS for k in counts)
    assert rep["logits"]["err_over_std"] <= smoke.LOGITS_TOL_STD
    assert rep["logits"]["top1_agreement"] >= smoke.TOP1_MIN


def test_dispatch_check_rejects_oracle_and_wrong_backend(smoke):
    from repro.core import pruning
    from repro.core.formats import pack_tiled_csc
    from repro.kernels import ops

    w = pack_tiled_csc(pruning.random_sparse(jax.random.PRNGKey(0),
                                             (256, 128), 0.3))
    x = jax.numpy.ones((8, 256))
    with registry.record_dispatches() as log:
        ops.sod_matmul(x, w, impl="jnp")
    with pytest.raises(smoke.SmokeFailure, match="jnp_oracle"):
        smoke.check_dispatch(log, packed=True, backend="interpret")
    with registry.record_dispatches() as log:
        ops.sod_matmul(x, w)
    smoke.check_dispatch(log, packed=True, backend="interpret")
    with pytest.raises(smoke.SmokeFailure, match="'tpu' backend"):
        smoke.check_dispatch(log, packed=True, backend="tpu")
    with pytest.raises(smoke.SmokeFailure, match="no packed matmul"):
        smoke.check_dispatch([], packed=True, backend="interpret")


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_four_chip_phase_on_virtual_devices(tmp_path):
    """The --four-chips phase on four virtual CPU devices, in a child
    process that sets its own device count."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "import chip_smoke\n"
        "from repro.kernels import registry\n"
        "registry.set_backend_override('interpret')\n"
        "print(json.dumps(chip_smoke.four_chip_phase(\n"
        "    reduced=True, batch=4, seq=32, backend='interpret')))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               REPRO_TUNING_CACHE=str(tmp_path / "tc.json"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    rep = json.loads(r.stdout.strip().splitlines()[-1])
    assert rep["mesh"] == {"data": 2, "model": 2}
    assert rep["kernel_dispatch_4chip"]
    assert all(k.startswith("pallas_fused") for k in rep["kernel_dispatch_4chip"])
    assert all(rep["mesh_keys"]) and rep["sharded_arrays"] > 0
    assert rep["loss_rel_diff"] <= rep["tol_rel"]
    assert rep["grad_norm_rel_diff"] <= rep["tol_rel"]


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_helper(monkeypatch, tmp_path, env_set):
    """JAX_COMPILATION_CACHE_DIR set: nothing changes; unset: the cache goes
    to <checkout>/.jax_cache."""
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_set:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            jax.config.update("jax_compilation_cache_dir", before)
            assert compile_cache.use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = compile_cache.use_compile_cache()
            assert path == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
