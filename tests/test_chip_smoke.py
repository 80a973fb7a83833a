"""chip_smoke.py rehearsed on CPU: its training path at smoke size, and the
platform guard that keeps it off anything but a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_training_path_pallas_matches_jnp(chip_smoke):
    """The one-chip phase at reddit_like@smoke with the reduced GCN: Sylvie-A
    trains to finite losses, and the interpret-mode Pallas Low-bit Module
    agrees with the jnp one within the script's tolerance."""
    tr, pallas = chip_smoke.train("reddit_like@smoke", reduced=True,
                                  impl="pallas", epochs=3)
    _, ref = chip_smoke.train("reddit_like@smoke", reduced=True, impl="jnp",
                              epochs=3)
    assert [m.mode for m in tr.history] == ["sync", "async", "async"]
    assert chip_smoke.max_rel_dev(pallas, ref) <= chip_smoke.PALLAS_RTOL


def test_four_chip_phase_on_host_devices():
    """The --chips 4 phase at smoke size on four forced CPU devices: sharded
    and simulated losses agree and the halo state spans four devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import chip_smoke; "
            "chip_smoke.four_chips('reddit_like@smoke', reduced=True)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=SCRIPT.parent)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.count("halo state spans 4 devices") == 2


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_location(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the entry points
    cache in the fixed .jax_cache at the repository root."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(SCRIPT.parent / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = ("import jax; from repro.launch.cache import use_compile_cache; "
            "print(use_compile_cache()); "
            "print(jax.config.jax_compilation_cache_dir)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    used, configured = r.stdout.split()[-2:]
    want = str(tmp_path) if from_env else str(SCRIPT.parent / ".jax_cache")
    assert used == want == configured


def test_platform_guard_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert '"ok"' not in r.stdout
