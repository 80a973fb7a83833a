"""``peak_hbm_gb`` is what a chip holds for the compiled steps: arguments,
outputs not aliased to an argument, and the compiler's temporaries."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness as H
from bench.tests import smoke


def test_footprint_counts_arguments_outputs_and_temporaries():
    x = jnp.ones((256, 128), jnp.float32)
    c = jax.jit(lambda a: jnp.tanh(a @ a.T) @ a).lower(x).compile()
    ma = c.memory_analysis()
    assert H.footprint(c) == (ma.argument_size_in_bytes
                              + ma.output_size_in_bytes
                              - ma.alias_size_in_bytes
                              + ma.temp_size_in_bytes)
    assert H.footprint(c) >= 2 * x.nbytes


def test_a_donated_argument_is_counted_once():
    x = jnp.ones((512, 128), jnp.float32)
    plain = jax.jit(lambda a: a * 2.0).lower(x).compile()
    donated = jax.jit(lambda a: a * 2.0, donate_argnums=0).lower(x).compile()
    assert H.footprint(donated) <= H.footprint(plain) - x.nbytes + 1024


@pytest.fixture
def _cpu_peaks(monkeypatch):
    monkeypatch.setattr(H, "load_peaks", lambda kind: smoke.CPU_PEAKS)
    jax.clear_caches()


def test_run_reports_the_largest_compiled_step(_cpu_peaks, capsys):
    res = smoke.run(smoke.smoke_cell("gcn-reddit25k.sylvie-a1"))
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if ln.startswith("memory: "))
    step_bytes = int(line.split()[1])
    assert res["metrics"]["peak_hbm_gb"]["value"] == step_bytes / 1e9
    assert res["device"]["memory_peak_bytes"] >= step_bytes > 0
