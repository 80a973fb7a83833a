"""Each fault a training cell can have, planted underneath the timed path,
turns ``correct`` false; the unbroken path stays correct."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests import smoke

CELL = "gcn-reddit25k.sylvie-a1"


def _wrap_steps(monkeypatch, wrap):
    """Wrap the compiled train steps the trainer builds."""
    import repro.train.trainer as trainer
    real = trainer.make_gnn_steps

    def make(*a, **k):
        ts, ta, ev = real(*a, **k)
        return wrap(ts), wrap(ta), ev
    monkeypatch.setattr(trainer, "make_gnn_steps", make)


def _state_unchanged(step):
    def f(state, block, x, y, mask, key):
        _, loss = step(state, block, x, y, mask, key)
        return state, loss
    return f


def _half_batch(step):
    def f(state, block, x, y, mask, key):
        flat = mask.reshape(-1).astype(jnp.int32)
        keep = (jnp.cumsum(flat) % 2 == 1).reshape(mask.shape)
        return step(state, block, x, y, mask & keep, key)
    return f


def _no_exchange(monkeypatch):
    from repro.dist.backend import SimulatedBackend
    monkeypatch.setattr(SimulatedBackend, "exchange_compact",
                        lambda self, buf, sizes, reverse=False:
                        jnp.zeros_like(buf))


def _wrong_rows(monkeypatch):
    """The exchange's answer altered where it is produced: every ring
    bucket lands one partition further on."""
    from repro.dist.backend import SimulatedBackend
    real = SimulatedBackend.exchange_compact

    def shifted(self, buf, sizes, reverse=False):
        return jnp.roll(real(self, buf, sizes, reverse), 1, axis=0)
    monkeypatch.setattr(SimulatedBackend, "exchange_compact", shifted)


FAULTS = {
    "state_unchanged": lambda mp: _wrap_steps(mp, _state_unchanged),
    "half_batch": lambda mp: _wrap_steps(mp, _half_batch),
    "no_exchange": _no_exchange,
    "wrong_rows": _wrong_rows,
}


@pytest.fixture(autouse=True)
def _cpu_peaks(monkeypatch):
    from bench import harness
    monkeypatch.setattr(harness, "load_peaks", lambda kind: smoke.CPU_PEAKS)
    jax.clear_caches()


def test_sound_path_is_correct():
    res = smoke.run(smoke.smoke_cell(CELL))
    assert res["correct"], res["check"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_turns_correct_false(monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    res = smoke.run(smoke.smoke_cell(CELL))
    assert not res["correct"], (fault, res["check"])
    assert list(res)[-1] == "check"
