"""The control (the reference computed in bfloat16, put in the program's
place) comes out as not correct against each cell's limits."""
import pytest

from bench import check
from bench import harness as H
from bench.tests import smoke


@pytest.mark.parametrize("name", smoke.cell_names())
def test_control_is_not_correct(name):
    cell = smoke.smoke_cell(name)
    built_seed = 4294967311
    from bench import graphgen
    g = graphgen.generate(cell.config)
    w_seed, t_seed = H.seeds(built_seed)
    import jax
    params0 = jax.device_get(H.make_weights(cell.config["arch"],
                                            H.dims_of(cell), w_seed))
    ref = H.reference_readings(cell, g, params0, t_seed)
    control = H.reference_readings(cell, g, params0, t_seed, dtype="bfloat16")
    values = check.readings(control, ref)
    assert not check.verdict(values, cell.limits), values
