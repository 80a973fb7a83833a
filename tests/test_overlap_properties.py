"""Property-based invariants of the quantized halo exchange.

Needs the ``hypothesis`` dev extra (CI installs it; skipped otherwise, like
``test_graph.py``). Two families, each over randomly drawn skewed
partitions — so the ring buckets are ragged and every example exercises a
different static bucket-size tuple:

* quantize -> exchange -> dequantize commutes with exchange -> dequantize
  across the whole low-bit lattice {1, 2, 4, 8} (the exchange permutes whole
  rows together with their per-row scale/zero, so dequantized values are
  *bit-identical* either way — the property the fenced (overlap) schedule
  relies on to be value-transparent);
* the compact ring exchange is an involution: ``reverse=True`` undoes
  ``reverse=False`` bit-exactly, for raw buffers and quantized payloads (the
  backward gradient communication of ``core/sylvie.py`` depends on this
  inversion).
"""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property-based overlap tests need the 'hypothesis' dev extra")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

import jax  # noqa: E402

from repro.core import quantization as qlib  # noqa: E402
from repro.core.exchange import (PlanArrays, exchange_halo,  # noqa: E402
                                 exchange_quantized_halo)
from repro.dist.backend import SimulatedBackend  # noqa: E402
from repro.graph import formats, partition, synthetic  # noqa: E402

pytestmark = pytest.mark.overlap

BE = SimulatedBackend()


def _plan(n, parts, seed):
    g = synthetic.powerlaw(n_nodes=n, d_feat=8, avg_degree=8, seed=seed)
    ei = formats.add_self_loops(g.edge_index, g.n_nodes)
    ew = formats.gcn_edge_weights(ei, g.n_nodes)
    g = formats.Graph(g.n_nodes, ei, g.x, g.y, g.train_mask, g.val_mask,
                      g.test_mask, n_classes=g.n_classes)
    pg = partition.partition_graph(g, parts, method="skewed",
                                   edge_weight=ew, layout="compact")
    return PlanArrays.from_plan(pg.plan)


def _buf(plan, d_feat, seed):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (plan.n_parts, plan.halo_rows, d_feat))


@settings(max_examples=8, deadline=None)
@given(bits=st.sampled_from([1, 2, 4, 8]),
       n=st.integers(min_value=120, max_value=320),
       parts=st.sampled_from([2, 4]),
       seed=st.integers(min_value=0, max_value=31))
def test_quantized_exchange_dequantize_roundtrip(bits, n, parts, seed):
    """dequantize(exchange(quantize(x))) == exchange(dequantize(quantize(x)))
    bit-exactly: the exchange moves payload + scale + zero as one row."""
    plan = _plan(n, parts, seed)
    x = _buf(plan, 8, seed)
    qt = qlib.quantize(x, bits, jax.random.PRNGKey(seed), stochastic=False)
    via_wire = qlib.dequantize(exchange_quantized_halo(qt, plan, BE))
    local = exchange_halo(qlib.dequantize(qt), plan, BE)
    np.testing.assert_array_equal(np.asarray(via_wire), np.asarray(local))


@settings(max_examples=8, deadline=None)
@given(n=st.integers(min_value=120, max_value=320),
       parts=st.sampled_from([2, 4]),
       seed=st.integers(min_value=0, max_value=31),
       bits=st.sampled_from([1, 4]))
def test_exchange_involution(n, parts, seed, bits):
    """reverse=True inverts reverse=False over random ragged buckets, for raw
    buffers and for quantized payload/scale/zero triples."""
    plan = _plan(n, parts, seed)
    x = _buf(plan, 8, seed)
    back = exchange_halo(exchange_halo(x, plan, BE), plan, BE, reverse=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    qt = qlib.quantize(x, bits, jax.random.PRNGKey(seed), stochastic=False)
    qback = exchange_quantized_halo(
        exchange_quantized_halo(qt, plan, BE), plan, BE, reverse=True)
    for a, b in zip(jax.tree.leaves(qt), jax.tree.leaves(qback)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
