"""FLOPs and Low-bit bytes of a tiny plan, against hand counts."""
import numpy as np
import pytest

from bench import counts, graphgen, reference


def tiny_graph():
    # 4 nodes in 2 partitions ({0,1}, {2,3}); edges 0->2, 1->2, 3->0, 2->3
    src = np.array([0, 1, 3, 2])
    dst = np.array([2, 2, 0, 3])
    x = np.zeros((4, 3), np.float32)
    m = np.ones(4, bool)
    return graphgen.Graph(4, src, dst, x, np.zeros(4, np.int32), m, m, m, 2)


def test_plan_halo_rows_and_layout():
    plan = reference.build_plan(tiny_graph(), parts=2, alignment=8)
    # halo entries: (recv 1, node 0), (recv 1, node 1), (recv 0, node 3)
    assert sorted(zip(plan.halo_recv.tolist(), plan.halo_node.tolist())) == [
        (0, 3), (1, 0), (1, 1)]
    assert counts.halo_rows(plan) == 3
    assert plan.rows == 8                 # one ring bucket of 2 rows, aligned
    assert plan.dst.shape[0] == 4 + 4     # edges plus one self loop per node


def test_flops_per_epoch_by_hand():
    # GCN 3 -> 5 -> 2 over n=4 nodes and e=8 edges, forward:
    # layer 0: 2*8*3 + 2*4*3*5 = 48 + 120; layer 1: 2*8*5 + 2*4*5*2 = 80 + 80
    fwd = 48 + 120 + 80 + 80
    assert counts.flops_per_epoch("gcn", 4, 8, (3, 5, 2)) == 3 * fwd
    # GraphSAGE adds a second dense update per layer
    sage = 48 + 240 + 80 + 160
    assert counts.flops_per_epoch("graphsage", 4, 8, (3, 5, 2)) == 3 * sage


def test_lowbit_bytes_by_hand():
    rows, bits = 3, 1
    # site 0, d=10: fp32 read 120, packed 3*2=6, scale+zero 3*2*2=12; the
    # dequantize moves the same again: 2*(120+6+12) = 276, forward only.
    # site 1, d=5: 2*(60+3+12) = 150, forward and backward.
    assert counts.lowbit_bytes_per_epoch(rows, (10, 5), bits,
                                         "bfloat16") == 276 + 2 * 150
    assert counts.lowbit_bytes_per_epoch(rows, (10, 5), 32, "bfloat16") == 0


@pytest.mark.parametrize("bits", [1, 2, 4, 8])
def test_packed_bytes_round_up(bits):
    d = 602
    one = counts.exchange_bytes(1, d, bits, "bfloat16") / 2
    assert one == d * 4 + -(-d * bits // 8) + 4
