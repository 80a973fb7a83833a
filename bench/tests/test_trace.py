"""The trace reducer on a synthetic XSpace: busy union, idle gaps named by
the host span open in them, and attribution by instruction and opcode."""
import pytest

from bench import trace as T

HLO = """HloModule jit_train_step_async, entry_computation_layout={()}

%fused_computation.1 (param_0: f32[8,4], param_1: s32[8,1]) -> f32[4,4] {
  %param_0 = f32[8,4]{1,0} parameter(0)
  %param_1 = s32[8,1]{1,0} parameter(1)
  ROOT %scatter.1 = f32[4,4]{1,0} scatter(%param_0, %param_1, %param_0), to_apply=%add
}

%fused_computation.2 (param_0.2: f32[8,4]) -> f32[8,4] {
  %param_0.2 = f32[8,4]{1,0} parameter(0)
  ROOT %multiply.2 = f32[8,4]{1,0} multiply(%param_0.2, %param_0.2)
}

ENTRY %main.9 (p0: f32[8,4], p1: s32[8,1]) -> (f32[4,4], u8[8,1]) {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = s32[8,1]{1,0} parameter(1)
  %fusion.1 = f32[4,4]{1,0} fusion(%p0, %p1), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8,4]{1,0} fusion(%p0), kind=kLoop, calls=%fused_computation.2
  %quantize_pack.3 = (u8[8,1]{1,0}, f32[8,1]{1,0}) custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(quantize_pack)/pallas_call"}
  %gather.4 = f32[8,4]{1,0} gather(%p0, %p1), offset_dims={1}
  ROOT %tuple.5 = (f32[4,4]{1,0}, u8[8,1]{1,0}) tuple(%fusion.1, %quantize_pack.3)
}
"""

# device 0: ops at [1,3) [2,4) [6,7) [8,9) ms; window [0, 10) ms
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 9 offset_ps: 0 duration_ps: 10000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 2000000000 }
    events { metadata_id: 3 offset_ps: 6000000000 duration_ps: 1000000000 }
    events { metadata_id: 4 offset_ps: 8000000000 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 20000000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "quantize_pack.3" } }
  event_metadata { key: 4 value { id: 4 name: "gather.4" } }
  event_metadata { key: 9 value { id: 9 name: "jit_train_step_async(42)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 4000000000 duration_ps: 2000000000 }
    events { metadata_id: 3 offset_ps: 4500000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.epoch" } }
  event_metadata { key: 3 value { id: 3 name: "trainer._absorb_site_stats" } }
}
"""


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return T.reduce(ProfileData.from_text_proto(XSPACE), "bench.window")


def test_busy_is_the_union_of_op_intervals(reduced):
    dev = reduced.devices[0]
    assert reduced.window_s == pytest.approx(10e-3)
    # [1,4) + [6,7) + [8,9) ms; the op at 20 ms lies outside the window
    assert dev.busy_s == pytest.approx(5e-3)
    assert len(dev.ops) == 4


def test_idle_gaps_and_their_host_cause(reduced):
    gaps = sorted(reduced.devices[0].gaps)
    assert [pytest.approx(g, abs=1e-12) for g in gaps] == [
        (0.0, 1e-3), (4e-3, 2e-3), (7e-3, 1e-3), (9e-3, 1e-3)]
    assert reduced.gap_cause(4e-3, 2e-3) == "trainer._absorb_site_stats"
    assert reduced.gap_cause(7e-3, 1e-3) == "bench.window"


def test_attribution_by_instruction_and_opcode(reduced):
    index = T.hlo_index([HLO])
    assert set(index) == {"jit_train_step_async"}
    kinds = {}
    for op in reduced.devices[0].ops:
        ins = T.lookup(index, op)
        assert op.module == "jit_train_step_async"
        kinds[op.name] = (ins.opcode, ins.root_opcode, ins.target)
    assert kinds["fusion.1"] == ("fusion", "scatter", "")
    assert kinds["fusion.2"] == ("fusion", "multiply", "")
    assert kinds["quantize_pack.3"] == ("custom-call", "", "tpu_custom_call")
    assert kinds["gather.4"] == ("gather", "", "")


def test_metric_readers_select_their_ops(reduced):
    from bench.harness import RunRecord
    from bench.metrics import aggregation_ms, device_idle_share, lowbit_ms

    rec = RunRecord(cell=None, chips=1, epochs=[object(), object()],
                    epoch_s=5e-3, trace=reduced, index=T.hlo_index([HLO]),
                    counts={}, peak={})
    # fusion.1 (scatter root) 2 ms + gather.4 1 ms, over 2 epochs
    assert aggregation_ms.read(rec) == pytest.approx(1.5)
    assert lowbit_ms.read(rec) == pytest.approx(0.5)
    assert device_idle_share.read(rec) == pytest.approx(50.0)
