"""The scope and span readers on a synthetic XSpace: operations attributed by
the innermost named scope of their ``op_name`` (through ``jvp(`` and
``transpose(jvp(`` wrappers), and device idle time split by the host span
open over it."""
import pytest

from bench import scopes as S
from bench import trace as T

HLO = """HloModule jit_train_step_sync, entry_computation_layout={()}

%fused_computation.1 (param_0: f32[8,4], param_1: s32[8,1]) -> f32[4,4] {
  %param_0 = f32[8,4]{1,0} parameter(0)
  %param_1 = s32[8,1]{1,0} parameter(1)
  ROOT %scatter.1 = f32[4,4]{1,0} scatter(%param_0, %param_1, %param_0), to_apply=%add
}

ENTRY %main.9 (p0: f32[8,4], p1: s32[8,1]) -> f32[4,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = s32[8,1]{1,0} parameter(1)
  %gather.1 = f32[8,4]{1,0} gather(%p0, %p1), offset_dims={1}, metadata={op_name="jit(train_step_sync)/jvp(aggregation)/jit(take_along_axis)/gather"}
  %fusion.2 = f32[4,4]{1,0} fusion(%gather.1, %p1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step_sync)/transpose(jvp(aggregation))/vmap()/scatter-add"}
  %gather.3 = f32[8,4]{1,0} gather(%p0, %p1), offset_dims={1}, metadata={op_name="jit(train_step_sync)/jvp(exchange)/jit(take_along_axis)/gather"}
  %quantize_pack.4 = (u8[8,1]{1,0}, f32[8,1]{1,0}) custom-call(%gather.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step_sync)/jvp(lowbit)/jit(quantize_pack)/pallas_call"}
  %slice.5 = f32[8,4]{1,0} slice(%gather.3), slice={[0:8], [0:4]}, metadata={op_name="jit(train_step_sync)/transpose(jvp(exchange))/jit(_roll_static)/slice"}
  %fusion.6 = f32[4,4]{1,0} fusion(%slice.5, %p1), kind=kLoop, calls=%fused_computation.1
  %multiply.7 = f32[8,4]{1,0} multiply(%p0, %p0), metadata={op_name="jit(train_step_sync)/jvp()/mul"}
  ROOT %add.8 = f32[4,4]{1,0} add(%fusion.2, %fusion.6), metadata={op_name="jit(train_step_sync)/aggregation/jvp(jit(exchange))/add"}
}
"""

# Two epochs over a 20 ms window on one device. Device ops (ms):
#   gather.1 [1,2) fusion.2 [2,4) gather.3 [4,5) quantize_pack.4 [5,5.5)
#   slice.5 [5.5,6) fusion.6 [6,7) multiply.7 [7,8)        -- epoch 0
#   gather.1 [12,13) fusion.2 [13,15) add.8 [15,16)         -- epoch 1
# idle gaps: [0,1) [8,12) [16,20)
# host spans: dispatch [0,1.5) and [11,12.5); readback.loss [1.5,9) and
# [12.5,17); readback.stats [9,10) and [17,18)
OPS = [("gather.1", 1, 1), ("fusion.2", 2, 2), ("gather.3", 4, 1),
       ("quantize_pack.4", 5, 0.5), ("slice.5", 5.5, 0.5),
       ("fusion.6", 6, 1), ("multiply.7", 7, 1),
       ("gather.1", 12, 1), ("fusion.2", 13, 2), ("add.8", 15, 1)]
SPANS = [("bench.window", 0, 20), ("dispatch", 0, 1.5),
         ("readback.loss", 1.5, 7.5), ("readback.stats", 9, 1),
         ("dispatch", 11, 1.5), ("readback.loss", 12.5, 4.5),
         ("readback.stats", 17, 1)]


def _ps(ms):
    return int(round(ms * 1e9))


def _xspace(ops, spans):
    names = sorted({n for n, _, _ in ops})
    ids = {n: i + 1 for i, n in enumerate(names)}
    dev = "".join(f"events {{ metadata_id: {ids[n]} offset_ps: {_ps(s)} "
                  f"duration_ps: {_ps(d)} }}\n" for n, s, d in ops)
    dev_md = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                     for n, i in ids.items())
    hnames = sorted({n for n, _, _ in spans})
    hids = {n: i + 1 for i, n in enumerate(hnames)}
    host = "".join(f"events {{ metadata_id: {hids[n]} offset_ps: {_ps(s)} "
                   f"duration_ps: {_ps(d)} }}\n" for n, s, d in spans)
    host_md = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                      for n, i in hids.items())
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 99 offset_ps: 0 duration_ps: {_ps(20)} }} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
{dev}  }}
{dev_md}  event_metadata {{ key: 99 value {{ id: 99 name: "jit_train_step_sync(7)" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{host}  }}
{host_md}}}
"""


def _record(spans=SPANS, hlo=HLO):
    from jax.profiler import ProfileData

    from bench.harness import RunRecord
    red = T.reduce(ProfileData.from_text_proto(_xspace(OPS, spans)),
                   "bench.window")
    return RunRecord(cell=None, chips=1, epochs=[object(), object()],
                     epoch_s=10e-3, trace=red, index=T.hlo_index([hlo]),
                     counts={}, peak={})


@pytest.mark.parametrize("op_name, scope", [
    ("jit(f)/jvp(aggregation)/jit(take_along_axis)/gather", "aggregation"),
    ("jit(f)/transpose(jvp(exchange))/vmap()/scatter-add", "exchange"),
    ("jit(f)/transpose(jvp(lowbit))/jit(_uniform)/max", "lowbit"),
    ("jit(f)/aggregation/jvp(jit(exchange))/add", "aggregation"),
    ("jit(f)/jvp(exchange)/lowbit/convert_element_type", "lowbit"),
    ("jit(f)/jvp(jit(take_along_axis))/gather", None),
    ("jit(exchange_table)/gather", None),
    ("", None),
])
def test_scope_of_is_the_innermost_scope(op_name, scope):
    assert S.scope_of(op_name) == scope


def test_scope_readers_sum_their_ops():
    from bench.metrics import (aggregation_scope_ms, exchange_scope_ms,
                               lowbit_scope_ms)
    rec = _record()
    # aggregation: gather.1 and fusion.2, twice: 2 x (1 + 2) ms over 2 epochs;
    # add.8 names "jit(exchange)", which is no scope, inside "aggregation"
    assert aggregation_scope_ms.read(rec) == pytest.approx(3.5)
    # exchange: gather.3 1 ms + slice.5 0.5 ms in one of two epochs
    assert exchange_scope_ms.read(rec) == pytest.approx(0.75)
    assert lowbit_scope_ms.read(rec) == pytest.approx(0.25)
    # fusion.6 (no op_name) and multiply.7 are in no scope: the scopes and
    # that remainder add up to the device's busy time
    busy_ms = 1e3 * rec.trace.devices[0].busy_s / 2
    assert 3.5 + 0.75 + 0.25 + 1.0 == pytest.approx(busy_ms)


def test_span_readers_split_idle_gaps():
    from bench.metrics import dispatch_idle_ms, readback_idle_ms
    rec = _record()
    # idle [0,1) under dispatch; [11,12) under dispatch
    assert dispatch_idle_ms.read(rec) == pytest.approx(1.0)
    # idle [8,9) under readback.loss, [9,10) under readback.stats,
    # [16,17) under readback.loss, [17,18) under readback.stats
    assert readback_idle_ms.read(rec) == pytest.approx(2.0)
    # the rest of the idle time, [10,11) and [18,20), lies under no span
    idle_ms = 1e3 * sum(d for _, d in rec.trace.devices[0].gaps) / 2
    assert idle_ms - 1.0 - 2.0 == pytest.approx(1.5)


def test_readers_find_nothing_in_a_program_without_scopes_or_spans():
    """The parent program: no scope in any op_name, no trainer spans."""
    from bench.metrics import (aggregation_scope_ms, dispatch_idle_ms,
                               exchange_scope_ms, lowbit_scope_ms,
                               readback_idle_ms)
    bare = HLO
    for scope in S.SCOPES:
        bare = bare.replace(f"({scope})", "()").replace(f"/{scope}/", "/")
    rec = _record(spans=SPANS[:1], hlo=bare)
    for reader in (aggregation_scope_ms, exchange_scope_ms, lowbit_scope_ms,
                   dispatch_idle_ms, readback_idle_ms):
        assert reader.read(rec) is None
