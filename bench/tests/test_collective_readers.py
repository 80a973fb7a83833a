"""The collective reader on synthetic traces: a collective is in flight from
its ``-start`` to its ``-done`` (or for its own duration where it is
synchronous), under the ``collective`` scope only; the exposed part is what
no other operation on the same chip covers."""
import pytest

from bench import harness as H
from bench import trace as T
from bench.metrics import collective_exposed_ms as X

PERMUTE = 'metadata={op_name="jit(train_step_async)/shard_map/jvp(exchange)/collective/ppermute"}'
PSUM = 'metadata={op_name="jit(train_step_async)/shard_map/collective/psum"}'
BARE = 'metadata={op_name="jit(train_step_async)/shard_map/jvp(exchange)/ppermute"}'
AGG = 'metadata={op_name="jit(train_step_async)/shard_map/jvp(aggregation)/gather"}'

HLO = f"""HloModule jit_train_step_async, entry_computation_layout={{()}}

ENTRY %main.1 (p0: u8[1,8,76]) -> f32[4] {{
  %p0 = u8[1,8,76]{{2,1,0}} parameter(0)
  %collective-permute-start.1 = (u8[1,8,76]{{2,1,0}}, u8[1,8,76]{{2,1,0}}, u32[], u32[]) collective-permute-start(%p0), channel_id=1, source_target_pairs={{{{0,1}},{{1,0}}}}, {PERMUTE}
  %collective-permute-start.2 = (u8[1,8,76]{{2,1,0}}, u8[1,8,76]{{2,1,0}}, u32[], u32[]) collective-permute-start(%p0), channel_id=2, source_target_pairs={{{{0,1}},{{1,0}}}}, {PERMUTE}
  %collective-permute-done.1 = u8[1,8,76]{{2,1,0}} collective-permute-done(%collective-permute-start.1), {PERMUTE}
  %collective-permute-done.2 = u8[1,8,76]{{2,1,0}} collective-permute-done(%collective-permute-start.2), {PERMUTE}
  %all-reduce.5 = f32[4]{{0}} all-reduce(%p0), channel_id=3, replica_groups={{{{0,1}}}}, to_apply=%add, {PSUM}
  %fusion.6 = f32[4]{{0}} fusion(%p0), kind=kLoop, calls=%fused_computation, {AGG}
  %collective-permute-start.7 = (u8[1,8,76]{{2,1,0}}, u8[1,8,76]{{2,1,0}}, u32[], u32[]) collective-permute-start(%p0), channel_id=4, source_target_pairs={{{{0,1}},{{1,0}}}}, {BARE}
  %collective-permute-done.7 = u8[1,8,76]{{2,1,0}} collective-permute-done(%collective-permute-start.7), {BARE}
  ROOT %t = f32[4]{{0}} copy(%all-reduce.5)
}}
"""


def _ps(ms):
    return int(round(ms * 1e9))


def _xspace(devices, window_ms):
    """``devices``: one list of (instruction, start ms, duration ms) a chip."""
    names = sorted({n for ops in devices for n, _, _ in ops})
    ids = {n: i + 1 for i, n in enumerate(names)}
    md = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                 for n, i in ids.items())
    planes = ""
    for dev, ops in enumerate(devices):
        evs = "".join(f"events {{ metadata_id: {ids[n]} offset_ps: {_ps(s)} "
                      f"duration_ps: {_ps(d)} }}\n" for n, s, d in ops)
        planes += f"""
planes {{
  id: {dev + 1} name: "/device:TPU:{dev}"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 99 offset_ps: 0 duration_ps: {_ps(window_ms)} }} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
{evs}  }}
{md}  event_metadata {{ key: 99 value {{ id: 99 name: "jit_train_step_async(3)" }} }}
}}"""
    return planes + f"""
planes {{
  id: 100 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {_ps(window_ms)} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
}}
"""


def _cell():
    return H.Cell("x4", {"d_feat": 602, "d_hidden": 256, "n_layers": 2,
                         "n_classes": 41},
                  {"mode": "async", "bits": 1, "scale_dtype": "bfloat16"},
                  2, {}, [], [])


def _record(devices, window_ms=20.0, epochs=2):
    from jax.profiler import ProfileData
    red = T.reduce(ProfileData.from_text_proto(_xspace(devices, window_ms)),
                   "bench.window")
    return H.RunRecord(cell=_cell(), chips=len(devices),
                       epochs=[object()] * epochs, epoch_s=window_ms / epochs,
                       trace=red, index=T.hlo_index([HLO]),
                       counts={}, peak={})


S1, S2 = "collective-permute-start.1", "collective-permute-start.2"
D1, D2 = "collective-permute-done.1", "collective-permute-done.2"


def test_start_done_pairing_takes_the_union_in_flight():
    dev0 = [(S1, 1, 0.1), (S2, 1.5, 0.1), (D1, 3, 0.2), (D2, 4, 0.5),
            # the second pair's dones in the other order: the same union
            (S1, 10, 0.1), (S2, 10.5, 0.1), (D2, 11, 0.2), (D1, 12, 0.5),
            # a synchronous all-reduce by its own duration
            ("all-reduce.5", 14, 1),
            # no collective scope on its op_name: not counted
            ("collective-permute-start.7", 16, 0.1),
            ("collective-permute-done.7", 17, 0.2)]
    dev1 = [(S1, 2, 0.1), (D1, 4, 0.1)]
    rec = _record([dev0, dev1])
    ivs = X.in_flight(rec)
    assert ivs[0] == [pytest.approx((1e-3, 4.5e-3)),
                      pytest.approx((10e-3, 12.5e-3)),
                      pytest.approx((14e-3, 15e-3))]
    assert ivs[1] == [pytest.approx((2e-3, 4.1e-3))]
    # nothing else runs: all of it is exposed; device 0: 3.5 + 2.5 + 1 ms,
    # device 1: 2.1 ms; mean over 2 chips, 2 epochs
    assert X.read(rec) == pytest.approx((7.0 + 2.1) / 2 / 2)


def test_a_done_with_its_start_before_the_window_closes_nothing_open():
    rec = _record([[(D1, 1, 0.5), (S2, 2, 0.1), (D2, 3, 1)]], epochs=1)
    assert X.in_flight(rec)[0] == [pytest.approx((2e-3, 4e-3))]
    assert X.read(rec) == pytest.approx(2.0)


@pytest.mark.parametrize("others, exposed_ms", [
    # half the 4 ms in flight covered by another scope's op: half exposed
    ([("fusion.6", 1, 2)], 2.0),
    # wholly covered: hidden
    ([("fusion.6", 0, 6)], 0.0),
    # covered twice over the same span counts once
    ([("fusion.6", 1, 2), ("fusion.6", 2, 1)], 2.0),
    # another op outside the span covers nothing of it
    ([("fusion.6", 6, 3)], 4.0),
    # an all-reduce is in the scope: it waits, it does not hide
    ([("all-reduce.5", 2, 1)], 4.0),
])
def test_exposed_is_what_no_other_operation_covers(others, exposed_ms):
    dev0 = [(S1, 1, 0.5), (D1, 4.5, 0.5)] + others
    rec = _record([dev0], window_ms=10, epochs=1)
    assert X.read(rec) == pytest.approx(exposed_ms)


def test_another_chips_work_hides_nothing():
    rec = _record([[(S1, 1, 0.5), (D1, 4.5, 0.5)], [("fusion.6", 0, 10)]],
                  window_ms=10, epochs=1)
    # chip 0 waits 4 ms, chip 1 runs no collective: the mean over 2 chips
    assert X.read(rec) == pytest.approx(2.0)


def test_no_collective_reads_none():
    rec = _record([[("fusion.6", 1, 2),
                    ("collective-permute-start.7", 3, 0.1),
                    ("collective-permute-done.7", 4, 0.2)]])
    assert X.in_flight(rec) is None
    assert X.read(rec) is None
