"""The four-chip cell ``ghcnd-4m-x4.analytic``: the loader finds its
files, and its collective reader, ``collective_ms_per_query``, reads
events written by hand, named as the trace names an operation: its
instruction's HLO text. The collectives' texts are those a
``shard_map`` with a ``psum`` and an ``all_gather`` compiles to for a
TPU v5e."""
import types

import pytest

from chipbench import spec
from chipbench import trace as T

READER = spec.metric("collective_ms_per_query")

PSUM = ("%psum.7 = s32[2048]{0:T(1024)S(1)} all-reduce(%bitcast.4), "
        "channel_id=1, replica_groups={{0,1,2,3}}, "
        "use_global_device_ids=true, to_apply=%region_1.0")
GATHER = ("%all-gather.5 = s32[4,1,250000]{2,1,0:T(1,128)S(1)} "
          "all-gather(%bitcast.3), channel_id=1, "
          "replica_groups={{0,1,2,3}}, dimensions={0}, "
          "use_global_device_ids=true")
START = ("%all-gather-start = (s32[2048]{0}, s32[4,2048]{1,0}) "
         "all-gather-start(%x), channel_id=2, dimensions={0}")
DONE = "%all-gather-done = s32[4,2048]{1,0} all-gather-done(%all-gather-start)"
LOOP = ("%while.32 = (s32[], s32[2048]{0:T(1024)}) while(%tuple.9), "
        "condition=%region_2.0, body=%region_3.0")
KERNEL = ('%shmap_body.3 = s32[2048]{0} custom-call(%a, %b), '
          'custom_call_target="tpu_custom_call"')
# a fusion named after the collective it consumes is not one
REDUCE = ("%all-gather.5.reduce = s32[250000]{0:T(1024)S(1)} "
          "reduce(%all-gather.5, %constant.5), dimensions={0,1}")


@pytest.mark.parametrize("text,op", [
    (PSUM, "all-reduce"), (GATHER, "all-gather"),
    (START, "all-gather-start"), (DONE, "all-gather-done"),
    (LOOP, "while"), (KERNEL, "custom-call"), (REDUCE, "reduce"),
    ("%fusion.12 = f32[8]{0:T(256)} fusion(%p), kind=kLoop", "fusion"),
])
def test_opcode(text, op):
    assert READER.opcode(text) == op


# Operations as a TPU v5 lite trace of the cell names them (cut to 200
# characters): the collectives the reader counts, then the largest
# operations whose names hold a collective's words but are none.
CHIP_COLLECTIVES = [
    ("%all-reduce.26 = (s32[800000]{0:T(1024)S(1)}, s32[800000]{0:T(1024)S(1)}) "
     "all-reduce(s32[800000]{0:T(1024)S(1)} %fusion.46, s32[800000]{0:T(1024)} "
     "%fusion.47), channel_id=2, replica_groups={{0,1,2,3}}", "all-reduce"),
    ("%all-gather.37 = s32[26112,1]{1,0:T(8,128)} all-gather(s32[6528,1]{1,0:"
     "T(8,128)S(1)} %custom-call.77), channel_id=4, replica_groups={{0,1,2,3}}, "
     "dimensions={0}, use_global_device_ids=true, frontend_at", "all-gather"),
    ("%all-gather.11 = pred[4,1,6528]{2,1,0:T(4,128)(4,1)S(1)} all-gather(pred"
     "[1,1,6528]{2,1,0:T(4,128)(4,1)S(1)} %copy-done.38), channel_id=1, "
     "replica_groups={{0,1,2,3}}, dimensions={0}, use_global_device_", "all-gather"),
    ("%all-reduce.17 = s32[8004]{0:T(1024)S(1)} all-reduce(s32[8004]{0:T(1024)"
     "S(1)} %select_dynamic-update-slice_fusion), channel_id=2, replica_groups="
     "{{0,1,2,3}}, use_global_device_ids=true, to_apply=%add.", "all-reduce"),
]
CHIP_OTHERS = [
    ("%hash_join_probe.1 = s32[1,200192]{1,0:T(1,128)S(1)} custom-call(s32[1,"
     "200192]{1,0:T(1,128)S(1)} %reshape.266, s32[1,200192]{1,0:T(1,128)S(1)} "
     "%copy-done.55, s32[1,800256]{1,0:T(1,128)S(1)} %custom-ca", "custom-call"),
    ("%fusion.11 = s32[1000000]{0:T(1024)S(1)} fusion(s32[5031296]{0:T(1024)} "
     "%copy-done.6, s32[1000448]{0:T(1024)S(1)} %pad_clamp_fusion.9), "
     "kind=kCustom, calls=%fused_computation.11", "fusion"),
    ("%copy-done.1 = s32[1000000]{0:T(1024)} copy-done((s32[1000000]{0:T(1024)}, "
     "s32[1000000]{0:T(1024)S(1)}, u32[]{:S(2)}) %copy-start.1)", "copy-done"),
]


@pytest.mark.parametrize("text,op", CHIP_COLLECTIVES + CHIP_OTHERS)
def test_opcode_of_chip_trace_names(text, op):
    assert READER.opcode(text) == op
    assert (op in READER.COLLECTIVES) == ((text, op) in CHIP_COLLECTIVES)


def _ctx(ops, requests=4):
    spans = [("bench.execute:Q9", 0.0, 10.0)]
    summary = T.summarize(T.Trace(ops, spans, []))
    return types.SimpleNamespace(
        trace=summary, window=types.SimpleNamespace(attempted=requests))


def test_collectives_counted_once_a_chip_mean_over_chips():
    ops = {
        # a psum inside a while loop: the loop is not a collective
        "/device:TPU:0": [(LOOP, 1.0, 3.0), (PSUM, 1.5, 1.7),
                          (GATHER, 4.0, 4.4), (KERNEL, 5.0, 6.0),
                          (REDUCE, 4.4, 4.9)],
        # the same collectives overlap those of chip 0 in time
        "/device:TPU:1": [(LOOP, 1.0, 3.0), (PSUM, 1.6, 1.7),
                          (START, 3.9, 4.0), (DONE, 4.3, 4.6)],
    }
    # chip 0: 0.2 + 0.4; chip 1: 0.1 + 0.1 + 0.3; mean 0.55 s, 4 requests
    assert READER.read(_ctx(ops)) == pytest.approx(1e3 * 0.55 / 4)


def test_nothing_to_read():
    assert READER.read(types.SimpleNamespace(trace=None)) is None
    ops = {"/device:TPU:0": [(LOOP, 1.0, 3.0), (KERNEL, 5.0, 6.0)]}
    assert READER.read(_ctx(ops)) is None


def test_cell_finds_config_traffic_and_limits():
    bench = spec.benchmark()
    cell = spec.cell(bench, "ghcnd-4m-x4.analytic")
    cfg = spec.config(bench, cell["config"])
    assert cell["chips"] == cfg["chips"] == cfg["num_partitions"] == 4
    assert cfg["mode"] == "spmd"
    assert cfg["num_stations"] * (cfg["years"][1] - cfg["years"][0] + 1) \
        * cfg["days_per_year"] * len(cfg["datatypes"]) == 4_000_000
    assert spec.traffic(cell["traffic"]) == spec.traffic("analytic")
    assert spec.limits(cell["name"])["wrong_answers"] == 0
    names = {m["name"] for m in spec.metrics(bench, "per_layer",
                                             cell["name"])}
    assert "collective_ms_per_query" in names
    assert names >= {m["name"] for m in spec.metrics(
        bench, "per_layer", "ghcnd-1m.analytic")}
