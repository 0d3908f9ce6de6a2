"""The program's own stages: its spans, the named scopes of its device
operations and its counters, reduced on events written by hand and
read back from a profile of the program on the CPU."""
import types

import pytest

from chipbench import spec, stages


def test_under_matches_scope_components():
    assert stages.under("jit(local)/vmap(rows_from_mask)/cumsum",
                        "rows_from_mask")
    assert stages.under("jit(local)/vmap(group_by)/exchange/psum",
                        "exchange")
    assert stages.under("a/x;jit(local)/join_probe", "join_probe")
    assert stages.under("exchange", "exchange")
    assert not stages.under("jit(local)/rows_from_masks/x",
                            "rows_from_mask")
    assert not stages.under("", "exchange")


HLO = """HloModule jit_local, is_scheduled=true, entry_computation_layout={()}

%fused (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  ROOT %add.1 = s32[4]{0} add(%p, %p), metadata={op_name="jit(local)/vmap(rows_from_mask)/add"}
}

ENTRY %main (q: s32[4]) -> s32[4] {
  %q = s32[4]{0:T(128)} parameter(0)
  %fusion.3 = (s32[4]{0:T(128)}, s32[]) fusion(%q), kind=kLoop, calls=%fused, metadata={op_name="jit(local)/vmap(group_by)/exchange/psum" stack_frame_id=2}, backend_config={"a":[]}
  ROOT %copy = s32[4]{0} copy(%fusion.3)
}
"""
# the same instruction as a TPU trace names it: operand shapes, no
# metadata
TPU_OP = ("%fusion.3 = (s32[4]{0:T(128)}, s32[]) fusion(s32[4]{0:T(128)} "
          "%q), kind=kLoop, calls=%fused")


def test_signature():
    line = HLO.splitlines()[9].strip()
    assert stages.signature(line) == stages.signature(TPU_OP) == (
        "fusion.3", "(s32[4]{0:T(128)}, s32[])", "fusion",
        ", kind=kLoop, calls=%fused")
    assert stages.signature("fusion.3") == ("fusion.3",)
    # as_text numbers the parts of long tuples; a trace may not
    assert stages.signature("w = (s32[], /*index=5*/s32[]) while(%t)") == \
        stages.signature("w = (s32[], s32[]) while((s32[], s32[]) %t)")


def test_executables():
    (x,) = stages.executables([HLO])
    assert x.name == "jit_local"
    assert x.paths == {"add.1": "jit(local)/vmap(rows_from_mask)/add",
                       "fusion.3": "jit(local)/vmap(group_by)/exchange/psum"}
    assert stages.signature(TPU_OP) in x.signatures
    assert ("copy",) in x.signatures and "copy" not in x.paths


@pytest.mark.parametrize("event,name", [
    ("jit_local(1234567)", "jit_local"), ("jit_local", "jit_local"),
    ("jit_f(x)", "jit_f(x)")])
def test_module_name(event, name):
    assert stages.module_name(event) == name


# a second executable of the same module name, with an instruction of
# the same name under another scope and with another result
OTHER = HLO.replace("(s32[4]{0:T(128)}, s32[]) fusion",
                    "s32[8]{0} fusion").replace("exchange/psum",
                                                "order_by/sort")
OTHER_OP = ("%fusion.3 = s32[8]{0} fusion(s32[4]{0:T(128)} %q), kind=kLoop, "
            "calls=%fused")


def test_op_paths_read_each_operation_in_its_own_executable():
    exes = stages.executables([HLO, OTHER])
    modules = [("jit_local(7)", 0.0, 1.0), ("jit_local(8)", 2.0, 3.0)]
    ops = [(TPU_OP, 0.1), ("%copy = s32[4]{0} copy(%fusion.3)", 0.2),
           (OTHER_OP, 2.1),
           ("fusion.3", 2.2),      # a bare name, in OTHER's interval
           (TPU_OP, 5.0),          # outside every module interval
           ('%f = s32[] fusion(), metadata={op_name="a/exchange/b"}', 5.0)]
    assert stages.op_paths(ops, modules, exes) == [
        ("jit(local)/vmap(group_by)/exchange/psum", "executable text"),
        ("", "none"),
        ("jit(local)/vmap(group_by)/order_by/sort", "executable text"),
        ("jit(local)/vmap(group_by)/order_by/sort", "executable text"),
        ("", "no module"),
        ("a/exchange/b", "op_name in the event")]
    assert stages.op_paths(ops[:1], modules, []) == [("", "none")]


def test_match_takes_the_module_name_first():
    exes = stages.executables([HLO, HLO.replace("jit_local", "jit_other")])
    sig = {stages.signature(TPU_OP)}
    assert stages.match("jit_other", sig, exes) is exes[1]
    assert stages.match("jit_local", sig, exes) is exes[0]
    # an unknown name: the best of all of them
    assert stages.match("jit_x", sig, exes) is exes[0]
    assert stages.match("jit_local", {("nothing",)}, exes) is None


def test_kernel_name():
    assert stages.kernel_name(
        '%seg_topk.2 = s32[1,128] custom-call(), '
        'custom_call_target="tpu_custom_call"', []) == "seg_topk"
    assert stages.kernel_name(
        '%vmap__.7 = custom-call(), custom_call_target="tpu_custom_call"',
        []) == "unnamed"
    assert stages.kernel_name("fusion.3", [("hlo_op", "fusion.3")]) is None


def test_idle_by_stage_labels_the_innermost_program_span():
    bench = [("bench.gen", 0.0, 1.0), ("bench.execute:Q7", 1.0, 5.0),
             ("bench.decode:Q7", 5.0, 6.0)]
    program = [("execute", 1.1, 4.9), ("prepare", 1.1, 2.0),
               ("verify", 1.5, 1.8), ("fetch", 4.0, 4.8),
               ("decode", 5.0, 5.9)]
    idle = [(0.5, 1.2), (1.6, 1.7), (1.9, 2.1), (4.1, 4.3), (4.85, 5.5)]
    got = stages.idle_by_stage(idle, bench, program)
    # 1.1-1.2: prepare and execute open together; prepare, the shorter,
    # is the inner one. 4.85-4.9: fetch has closed, execute has not.
    assert got == pytest.approx({
        "gen": 0.5, "execute:Q7": 0.1 + 0.1,
        "execute:Q7/prepare": 0.1 + 0.1, "execute:Q7/verify": 0.1,
        "execute:Q7/execute": 0.1 + 0.05, "execute:Q7/fetch": 0.2,
        "decode:Q7/decode": 0.5})
    assert sum(got.values()) == pytest.approx(
        sum(b - a for a, b in idle))


def test_idle_by_stage_without_program_spans_matches_trace():
    from chipbench import trace as T
    bench = [("bench.gen", 0.0, 0.1), ("bench.execute:Q5", 0.1, 2.0),
             ("bench.decode:Q5", 2.0, 3.0)]
    idle = [(0.0, 0.5), (1.5, 2.5), (3.0, 3.25)]
    assert stages.idle_by_stage(idle, bench, []) == pytest.approx(
        T.idle_by_span(idle, bench))


def _program():
    spans = [("execute", 1.0, 4.0), ("prepare", 1.0, 1.5),
             ("bind", 1.5, 1.6), ("launch", 1.6, 1.7), ("wait", 1.7, 3.0),
             ("fetch", 3.0, 3.8), ("decode", 4.0, 4.5),
             ("decode", -2.0, -1.0)]          # warm-up, outside the window
    path = "jit(local)/vmap(rows_from_mask)/cumsum"
    ops = {"/device:TPU:0": [(path, 1.7, 2.2), (path + "/while", 1.9, 2.4),
                             ("jit(local)/vmap(exchange)/x", 2.4, 2.5),
                             ("jit(local)/vmap()/add", 2.5, 3.0)],
           "/device:TPU:1": [(path, 1.7, 2.0),
                             ("jit(local)/vmap()/add", 2.0, 3.0)]}
    kernels = {"/device:TPU:0": [("seg_aggregate", 2.5, 2.6)],
               "/device:TPU:1": [("seg_aggregate", 2.5, 2.7)]}
    return stages.Program(spans, ops, kernels, {"executable text": 6})


BENCH = [("bench.gen", 0.0, 1.0), ("bench.execute:Q9", 1.0, 4.0),
         ("bench.decode:Q9", 4.0, 5.0)]


def test_summarize():
    s = stages.summarize(_program(), BENCH)
    assert s["span_s"] == pytest.approx({
        "execute": 3.0, "prepare": 0.5, "bind": 0.1, "launch": 0.1,
        "wait": 1.3, "fetch": 0.8, "decode": 0.5})
    # chip 0: 1.7-2.4 under rows_from_mask; chip 1: 1.7-2.0
    assert s["scope_s"]["rows_from_mask"] == pytest.approx((0.7 + 0.3) / 2)
    assert s["scope_s"]["exchange"] == pytest.approx(0.1 / 2)
    assert s["scope_s"]["join_probe"] == 0.0
    assert s["scoped"]
    assert s["kernel_s"] == pytest.approx({"seg_aggregate": 0.15})
    gaps = dict(s["idle_gaps"])
    # busy (any chip) 1.7-3.0; idle 0-1.7 and 3.0-5.0
    assert gaps == pytest.approx({
        "gen": 1.0, "execute:Q9/prepare": 0.5, "execute:Q9/bind": 0.1,
        "execute:Q9/launch": 0.1, "execute:Q9/fetch": 0.8,
        "execute:Q9/execute": 0.2, "decode:Q9/decode": 0.5,
        "decode:Q9": 0.5})
    assert s["staged_idle"] == pytest.approx(1.5 / 1.7)


def test_summarize_unscoped_share():
    p = _program()
    assert stages.summarize(p, BENCH)["unscoped"] == 0.0
    p.ops["/device:TPU:1"].append(("", 3.0, 3.5))
    # op-seconds in the window: 0.5 + 0.5 + 0.1 + 0.5 + 0.3 + 1.0 + 0.5
    assert stages.summarize(p, BENCH)["unscoped"] == pytest.approx(
        0.5 / 3.4)


def test_summarize_without_program_spans_reads_none():
    p = _program()
    p.spans = []
    assert stages.summarize(p, BENCH) is None


def _ctx(program=True, counters=True, requests=4):
    c = types.SimpleNamespace(fetch_bytes=8_000_000, rows_decoded=1000,
                              exchange_bytes=2_000_000, prepares=4)
    return types.SimpleNamespace(
        window=types.SimpleNamespace(attempted=requests),
        program=stages.summarize(_program(), BENCH) if program else None,
        counters=c if counters else None)


@pytest.mark.parametrize("name,value", [
    ("prepare_ms_per_query", 1e3 * 0.5 / 4),
    ("fetch_ms_per_query", 1e3 * 0.8 / 4),
    ("fetch_mb_per_query", 8.0 / 4),
    ("compact_ms_per_query", 1e3 * 0.5 / 4),
    ("decode_us_per_row", 1e6 * 0.5 / 1000),
    ("exchange_ms_per_query", 1e3 * 0.05 / 4),
    ("exchange_mb_per_query", 2.0 / 4),
])
def test_stage_readers(name, value):
    reader = spec.metric(name)
    assert reader.read(_ctx()) == pytest.approx(value)
    # a run without the program's spans or counters has nothing to read
    assert reader.read(_ctx(program=False, counters=False)) is None
    assert reader.read(types.SimpleNamespace(
        window=types.SimpleNamespace(attempted=4))) is None


def test_scope_readers_read_none_without_scope_paths():
    ctx = _ctx()
    ctx.program["scoped"] = False
    assert spec.metric("compact_ms_per_query").read(ctx) is None
    assert spec.metric("exchange_ms_per_query").read(ctx) is None


def test_read_keeps_the_program_spans_of_a_cpu_profile(cfg, db, tmp_path):
    """The program's tracer, mirrored into a real profile: ``read``
    keeps its spans under their own names."""
    import jax

    from chipbench import traffic
    from repro.core import QueryService
    from repro.core.obs.trace import Tracer
    svc = QueryService(db, tracer=Tracer())
    q4 = [t for t in spec.traffic("scan")["templates"] if t["name"] == "Q4"]
    req = next(iter(traffic.Generator({"templates": q4}, cfg, 7)))
    svc.execute(req.text).rows()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.execute:Q4"):
            rs = svc.execute(req.text)
        with jax.profiler.TraceAnnotation("bench.decode:Q4"):
            rs.rows()
    finally:
        jax.profiler.stop_trace()
    prog = stages.read(str(tmp_path))
    names = [s[0] for s in prog.spans]
    assert names[0] == "execute"
    assert {"bind", "launch", "wait", "fetch", "decode"} <= set(names)
    assert prog.ops == {}                 # no TPU plane on the CPU
