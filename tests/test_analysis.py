"""Static plan verifier suites: schema inference, capacity flow,
rewrite soundness, the parameter-type check, error diagnostics, and
the tracing-hazard linter.  Host-only (no device execution beyond the
service's table build)."""
import dataclasses
import pathlib

import pytest

from repro.core import algebra as A
from repro.core import executor, queries, service
from repro.core.analysis import capflow, lint
from repro.core.analysis.check import (check_rewrite, output_signature,
                                       verify_plan)
from repro.core.analysis.schema import ColType, infer_schema
from repro.core.errors import (ParseError, PlanTypeError, QueryError,
                               RewriteSoundnessError, TranslateError)
from repro.core.prepared import prepare_plan
from repro.core.rewrite import optimize
from repro.core.rewrite.engine import run_rules, set_soundness_checks
from repro.core.translator import translate
from repro.core.xqparser import parse

pytestmark = pytest.mark.analysis

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def svc(weather_db_small):
    return service.QueryService(weather_db_small)


# -- positive: the whole paper suite verifies --------------------------------


@pytest.mark.parametrize("name", sorted(queries.ALL))
def test_all_queries_verify_at_prepare(svc, name):
    pq = svc.prepare(queries.ALL[name])
    s = verify_plan(pq.plan, db=svc.db)
    assert isinstance(pq.plan, A.DistributeResult)
    for v in pq.plan.vars:
        assert v in s


@pytest.mark.parametrize("name", sorted(queries.ALL))
def test_logical_inference_covers_raw_plans(weather_db_small, name):
    raw = translate(queries.ALL[name])
    s = infer_schema(raw, db=weather_db_small, mode="logical")
    assert s, "raw plan must produce result columns"


def test_schema_types_are_meaningful(svc):
    pq = svc.prepare(queries.ALL["Q9"])
    s = verify_plan(pq.plan, db=svc.db)
    kinds = sorted(s[v].kind for v in pq.plan.vars)
    # group key (sid) + count + avg
    assert kinds == ["num", "num", "str"]


def test_coltype_rendering():
    t = ColType("node", "/sensors", nullable=True, seq=True)
    assert str(t) == "node[/sensors]*?"
    assert str(t.item()) == "node[/sensors]?"


# -- negative: ill-typed query texts rejected at prepare ---------------------

ILL_TYPED = {
    "sid_vs_num": (
        'for $r in collection("/sensors")/dataCollection/data\n'
        'where string(data($r/station)) gt 5\n'
        'return $r/value',
        "string sid with a num"),
    "date_vs_num": (
        'for $r in collection("/sensors")/dataCollection/data\n'
        'where dateTime(data($r/date)) gt 5\n'
        'return $r',
        "packed date with a num"),
    "sum_over_string": (
        'sum(\n'
        ' for $r in collection("/sensors")/dataCollection/data\n'
        ' where $r/dataType eq "PRCP"\n'
        ' return string(data($r/station))\n'
        ') div 10',
        "SUM() over a str"),
    "groupby_sum_string": (
        'for $r in collection("/sensors")/dataCollection/data\n'
        'group by $st := $r/station\n'
        'return ($st, sum(string(data($r/dataType))))',
        "SUM() over a str"),
}


@pytest.mark.parametrize("name", sorted(ILL_TYPED))
def test_ill_typed_query_rejected(svc, name):
    text, expected = ILL_TYPED[name]
    with pytest.raises(PlanTypeError) as ei:
        svc.prepare(text)
    assert expected in ei.value.message
    # diagnostics carry an operator path into the plan
    assert ei.value.path


def test_diagnostic_renders_operator_path(svc):
    with pytest.raises(PlanTypeError) as ei:
        svc.prepare(ILL_TYPED["sid_vs_num"][0])
    rendered = str(ei.value)
    assert "SELECT" in rendered or "ASSIGN" in rendered


# -- negative: hand-built plan violations ------------------------------------


def _optimized(name):
    return optimize(translate(queries.ALL[name]))


def test_order_by_missing_column_rejected(weather_db_small):
    dr = _optimized("Q9")
    bad = dr.replace(
        child=A.OrderBy(((A.Var(9999), True),), dr.child))
    with pytest.raises(PlanTypeError) as ei:
        verify_plan(bad, db=weather_db_small)
    assert "undefined column $$9999" in ei.value.message


def test_having_unshared_slot_rejected(weather_db_small):
    dr = _optimized("Q9")
    pred = A.Call("boolean", (A.Call("value-ge", (
        A.Var(9999), A.Const(100.0, "double"))),))
    bad = dr.replace(child=A.Select(pred, dr.child))
    with pytest.raises(PlanTypeError) as ei:
        verify_plan(bad, db=weather_db_small)
    assert "undefined column $$9999" in ei.value.message


def test_result_column_never_produced_rejected(weather_db_small):
    dr = _optimized("Q1")
    bad = dr.replace(vars=dr.vars + (9999,))
    with pytest.raises(PlanTypeError) as ei:
        verify_plan(bad, db=weather_db_small)
    assert "never produced" in ei.value.message


# -- parameter-type verification ---------------------------------------------


def _swap_param_type(e, typ):
    if isinstance(e, A.Param):
        return A.Param(e.idx, typ)
    if isinstance(e, A.Call):
        return A.Call(e.fn, tuple(_swap_param_type(a, typ)
                                  for a in e.args))
    if isinstance(e, A.Some):
        return A.Some(e.var, _swap_param_type(e.source, typ),
                      _swap_param_type(e.cond, typ))
    return e


def test_param_misuse_rejected_by_prepare_plan(svc):
    # Q2 compares decimal(value) against a lifted num parameter; an
    # externally built erased plan declaring that slot "str" smuggles
    # a sid into an f32 comparison — prepare_plan must reject it
    pq = svc.prepare(queries.ALL["Q2"])
    specs = {s.typ for s in pq.specs}
    assert "num" in specs

    def bad_op(op):
        if isinstance(op, A.Select):
            return op.replace(expr=_swap_param_type(op.expr, "str"))
        return op
    from repro.core.algebra import transform_bottom_up
    bad = transform_bottom_up(pq.plan, bad_op)
    with pytest.raises(PlanTypeError):
        prepare_plan(bad)


# -- rewrite soundness --------------------------------------------------------


def drop_order_by(op, ctx):
    """Intentionally unsound: discards the sort under a LIMIT —
    capacity-set shrink (topk_cap site vanishes)."""
    if isinstance(op, A.Limit) and isinstance(op.child, A.OrderBy):
        return A.Limit(op.k, op.child.child)
    return None


def drop_group_by(op, ctx):
    """Intentionally unsound: unwraps GROUP-BY — the result columns
    it defined are gone, the after-plan is ill-formed."""
    if isinstance(op, A.GroupBy):
        return op.child
    return None


def test_unsound_capacity_dropping_rule_caught():
    plan = _optimized("Q11")
    prev = set_soundness_checks(True)
    try:
        with pytest.raises(RewriteSoundnessError) as ei:
            run_rules(plan, [drop_order_by])
    finally:
        set_soundness_checks(prev)
    assert "drop_order_by" in ei.value.message
    assert "topk_cap" in ei.value.message


def test_unsound_schema_breaking_rule_caught():
    plan = _optimized("Q9")
    prev = set_soundness_checks(True)
    try:
        with pytest.raises(RewriteSoundnessError) as ei:
            run_rules(plan, [drop_group_by])
    finally:
        set_soundness_checks(prev)
    assert "drop_group_by" in ei.value.message
    assert "ill-formed" in ei.value.message


def test_existing_rules_are_sound_on_a_representative():
    prev = set_soundness_checks(True)
    try:
        for name in ("Q1", "Q5", "Q9", "Q11"):
            optimize(translate(queries.ALL[name]))
    finally:
        set_soundness_checks(prev)


def test_check_rewrite_passes_identity():
    plan = _optimized("Q9")
    check_rewrite(plan, plan, "identity")
    assert output_signature(plan) == output_signature(plan)


# -- capacity flow ------------------------------------------------------------

EXPECTED_CAPS = {
    "Q1": {"scan_cap"},
    "Q5": {"scan_cap", "join_bucket", "join_cap"},
    "Q9": {"scan_cap", "group_cap"},
    "Q11": {"scan_cap", "group_cap", "topk_cap"},
}


@pytest.mark.parametrize("name", sorted(EXPECTED_CAPS))
def test_capflow_derives_expected_caps(weather_db_small, name):
    flow = capflow.analyze(_optimized(name), db=weather_db_small)
    assert flow.caps == frozenset(EXPECTED_CAPS[name])
    capflow.check_registry(flow)


@pytest.mark.parametrize("name", sorted(EXPECTED_CAPS))
def test_presizing_covers_static_bounds(svc, name):
    pq = svc.prepare(queries.ALL[name])
    cfg = svc._presized_config(pq.plan)
    assert capflow.cross_validate(pq.plan, svc.db, cfg) == []


def test_cross_validate_flags_undersized_cap(svc):
    pq = svc.prepare(queries.ALL["Q1"])
    tiny = dataclasses.replace(svc.base_config, scan_cap=1)
    problems = capflow.cross_validate(pq.plan, svc.db, tiny)
    assert problems and "scan_cap=1" in problems[0]


def test_registry_completeness():
    # analysis-side cap->flag map literally equals the executor's
    assert capflow.registry_coverage() == executor.OVERFLOW_FLAGS


@pytest.fixture(scope="module")
def kernel_and_plain(weather_db_small):
    """Services that differ only in the kernel knobs."""
    return tuple(
        service.QueryService(
            weather_db_small,
            executor.ExecConfig(use_pallas_segments=on,
                                use_pallas_join=on))
        for on in (True, False))


@pytest.mark.parametrize("name", list(queries.ALL))
def test_capflow_invariant_under_kernel_policy(kernel_and_plain,
                                              weather_db_small, name):
    """The kernel knobs pick an implementation (Pallas kernel vs jnp
    twin), never capacity semantics: the kernel-path and jnp-path
    compilations derive the identical capacity-site set — same caps,
    same flags, same operator paths, same static bounds. The fused
    kernels read the same resolved caps and raise the same
    OVERFLOW_FLAGS entries, so regrowth ladders are path-independent."""
    kern, plain = kernel_and_plain
    fk = capflow.analyze(kern.prepare(queries.ALL[name]).plan,
                         db=weather_db_small)
    fj = capflow.analyze(plain.prepare(queries.ALL[name]).plan,
                         db=weather_db_small)
    assert fk.sites == fj.sites, name
    assert fk.caps and fk.flags, name


def test_overflow_flags_are_config_fields():
    """Every OVERFLOW_FLAGS cap is an ExecConfig field, and the cache
    key covers every field."""
    fields = {f.name for f in dataclasses.fields(executor.ExecConfig)}
    for cap in executor.OVERFLOW_FLAGS:
        assert cap in fields
    # signature() is derived from dataclasses.fields — adding a knob
    # without extending it is impossible by construction
    cfg = executor.ExecConfig()
    assert len(cfg.signature()) == len(fields)
    assert cfg.cap_key() == cfg.signature()


# -- error hierarchy & diagnostics -------------------------------------------


def test_parse_error_position_and_caret():
    with pytest.raises(ParseError) as ei:
        parse("for $r in")
    e = ei.value
    assert isinstance(e, SyntaxError)
    assert e.pos >= 0
    rendered = str(e.with_text("for $r in"))
    assert "line 1" in rendered and "^" in rendered


def test_translate_error_unbound_variable():
    q = ('for $r in collection("/sensors")/dataCollection/data\n'
         'return $q')
    with pytest.raises(TranslateError) as ei:
        translate(q)
    e = ei.value
    assert isinstance(e, ValueError)
    assert "unbound variable $q" in e.message
    assert e.pos >= 0
    assert "line 2" in str(e)


def test_query_errors_share_base():
    for exc in (ParseError, TranslateError, PlanTypeError,
                RewriteSoundnessError):
        assert issubclass(exc, QueryError)


# -- linter -------------------------------------------------------------------

TRACED_PATH = "repro/kernels/example.py"


def _codes(findings):
    return [f.code for f in findings]


def test_lint_host_cast_on_traced_value():
    src = ("def f(x):\n"
           "    return float(jnp.sum(x))\n")
    assert _codes(lint.lint_source(src, TRACED_PATH)) == ["TRACE001"]


def test_lint_item_in_traced_scope():
    src = ("def f(x):\n"
           "    return x.item()\n")
    assert _codes(lint.lint_source(src, TRACED_PATH)) == ["TRACE002"]


def test_lint_control_flow_on_traced_value():
    src = ("def f(x):\n"
           "    if jnp.any(x > 0):\n"
           "        return x\n"
           "    while lax.lt(x, 3):\n"
           "        x = x + 1\n")
    assert _codes(lint.lint_source(src, TRACED_PATH)) == [
        "TRACE003", "TRACE003"]


def test_lint_dtype_compare_is_clean():
    # attribute constants are trace-time: must NOT fire TRACE003
    src = ("def f(x):\n"
           "    if x.dtype == jnp.bool_:\n"
           "        return x\n")
    assert lint.lint_source(src, TRACED_PATH) == []


def test_lint_host_scope_is_exempt():
    # same cast outside a traced scope: result materialization
    src = ("def rows(x):\n"
           "    return float(jnp.sum(x))\n")
    assert lint.lint_source(src, "repro/core/service.py") == []


def test_lint_wall_clock_in_core():
    src = "t = time.perf_counter()\n"
    assert _codes(lint.lint_source(
        src, "repro/core/serving/x.py")) == ["DET001"]
    # and not outside core/
    assert lint.lint_source(src, "repro/data/weather.py") == []


def test_lint_unseeded_rng_in_core():
    bad = "x = np.random.rand(3)\n"
    good = "rng = np.random.default_rng(0)\n"
    assert _codes(lint.lint_source(
        bad, "repro/core/workload.py")) == ["DET002"]
    assert lint.lint_source(good, "repro/core/workload.py") == []


def test_lint_waiver_suppresses():
    src = "t = time.perf_counter()  # lint: allow(DET001)\n"
    assert lint.lint_source(src, "repro/core/x.py") == []
    prev = ("# lint: allow(DET001)\n"
            "t = time.perf_counter()\n")
    assert lint.lint_source(prev, "repro/core/x.py") == []
    other = "t = time.perf_counter()  # lint: allow(TRACE001)\n"
    assert _codes(lint.lint_source(
        other, "repro/core/x.py")) == ["DET001"]


def test_lint_repo_is_clean():
    findings = lint.lint_paths([str(ROOT / "src" / "repro")])
    findings += lint.lint_registry(str(ROOT / "src"))
    findings += lint.lint_kernel_registry(str(ROOT / "src"))
    assert findings == [], "\n".join(str(f) for f in findings)


def test_lint_registry_catches_orphan_flag(tmp_path):
    # a registry entry whose flag is never noted / never regrown
    (tmp_path / "repro" / "core").mkdir(parents=True)
    (tmp_path / "repro" / "core" / "executor.py").write_text(
        "class ExecConfig:\n"
        "    scan_cap: int = 0\n"
        'OVERFLOW_FLAGS: dict = {"scan_cap": "overflow_scan"}\n')
    (tmp_path / "repro" / "core" / "service.py").write_text("x = 1\n")
    codes = _codes(lint.lint_registry(str(tmp_path)))
    assert "CAP002" in codes       # flag never ctx.note()d
    assert "CAP003" in codes       # no regrowth rung
    assert "CAP004" in codes       # never presized


def test_lint_kernel_registry_catches_unreferenced_kernel(tmp_path):
    # an unregistered pallas entry point, a registry value naming no
    # ref.py function, and a stale key all flag as KRN001
    kdir = tmp_path / "repro" / "kernels"
    kdir.mkdir(parents=True)
    (kdir / "mykern.py").write_text(
        "def my_kernel(x):\n"
        "    return pl.pallas_call(lambda r: r)(x)\n"
        "def registered_kernel(x):\n"
        "    return pl.pallas_call(lambda r: r)(x)\n"
        "def bad_ref_kernel(x):\n"
        "    return pl.pallas_call(lambda r: r)(x)\n"
        "def helper(x):\n"
        "    return x\n")
    (kdir / "ref.py").write_text(
        "def registered_ref(x):\n"
        "    return x\n")
    (kdir / "registry.py").write_text(
        'KERNEL_REFS: dict = {\n'
        '    "mykern.registered_kernel": "registered_ref",\n'
        '    "mykern.gone_kernel": "registered_ref",\n'
        '    "mykern.bad_ref_kernel": "no_such_ref",\n'
        '}\n')
    msgs = [f.message for f in
            lint.lint_kernel_registry(str(tmp_path))]
    assert any("mykern.my_kernel" in m and "no jnp reference" in m
               for m in msgs)
    assert any("mykern.gone_kernel" in m and "stale" in m
               for m in msgs)
    assert any("no_such_ref" in m for m in msgs)
    # helper has no pallas_call and registered_kernel is declared —
    # neither flags
    assert not any("helper" in m or "registered_kernel" in m
                   for m in msgs)
