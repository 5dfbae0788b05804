"""The explicit compilation-stages API (repro.core.stages).

Covers the acceptance surface of the stages redesign:

* differential: ``Lowered -> Compiled`` paths of volcano / stage /
  compiled agree on TPC-H q1/q6 and join-heavy q3,
* prepared queries: one parameterized Q6 template compiled ONCE serves
  many bindings (``CompileStats.cache_hit`` True after the first), with
  results identical to the volcano oracle per binding,
* introspection: ``.plan()`` / ``.params()`` / ``.compiler_ir()``,
* engine registry extensibility,
* the legacy shims (``flare()``, ``collect(engine=...)``) still work.
"""
import warnings

import numpy as np
import pytest

from conftest import assert_results_equal
from repro.core import FlareContext, col, flare, param, sum_, udf
from repro.core import stages as S
from repro.relational import queries as Q

SF = 0.005

ENGINES = ["volcano", "stage", "compiled"]


@pytest.fixture(scope="module")
def ctx():
    c = FlareContext()
    Q.register_tpch(c, sf=SF)
    return c


# ---------------------------------------------------------------------------
# differential: all engines agree through Lowered -> Compiled
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qname", ["q1", "q3", "q6"])
def test_lower_compile_differential(ctx, qname):
    q = Q.QUERIES[qname](ctx)
    results = {}
    for engine in ENGINES:
        compiled = q.lower(engine=engine).compile()
        results[engine] = compiled()
    assert_results_equal(results["volcano"], results["stage"],
                         msg=f"{qname} stage")
    assert_results_equal(results["volcano"], results["compiled"],
                         msg=f"{qname} compiled")


def test_join_micro_differential(ctx):
    q = Q.join_micro(ctx)
    base = q.lower(engine="volcano").compile()()
    for engine in ("stage", "compiled"):
        got = q.lower(engine=engine).compile()()
        assert_results_equal(base, got, msg=f"join_micro {engine}")


@pytest.mark.parametrize("tname", list(Q.TEMPLATES))
def test_templates_differential(ctx, tname):
    tmpl = Q.TEMPLATES[tname](ctx)
    compiled = tmpl.lower(engine="compiled").compile()
    for binding in Q.TEMPLATE_BINDINGS[tname]:
        oracle = tmpl.collect(engine="volcano", params=binding)
        got = compiled(**binding)
        assert_results_equal(oracle, got, msg=f"{tname} {binding}")


def test_q22_template_two_phase(ctx):
    binding = Q.q22_params(ctx, "volcano")
    oracle = Q.q22(ctx).collect(engine="volcano", params=binding)
    for engine in ("stage", "compiled"):
        got = Q.q22(ctx).lower(engine=engine).compile()(**binding)
        assert_results_equal(oracle, got, msg=f"q22 {engine}")


# ---------------------------------------------------------------------------
# prepared queries: compile once, bind many
# ---------------------------------------------------------------------------


def test_q6_template_compiles_once_serves_many(ctx):
    cache = S.CompileCache()
    tmpl = Q.q6_template(ctx)
    bindings = Q.TEMPLATE_BINDINGS["q6"]
    assert len(bindings) >= 3
    hits = []
    for binding in bindings:
        compiled = tmpl.lower(engine="compiled").compile(cache=cache)
        hits.append(compiled.stats.cache_hit)
        got = compiled(**binding)
        oracle = tmpl.collect(engine="volcano", params=binding)
        assert_results_equal(oracle, got, msg=f"q6 template {binding}")
    assert hits[0] is False and all(hits[1:])  # compiled exactly once
    assert cache.misses == 1 and cache.hits == len(bindings) - 1
    assert len(cache) == 1


def test_different_literals_different_cache_keys(ctx):
    # literals are baked in -> distinct keys; params are not -> shared key
    lit_a = ctx.table("lineitem").filter(col("l_quantity") < 10.0).count
    k1 = ctx.table("lineitem").filter(
        col("l_quantity") < 10.0).lower("compiled").cache_key
    k2 = ctx.table("lineitem").filter(
        col("l_quantity") < 20.0).lower("compiled").cache_key
    k3 = ctx.table("lineitem").filter(
        col("l_quantity") < param("qty")).lower("compiled").cache_key
    k4 = ctx.table("lineitem").filter(
        col("l_quantity") < param("qty")).lower("compiled").cache_key
    assert k1 != k2
    assert k3 == k4
    assert lit_a(engine="volcano") > 0


def test_compile_stats_split(ctx):
    cache = S.CompileCache()
    lowered = Q.q6_template(ctx).lower(engine="compiled")
    compiled = lowered.compile(cache=cache)
    s = compiled.stats
    assert not s.cache_hit
    assert s.lower_s > 0 and s.compile_s > 0
    assert abs(s.trace_compile_s - (s.lower_s + s.compile_s)) < 1e-9
    compiled(**Q.q6_binding())
    assert s.run_s > 0
    again = Q.q6_template(ctx).lower(engine="compiled").compile(cache=cache)
    assert again.stats.cache_hit
    assert again.stats.trace_compile_s == 0.0


# ---------------------------------------------------------------------------
# introspection
# ---------------------------------------------------------------------------


def test_lowered_introspection(ctx):
    lowered = Q.q6_template(ctx).lower(engine="compiled")
    assert "Aggregate" in lowered.explain()
    assert [p.name for p in lowered.params()] == \
        ["date_hi", "date_lo", "disc_hi", "disc_lo", "qty_hi"]
    jaxpr = lowered.compiler_ir()          # default: jaxpr
    assert "lambda" in str(jaxpr)
    hlo = lowered.compiler_ir("stablehlo")
    assert "func" in str(hlo)


def test_stage_engine_compiler_ir_lists_stages(ctx):
    stages_ir = Q.q3(ctx).lower(engine="stage").compiler_ir()
    assert isinstance(stages_ir, list)
    assert len(stages_ir) >= 2  # q3: joins/aggregate/sort break pipelines
    assert any("Join" in s for s in stages_ir)


def test_volcano_compiler_ir_is_plan_text(ctx):
    ir = Q.q6(ctx).lower(engine="volcano").compiler_ir()
    assert "Filter" in ir or "Scan" in ir


# ---------------------------------------------------------------------------
# binding validation
# ---------------------------------------------------------------------------


def test_missing_binding_raises(ctx):
    compiled = Q.q6_template(ctx).lower(engine="compiled").compile()
    with pytest.raises(KeyError, match="date_hi"):
        compiled(date_lo=0)


def test_unknown_binding_raises(ctx):
    compiled = Q.q6(ctx).lower(engine="compiled").compile()
    with pytest.raises(TypeError, match="nope"):
        compiled(nope=1)


def test_string_param_rejected():
    with pytest.raises(TypeError, match="numeric"):
        param("bad", "string")


def test_unknown_engine_lists_available(ctx):
    with pytest.raises(ValueError, match="volcano"):
        Q.q6(ctx).lower(engine="warp-drive")


# ---------------------------------------------------------------------------
# composition: staged UDFs take params as traced scalars
# ---------------------------------------------------------------------------


def test_udf_composes_with_params(ctx):
    @udf("float64")
    def scaled(price, gain):
        return price * gain

    q = (ctx.table("lineitem")
         .select(("v", scaled(col("l_extendedprice"),
                              param("gain", "float64"))))
         .agg(sum_(col("v"), "total")))
    compiled = q.lower(engine="compiled").compile()
    for gain in (0.5, 2.0):
        oracle = q.collect(engine="volcano", params={"gain": gain})
        got = compiled(gain=gain)
        assert_results_equal(oracle, got, msg=f"udf gain={gain}")


# ---------------------------------------------------------------------------
# engine registry extensibility
# ---------------------------------------------------------------------------


def test_register_custom_engine(ctx):
    class EchoVolcano:
        """A user back-end: delegates to the volcano adapter."""

        name = "echo-volcano"
        _inner = S.get_engine("volcano")

        def lower(self, p, catalog, param_specs):
            return self._inner.lower(p, catalog, param_specs)

        def compiler_ir(self, artifact, dialect=None):
            return self._inner.compiler_ir(artifact, dialect)

        def compile(self, artifact):
            return self._inner.compile(artifact)

    try:
        S.register_engine(EchoVolcano())
        assert "echo-volcano" in S.available_engines()
        got = Q.q6(ctx).lower(engine="echo-volcano").compile()()
        assert_results_equal(Q.q6(ctx).collect(engine="volcano"), got,
                             msg="custom engine")
    finally:
        S.ENGINES.pop("echo-volcano", None)


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------


def test_flare_shim_delegates(ctx):
    q = Q.q6(ctx)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        with pytest.raises(DeprecationWarning):
            flare(q)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        fd = flare(q)
    got = fd.collect()
    assert fd.stats.engine == "compiled"
    assert_results_equal(q.collect(engine="volcano"), got, msg="flare shim")


def test_collect_engine_shim(ctx):
    q = Q.q1(ctx)
    assert_results_equal(q.collect(engine="volcano"),
                         q.collect(engine="compiled"), msg="collect shim")
    s1, s2 = Q.q6(ctx), Q.q6(ctx)
    import repro.core.engines as ENG
    st1, st2 = ENG.CompileStats(), ENG.CompileStats()
    ctx.execute(s1.plan, "compiled", st1)
    ctx.execute(s2.plan, "compiled", st2)
    assert st2.cache_hit  # context compile cache survives across calls


def test_grouped_sum_stays_exact_over_long_groups():
    """Millions of f32 rows in one group: the compiled grouped sum keeps
    the float64 answer (a plain scatter-add drifts with group size)."""
    from repro.core import ml as ML
    from repro.relational.table import Table
    rng = np.random.default_rng(5)
    n = 4_000_000
    k = rng.integers(0, 2, n).astype(np.int32)
    v = rng.integers(1, 51, n).astype(np.float32)
    c = FlareContext()
    c.register("t", Table.from_arrays({"k": k, "v": v},
                                      domains={"k": 2}))
    got = (c.table("t").group_by("k").agg(sum_(col("v"), "s"))
           .lower(engine="compiled").compile()())
    want = np.bincount(k, weights=v.astype(np.float64), minlength=2)
    np.testing.assert_allclose(got["s"], want, rtol=1e-6)
    direct = ML.segment_sum(v, k, 2)
    np.testing.assert_allclose(np.asarray(direct), want, rtol=1e-6)
