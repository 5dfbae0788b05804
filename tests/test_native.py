"""Native kernel dispatch (repro.native): patterns, fallback, caching.

The acceptance surface of the dispatch subsystem:

* differential: ``native=True`` agrees with the volcano oracle AND the
  plain compiled engine over the TPC-H suite (Pallas interpret mode on
  this CPU container -- the ops pick the mode from the backend),
* dispatch report: q6 fires the filter+aggregate pattern, a q1-shaped
  grouped aggregate fires the segmented-reduce pattern, unsupported
  fragments fall back with a recorded reason,
* prepared queries: the native q6 template compiles ONCE and serves
  every ``param()`` binding (params ride as scalar-prefetch arguments,
  never baked into the kernel),
* the ``compiled-native`` registry alias and the kernel-level
  generalized entry points.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from conftest import assert_results_equal
from repro.core import CompileCache, FlareContext, col, count, sum_, min_
from repro.core import stages as S
from repro.native import registry as NR
from repro.relational import queries as Q

SF = 0.005


@pytest.fixture(scope="module")
def ctx():
    c = FlareContext()
    Q.register_tpch(c, sf=SF)
    return c


# ---------------------------------------------------------------------------
# differential: native vs volcano vs compiled over the TPC-H suite
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qname", list(Q.QUERIES))
def test_native_differential(ctx, qname):
    q = Q.QUERIES[qname](ctx)
    oracle = q.collect(engine="volcano")
    plain = q.lower(engine="compiled").compile()()
    native = q.lower(engine="compiled", native=True).compile()()
    assert_results_equal(oracle, plain, msg=f"{qname} compiled")
    assert_results_equal(oracle, native, msg=f"{qname} native")


@pytest.mark.parametrize("tname", list(Q.TEMPLATES))
def test_native_templates_differential(ctx, tname):
    tmpl = Q.TEMPLATES[tname](ctx)
    compiled = tmpl.lower(engine="compiled", native=True).compile()
    for binding in Q.TEMPLATE_BINDINGS[tname]:
        oracle = tmpl.collect(engine="volcano", params=binding)
        got = compiled(**binding)
        assert_results_equal(oracle, got, msg=f"{tname} {binding}")


def test_q22_native_two_phase(ctx):
    binding = Q.q22_params(ctx, "volcano")
    oracle = Q.q22(ctx).collect(engine="volcano", params=binding)
    got = Q.q22(ctx).lower(engine="compiled", native=True)\
        .compile()(**binding)
    assert_results_equal(oracle, got, msg="q22 native")


# ---------------------------------------------------------------------------
# dispatch report: what fired, what fell back, and why
# ---------------------------------------------------------------------------


def test_q6_dispatches_filter_agg_pattern(ctx):
    lowered = Q.q6(ctx).lower(engine="compiled", native=True)
    rep = lowered.dispatch_report()
    assert rep is not None
    assert rep.fired_patterns() == ["filter-scalar-agg"]
    assert not rep.fallbacks
    # the annotation is visible in the physical plan
    assert "NativeKernel[filter-scalar-agg" in lowered.explain()
    # and the report rides on CompileStats
    compiled = lowered.compile()
    assert compiled.stats.dispatch is rep


def test_q1_dispatches_grouped_pattern(ctx):
    """q1-shaped grouped aggregate -> the segmented_reduce pattern."""
    lowered = Q.q1(ctx).lower(engine="compiled", native=True)
    rep = lowered.dispatch_report()
    assert rep.fired_patterns() == ["grouped-agg"]
    got = lowered.compile()()
    assert_results_equal(Q.q1(ctx).collect(engine="volcano"), got,
                         msg="q1 grouped native")


def test_masked_pattern_fires_post_join(ctx):
    """A fragment downstream of a non-inner join (masked boundary
    stream, no fusable probe) streams the mask into the kernel as a
    weight column -- q4's semi join."""
    lowered = Q.q4(ctx).lower(engine="compiled", native=True)
    assert lowered.dispatch_report().fired_patterns() == \
        ["masked-filter-project"]


def test_join_probe_fires_on_indexed_inner_joins(ctx):
    """Inner joins whose build side is served by the cached index fuse
    probe + gather + residual predicate + aggregate into the join-probe
    kernel: q14/q19 keyless, q5 grouped, q10 grouped with any_
    carry-alongs, q3 grouped beyond the one-hot domain (scatter)."""
    for qname in ("q14", "q19", "q5", "q10", "q3"):
        lowered = Q.QUERIES[qname](ctx).lower(engine="compiled",
                                              native=True)
        rep = lowered.dispatch_report()
        assert rep.fired_patterns() == ["join-probe"], (qname, str(rep))
        assert not rep.fallbacks, (qname, str(rep))
        # every join of the fragment chain probes the cached index
        assert rep.joins_cached and not rep.joins_rebuilt, str(rep)


def test_grouped_any_carry_along_dispatches(ctx):
    """The FD any_ carry-along (q3/q10's blocker before the join-probe
    pattern) accumulates as a masked per-group max: exercise it on the
    grouped one-hot path via a small-domain group key."""
    from repro.core.dataframe import any_
    q = (ctx.table("orders")
         .group_by("o_orderpriority")
         .agg(count("n"), any_(col("o_shippriority"), "ship")))
    lowered = q.lower(engine="compiled", native=True)
    assert lowered.dispatch_report().fired_patterns() == ["grouped-agg"]
    assert_results_equal(q.collect(engine="volcano"),
                         lowered.compile()(), msg="grouped any_")


def test_fallback_reason_reported(ctx):
    # min/max are not in the streaming-sum kernels' op set -> fallback,
    # with the reason in the report; results still correct via jnp
    q = (ctx.table("lineitem")
         .filter(col("l_quantity") < 10.0)
         .agg(min_(col("l_extendedprice"), "cheapest")))
    lowered = q.lower(engine="compiled", native=True)
    rep = lowered.dispatch_report()
    assert not rep.fired
    assert len(rep.fallbacks) == 1
    assert "unsupported aggregate op" in rep.fallbacks[0].reason
    assert_results_equal(q.collect(engine="volcano"),
                         lowered.compile()(), msg="min fallback")


def test_cast_bool_predicate_matches_engines():
    """astype(bool) is `!= 0`, not the 0/1-column `> 0.5` coercion --
    a float in (0, 0.5] must still pass a cast-to-bool filter."""
    from repro.core import cast
    from repro.relational.table import Table
    c2 = FlareContext()
    f = np.linspace(0.0, 1.0, 300)
    c2.register("t", Table.from_arrays(
        {"f": f, "price": np.ones(300)}))
    q = (c2.table("t").filter(cast(col("f"), "bool"))
         .agg(sum_(col("price"), "s")))
    lowered = q.lower(engine="compiled", native=True)
    assert lowered.dispatch_report().fired_patterns() == \
        ["filter-scalar-agg"]
    assert_results_equal(q.collect(engine="volcano"),
                         lowered.compile()(), msg="cast-bool pred")


def test_group_domain_fallback_reason(ctx):
    # l_orderkey's dense domain exceeds MAX_GROUPS at any sf -> the
    # grouped pattern must refuse (one-hot tile would blow VMEM)
    q = (ctx.table("lineitem").group_by("l_orderkey")
         .agg(count("n")))
    rep = q.lower(engine="compiled", native=True).dispatch_report()
    assert not rep.fired
    assert "MAX_GROUPS" in rep.fallbacks[0].reason


def test_report_str_and_dict(ctx):
    rep = Q.q6(ctx).lower(engine="compiled", native=True).dispatch_report()
    txt = str(rep)
    assert "filter-scalar-agg" in txt
    d = rep.to_dict()
    assert d["fired"][0]["pattern"] == "filter-scalar-agg"
    assert d["fired"][0]["mode"] in ("interpret", "pallas")


# ---------------------------------------------------------------------------
# prepared queries: one native compilation serves every binding
# ---------------------------------------------------------------------------


def test_native_q6_template_compiles_once(ctx):
    """Acceptance: prepared q6 with two param() bindings is served from
    ONE cached native compilation (params are scalar-prefetch runtime
    arguments, not baked into the kernel)."""
    cache = CompileCache()
    tmpl = Q.q6_template(ctx)
    bindings = Q.TEMPLATE_BINDINGS["q6"][:2]
    hits = []
    for binding in bindings:
        lowered = tmpl.lower(engine="compiled", native=True)
        assert lowered.dispatch_report().fired_patterns() == \
            ["filter-scalar-agg"]
        compiled = lowered.compile(cache=cache)
        hits.append(compiled.stats.cache_hit)
        got = compiled(**binding)
        oracle = tmpl.collect(engine="volcano", params=binding)
        assert_results_equal(oracle, got, msg=f"native q6 {binding}")
    assert hits == [False, True]
    assert cache.misses == 1 and cache.hits == 1 and len(cache) == 1


def test_native_and_plain_compiled_have_distinct_cache_keys(ctx):
    k_plain = Q.q6(ctx).lower(engine="compiled").cache_key
    k_native = Q.q6(ctx).lower(engine="compiled", native=True).cache_key
    assert k_plain != k_native


def test_native_requires_compiled_engine(ctx):
    with pytest.raises(ValueError, match="compiled"):
        Q.q6(ctx).lower(engine="volcano", native=True)


# ---------------------------------------------------------------------------
# the registry alias + registry surface
# ---------------------------------------------------------------------------


def test_compiled_native_alias_registered(ctx):
    assert "compiled-native" in S.available_engines()
    got = Q.q6(ctx).lower(engine="compiled-native").compile()()
    assert_results_equal(Q.q6(ctx).collect(engine="volcano"), got,
                         msg="alias engine")


def test_builtin_patterns_registered():
    names = NR.available_patterns()
    for expected in ("filter-scalar-agg", "grouped-agg", "join-probe",
                     "masked-filter-project"):
        assert expected in names
    # join-probe outranks masked-filter-project (more fusion)
    assert names.index("join-probe") < names.index("masked-filter-project")


def test_vmem_budget_is_respected():
    # the resident G=512 accumulator plus the live value blocks force
    # block_rows below a large default
    br = NR.choose_block_rows(4, 8, num_groups=512, default=1024)
    assert br is not None and br < 1024
    assert NR.vmem_estimate(4, br, 8, 512) <= NR.VMEM_BUDGET_BYTES
    assert NR.vmem_estimate(4, br * 2, 8, 512) > NR.VMEM_BUDGET_BYTES


# ---------------------------------------------------------------------------
# generalized kernel entry points (direct, interpret mode)
# ---------------------------------------------------------------------------


def test_filter_agg_general_matches_ref():
    from repro.kernels.filter_agg import kernel as FA_K
    rng = np.random.default_rng(0)
    n = 1000
    x = rng.uniform(0, 10, n).astype(np.float32)
    y = rng.uniform(0, 10, n).astype(np.float32)

    def value_fn(scal_ref, blocks):
        xb, yb, valid = blocks
        pred = (valid > 0.5) & (xb >= scal_ref[0]) & (xb < scal_ref[1])
        w = pred.astype(jnp.float32)
        return [xb * yb * w, w]

    block_rows = max(1, n // 128)
    per = block_rows * 128
    padded = (n + per - 1) // per * per

    def pad(a, fill):
        return jnp.pad(jnp.asarray(a), (0, padded - n),
                       constant_values=fill).reshape(-1, 128)

    blocks = [pad(x, 0.0), pad(y, 0.0), pad(np.ones(n, np.float32), 0.0)]
    scal = jnp.asarray([2.0, 7.0], jnp.float32)
    outs = FA_K.filter_agg_general(value_fn, blocks, scal, 2, block_rows,
                                   interpret=True)
    pred = (x >= 2.0) & (x < 7.0)
    np.testing.assert_allclose(float(jnp.sum(outs[0])),
                               float((x * y)[pred].sum()), rtol=1e-4)
    assert float(jnp.sum(outs[1])) == pred.sum()


def test_join_probe_kernel_matches_ref():
    from repro.kernels.join_probe.ops import probe_join_sum
    from repro.kernels.join_probe.ref import probe_join_sum_ref
    rng = np.random.default_rng(2)
    n, b = 4000, 600
    bk = rng.permutation(b).astype(np.int32)
    pk = rng.integers(0, 2 * b, n).astype(np.int32)  # half the keys miss
    pv = rng.uniform(0, 10, n).astype(np.float32)
    mask = rng.random(b) < 0.6
    for bm in (None, mask):
        s, c = probe_join_sum(pk, pv, bk, build_mask=bm, interpret=True)
        rs, rc = probe_join_sum_ref(pk, pv, bk, build_mask=bm)
        np.testing.assert_allclose(float(s), rs, rtol=1e-4)
        assert int(c) == rc


def test_segmented_multi_sum_max_slots_match_ref():
    """ops=("sum","max",...): any_ slots accumulate as per-group masked
    max sharing the one-hot tile."""
    from repro.kernels.segmented_reduce import kernel as SR_K
    rng = np.random.default_rng(3)
    n, g = 3000, 9
    c = rng.integers(0, g, n).astype(np.int32)
    v = (c * 7).astype(np.float32)  # FD: constant within each group
    w = rng.uniform(-5, 5, n).astype(np.float32)
    fill = float(np.iinfo(np.int32).min)

    def value_fn(scal_ref, blocks, code_block):
        wb, vb, valid = blocks
        ok = valid > 0.5
        return [jnp.where(ok, wb, 0.0),
                jnp.where(ok, vb, jnp.float32(fill)),
                ok.astype(jnp.float32)]

    block_rows = 8
    per = block_rows * 128
    padded = (n + per - 1) // per * per

    def pad(a, fill_):
        return jnp.pad(jnp.asarray(a), (0, padded - n),
                       constant_values=fill_).reshape(-1, 128)

    out = SR_K.segmented_multi_sum(
        value_fn, [pad(w, 0.0), pad(v, fill), pad(np.ones(n, np.float32),
                                                  0.0)],
        pad(c, 0), jnp.zeros((1,), jnp.float32), 3, g, block_rows,
        True, ops=("sum", "max", "sum"), fills=(0.0, fill, 0.0))
    for grp in range(g):
        sel = c == grp
        np.testing.assert_allclose(float(out[0, grp]), w[sel].sum(),
                                   rtol=1e-3, atol=1e-3)
        assert float(out[1, grp]) == grp * 7  # the carried-along value
        assert float(out[2, grp]) == sel.sum()


def test_segmented_multi_sum_matches_ref():
    from repro.kernels.segmented_reduce import kernel as SR_K
    rng = np.random.default_rng(1)
    n, g = 5000, 7
    v = rng.uniform(-5, 5, n).astype(np.float32)
    c = rng.integers(0, g, n).astype(np.int32)

    def value_fn(scal_ref, blocks, code_block):
        vb, valid = blocks
        w = (valid > 0.5).astype(jnp.float32)
        return [vb * w, w]

    block_rows = 8
    per = block_rows * 128
    padded = (n + per - 1) // per * per

    def pad(a, fill):
        return jnp.pad(jnp.asarray(a), (0, padded - n),
                       constant_values=fill).reshape(-1, 128)

    out = SR_K.segmented_multi_sum(
        value_fn, [pad(v, 0.0), pad(np.ones(n, np.float32), 0.0)],
        pad(c, 0), jnp.zeros((1,), jnp.float32), 2, g, block_rows,
        interpret=True)
    for grp in range(g):
        sel = c == grp
        np.testing.assert_allclose(float(out[0, grp]), v[sel].sum(),
                                   rtol=1e-3, atol=1e-3)
        assert float(out[1, grp]) == sel.sum()
