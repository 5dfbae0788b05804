"""The build-side join index cache (DESIGN.md section 10).

Acceptance surface of the IndexCache subsystem:

* differential: the cached-index lowering agrees with the in-program-
  argsort lowering (``join_index=False``) AND the volcano oracle for
  inner/left/semi/anti joins and for *filtered* build sides (post-probe
  mask validation on declared-unique keys),
* telemetry: ``preload()`` builds PK indexes, executions hit the cache,
  hit-rate accounting mirrors CompileCache,
* identity: indexed and argsort templates never share a compile-cache
  entry; prepared templates stay ONE compile across bindings,
* the dispatch report names which joins probe the cache vs rebuild,
* safety: a false ``Field.unique`` declaration fails loudly at index
  build; undeclared filtered build sides fall back to in-program sort,
* a hypothesis property test over adversarial duplicate/absent keys.
"""
import numpy as np
import pytest

from conftest import assert_results_equal
from repro.core import CompileCache, FlareContext, col, count, sum_
from repro.core import engines as ENG
from repro.relational import queries as Q
from repro.relational.table import Table

SF = 0.005


@pytest.fixture(scope="module")
def ctx():
    c = FlareContext()
    Q.register_tpch(c, sf=SF)
    return c


def _toy_ctx(build_keys, build_mask_col=None, uniques=("k",)):
    """probe (20 rows, keys 0..9) |><| build(k, payload v)."""
    c = FlareContext()
    n = 20
    rng = np.random.default_rng(0)
    c.from_arrays("probe", {
        "pk": (np.arange(n, dtype=np.int32) % 10),
        "x": rng.uniform(0, 10, n),
    }, domains={"pk": 16})
    build = {"k": np.asarray(build_keys, np.int32),
             "v": np.arange(len(build_keys), dtype=np.float64) * 10.0}
    if build_mask_col is not None:
        build["flag"] = np.asarray(build_mask_col, np.int32)
    c.from_arrays("build", build, domains={"k": 16},
                  uniques=list(uniques))
    return c


# ---------------------------------------------------------------------------
# differential: cached index vs in-program argsort vs volcano
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_cached_index_matches_argsort_all_join_kinds(how):
    c = _toy_ctx(build_keys=[0, 1, 2, 3, 5, 7, 8, 11])
    q = (c.table("probe")
         .join(c.table("build"), on="pk", right_on="k", how=how)
         .sort("pk", "x"))
    oracle = q.collect(engine="volcano")
    warm = q.lower(engine="compiled").compile()()
    cold = q.lower(engine="compiled", join_index=False).compile()()
    assert_results_equal(oracle, warm, msg=f"{how} cached")
    assert_results_equal(oracle, cold, msg=f"{how} argsort")


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_masked_build_side_post_probe_validation(how):
    """Filtered build side with a declared-unique key: the cached index
    covers the UNFILTERED table and the probe validates the matched
    row's mask -- exact for every join kind."""
    c = _toy_ctx(build_keys=[0, 1, 2, 3, 5, 7, 8, 11],
                 build_mask_col=[1, 0, 1, 0, 1, 1, 0, 1])
    q = (c.table("probe")
         .join(c.table("build").filter(col("flag") == 1),
               on="pk", right_on="k", how=how)
         .sort("pk", "x"))
    lowered = q.lower(engine="compiled")
    rep = lowered.dispatch_report()
    assert len(rep.joins_cached) == 1, str(rep)
    got = lowered.compile()()
    assert_results_equal(q.collect(engine="volcano"), got,
                         msg=f"masked {how}")
    cold = q.lower(engine="compiled", join_index=False).compile()()
    assert_results_equal(got, cold, msg=f"masked {how} vs argsort")


def test_masked_build_without_unique_declaration_falls_back():
    """No Field.unique on the filtered build key -> the join must keep
    its in-program argsort (post-probe validation would be inexact under
    duplicates) -- and still compute correctly."""
    c = _toy_ctx(build_keys=[0, 1, 2, 3, 5, 7, 8, 11],
                 build_mask_col=[1, 0, 1, 0, 1, 1, 0, 1], uniques=())
    q = (c.table("probe")
         .join(c.table("build").filter(col("flag") == 1),
               on="pk", right_on="k")
         .agg(sum_(col("v"), "s"), count("n")))
    lowered = q.lower(engine="compiled")
    rep = lowered.dispatch_report()
    assert len(rep.joins_cached) == 0
    assert "declared-unique" in rep.joins_rebuilt[0].reason
    assert_results_equal(q.collect(engine="volcano"),
                         lowered.compile()(), msg="undeclared masked")


def test_unfiltered_duplicate_build_keys_still_cached():
    """Duplicate keys violate the N:1 contract, but with stable sorts
    cached and in-program probes resolve to the SAME first row --
    unmasked build sides stay cacheable."""
    c = _toy_ctx(build_keys=[0, 1, 2, 2, 5, 7, 8, 11], uniques=())
    q = (c.table("probe")
         .join(c.table("build"), on="pk", right_on="k")
         .sort("pk", "x"))
    lowered = q.lower(engine="compiled")
    assert len(lowered.dispatch_report().joins_cached) == 1
    assert_results_equal(
        q.lower(engine="compiled", join_index=False).compile()(),
        lowered.compile()(), msg="dup keys cached vs argsort")


def test_int64_overflow_keys_are_unindexable_not_duplicates():
    """A genuinely-unique int64 PK whose values overflow the engine's
    int32 key range is UNINDEXABLE -- never a false 'duplicate keys'
    declaration error -- and preload() skips it gracefully."""
    c = FlareContext()
    c.from_arrays("big", {
        "k": np.array([1, 2 ** 32 + 1, 3], np.int64),
        "v": np.ones(3),
    }, uniques=["k"])
    with pytest.raises(ENG.UnindexableKeyError, match="int32"):
        c.cache.get_index(c.catalog.table("big"), ("k",))
    c.preload("big")  # must not raise
    assert len(c.cache.indexes) == 0


def test_false_unique_declaration_raises_at_build():
    c = _toy_ctx(build_keys=[0, 1, 2, 2, 5, 7, 8, 11], uniques=("k",))
    q = c.table("probe").join(c.table("build"), on="pk", right_on="k") \
        .agg(count("n"))
    compiled = q.lower(engine="compiled").compile()
    with pytest.raises(ValueError, match="declared unique"):
        compiled()


# ---------------------------------------------------------------------------
# telemetry + identity
# ---------------------------------------------------------------------------


def test_preload_builds_pk_indexes():
    c = FlareContext()
    Q.register_tpch(c, sf=SF)
    assert len(c.cache.indexes) == 0
    c.preload("orders", "customer")
    # o_orderkey + c_custkey are the declared-unique keys
    assert len(c.cache.indexes) == 2
    assert c.cache.indexes.misses == 2 and c.cache.indexes.hits == 0
    c.preload("orders")  # idempotent: second preload hits
    assert c.cache.indexes.misses == 2 and c.cache.indexes.hits == 1
    c.preload("nation", indexes=False)
    assert len(c.cache.indexes) == 2


def test_index_cache_hit_rate_over_executions(ctx):
    """Acceptance: steady-state executions HIT the index cache (the
    ctx's DeviceCache telemetry) -- the build-side sort runs once, not
    per execution."""
    q = Q.join_micro(ctx, strategy="sorted")
    compiled = ctx.lower(q.plan, "compiled").compile()
    before_hits = ctx.cache.indexes.hits
    for _ in range(3):
        compiled.result()
    assert ctx.cache.indexes.hits >= before_hits + 2


def test_indexed_and_argsort_templates_distinct_cache_keys(ctx):
    k_warm = Q.q3(ctx).lower(engine="compiled").cache_key
    k_cold = Q.q3(ctx).lower(engine="compiled",
                             join_index=False).cache_key
    assert k_warm != k_cold
    assert k_warm == Q.q3(ctx).lower(engine="compiled").cache_key


def test_prepared_template_one_compile_with_index(ctx):
    """Index arrays ride as runtime arguments, so every binding of a
    prepared join template shares ONE executable."""
    cache = CompileCache()
    tmpl = Q.q14_template(ctx)
    hits = []
    for binding in Q.TEMPLATE_BINDINGS["q14"]:
        compiled = tmpl.lower(engine="compiled").compile(cache=cache)
        hits.append(compiled.stats.cache_hit)
        got = compiled(**binding)
        assert_results_equal(tmpl.collect(engine="volcano",
                                          params=binding),
                             got, msg=f"q14 {binding}")
    assert hits == [False, True, True]
    assert cache.misses == 1 and len(cache) == 1


def test_dispatch_report_names_cached_joins(ctx):
    rep = Q.q10(ctx).lower(engine="compiled").dispatch_report()
    assert len(rep.joins_cached) == 3 and not rep.joins_rebuilt
    txt = str(rep)
    assert "join index cache" in txt and "cached index" in txt
    d = rep.to_dict()
    assert len(d["joins_cached"]) == 3
    # q13's build sides: an Aggregate (no base table) -> rebuilt
    rep13 = Q.q13(ctx).lower(engine="compiled").dispatch_report()
    assert len(rep13.joins_rebuilt) == 1
    assert "not a base-table scan" in rep13.joins_rebuilt[0].reason


def test_join_free_template_has_no_report(ctx):
    assert Q.q6(ctx).lower(engine="compiled").dispatch_report() is None


# ---------------------------------------------------------------------------
# parallel engine: replicated indexes
# ---------------------------------------------------------------------------


def test_parallel_engine_replicates_build_indexes(ctx):
    q = Q.q10(ctx)
    lowered = q.lower(engine="parallel")
    rep = lowered.dispatch_report()
    assert len(rep.joins_cached) == 3
    assert_results_equal(q.collect(engine="volcano"),
                         lowered.compile()(), msg="q10 parallel indexed")


def test_parallel_engine_places_inputs_once_per_mesh(subproc):
    """Spine columns are placed row-sharded (``P(axis)``) and build
    tables/indexes replicated, once per mesh in the device cache: a
    second execution places nothing new."""
    out = subproc(4, r"""
from conftest import assert_results_equal
from repro.core import FlareContext
from repro.launch.mesh import make_data_mesh
from repro.relational import queries as Q
ctx = FlareContext()
Q.register_tpch(ctx, sf=0.005)
ctx.preload()
q = Q.q10(ctx)
for n in (4, 2):
    compiled = q.lower(engine="parallel", mesh=make_data_mesh(n)).compile()
    assert_results_equal(q.collect(engine="volcano"), compiled())
    before = len(ctx.cache)
    compiled()
    assert len(ctx.cache) == before
    placed = [(c, p, s) for c, p, s in ctx.cache.placements()
              if s.mesh.size == n]
    spine = [c for c, p, s in placed if p is not None]
    assert spine and all(c.startswith("l_") for c in spine), placed
    for c, p, s in placed:
        assert tuple(s.spec) == (("data",) if p is not None else ()), (c, s)
print("PLACED_OK")
""")
    assert "PLACED_OK" in out


# The adversarial duplicate/absent-key hypothesis property test lives in
# tests/test_property.py (test_join_index_cache_adversarial_keys), with
# the other optional-dep property tests.
