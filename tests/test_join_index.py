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
* a hypothesis property test over adversarial duplicate/absent keys,
* the slot-table probe of a dense key domain: equal to the binary
  search and to NumPy for every join kind, chosen by the 8-slots-per-
  row rule, counted, replicated by the parallel engine and served in
  vmapped batches.
"""
import numpy as np
import pytest

from conftest import assert_results_equal
from repro.core import CompileCache, FlareContext, col, count, sum_
from repro.core import engines as ENG
from repro.core import lower as L
from repro.core import plan as P
from repro.core import stages as S
from repro.obs import metrics as OM
from repro.relational import queries as Q
from repro.relational import table as T
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


# ---------------------------------------------------------------------------
# the slot-table probe of a dense key domain (direct joins)
# ---------------------------------------------------------------------------

_DOM = 64  # per-key domain of the operator-level cases


def _probe_case(case):
    """(probe key columns, probe mask, build key columns, build mask,
    per-key domains) of one operator-level case."""
    rng = np.random.default_rng(7)
    n_probe, n_build = 300, 40
    pmask = rng.random(n_probe) < 0.9
    if case == "composite":
        doms = (8, 8)
        flat = rng.choice(64, n_build, replace=False)
        build = [flat // 8, flat % 8]
        probe = [rng.integers(0, 8, n_probe), rng.integers(0, 8, n_probe)]
        return probe, pmask, build, None, doms
    kb = rng.choice(_DOM, n_build, replace=False)
    kp = rng.integers(0, _DOM, n_probe)
    bmask = None
    if case == "duplicates":
        kb[10:20] = kb[:10]  # each of ten keys held by two rows
    elif case == "filtered":
        bmask = rng.random(n_build) < 0.6
    elif case == "out_of_range":
        kp[::3] = rng.integers(-2 * _DOM, 0, len(kp[::3]))
        kp[1::5] = rng.integers(_DOM, 3 * _DOM, len(kp[1::5]))
    return [kp], pmask, [kb], bmask, (_DOM,)


def _streams(case):
    probe, pmask, build, bmask, doms = _probe_case(case)
    lnames = [f"pk{i}" for i in range(len(probe))]
    rnames = [f"k{i}" for i in range(len(build))]

    def stream(names, keys, mask, extra):
        cols = {n: np.asarray(k, np.int32) for n, k in zip(names, keys)}
        cols.update(extra)
        info = L.StaticInfo(
            {n: L.StaticCol(T.INT32, None, d) for n, d in zip(names, doms)}
            | {n: L.StaticCol(T.FLOAT32) for n in extra},
            len(keys[0]))
        return L.Stream(cols, None if mask is None else np.asarray(mask),
                        info)

    n_build = len(build[0])
    left = stream(lnames, probe, pmask,
                  {"x": np.arange(len(probe[0]), dtype=np.float32)})
    right = stream(rnames, build, bmask,
                   {"v": 1.0 + np.arange(n_build, dtype=np.float32)})
    kb = np.zeros(n_build, np.int64)
    for k, d in zip(build, doms):
        kb = kb * d + k
    perm = np.argsort(kb, kind="stable").astype(np.int32)
    index = ENG.JoinIndex(perm, kb[perm].astype(np.int32), False)
    return left, right, lnames, rnames, index, doms


def _numpy_join(case, how):
    """Per probe row: matched, and the first matching build row's v."""
    probe, pmask, build, bmask, doms = _probe_case(case)
    kp = np.zeros(len(probe[0]), np.int64)
    for k, d in zip(probe, doms):
        kp = kp * d + k
    kb = np.zeros(len(build[0]), np.int64)
    for k, d in zip(build, doms):
        kb = kb * d + k
    matched = np.zeros(len(kp), bool)
    v = np.zeros(len(kp), np.float32)
    for i, key in enumerate(kp):
        rows = np.flatnonzero(kb == key)
        if len(rows) and pmask[i]:
            row = rows[0]  # the first row among duplicates
            if bmask is None or bmask[row]:
                matched[i], v[i] = True, 1.0 + row
    return matched, v


_CASES = ["unique", "filtered", "duplicates", "out_of_range", "composite"]


@pytest.mark.parametrize("case", _CASES)
@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_direct_probe_matches_sorted_and_numpy(how, case):
    """The slot-table probe gives the binary search's match mask and
    probe mask bit for bit, its build row wherever a row matched, and
    NumPy's answer for every join kind."""
    left, right, lnames, rnames, index, doms = _streams(case)
    join = P.Join(P.Scan("probe"), P.Scan("build"), tuple(lnames),
                  tuple(rnames), how)
    slots = index.slot_table(int(np.prod(doms)))
    sorted_ = L._join_probe(join, left, right,
                            (index.perm, index.keys, None))
    direct = L._join_probe(join, left, right,
                           (index.perm, index.keys, slots))
    np.testing.assert_array_equal(direct[1], sorted_[1])
    np.testing.assert_array_equal(direct[2], sorted_[2])
    hit = np.asarray(direct[1])
    np.testing.assert_array_equal(np.asarray(direct[0])[hit],
                                  np.asarray(sorted_[0])[hit])

    out = L._lower_join(join, left, right, None,
                        (index.perm, index.keys, slots))
    want_matched, want_v = _numpy_join(case, how)
    pmask = np.asarray(left.mask)
    want_mask = {"inner": want_matched, "left": pmask,
                 "semi": want_matched,
                 "anti": pmask & ~want_matched}[how]
    np.testing.assert_array_equal(np.asarray(out.mask), want_mask)
    if how in ("inner", "left"):
        got_v = np.asarray(out.cols["v"])
        np.testing.assert_array_equal(got_v[want_mask],
                                      want_v[want_mask])


def test_slot_table_first_row_wins_and_rejects_keys_outside_domain():
    perm = np.array([1, 3, 0, 2], np.int32)  # keys 2, 5, 5, 9 sorted
    keys = np.array([2, 5, 5, 9], np.int32)
    slots = np.asarray(ENG.JoinIndex(perm, keys, False).slot_table(12))
    want = np.full(12, -1, np.int32)
    want[[2, 5, 9]] = [1, 0, 2]  # key 5: rows 3 and 0, the lowest wins
    np.testing.assert_array_equal(slots, want)
    with pytest.raises(ValueError, match="declared domain"):
        ENG.JoinIndex(perm, keys, False).slot_table(9)


@pytest.mark.parametrize("domain,n_build,declared,mode", [
    (16, 8, True, "direct"),     # 2 slots a row
    (64, 8, True, "direct"),     # exactly 8 slots a row
    (72, 8, True, "sorted"),     # over 8: sparse
    (16, 8, False, "sorted"),    # no declared domain
])
def test_probe_form_follows_declared_domain(domain, n_build, declared,
                                            mode):
    """Dense declared key domains probe the slot table; a domain of
    more than 8 slots per build row, or none at all, keeps the binary
    search -- and both answer like the oracle."""
    c = FlareContext()
    rng = np.random.default_rng(3)
    c.from_arrays("probe", {
        "pk": rng.integers(0, domain, 50).astype(np.int32),
        "x": rng.uniform(0, 10, 50),
    }, domains={"pk": domain} if declared else None)
    c.from_arrays("build", {
        "k": rng.choice(domain, n_build, replace=False).astype(np.int32),
        "v": np.arange(n_build, dtype=np.float64),
    }, domains={"k": domain} if declared else None, uniques=["k"])
    q = (c.table("probe")
         .join(c.table("build"), on="pk", right_on="k", how="left")
         .sort("pk", "x"))
    lowered = q.lower(engine="compiled")
    (decision,) = lowered.dispatch_report().joins_cached
    assert decision.mode == mode
    before = OM.REGISTRY.get(f"join.probe.{mode}")
    got = lowered.compile(cache=CompileCache())()
    assert OM.REGISTRY.get(f"join.probe.{mode}") == before + 1
    assert_results_equal(q.collect(engine="volcano"), got, msg=mode)


_STREAM_JOINS = {"q3": 2, "q5": 5, "q10": 3, "q14": 1, "q19": 1, "q22": 1}


@pytest.fixture(scope="module")
def ctx01():
    c = FlareContext()
    Q.register_tpch(c, sf=0.01)
    return c


@pytest.mark.parametrize("name", sorted(_STREAM_JOINS))
def test_stream_joins_all_probe_direct(ctx01, name):
    """Every cached join of the power stream's queries is direct: the
    dispatch report names the form and the lowering counts it; none
    takes the binary search."""
    lowered = getattr(Q, name)(ctx01).lower(engine="compiled")
    rep = lowered.dispatch_report()
    assert [d.mode for d in rep.joins_cached] == \
        ["direct"] * _STREAM_JOINS[name]
    assert "direct probe" in str(rep)
    before = {m: OM.REGISTRY.get(f"join.probe.{m}")
              for m in ("direct", "sorted")}
    lowered.compile(cache=CompileCache())
    assert OM.REGISTRY.get("join.probe.direct") - before["direct"] == \
        _STREAM_JOINS[name]
    assert OM.REGISTRY.get("join.probe.sorted") == before["sorted"]


def test_preload_builds_no_slot_table():
    c = FlareContext()
    Q.register_tpch(c, sf=SF)
    c.preload()
    assert len(c.cache.indexes) > 0
    assert all(not e.slots for e in c.cache.indexes._entries.values())


@pytest.mark.parametrize("name", ["q3", "q10"])
def test_parallel_engine_replicates_slot_tables(ctx, name):
    q = getattr(Q, name)(ctx)
    lowered = q.lower(engine="parallel")
    assert all(d.mode == "direct"
               for d in lowered.dispatch_report().joins_cached)
    assert_results_equal(q.collect(engine="volcano"), lowered.compile()(),
                         msg=f"{name} parallel direct")
    specs, _ = L.join_index_plan(lowered._plan, ctx.catalog)
    placed = {k[1] for k in ctx.cache._cache if k[0] == "placed"}
    for spec in specs.values():
        slots = S.index_args((spec,), ctx.catalog, ctx.cache)[2]
        assert id(slots) in placed, spec


@pytest.mark.parametrize("tname", ["q14", "q19"])
def test_served_join_template_batches_probe_direct(ctx, tname):
    """A vmapped batch of a join template probes the slot table once
    for all bindings and answers each like its own execution."""
    tmpl = Q.TEMPLATES[tname](ctx)
    lowered = tmpl.lower(engine="compiled")
    assert [d.mode for d in lowered.dispatch_report().joins_cached] == \
        ["direct"]
    compiled = lowered.compile()
    bindings = [dict(b) for b in Q.TEMPLATE_BINDINGS[tname]]
    batched = compiled.batch(bindings)
    for b, got in zip(bindings, batched):
        assert_results_equal(compiled.result(**b).compact(),
                             got.compact(), msg=f"{tname} {b}")
        assert_results_equal(tmpl.collect(engine="volcano", params=b),
                             got.compact(), msg=f"{tname} {b} volcano")
