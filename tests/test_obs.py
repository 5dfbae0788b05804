"""The observability layer: tracer semantics, span coverage across the
engine matrix, Chrome-trace export, metrics snapshot, EXPLAIN ANALYZE.

DESIGN.md section 13.  The span-name vocabulary asserted here
(``optimize``/``dispatch``/``lower``/``compile``/``persist``/``execute``
plus the serve/store/index names) is the contract flare_top,
trace_ci_check and the EXPLAIN ANALYZE renderer all consume -- renaming
a span is an interface change and must update all of them.
"""
import glob
import json
import os
import sys

import jax
import pytest

import conftest
from repro.core import CompileCache, FlareContext
from repro.core import engines as ENG
from repro.obs import export as OX
from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.relational import queries as Q
from test_engine_matrix import MATRIX_ENGINES

if conftest.REPO not in sys.path:  # benchmarks/ is not on PYTHONPATH=src
    sys.path.insert(0, conftest.REPO)

from benchmarks.common import Timing, emit, time_call, write_report

SF = 0.005


@pytest.fixture(scope="module")
def ctx():
    c = FlareContext()
    Q.register_tpch(c, sf=SF)
    return c


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_disabled_mode_is_a_noop(monkeypatch):
    monkeypatch.delenv(OT.ENV_VAR, raising=False)
    OT.TRACER.refresh_from_env()
    assert not OT.TRACER.on
    before = len(OT.TRACER.spans())
    sp = OT.span("anything", key="value")
    assert sp is OT.NULL_SPAN  # one shared object: no allocation per call
    with sp as inner:
        inner.set(more="attrs")  # all no-ops
    assert len(OT.TRACER.spans()) == before
    assert not OT.enabled()


def _profiled_spans(trace_dir):
    """``(name, start_ns, end_ns, stats)`` of every ``flare:`` host event
    in the newest profile under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                      "*", "*.xplane.pb")),
               key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(OT.PREFIX):
                    out.append((e.name[len(OT.PREFIX):], e.start_ns,
                                e.end_ns, dict(e.stats)))
    return out


def test_span_is_a_profiler_annotation(tmp_path, monkeypatch):
    """Under a profiler session a span is a ``flare:`` host event that
    carries its attributes, those set later included, with the buffer
    off."""
    monkeypatch.delenv(OT.ENV_VAR, raising=False)
    OT.TRACER.refresh_from_env()
    before = len(OT.TRACER.spans())
    with jax.profiler.trace(str(tmp_path)):
        assert OT.active()
        with OT.span("demo", req=7, note="a,b=c#d") as sp:
            assert sp is not OT.NULL_SPAN
            sp.set(polls=3)
    assert len(OT.TRACER.spans()) == before  # the buffer stayed off
    got = [st for name, _, _, st in _profiled_spans(tmp_path)
           if name == "demo"]
    assert got == [{"req": 7, "note": "a;b:c d", "polls": 3}]


def test_span_is_null_with_no_profiler_and_buffer_off(tmp_path,
                                                      monkeypatch):
    monkeypatch.delenv(OT.ENV_VAR, raising=False)
    OT.TRACER.refresh_from_env()
    with jax.profiler.trace(str(tmp_path)):
        pass
    assert not OT.active()
    assert OT.span("after", key="value") is OT.NULL_SPAN
    assert OT.current_span() is OT.NULL_SPAN


def test_span_nesting_parent_ids_and_attrs():
    with OT.capture() as trace:
        with OT.span("outer", a=1) as outer:
            with OT.span("inner") as inner:
                inner.set(b=2)
        outer.set(after_exit=True)  # recorded spans mutate in place
    assert OT.enabled() is False  # capture() disables on exit
    outer_sp = trace.first("outer")
    inner_sp = trace.first("inner")
    assert inner_sp.parent_id == outer_sp.span_id
    assert outer_sp.parent_id is None
    assert outer_sp.attrs == {"a": 1, "after_exit": True}
    assert inner_sp.attrs == {"b": 2}
    assert outer_sp.t1 >= inner_sp.t1 >= inner_sp.t0 >= outer_sp.t0
    assert trace.children(outer_sp) == [inner_sp]
    assert "inner" in trace.descendant_names(outer_sp)


def test_span_records_exceptions():
    with OT.capture() as trace:
        with pytest.raises(ValueError):
            with OT.span("doomed"):
                raise ValueError("boom")
    assert trace.first("doomed").attrs["error"] == "ValueError"


def test_capture_isolates_concurrent_buffers():
    """Two sequential captures over a shared global buffer must not
    leak spans into each other (watermark fencing)."""
    with OT.capture() as first:
        with OT.span("one"):
            pass
    with OT.capture() as second:
        with OT.span("two"):
            pass
    assert [s.name for s in first.spans] == ["one"]
    assert [s.name for s in second.spans] == ["two"]


# ---------------------------------------------------------------------------
# span coverage across the engine matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("label,engine,native,ordered", MATRIX_ENGINES,
                         ids=[m[0] for m in MATRIX_ENGINES])
def test_engine_matrix_span_coverage(ctx, label, engine, native, ordered):
    """Every engine leaves the full lifecycle in the trace: the stages
    funnel (Lowered/Compiled) is the one choke point, so lower, compile
    and execute spans appear no matter which engine runs the plan."""
    df = Q.q6(ctx)
    with OT.capture() as trace:
        df.lower(engine=engine, native=native).compile(
            cache=CompileCache()).collect()
    names = {s.name for s in trace.spans}
    assert {"optimize", "lower", "compile", "execute"} <= names, \
        (label, sorted(names))
    execute = trace.first("execute")
    # native=True on the compiled engine reports as "compiled-native"
    assert execute.attrs["engine"].startswith(engine)
    assert execute.attrs["mode"] == "sync"
    assert execute.attrs["rows"] == 1  # q6 is a scalar aggregate
    compile_sp = trace.first("compile")
    assert compile_sp.attrs["cache"] == "miss"  # fresh CompileCache
    # lower nests under compile (forced lazily inside the compile path)
    assert "lower" in trace.descendant_names(compile_sp)
    if native:
        assert "dispatch" in names and "dispatch.match" in names, label
        fired = [s for s in trace.find("dispatch.match")
                 if s.attrs.get("fired")]
        assert any(s.attrs["fired"] == "filter-scalar-agg" for s in fired)
    if engine == "parallel":
        assert "shard_plan" in names, label


def test_served_path_span_coverage(ctx):
    from repro.serve import QueryServer
    server = QueryServer(ctx)
    with OT.capture() as trace:
        futs = [server.submit("q6", **b)
                for b in Q.TEMPLATE_BINDINGS["q6"][:2]]
        server.flush()
        for f in futs:
            f.result()
    names = {s.name for s in trace.spans}
    assert {"serve.submit", "serve.flush", "serve.dispatch",
            "serve.sync", "execute"} <= names, sorted(names)
    flush = trace.first("serve.flush")
    assert flush.attrs == {"drained": 2, "groups": 1}
    dispatch = trace.first("serve.dispatch")
    assert dispatch.attrs["template"] == "q6"
    assert dispatch.attrs["requests"] == 2
    # the coalesced batch executes under the dispatch span
    assert "execute" in trace.descendant_names(dispatch)
    batch_exec = trace.first("execute")
    assert batch_exec.attrs["mode"] == "batch"
    # each request's sync splits into waiting for the device and
    # bringing its rows to the host
    for f in futs:
        sync = next(s for s in trace.find("serve.sync")
                    if s.attrs["req"] == f.req)
        assert sync.attrs["batch"] == dispatch.attrs["batch"]
        assert [c.name for c in trace.children(sync)] == [
            "serve.wait", "serve.finalize"]


def test_served_request_chain_in_profile(ctx, tmp_path, monkeypatch):
    """One served request leaves submit -> dispatch -> sync {wait,
    finalize} in the profile, joined by its sequence number."""
    from repro.serve import QueryServer
    monkeypatch.delenv(OT.ENV_VAR, raising=False)
    OT.TRACER.refresh_from_env()
    server = QueryServer(ctx)
    b = Q.TEMPLATE_BINDINGS["q6"][0]
    server.serve([("q6", b)])  # compiles outside the profile
    with jax.profiler.trace(str(tmp_path)):
        fut = server.submit("q6", **b)
        server.flush()
        fut.result(timeout=30)
    spans = _profiled_spans(tmp_path)

    def one(name, match):
        got = [(s, e, st) for n, s, e, st in spans
               if n == name and match(st)]
        assert len(got) == 1, (name, spans)
        return got[0]
    req = fut.req
    submit = one("serve.submit", lambda st: st["req"] == req)
    dispatch = one("serve.dispatch",
                   lambda st: st["req_first"] <= req <= st["req_last"])
    sync = one("serve.sync", lambda st: st["req"] == req)
    wait = one("serve.wait", lambda st: st["req"] == req)
    final = one("serve.finalize", lambda st: st["req"] == req)
    assert sync[2]["batch"] == dispatch[2]["batch"] == fut.batch
    assert dispatch[2]["requests"] == 1 and dispatch[2]["bucket"] == 1
    assert submit[1] <= dispatch[0] and dispatch[1] <= sync[0]
    assert sync[0] <= wait[0] <= wait[1] <= final[0] <= final[1] <= sync[1]
    assert wait[2]["polls"] >= 0


def test_generic_lowering_names_operators_on_device(ctx):
    """The compiled q3 carries each generic operator's scope in its
    ops' ``op_name`` metadata, which device profiles keep as
    ``tf_op``."""
    compiled = Q.q3(ctx).lower(engine="compiled").compile(
        cache=CompileCache())
    hlo = compiled._exe.jax_exe.as_text()
    for scope in ("flare:join.probe", "flare:join.gather", "flare:agg",
                  "flare:filter", "flare:sort"):
        assert f"/{scope}/" in hlo, scope


def test_last_trace_rides_on_compiled(ctx):
    compiled = Q.q6(ctx).lower(engine="compiled").compile(
        cache=CompileCache())
    assert compiled.last_trace() is None  # nothing traced yet
    with OT.capture():
        compiled.collect()
        got = compiled.last_trace()
    assert got is not None
    assert got.first("execute").attrs["engine"] == "compiled"
    tree = got.tree_str()
    assert "execute" in tree and "ms" in tree


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------


def test_chrome_export_schema(tmp_path):
    with OT.capture() as trace:
        with OT.span("parent", kind="demo"):
            with OT.span("child"):
                pass
    doc = OX.to_chrome(trace.spans)
    json.dumps(doc)  # must be JSON-serializable as-is
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "process_name"
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == 2
    for ev in xs:
        for key in ("name", "ph", "ts", "dur", "pid", "tid", "args"):
            assert key in ev, (ev, key)
        assert ev["dur"] >= 0
    parent = next(e for e in xs if e["name"] == "parent")
    assert parent["args"]["kind"] == "demo"

    path = tmp_path / "trace.json"
    OX.dump_chrome(str(path), trace.spans)
    rebuilt = OT.Trace(OX.spans_from_chrome(json.loads(path.read_text())))
    assert {s.name for s in rebuilt.spans} == {"parent", "child"}
    assert (rebuilt.first("child").parent_id
            == rebuilt.first("parent").span_id)


def test_chrome_export_sanitizes_exotic_attrs():
    with OT.capture() as trace:
        with OT.span("odd") as sp:
            sp.set(obj=object(), nested={"k": (1, 2)})
    doc = OX.to_chrome(trace.spans)
    json.dumps(doc)  # _json_safe must have flattened everything


# ---------------------------------------------------------------------------
# metrics registry + snapshot
# ---------------------------------------------------------------------------


def test_snapshot_is_a_superset_of_cache_stats(ctx):
    Q.q6(ctx).collect(engine="compiled")
    snap = OM.snapshot()
    assert snap["caches"] == ENG.cache_stats()  # the shim contract
    for key in ("caches", "disk", "dispatch", "serve", "counters",
                "trace"):
        assert key in snap
    assert {"exec", "index"} <= set(snap["disk"])
    assert isinstance(snap["trace"]["phases"], dict)


def test_dispatch_counters_accumulate(ctx):
    before = OM.dispatch_section()
    Q.q6(ctx).lower(engine="compiled", native=True)
    after = OM.dispatch_section()
    assert after["rewrites"] == before["rewrites"] + 1
    assert after["fired"] == before["fired"] + 1
    pat = after["patterns"]["filter-scalar-agg"]
    assert pat["fired"] >= 1


def test_registry_counters():
    reg = OM.MetricsRegistry()
    reg.inc("x")
    reg.inc("x", 2)
    assert reg.get("x") == 3 and reg.counters() == {"x": 3}
    reg.reset_counters()
    assert reg.get("x") == 0


def test_serve_stats_latency_decomposition():
    from repro.serve.stats import ServeStats
    st = ServeStats()
    for ms in (1, 2, 3):
        st.record_queue(ms / 1e3)
        st.record_sync(ms / 1e3)
        st.record_latency(ms / 1e3)
    d = st.to_dict()
    assert d["p95_ms"] == 3.0
    assert set(d["queue"]) == {"p50_ms", "p95_ms", "p99_ms"}
    assert set(d["sync"]) == {"p50_ms", "p95_ms", "p99_ms"}
    assert d["queue"]["p50_ms"] == 2.0


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE
# ---------------------------------------------------------------------------


def test_explain_analyze_q6_native(ctx):
    text = Q.q6(ctx).explain(analyze=True, native=True)
    assert "== Physical Plan (analyzed: engine=compiled" in text
    assert "== Query Lifecycle ==" in text
    for phase in ("optimize", "dispatch", "lower", "compile", "execute"):
        assert phase in text, phase
    assert "== Native Dispatch ==" in text
    assert "FIRED" in text and "filter-scalar-agg" in text
    assert "Scan lineitem" in text and "rows=" in text and "bytes=" in text
    assert "== Spans ==" in text
    assert "rows_out=1" in text


def test_explain_analyze_q19_join_provenance(ctx):
    text = Q.QUERIES["q19"](ctx).explain(analyze=True, native=True)
    assert "join-probe" in text
    assert "indexed" in text  # the join-index provenance row
    assert "== Query Lifecycle ==" in text


def test_explain_analyze_scan_stats_cover_every_scan(ctx):
    """Per-scan stats are keyed by structural path, not id(node): every
    Scan line must carry the *pruned* bound-column count even after the
    lowering pipeline copies the plan (join_index=False rebuilds the
    root, which used to orphan the id()-keyed stats)."""
    import re

    from repro.core import lower as L

    for join_index in (True, False):
        df = Q.q6(ctx)
        text = df.explain(analyze=True, join_index=join_index)
        scan_lines = [ln for ln in text.splitlines() if "Scan " in ln]
        assert scan_lines, text
        # every rendered Scan carries stats...
        assert all("cols=" in ln for ln in scan_lines), scan_lines
        # ...and lineitem's count is the pruned binding set, not the
        # full 16-column schema fallback
        plan = df.lower(engine="compiled",
                        join_index=join_index).plan()
        by_path = L.required_scan_columns_by_path(plan, ctx.catalog)
        want = {len(cols) for cols in by_path.values()}
        li = next(ln for ln in scan_lines if "lineitem" in ln)
        got = int(re.search(r"cols=(\d+)", li).group(1))
        assert got in want and got < 16, (got, want, li)


def test_scan_paths_stable_across_plan_copies(ctx):
    from repro.core import lower as L

    plan = Q.q6(ctx).plan
    copy = plan.with_children(plan.children())
    a = L.required_scan_columns_by_path(plan, ctx.catalog)
    b = L.required_scan_columns_by_path(copy, ctx.catalog)
    assert a == b and a  # same structural keys, same pruned columns


def test_explain_analyze_leaves_tracing_off(ctx):
    assert not OT.TRACER.on
    Q.q6(ctx).explain(analyze=True)
    assert not OT.TRACER.on


def test_plain_explain_unchanged(ctx):
    text = Q.q6(ctx).explain()
    assert "Scan lineitem" in text
    assert "Lifecycle" not in text


# ---------------------------------------------------------------------------
# benchmark plumbing (satellite of the same PR: unified emission)
# ---------------------------------------------------------------------------


def test_time_call_records_cap_hit():
    t = time_call(lambda: None, iters=2, min_time_s=60.0, max_iters=5)
    assert isinstance(t, Timing)
    assert t.iters == 5 and t.cap_hit and t.total_s < 1.0
    line = emit("obs_test_row", t)
    assert "iters=5" in line and "cap_hit=1" in line


def test_time_call_uncapped_budget():
    t = time_call(lambda: None, iters=3)
    assert t.iters == 3 and not t.cap_hit
    assert "cap_hit" not in emit("obs_test_row2", t)


def test_write_report_embeds_trace(tmp_path, monkeypatch):
    path = tmp_path / "report.json"
    monkeypatch.setenv("OBS_TEST_JSON", str(path))
    assert write_report({"n": 1}, "OBS_TEST_JSON") == str(path)
    doc = json.loads(path.read_text())
    assert doc["n"] == 1
    assert "phases" in doc["trace"]
    # opt-in knobs stay opt-in: no env var + no default -> no file
    monkeypatch.delenv("OBS_TEST_JSON")
    assert write_report({"n": 1}, "OBS_TEST_JSON") is None
