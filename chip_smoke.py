"""Flare's main path, end to end, on one TPU chip.

    python chip_smoke.py              # TPC-H SF 1 on one chip
    python chip_smoke.py --sf 10      # another scale factor
    python chip_smoke.py --chips 4    # only the parallel engine, 4-chip mesh

The tables are generated in-process from ``--seed``
(``relational/tpch.generate``), loaded onto the device with their join
indexes, and driven through the entry points a user calls:
``df.lower(...).compile()`` on every TPC-H query and prepared template,
with and without the native Pallas kernels; the persistent executable
store; a ``QueryServer``; and fused ETL + training through
``df.train``.  Every answer is checked against the ``volcano`` oracle
at the test suite's tolerance (``repro.core.compare``).

A run fails (non-zero exit, no final line) when JAX finds no TPU, when
any answer differs from the oracle, when any template degraded to a
weaker engine (``Compiled.stats.degraded``, ``obs.snapshot()``), or
when a native kernel ran in Pallas interpret mode instead of compiling
for the chip.  Each phase prints one line; the last line of a passing
run is ``{"ok": true, "device": {...}}``.

``--chips 4`` runs only the sharded ``parallel`` engine over a 4-chip
``make_data_mesh``: q1, q6, q5 and q14, native kernels on and off,
against single-chip ``compiled`` results and ``volcano``, and prints
the sharding of every spine column the engine placed.

JAX's persistent compilation cache lives at
``$JAX_COMPILATION_CACHE_DIR`` or ``<repo>/.jax_cache``
(``repro.persist.xla_cache``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

#: Objective agreement between the fused pipeline and the oracle: both
#: train the same kernel on the same rows (padded-and-masked vs
#: compacted), so only f32 reduction order separates them.
TRAIN_RTOL = 1e-3


class SmokeFailure(AssertionError):
    """A phase's result is wrong (not a crash)."""


def phase(name: str, t0: float, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{name}] {time.perf_counter() - t0:.3f}s {body}", flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_info(chips: int) -> dict:
    """The device JAX reports; exits 2 when it is not a TPU host with
    ``chips`` chips -- this check never falls back to the CPU."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[device] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found (JAX platform "
              f"{dev['platform']!r}); nothing was run", file=sys.stderr)
        sys.exit(2)
    if dev["count"] < chips:
        print(f"chip_smoke: {chips} chips asked for, {dev['count']} "
              "found", file=sys.stderr)
        sys.exit(2)
    return dev


def bytes_in_use() -> int:
    import jax
    return int(jax.devices()[0].memory_stats().get("bytes_in_use", -1))


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------


def check_compiled(compiled, native: bool, what: str) -> str:
    """No degradation; every fired kernel compiled for the chip.
    Returns the dispatch summary (empty without native)."""
    if compiled.stats.degraded:
        raise SmokeFailure(f"{what}: degraded {compiled.stats.degraded}")
    if not native:
        return ""
    rep = compiled.stats.dispatch
    if rep is None:
        raise SmokeFailure(f"{what}: native lowering left no dispatch "
                           "report")
    bad = [d for d in rep.fired if d.mode != "pallas"]
    if bad:
        raise SmokeFailure(f"{what}: kernel(s) not compiled for the "
                           f"chip: {[(d.pattern, d.mode) for d in bad]}")
    fired = [f"{d.pattern}[{d.mode}]" for d in rep.fired]
    fell = [d.reason for d in rep.fallbacks]
    passed = [d.reason[len("ok; "):] for d in rep.fired if d.reason != "ok"]
    return (f"fired={fired or 'none'} fallbacks={fell or 'none'}"
            + (f" passed_over={passed}" if passed else ""))


def check_equal(want, got, what: str) -> None:
    from repro.core.compare import assert_results_equal
    try:
        assert_results_equal(want, got, msg=what)
    except AssertionError as e:
        raise SmokeFailure(f"{what}: differs from the oracle: {e}") from e


def check_no_degrade_events() -> None:
    from repro import obs
    deg = obs.snapshot()["resilience"]["degrade"]
    if deg["events"]:
        raise SmokeFailure(f"degradation events recorded: {deg}")


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def load(sf: float, seed: int):
    from repro.core import FlareContext
    from repro.relational import queries as Q
    t0 = time.perf_counter()
    ctx = FlareContext()
    Q.register_tpch(ctx, sf=sf, seed=seed)
    gen_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    ctx.preload()
    rows = {n: ctx.catalog.table(n).num_rows for n in ctx.catalog.names()}
    phase("load", t0, sf=sf, generate_s=f"{gen_s:.3f}",
          preload_s=f"{time.perf_counter() - t1:.3f}",
          lineitem_rows=rows["lineitem"], total_rows=sum(rows.values()),
          bytes_in_use=bytes_in_use())
    return ctx


def run_queries(ctx) -> None:
    from repro.relational import queries as Q
    for name, build in Q.QUERIES.items():
        t0 = time.perf_counter()
        df = build(ctx)
        want = df.collect(engine="volcano")
        for native in (False, True):
            what = f"{name}/{'compiled-native' if native else 'compiled'}"
            compiled = df.lower(engine="compiled", native=native).compile()
            check_equal(want, compiled(), what)
            summary = check_compiled(compiled, native, what)
            phase("query", t0, query=what, rows=len(next(iter(
                want.values()))), compile_s=f"{compiled.stats.compile_s:.3f}",
                run_s=f"{compiled.stats.run_s:.6f}", dispatch=summary)


def run_templates(ctx) -> None:
    from repro.relational import queries as Q
    for name, build in Q.TEMPLATES.items():
        df = build(ctx)
        bindings = Q.TEMPLATE_BINDINGS[name][:2]
        wants = [df.collect(engine="volcano", params=b) for b in bindings]
        for native in (False, True):
            t0 = time.perf_counter()
            what = (f"template {name}/"
                    f"{'compiled-native' if native else 'compiled'}")
            compiled = df.lower(engine="compiled", native=native).compile()
            for b, want in zip(bindings, wants):
                check_equal(want, compiled(**b), f"{what} {b}")
            summary = check_compiled(compiled, native, what)
            phase("template", t0, template=what, bindings=len(bindings),
                  dispatch=summary)


def run_persist(ctx) -> None:
    """A prepared template compiled into a fresh store, then loaded back
    from it by a second compile with an empty memory cache: the native
    executable tier must round-trip on the chip and answer the same."""
    from repro.core import CompileCache
    from repro.persist import ArtifactStore
    from repro.relational import queries as Q
    b = Q.TEMPLATE_BINDINGS["q6"][0]
    with tempfile.TemporaryDirectory(prefix="flare-smoke-store-") as d:
        store = ArtifactStore(d)
        for native in (False, True):
            t0 = time.perf_counter()
            what = f"persist q6/{'compiled-native' if native else 'compiled'}"
            df = Q.TEMPLATES["q6"](ctx)
            first = df.lower(engine="compiled", native=native).compile(
                cache=CompileCache(), persist=store)
            again = df.lower(engine="compiled", native=native).compile(
                cache=CompileCache(), persist=store)
            if first.stats.persist != "written" or not again.stats.disk_hit \
                    or again.stats.persist != "hit:native":
                raise SmokeFailure(
                    f"{what}: store round trip failed: first="
                    f"{first.stats.persist!r} again={again.stats.persist!r}")
            check_equal(first(**b), again(**b), what)
            check_compiled(again, native, what)
            phase("persist", t0, template=what,
                  first=first.stats.persist, again=again.stats.persist)


def run_serve(ctx, seed: int, per_template: int = 8) -> None:
    from repro.relational import queries as Q
    from repro.serve import QueryServer
    t0 = time.perf_counter()
    server = QueryServer(ctx)
    reqs = []
    for name in Q.TEMPLATES:
        for b in Q.random_bindings(name, per_template, seed=seed):
            reqs.append((name, b, server.submit(name, **b)))
    dispatched = server.flush()
    for name, b, fut in reqs:
        compiled = server.compiled_for(name)
        check_equal(compiled(**b), fut.compact(), f"serve {name} {b}")
        check_compiled(compiled, False, f"serve {name}")
    phase("serve", t0, requests=len(reqs), dispatched=dispatched,
          templates=len(Q.TEMPLATES))


def _inertia(x, centroids):
    d = ((x * x).sum(1)[:, None] + (centroids * centroids).sum(1)[None]
         - 2.0 * x @ centroids.T)
    return float(d.min(axis=1).sum())


def _log_loss(x, y, w):
    import numpy as np
    z = x @ w
    # log(1 + e^z) - y z, stably
    return float(np.mean(np.logaddexp(0.0, z) - y * z))


def run_heterogeneous(ctx) -> None:
    """Fused relational ETL + training (paper Fig. 8/13) over lineitem,
    on ``compiled`` against the same pipeline on ``volcano``; the
    objective at each returned model is evaluated in float64 NumPy."""
    import numpy as np
    from repro.core import col, lit, when
    from repro.relational.tpch import date
    feats = (ctx.table("lineitem")
             .filter(col("l_shipdate") < lit(date("1996-01-01")))
             .select(("qty", col("l_quantity") / lit(50.0)),
                     ("disc", col("l_discount") * lit(10.0)),
                     ("tax", col("l_tax") * lit(10.0)),
                     ("returned", when(col("l_returnflag") == "R",
                                       1.0, 0.0))))
    rows = feats.collect(engine="volcano")
    x = np.stack([rows[c].astype(np.float64)
                  for c in ("qty", "disc", "tax")], axis=1)
    y = rows["returned"].astype(np.float64)
    jobs = [
        ("kmeans", feats.to_matrix("qty", "disc", "tax").train(
            "kmeans", k=8, tol=1e-4, max_iter=50),
         lambda r: _inertia(x, np.asarray(r.centroids, np.float64))),
        ("logreg", feats.train("logreg", columns=["qty", "disc", "tax"],
                               label="returned", lr=0.5, tol=1e-6,
                               max_iter=200),
         lambda r: _log_loss(x, y, np.asarray(r.weights, np.float64))),
    ]
    for name, pipeline, objective in jobs:
        t0 = time.perf_counter()
        compiled = pipeline.lower(engine="compiled").compile()
        got = compiled()
        want = pipeline.lower(engine="volcano").compile()()
        check_compiled(compiled, False, f"train {name}")
        leaves = [np.asarray(v) for v in got]
        if not all(np.isfinite(v).all() for v in leaves
                   if v.dtype.kind == "f"):
            raise SmokeFailure(f"train {name}: non-finite model")
        g, w = objective(got), objective(want)
        if not abs(g - w) <= TRAIN_RTOL * abs(w):
            raise SmokeFailure(f"train {name}: objective {g!r} vs oracle "
                               f"{w!r} (rtol {TRAIN_RTOL})")
        phase("heterogeneous", t0, kernel=name, rows=x.shape[0],
              objective=f"{g!r}", oracle=f"{w!r}",
              compile_s=f"{compiled.stats.compile_s:.3f}")


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------


def run_parallel(ctx, chips: int) -> None:
    """q1, q6 and the join-bearing q5/q14 on the sharded ``parallel``
    engine over a ``chips``-way data mesh, native kernels off and on,
    against single-chip ``compiled`` results and the oracle."""
    from repro.launch.mesh import make_data_mesh
    from repro.relational import queries as Q
    mesh = make_data_mesh(chips)
    for name in ("q1", "q6", "q5", "q14"):
        df = Q.QUERIES[name](ctx)
        want = df.collect(engine="volcano")
        single = df.lower(engine="compiled").compile()()
        check_equal(want, single, f"{name}/compiled")
        for native in (False, True):
            t0 = time.perf_counter()
            what = f"{name}/parallel{'-native' if native else ''}"
            compiled = df.lower(engine="parallel", mesh=mesh,
                                native=native).compile()
            for _ in range(2):  # second call: inputs already placed
                got = compiled()
                check_equal(want, got, what)
                check_equal(single, got, f"{what} vs compiled")
            summary = check_compiled(compiled, native, what)
            phase("parallel", t0, query=what, shards=chips,
                  dispatch=summary)
    for column, pad_to, sharding in ctx.cache.placements():
        if pad_to is not None:  # row-sharded spine columns
            print(f"[spine] {column} rows={pad_to} sharding={sharding}",
                  flush=True)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (default 1)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated tables and bindings")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the parallel engine on a 4-chip "
                    "mesh")
    args = ap.parse_args(argv)

    dev = device_info(args.chips)
    from repro.persist.xla_cache import enable_jax_compile_cache
    cache_dir = enable_jax_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"[cache] jax compilation cache at {cache_dir}: {entries} "
          "entries at start", flush=True)
    ctx = load(args.sf, args.seed)
    if args.chips == 4:
        run_parallel(ctx, args.chips)
    else:
        run_queries(ctx)
        run_templates(ctx)
        run_persist(ctx)
        run_serve(ctx, args.seed)
        run_heterogeneous(ctx)
    check_no_degrade_events()
    print(f"[done] bytes_in_use={bytes_in_use()}", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
