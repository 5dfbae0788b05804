"""One run of one cell: set-up, measured window, check, result line.

Everything that belongs to one configuration, traffic mix, per-layer
metric or cell lives in a file of its own, found by the name
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``: the deployment (scale factor, chips,
  source, cuts, guarantees);
* ``traffic/<mix>.json``: the parameters ``traffic.py`` reads;
* ``layer_metrics/<metric>.py``: a reader ``read(run) -> float | None``;
* ``limits/<cell>.json``: the limit of each number the check compares.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def spec() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find(items: List[Dict[str, Any]], name: str) -> Dict[str, Any]:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"{name!r} is not in BENCHMARK.json")


def cell_files(workload: str) -> Tuple[Dict[str, Any], Dict[str, Any],
                                       Dict[str, Any], Dict[str, Any]]:
    """(BENCHMARK.json, the cell's entry, its configuration, its mix)."""
    bench = spec()
    cell = find(bench["workloads"], workload)
    config = load_json(os.path.join(
        ROOT, find(bench["configs"], cell["config"])["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return bench, cell, config, mix


def device_info(chips: int) -> Dict[str, Any]:
    """The device JAX reports.  Raises :class:`NoChip` unless it is a
    TPU with at least ``chips`` chips: the benchmark never falls back
    to the CPU."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoChip(f"JAX platform is {info['platform']!r}, not a TPU")
    if info["count"] < chips:
        raise NoChip(f"{chips} chips needed, {info['count']} found")
    return info


def memory_peak_bytes(count: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:count]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def metric_reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "flare_bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts JAX's tracing and compile events while ``on``, and the
    persistent compilation cache's hits and misses."""

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        self.cache = {"cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event: str, duration: float, **kw) -> None:
        if self.on and event.startswith("/jax/core/compile/"):
            self.count += 1

    def _event(self, event: str, **kw) -> None:
        name = event.rsplit("/", 1)[-1]
        if name in self.cache:
            self.cache[name] += 1


def kernel_faults(compiled_list, platform: str) -> int:
    """Queries that degraded to a weaker engine, plus native kernels not
    compiled for the chip (interpret mode runs only off the TPU)."""
    bad = 0
    for c in compiled_list:
        bad += len(c.stats.degraded)
        rep = c.stats.dispatch
        if rep is not None and platform == "tpu":
            bad += sum(1 for d in rep.fired if d.mode != "pallas")
    from repro import obs
    bad += int(obs.snapshot()["resilience"]["degrade"]["events"])
    return bad


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, config: Optional[Dict[str, Any]] = None,
             mix: Optional[Dict[str, Any]] = None,
             require_chip: Callable[[int], Dict[str, Any]] = device_info,
             ) -> Dict[str, Any]:
    """Run cell ``workload`` once and return its result line.

    ``config`` and ``mix`` default to the cell's files; a test passes
    smaller ones, and a ``require_chip`` that accepts the CPU."""
    from benchmarks.flare_bench import compare as C
    from benchmarks.flare_bench import traffic as TR
    from benchmarks.flare_bench.reference import Reference
    from benchmarks.flare_bench.tables import generate, to_tables

    bench, cell, cell_config, cell_mix = cell_files(workload)
    config = config or cell_config
    mix = mix or cell_mix
    limits = load_json(os.path.join(HERE, "limits", workload + ".json"))
    device = require_chip(int(cell["chips"]))

    # JAX's compilation cache at one fixed path inside the checkout; the
    # program's own store stays off, so set-up is the same every run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    os.environ.pop("FLARE_CACHE_DIR", None)
    from repro.persist.xla_cache import enable_jax_compile_cache
    enable_jax_compile_cache()
    # no size limit: a limit turns on JAX's LRU eviction, whose access
    # time files went missing on the chip's machine and then made every
    # later cache write fail
    import jax
    jax.config.update("jax_compilation_cache_max_size", -1)
    counter = CompileCounter()

    from repro.core import FlareContext
    t = time.perf_counter()
    raw = generate(config, seed)
    gen_s = time.perf_counter() - t
    ctx = FlareContext()
    for name, tbl in to_tables(raw).items():
        ctx.register(name, tbl)
    t = time.perf_counter()
    ctx.preload()
    load_s = time.perf_counter() - t
    reference = Reference(raw)
    rng = np.random.default_rng(seed)
    t = time.perf_counter()
    loop = TR.make(mix, ctx, reference, rng)
    loop.warm()
    warm_s = time.perf_counter() - t
    compiled = loop.all_compiled()
    compile_s = sum(c.stats.lower_s + c.stats.compile_s for c in compiled)
    # set-up as a deployment pays it: process start to the first timed
    # request, less the benchmark's own drawing of the tables
    setup_s = time.perf_counter() - t_start - gen_s
    for c in compiled:
        log(f"[compile] {c.stats.engine} lower_s={c.stats.lower_s:.3f} "
            f"compile_s={c.stats.compile_s:.3f} "
            f"key={str(c.cache_key[1])[:60]!r}")
    log(f"[setup] generate_s={gen_s:.3f} preload_s={load_s:.3f} "
        f"compile_and_warm_s={warm_s:.3f} compile_s={compile_s:.3f} "
        f"setup_s={setup_s:.3f} jax_cache={counter.cache}")

    if trace:
        trace_dir = os.path.join(ROOT, ".bench_traces", workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        tr = mix["trace"]
        tracer = TR.Tracer(trace_dir, tr["skip_s"], tr["length_s"])
    else:
        tracer = TR.Tracer(None, 0.0, 0.0)
    counter.count, counter.on = 0, True
    measured = loop.measure(seconds, tracer)
    counter.on = False
    faults = kernel_faults(compiled, device["platform"])
    peak = memory_peak_bytes(int(cell["chips"]))
    log(f"[window] compiles_in_window={counter.count} "
        + " ".join(f"{k}={v}" for k, v in measured.items()))
    answers = loop.sample(rng)
    loop.close()
    del loop, compiled, ctx
    gc.collect()

    # the check, on the host, once the program's state is freed
    t = time.perf_counter()
    want: Dict[Any, Any] = {}
    results = []
    for query, params, rows in answers:
        key = (query, tuple(sorted(params.items())))
        if key not in want:
            want[key] = reference.run(query, params)
        results.append(C.compare(query, rows, want[key]))
    numbers = C.merge(results)
    numbers["failed"] = float(measured["failed"])
    numbers["faults"] = float(faults)
    check_s = time.perf_counter() - t
    checks = {k: {"value": numbers[k], "limit": limits[k]}
              for k in ("rel_gap", "mismatched", "failed", "faults")}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    bad = [s for _, b in results for s in b][:5]
    log(f"[check] answers={len(answers)} distinct={len(want)} "
        f"check_s={check_s:.3f}" + (f" first_differences={bad}"
                                    if bad else ""))

    run = {"device": device, "measured": measured, "compile_s": compile_s,
           "load_s": load_s, "tables": raw, "trace": None}
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(measured["attempted"]),
                           "failed": int(measured["failed"])}
    dev_out = dict(device, memory_peak_bytes=peak)
    if trace:
        from benchmarks.flare_bench import traces
        run["trace"] = traces.reduce(traces.load(trace_dir))
        metrics = {}
        for m in bench["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            value = metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if run["trace"] is not None:
            dev_out["busy_s"] = run["trace"]["busy_s"]
            dev_out["window_s"] = run["trace"]["window_s"]
    else:
        metrics = {}
        for m in bench["end_to_end"]:
            if workload not in m.get("workloads", [workload]):
                continue
            # "<quantity>.<cell kind>" reads the loop's <quantity>
            value = setup_s if m["name"] == "setup_s" else measured.get(
                m["name"].split(".")[0])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = dev_out
    if trace and run["trace"] is not None:
        out["breakdown"] = run["trace"]["breakdown"]
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r}")
    return out
