"""The one traffic generator: drives a traffic mix through the program.

A mix is a JSON file under ``traffic/`` whose ``loop`` names one of
two shapes, each with its own parameters:

* ``stream``: closed loop, one client running the mix's ``queries`` in
  order, over and over, each compiled once in set-up with ``engine``
  (``compiled`` or ``compiled-native``) and called through
  ``Compiled(**params)``, which brings the rows to the host.  Whole
  streams run until the window has passed.
* ``open``: open loop of one ``template`` at ``rate_per_s`` through a
  ``QueryServer`` whose flush worker runs every ``flush_interval_s``.
  Each request is timed from when it was due to when its rows were on
  the host.

Inputs come from the seed alone.  Every seed gets the same set of
template bindings and of arrival gaps, in an order the seed draws, so
seeds change the order of the work and not the work.
"""
from __future__ import annotations

import math
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from benchmarks.flare_bench.data.tpch import date

Binding = Dict[str, Any]


def q6_bindings() -> List[Binding]:
    """Every Q6 substitution the spec allows (TPC-H 2.4.6.3): DATE the
    first of January of 1993..1997, DISCOUNT 0.02..0.09, QUANTITY 24 or
    25; the template filters DISCOUNT +/- 0.01."""
    out = []
    for year in range(1993, 1998):
        for cents in range(2, 10):
            for qty in (24.0, 25.0):
                out.append({"date_lo": date(f"{year}-01-01"),
                            "date_hi": date(f"{year + 1}-01-01"),
                            "disc_lo": round((cents - 1) / 100.0, 2),
                            "disc_hi": round((cents + 1) / 100.0, 2),
                            "qty_hi": qty})
    return out


BINDINGS: Dict[str, Callable[[], List[Binding]]] = {"q6": q6_bindings}


def _key(b: Binding) -> Tuple:
    return tuple(sorted(b.items()))


def binding_sequence(template: str, n: int, rng: np.random.Generator
                     ) -> List[Binding]:
    """``n`` bindings: the whole binding set over and over, each pass in
    an order the seed draws."""
    pool = BINDINGS[template]()
    out: List[Binding] = []
    while len(out) < n:
        out.extend(pool[i] for i in rng.permutation(len(pool)))
    return out[:n]


def poisson_arrivals(rate: float, seconds: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Due times of ``rate * seconds`` requests with exponential gaps:
    the exponential distribution's mid-quantiles, scaled to span
    ``seconds`` exactly, in an order the seed draws."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps[rng.permutation(n)])


class Tracer:
    """Starts the profiler at the first tick after ``skip_s`` of window
    and stops it at the first tick after ``length_s`` more; a no-op
    when ``trace_dir`` is None.  ``on_start`` callbacks run just before
    tracing starts: tracing slows the host, so host-clock numbers of a
    traced run are taken from the part of the window before it."""

    def __init__(self, trace_dir: Optional[str], skip_s: float,
                 length_s: float):
        self.dir = trace_dir
        self.skip_s, self.length_s = skip_s, length_s
        self.t0: Optional[float] = None
        self.started = self.stopped = False
        self.on_start: List[Callable[[], None]] = []
        self._window = None
        self._lock = threading.Lock()

    def tick(self, now: float) -> None:
        if self.dir is None or self.stopped:
            return
        with self._lock:
            if self.t0 is None:
                self.t0 = now
            elapsed = now - self.t0
            if not self.started and elapsed >= self.skip_s:
                import jax
                for f in self.on_start:
                    f()
                jax.profiler.start_trace(self.dir)
                self._window = jax.profiler.TraceAnnotation("bench:traced")
                self._window.__enter__()
                self.started, self.t_start = True, now
            elif self.started and now - self.t_start >= self.length_s:
                self.stop()

    def stop(self) -> None:
        if self.started and not self.stopped:
            import jax
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.stopped = True


def annotate(label: str):
    import jax
    return jax.profiler.TraceAnnotation("bench:" + label)


# ---------------------------------------------------------------------------
# closed-loop query stream
# ---------------------------------------------------------------------------


class StreamLoop:
    """One client running a fixed stream of queries, closed loop."""

    def __init__(self, mix: Dict[str, Any], ctx, reference, rng):
        from repro.relational import queries as Q
        self.mix, self.ctx = mix, ctx
        self.queries: List[str] = list(mix["queries"])
        self.join_queries = set(mix.get("join_queries", ()))
        native = mix["engine"] == "compiled-native"
        self.params: Dict[str, Binding] = {}
        self.compiled = {}
        for q in self.queries:
            if q == "q22":
                df = Q.q22(ctx)
                self.params[q] = reference.q22_binding()
            else:
                df = Q.QUERIES[q](ctx)
                self.params[q] = {}
            self.compiled[q] = df.lower(engine="compiled",
                                        native=native).compile()
        self.answers: List[Tuple[str, Binding, Dict[str, np.ndarray]]] = []

    def all_compiled(self):
        return list(self.compiled.values())

    def warm(self) -> None:
        """One whole stream: every program runs once before the window."""
        for q in self.queries:
            self.compiled[q](**self.params[q])

    def measure(self, seconds: float, tracer: Tracer) -> Dict[str, Any]:
        # host seconds of each query in each stream that ran untraced
        per_query: Dict[str, List[float]] = {q: [] for q in self.queries}
        streams = 0
        t0 = time.perf_counter()
        now = t0
        while now - t0 < seconds:
            tracer.tick(now)
            traced = tracer.started and not tracer.stopped
            for q in self.queries:
                with annotate(q):
                    s = time.perf_counter()
                    rows = self.compiled[q](**self.params[q])
                    if not traced:
                        per_query[q].append(time.perf_counter() - s)
                self.answers.append((q, self.params[q], rows))
            streams += 1
            now = time.perf_counter()
        tracer.tick(now)
        tracer.stop()
        elapsed = now - t0
        untraced = len(per_query[self.queries[0]])
        join_s = sum(sum(per_query[q]) for q in self.join_queries)
        return {"stream_s": elapsed / streams, "streams": streams,
                "attempted": streams * len(self.queries), "failed": 0,
                "window_s": elapsed,
                "join_s": (join_s / untraced
                           if self.join_queries and untraced else None),
                "query_s": {q: float(np.mean(v)) for q, v in
                            per_query.items() if v}}

    def sample(self, rng) -> List[Tuple[str, Binding, Dict]]:
        return self.answers

    def close(self) -> None:
        self.compiled.clear()


# ---------------------------------------------------------------------------
# prepared templates through the query server
# ---------------------------------------------------------------------------


class ServerLoop:
    """A ``QueryServer`` serving one template to open-loop arrivals."""

    def __init__(self, mix: Dict[str, Any], ctx, reference, rng):
        from repro.relational import queries as Q
        from repro.serve import QueryServer
        self.mix = mix
        self.template = mix["template"]
        self.server = QueryServer(
            ctx, templates={self.template: Q.TEMPLATES[self.template]},
            engine=mix["engine"], max_batch=mix["max_batch"])
        self.rng = rng
        self.done: List[Tuple[Binding, Any]] = []
        self.buckets = [1 << k for k in range(
            int(math.log2(mix["max_batch"])) + 1)]
        self.server.warmup(self.buckets)

    def all_compiled(self):
        return [self.server.compiled_for(self.template)]

    def warm(self) -> None:
        """Every batch bucket once, every request of it read back, so the
        window finds each program, and each slice of a batch, compiled."""
        pool = BINDINGS[self.template]()
        for b in self.buckets:
            futs = [self.server.submit(self.template, **pool[i % len(pool)])
                    for i in range(b)]
            self.server.flush()
            for f in futs:
                f.result()

    def measure(self, seconds: float, tracer: Tracer) -> Dict[str, Any]:
        self.server.start(interval_s=self.mix["flush_interval_s"])
        st = self.server.stats

        def snapshot():
            return len(st.queue_s), st.submitted, st.batches
        marks = [snapshot()]
        tracer.on_start.append(lambda: marks.append(snapshot()))
        try:
            out = self._open(seconds, tracer)
        finally:
            tracer.stop()
            self.server.stop()
        # the server's own numbers over the window, or the part of it
        # before tracing started
        (q0, sub0, bat0), (q1, sub1, bat1) = marks[0], (
            marks[1] if len(marks) > 1 else snapshot())
        queue = st.queue_s[q0:q1]
        out["queue_wait_p95_ms"] = (float(np.percentile(queue, 95)) * 1e3
                                    if queue else None)
        out["batch_mean"] = ((sub1 - sub0) / (bat1 - bat0)
                             if bat1 > bat0 else None)
        return out

    def _open(self, seconds: float, tracer: Tracer) -> Dict[str, Any]:
        due = poisson_arrivals(float(self.mix["rate_per_s"]), seconds,
                               self.rng)
        bindings = binding_sequence(self.template, len(due), self.rng)
        pending: List[Tuple[float, Binding, Any]] = []
        cv = threading.Condition()
        late: List[float] = []
        t0 = time.perf_counter() + 0.05

        def send():
            for d, b in zip(due, bindings):
                wait = t0 + d - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                now = time.perf_counter()
                late.append(now - (t0 + d))
                tracer.tick(now)
                try:
                    with annotate("submit"):
                        fut = self.server.submit(self.template, **b)
                except Exception as err:  # refused: counts as missed
                    fut = err
                with cv:
                    pending.append((t0 + d, b, fut))
                    cv.notify()

        sender = threading.Thread(target=send, name="bench-open-loop")
        sender.start()
        lat: List[float] = []
        failed = 0
        for k in range(len(due)):
            with cv:
                while len(pending) <= k:
                    cv.wait()
                d, b, fut = pending[k]
            if isinstance(fut, Exception):
                failed += 1
                lat.append(math.inf)
                continue
            try:
                with annotate("read"):
                    rows = fut.result(timeout=60.0 + max(
                        0.0, t0 + seconds - time.perf_counter())).compact()
            except Exception:
                failed += 1
                lat.append(math.inf)
                continue
            lat.append(time.perf_counter() - d)
            self.done.append((b, rows))
        sender.join()
        lat_a = np.asarray(lat)  # a failed request is an infinite one
        return {"req_p95_ms": float(np.percentile(lat_a, 95,
                                                  method="higher")) * 1e3,
                "req_p50_ms": float(np.percentile(lat_a, 50,
                                                  method="higher")) * 1e3,
                "attempted": len(due), "failed": failed,
                "window_s": seconds,
                "late_p95_ms": float(np.percentile(late, 95)) * 1e3
                if late else 0.0}

    def sample(self, rng) -> List[Tuple[str, Binding, Dict]]:
        """Every answered request whose binding is one of
        ``check_bindings`` bindings drawn from the seed."""
        pool = BINDINGS[self.template]()
        pick = rng.choice(len(pool), min(int(self.mix["check_bindings"]),
                                         len(pool)), replace=False)
        chosen = {_key(pool[i]) for i in pick}
        return [(self.template, b, rows) for b, rows in self.done
                if _key(b) in chosen]

    def close(self) -> None:
        self.server.stop()


LOOPS = {"stream": StreamLoop, "open": ServerLoop}


def make(mix: Dict[str, Any], ctx, reference, rng):
    return LOOPS[mix["loop"]](mix, ctx, reference, rng)
