"""repro.obs -- unified observability for the whole query lifecycle.

One tracer, one metrics registry, one export path (DESIGN.md section
13):

* :mod:`repro.obs.trace` -- nested :func:`span` context managers
  threaded through optimize/dispatch/lower/compile/persist/execute and
  the cache, persist, and serving layers.  The buffer is off by
  default and near-free when off; enabled by ``FLARE_TRACE=1`` or
  scoped :func:`capture`.  While a ``jax.profiler`` session records,
  every span is also a ``"flare:"`` profiler annotation.
* :mod:`repro.obs.metrics` -- the process-wide :func:`snapshot` over
  every live cache, store, server, and dispatch counter (superset of
  ``engines.cache_stats()``, which is now a shim over it).
* :mod:`repro.obs.export` -- Chrome-trace JSON (Perfetto-loadable) via
  :func:`dump_chrome` / ``$FLARE_TRACE_OUT``, plus the
  ``jax.named_scope`` hook (:func:`kernel_scope`) naming operators and
  native Pallas kernels in device profiles.
* :mod:`repro.obs.analyze` -- the ``df.explain(analyze=True)`` report.
"""
from repro.obs.trace import (NULL_SPAN, TRACER, Trace, capture,  # noqa: F401
                             current_span, disable, enable, enabled, span)
from repro.obs.metrics import REGISTRY, snapshot  # noqa: F401
from repro.obs.export import (dump_chrome, install_atexit_dump,  # noqa: F401
                              kernel_scope, spans_from_chrome, to_chrome)
from repro.obs.analyze import explain_analyze  # noqa: F401

# honour $FLARE_TRACE_OUT as soon as observability is imported
install_atexit_dump()
