"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

Each kernel lives in its own subpackage:

* ``filter_agg``        -- the paper's TPC-H Q6 fused scan (Fig. 3),
* ``segmented_reduce``  -- grouped aggregation over dense group codes (Q1),
* ``flash_attention``   -- blocked online-softmax attention (LM prefill),
* ``decode_attention``  -- single-token GQA attention over a long KV cache.

Layout per subpackage: ``kernel.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jit'd public wrapper with padding/fallback), ``ref.py``
(pure-jnp oracle used by the allclose sweep tests).

Kernels execute with ``interpret=True`` on CPU and
compile natively on TPU; ``ops`` picks the mode from the backend via
:func:`should_interpret` -- the ONE place the fallback policy lives
(the native dispatch pass uses it too).
"""
import jax


class KernelBudgetError(ValueError):
    """A kernel was invoked outside its static resource envelope (group
    domain over ``MAX_GROUPS``, malformed block geometry, ...).

    Raised by explicit checks -- never ``assert`` -- so the guards
    survive ``python -O``.  The native dispatch eligibility layer
    (``repro.native.patterns``) screens these limits *before* emitting a
    kernel and routes over-budget fragments to the scatter/XLA
    fallbacks; seeing this exception at runtime means a caller bypassed
    eligibility."""


def should_interpret() -> bool:
    """Pallas interpret-mode fallback: anything that is not a TPU."""
    return jax.default_backend() != "tpu"
