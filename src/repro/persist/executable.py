"""Serialization of compiled query executables (DESIGN.md section 12).

Two payload tiers ride in one ``exec`` artifact:

* **native** -- the PjRt executable itself
  (``backend.serialize_executable``).  Loading is
  ``deserialize_executable``: single-digit milliseconds and ZERO XLA
  compilation, which is what lets a fresh process answer its first
  prepared query at warm-process speed.  Native code is only valid for
  the exact toolchain + topology that produced it, so this tier is
  gated on a full version-envelope match.
* **portable** -- the ``jax.export`` serialized StableHLO module.  It
  survives jaxlib upgrades and (for multi-platform lowerings) backend
  changes; loading deserializes the module and re-runs XLA compilation
  over it -- slower than the native tier but still skips the whole
  plan-lowering trace.  Gated only on the artifact format and the
  export's recorded target platforms.

Both tiers are rebuilt from the plan on any mismatch; artifacts
invalidate, they are never trusted across an envelope change.

What is NOT persisted is as important: executables here are *data-free*
(scan columns, join indexes and ``param()`` bindings are runtime
arguments; only dictionary LUTs and literals are baked in, and those
are covered by the cache key), so one artifact serves any catalog whose
table metadata matches -- the same catalog-free contract as the
in-memory :data:`repro.core.stages.Executor`.

Plans that capture Python functions (``expr.Udf``, ``MapBatches``,
``IterativeKernel``) fingerprint the function *content* -- sha256 over
bytecode, constants and closure values (:mod:`repro.core.fnhash`,
``name#token`` markers) -- so their cache keys are stable across
processes and they persist like any relational plan.  The historical
``name@id(fn)`` address markers made that impossible; the ``@hexaddr``
regex below stays as a refusal gate so any future fingerprint that
regresses to process-local identity is counted ``unsupported`` rather
than persisted under a key that could serve a stale closure.
"""
from __future__ import annotations

import re
from typing import Any, List, Optional, Sequence, Tuple

import jax

from repro.core import plan as P

#: Engines whose compiled artifacts can be persisted: single-process
#: whole-query XLA programs.  ``parallel`` executables are bound to a
#: live mesh (shard_map over concrete devices) and the interpreted
#: engines have no compiled artifact at all.
PERSISTABLE_ENGINES = ("compiled", "compiled-native")

#: ``name@processlocalid`` markers in plan/expr fingerprints
#: (repro.core.expr.fingerprint / plan.MapBatches.fingerprint).
_LOCAL_ID = re.compile(r"@[0-9a-f]+[,)\]]")


def plan_persistable(p: P.Plan) -> Tuple[bool, str]:
    """Can this plan's compiled form be addressed across processes?

    UDF / MapBatches / IterativeKernel plans are admitted: their
    fingerprints carry content hashes (``#token``), not addresses.
    Only a fingerprint that still embeds ``@hexaddr`` process-local
    identity is refused.
    """
    if _LOCAL_ID.search(p.fingerprint()):
        return False, ("plan fingerprint embeds process-local function "
                       "identity (udf)")
    return True, "ok"


def _backend():
    from jax.extend.backend import get_backend
    return get_backend()


def serialize_compiled(jax_exe: Any) -> Tuple[bytes, List[int]]:
    """Native tier: the PjRt executable's own serialization plus the
    executable's kept-argument indices (XLA prunes unused jit arguments;
    the loader must apply the same filter to the marshalled args)."""
    kept = getattr(getattr(jax_exe, "_executable", None),
                   "_kept_var_idx", None)
    if kept is None:
        raise TypeError("compiled object exposes no kept-argument set")
    data = _backend().serialize_executable(jax_exe.runtime_executable())
    return data, sorted(kept)


def deserialize_native(data: bytes) -> Any:
    """Load the native tier: a ready LoadedExecutable, no XLA compile.
    Templates compile for the default device, so that is where the
    executable loads."""
    backend = _backend()
    return backend.deserialize_executable(data, backend.local_devices()[:1])


def export_portable(fn: Any, avals: Sequence[Any]
                    ) -> Tuple[bytes, List[str]]:
    """Portable tier: ``jax.export`` the traced template function.

    Costs one extra trace at write time; buys artifacts that outlive
    the exact jaxlib build.  Returns ``(bytes, target platforms)``.
    """
    from jax import export
    exp = export.export(jax.jit(fn))(*avals)
    return exp.serialize(), list(exp.platforms)


def deserialize_portable(data: bytes) -> Any:
    """Compile the portable tier: deserialize the StableHLO module and
    AOT-compile it (XLA compile runs; plan lowering does not).  Returns
    a ``jax.stages.Compiled`` taking the template's full argument
    list."""
    from jax import export
    exp = export.deserialize(bytearray(data))
    return jax.jit(exp.call).lower(*exp.in_avals).compile()


def execute_flat(loaded: Any, args: Sequence[Any],
                 kept: Sequence[int]) -> List[Any]:
    """Run a native-tier executable over the full marshalled argument
    list, applying the executable's kept-argument filter.  Returns the
    flat output buffers (jax arrays, possibly not yet ready)."""
    kept_set = set(kept)
    return loaded.execute([a for i, a in enumerate(args)
                           if i in kept_set])
