"""Fused join probe + gather + residual filter + partial aggregate.

The compiled engine's sorted-array join (paper Fig. 6: the in-memory
hash-join analogue) probes with a vectorised binary search against the
build side's sorted keys.  With the build index hoisted into the
device-resident :class:`repro.core.engines.IndexCache` (DESIGN.md
section 10), the steady-state work of a join-bearing fragment is
exactly: probe, gather the matched build row, apply the residual
predicate, accumulate.  This kernel fuses those four steps into ONE
Pallas pass over the probe stream -- the join never materialises.

Layout: probe-side columns stream as [rows, 128] lane-aligned f32
blocks (the grid walks row blocks); the cached build-side arrays
(sorted keys, sorted filter mask, sorted payload columns -- all small,
the N:1 build side) ride in whole, pinned across grid steps by a
constant-index BlockSpec; runtime query parameters arrive via scalar
prefetch like the other kernels, so prepared templates stay ONE
compilation across bindings.

Accumulation:

* keyless -- per-output [1, 128] lane partial sums (the
  ``filter_agg`` scheme), final lane-reduce in the caller;
* grouped, ``accum="onehot"`` -- the ``segmented_reduce`` per-group
  membership-mask accumulator, group domains up to MAX_GROUPS, with
  "max" rows for the FD ``any_`` carry-along;
* grouped, ``accum="scatter"`` -- ``.at[].add/.max`` into the
  [n_out, G] accumulator, for group domains far beyond the dense
  accumulator (TPC-H Q3 groups by l_orderkey: ~15k groups at SF 0.01).

Interpret mode only.  The in-kernel binary search (``probe_sorted``)
and the payload gathers are data-dependent gathers from the flattened
build arrays (``jnp.searchsorted``/``jnp.take``), which Mosaic cannot
lower (``NotImplementedError: not a fori_loop index``), and so is the
scatter accumulator.  The ``join-probe`` pattern therefore declares a
``pallas_refusal`` (``repro.native.patterns``): on a TPU dispatch
records the fallback with that reason and the fragment keeps the
generic lowering.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segmented_reduce import kernel as SR_K

LANES = 128
DEFAULT_BLOCK_ROWS = 256

#: Scatter-accumulated group domains are bounded only by the [n_out, G]
#: accumulator, not the dense VMEM one; this is a sanity backstop.
SCATTER_MAX_GROUPS = 1 << 20


def pad_build(x: jnp.ndarray, fill,
              slab_rows: Optional[int] = None) -> jnp.ndarray:
    """Pad a 1-D build-side array to a lane multiple, as a [rows, 128]
    resident block.  Key arrays pad with +inf (no probe ever matches),
    masks and payload with 0.  With ``slab_rows`` the row count is
    additionally padded to a slab multiple, so the paged layout tiles
    evenly (see :func:`join_probe_agg`)."""
    n = x.shape[0]
    padded = (n + LANES - 1) // LANES * LANES
    if slab_rows is not None:
        rows = padded // LANES
        rows = (rows + slab_rows - 1) // slab_rows * slab_rows
        padded = rows * LANES
    x = jnp.pad(x, (0, padded - n), constant_values=fill)
    return x.reshape(padded // LANES, LANES)


def probe_sorted(kb_flat: jnp.ndarray, kp: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Binary-search probe: left-insertion positions of ``kp`` in the
    sorted ``kb_flat`` plus the exact-hit mask.  Clipped so gathers stay
    in range; padded +inf build slots never report a hit."""
    idx = jnp.clip(jnp.searchsorted(kb_flat, kp), 0,
                   kb_flat.shape[0] - 1).astype(jnp.int32)
    hit = jnp.take(kb_flat, idx, mode="clip") == kp
    return idx, hit


#: body_fn(scal_ref, probe_blocks, build_arrays) -> (vals, codes).
#: ``vals`` is one [block_rows, 128] f32 array per accumulator slot,
#: already probe/predicate-weighted ("sum" slots carry 0 for excluded
#: rows, "max" slots their fill); ``codes`` is the int32 group-code
#: block (None for keyless fragments).  Built from the query's join +
#: expression tree by ``repro.native.patterns``.
BodyFn = Callable[..., Tuple[List[jnp.ndarray], Optional[jnp.ndarray]]]


def join_probe_agg(body_fn: BodyFn, probe_cols: Sequence[jnp.ndarray],
                   build_arrays: Sequence[jnp.ndarray], scal: jnp.ndarray,
                   n_out: int, block_rows: int, *,
                   num_groups: Optional[int] = None,
                   ops: Optional[Sequence[str]] = None,
                   fills: Optional[Sequence[float]] = None,
                   accum: str = "onehot",
                   slab_rows: Optional[int] = None,
                   interpret: bool = False):
    """Run the fused probe/gather/filter/aggregate pass.

    ``probe_cols`` are [rows, 128] pre-padded blocks; ``build_arrays``
    [brows, 128] resident blocks (see :func:`pad_build`).  Keyless
    (``num_groups=None``): returns ``n_out`` [1, 128] lane partials.
    Grouped: returns the [n_out, G] f32 group accumulator.

    ``slab_rows`` selects the **paged** build layout for build sides too
    large for whole-VMEM residency: the grid grows a slab dimension and
    each build array streams HBM->VMEM one ``[slab_rows, 128]`` slab at
    a time (Pallas double-buffers the loads), with the slab dimension
    outermost so every slab is paged in once and all probe blocks
    stream against it.  Correctness needs no re-merge: each contiguous
    slab of the globally sorted build keys is itself sorted, a key
    matches in exactly one slab (``probe_sorted`` misses elsewhere, and
    the +inf padding never matches), so out-of-slab rows contribute the
    neutral element and the accumulator composes across slabs exactly
    like extra grid steps.
    """
    from repro.kernels import KernelBudgetError
    rows = probe_cols[0].shape[0]
    if rows % block_rows != 0:
        raise KernelBudgetError(
            f"join_probe: probe rows={rows} not a multiple of "
            f"block_rows={block_rows}")
    n_probe = len(probe_cols)
    n_build = len(build_arrays)
    if slab_rows is None:
        grid = (rows // block_rows,)
        pspec = pl.BlockSpec((block_rows, LANES), lambda i, s: (i, 0))
        bspecs = [pl.BlockSpec(b.shape, lambda i, s: (0, 0))
                  for b in build_arrays]
    else:
        brows = build_arrays[0].shape[0]
        if brows % slab_rows != 0:
            raise KernelBudgetError(
                f"join_probe: build rows={brows} not a multiple of "
                f"slab_rows={slab_rows} (pad with pad_build(...,"
                " slab_rows=))")
        # slab outermost (slowest): each slab pages into VMEM once,
        # every probe block streams against it before the next slab
        grid = (brows // slab_rows, rows // block_rows)
        pspec = pl.BlockSpec((block_rows, LANES), lambda b, i, s: (i, 0))
        bspecs = [pl.BlockSpec((slab_rows, LANES), lambda b, i, s: (b, 0))
                  for b_arr in build_arrays]

    def _edges():
        """(first-program, last-program) predicates over the grid."""
        if slab_rows is None:
            i = pl.program_id(0)
            return i == 0, i == pl.num_programs(0) - 1
        b, i = pl.program_id(0), pl.program_id(1)
        return ((b == 0) & (i == 0),
                (b == pl.num_programs(0) - 1)
                & (i == pl.num_programs(1) - 1))

    if num_groups is None:
        def kern(scal_ref, *refs):
            p_refs = refs[:n_probe]
            b_refs = refs[n_probe:n_probe + n_build]
            out_refs = refs[n_probe + n_build:n_probe + n_build + n_out]
            acc_refs = refs[n_probe + n_build + n_out:]
            first, last = _edges()

            @pl.when(first)
            def _init():
                for a in acc_refs:
                    a[...] = jnp.zeros_like(a)

            vals, _ = body_fn(scal_ref, [r[...] for r in p_refs],
                              [r[...] for r in b_refs])
            assert len(vals) == n_out, (len(vals), n_out)
            for j in range(n_out):
                acc_refs[j][...] += jnp.sum(vals[j], axis=0, keepdims=True)

            @pl.when(last)
            def _flush():
                for j in range(n_out):
                    out_refs[j][...] = acc_refs[j][...]

        zero_map = ((lambda i, s: (0, 0)) if slab_rows is None
                    else (lambda b, i, s: (0, 0)))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pspec] * n_probe + bspecs,
            out_specs=[pl.BlockSpec((1, LANES), zero_map)] * n_out,
            scratch_shapes=[pltpu.VMEM((1, LANES), jnp.float32)] * n_out,
        )
        return pl.pallas_call(
            kern,
            out_shape=[jax.ShapeDtypeStruct((1, LANES),
                                            jnp.float32)] * n_out,
            grid_spec=grid_spec,
            interpret=interpret,
        )(scal, *probe_cols, *build_arrays)

    # -- grouped ---------------------------------------------------------------
    if accum not in ("onehot", "scatter"):
        raise KernelBudgetError(f"join_probe: unknown accum {accum!r}")
    if num_groups > SCATTER_MAX_GROUPS:
        raise KernelBudgetError(
            f"join_probe: group domain {num_groups} exceeds "
            f"SCATTER_MAX_GROUPS={SCATTER_MAX_GROUPS}; the fragment "
            "must keep its generic XLA lowering")
    ops = tuple(ops) if ops is not None else ("sum",) * n_out
    if len(ops) != n_out or not set(ops) <= {"sum", "max"}:
        raise KernelBudgetError(
            f"join_probe: ops {ops!r} must be {n_out} entries drawn "
            "from {'sum', 'max'}")
    fills = tuple(fills) if fills is not None else (0.0,) * n_out

    zero_map = ((lambda i, s: (0, 0)) if slab_rows is None
                else (lambda b, i, s: (0, 0)))
    if accum == "onehot":
        def kern(scal_ref, *refs):
            p_refs = refs[:n_probe]
            b_refs = refs[n_probe:n_probe + n_build]
            o_ref = refs[n_probe + n_build]
            first, _ = _edges()

            @pl.when(first)
            def _init():
                SR_K.init_groups(o_ref, ops, fills)

            vals, codes = body_fn(scal_ref, [r[...] for r in p_refs],
                                  [r[...] for r in b_refs])
            assert len(vals) == n_out, (len(vals), n_out)
            SR_K.accumulate_groups(o_ref, vals, codes, ops, fills)

        acc_shape = (num_groups, n_out, LANES)
        acc_map = ((lambda i, s: (0, 0, 0)) if slab_rows is None
                   else (lambda b, i, s: (0, 0, 0)))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pspec] * n_probe + bspecs,
            out_specs=pl.BlockSpec(acc_shape, acc_map),
        )
        acc = pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct(acc_shape, jnp.float32),
            grid_spec=grid_spec,
            interpret=interpret,
        )(scal, *probe_cols, *build_arrays)
        return SR_K.reduce_lanes(acc, ops)

    def kern(scal_ref, *refs):
        p_refs = refs[:n_probe]
        b_refs = refs[n_probe:n_probe + n_build]
        o_ref, acc_ref = refs[n_probe + n_build], refs[n_probe + n_build + 1]
        first, last = _edges()

        @pl.when(first)
        def _init():
            # scalar-literal init: Pallas kernels must not capture
            # array constants
            acc_ref[...] = jnp.stack(
                [jnp.full((num_groups,), fills[j] if op == "max"
                          else 0.0, jnp.float32)
                 for j, op in enumerate(ops)])

        vals, codes = body_fn(scal_ref, [r[...] for r in p_refs],
                              [r[...] for r in b_refs])
        assert len(vals) == n_out, (len(vals), n_out)
        flat_v = jnp.stack([v.reshape(-1) for v in vals])   # [n_out, N]
        flat_c = codes.reshape(-1)                          # [N] int32
        acc = acc_ref[...]
        for j, op in enumerate(ops):
            row = acc[j]
            if op == "sum":
                row = row.at[flat_c].add(flat_v[j])
            else:
                row = row.at[flat_c].max(flat_v[j])
            acc = acc.at[j].set(row)
        acc_ref[...] = acc

        @pl.when(last)
        def _flush():
            o_ref[...] = acc_ref[...]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[pspec] * n_probe + bspecs,
        out_specs=pl.BlockSpec((n_out, num_groups), zero_map),
        scratch_shapes=[pltpu.VMEM((n_out, num_groups), jnp.float32)],
    )
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((n_out, num_groups), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(scal, *probe_cols, *build_arrays)
