"""Grouped aggregation over dense group codes (TPC-H Q1 hot loop).

CPU Flare aggregates Q1 with a tiny hash table updated per row.  Scatter
into a hash table is hostile to the TPU memory model; the TPU-native
formulation turns the scatter into dense, predicated compute over the
lane-aligned ``[rows, 128]`` blocks the scan already streams:

    out[g] = sum_i  values[i] * [codes[i] == g]

For each group ``g`` the block's membership mask ``codes == g`` selects
the values, a sublane reduction folds the block to one ``[1, 128]``
lane-partial row, and that row accumulates into a ``[G, n_out, 128]``
block that stays resident in VMEM across the grid.  The caller reduces
the 128 lanes once at the end.  The block is never flattened: Mosaic
cannot relayout ``[rows, 128]`` into ``[rows * 128]`` (the shape cast a
flat ``[N] x [N, G]`` one-hot matmul would need), while masks, selects
and sublane reductions keep the native ``(8, 128)`` tiling.  The mask
is exact f32 arithmetic, so the answer does not depend on matmul
precision either.

VMEM: the input blocks, the value blocks of one grid step (live across
the group loop) and the resident ``[G, n_out, 128]`` accumulator --
``repro.native.registry.vmem_estimate`` sizes ``block_rows`` for them.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 64
MAX_GROUPS = 512


def _check_limits(rows: int, block_rows: int, num_groups: int) -> None:
    """Explicit envelope checks (assert would vanish under python -O):
    the dispatch eligibility layer screens these before emitting, so a
    failure here means a caller bypassed eligibility."""
    from repro.kernels import KernelBudgetError
    if rows % block_rows != 0:
        raise KernelBudgetError(
            f"segmented_reduce: rows={rows} not a multiple of "
            f"block_rows={block_rows}")
    if num_groups > MAX_GROUPS:
        raise KernelBudgetError(
            f"segmented_reduce: group domain {num_groups} exceeds the "
            f"dense accumulator limit MAX_GROUPS={MAX_GROUPS}; route "
            "this fragment to the scatter/XLA fallback")


def init_groups(o_ref, ops: Sequence[str], fills: Sequence[float]) -> None:
    """Fill the ``[G, n_out, 128]`` accumulator with each row's identity:
    0 for "sum" rows, ``fills[j]`` for "max" rows (scalar literals --
    Pallas kernels must not capture array constants)."""
    g = o_ref.shape[0]
    for j, op in enumerate(ops):
        fill = fills[j] if op == "max" else 0.0
        o_ref[:, j:j + 1, :] = jnp.full((g, 1, LANES), fill, jnp.float32)


def accumulate_groups(o_ref, vals: Sequence[jnp.ndarray],
                      codes: jnp.ndarray, ops: Sequence[str],
                      fills: Sequence[float]) -> None:
    """Fold one block into the resident ``[G, n_out, 128]`` accumulator:
    row ``j`` of group ``g`` takes the lane partials of ``vals[j]`` over
    the elements whose code is ``g`` (a sum, or a max for "max" rows).
    ``vals`` and ``codes`` are ``[block_rows, 128]``; codes outside
    ``[0, G)`` match no group."""

    def one_group(g, carry):
        hit = codes == g
        for j, (v, op) in enumerate(zip(vals, ops)):
            if op == "sum":
                part = jnp.sum(jnp.where(hit, v, 0.0), axis=0,
                               keepdims=True)
                o_ref[g, j:j + 1, :] += part
            else:
                part = jnp.max(jnp.where(hit, v, jnp.float32(fills[j])),
                               axis=0, keepdims=True)
                o_ref[g, j:j + 1, :] = jnp.maximum(o_ref[g, j:j + 1, :],
                                                   part)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[0], one_group, 0)


def reduce_lanes(acc: jnp.ndarray, ops: Sequence[str]) -> jnp.ndarray:
    """``[G, n_out, 128]`` lane partials -> ``[n_out, G]`` group values
    (runs in XLA after the kernel)."""
    rows = [jnp.max(acc[:, j, :], axis=-1) if op == "max"
            else jnp.sum(acc[:, j, :], axis=-1)
            for j, op in enumerate(ops)]
    return jnp.stack(rows)


def segmented_sum(values: jnp.ndarray, codes: jnp.ndarray, num_groups: int,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: bool = False) -> jnp.ndarray:
    """values/codes: [rows, 128] pre-padded; returns [1, G] group sums.

    Padded elements must carry value 0 (any code)."""
    return segmented_multi_sum(
        lambda scal_ref, blocks, code_block: [blocks[0]], [values], codes,
        jnp.zeros((1,), jnp.float32), 1, num_groups, block_rows,
        interpret=interpret)


# ---------------------------------------------------------------------------
# multi-aggregate variant (repro.native dispatch target)
# ---------------------------------------------------------------------------

#: value_fn(scal_ref, col_blocks, code_block) -> one [block_rows, 128]
#: f32 array per aggregate row, already mask/predicate-weighted.  Built
#: from the query's expression tree by ``repro.native.patterns``.
ValueFn = Callable[..., List[jnp.ndarray]]


def segmented_multi_sum(value_fn: ValueFn, cols: Sequence[jnp.ndarray],
                        codes: jnp.ndarray, scal: jnp.ndarray, n_out: int,
                        num_groups: int, block_rows: int,
                        interpret: bool = False,
                        ops: Optional[Sequence[str]] = None,
                        fills: Optional[Sequence[float]] = None
                        ) -> jnp.ndarray:
    """Grouped multi-aggregate: ``out[j, g] = sum_i vals_j[i] * [code_i == g]``.

    Every aggregate of the fragment accumulates in the same pass (the
    Q1 hot loop with every sum/count/avg in one scan).  ``scal``
    carries runtime query parameters via scalar prefetch, so prepared
    templates keep ONE compilation across bindings.  Inputs are
    [rows, 128] pre-padded blocks (padded elements must carry value 0;
    out-of-range codes never match a group).  Returns [n_out, G] f32
    group values.

    ``ops`` (default all-"sum") picks the per-row accumulator: "sum"
    rows add; "max" rows (the FD ``any_`` carry-along: all group
    members share the value, take the max of the valid ones) keep a
    per-group max under the same membership mask.  ``fills[j]`` is the
    neutral element of a "max" row -- value_fn must emit it for
    excluded rows, and padded elements must carry it too.
    """
    from repro.kernels import KernelBudgetError
    rows = codes.shape[0]
    _check_limits(rows, block_rows, num_groups)
    n_cols = len(cols)
    ops = tuple(ops) if ops is not None else ("sum",) * n_out
    if len(ops) != n_out or not set(ops) <= {"sum", "max"}:
        raise KernelBudgetError(
            f"segmented_reduce: ops {ops!r} must be {n_out} entries "
            "drawn from {'sum', 'max'}")
    fills = tuple(fills) if fills is not None else (0.0,) * n_out

    def kern(scal_ref, *refs):
        col_refs = refs[:n_cols]
        code_ref, o_ref = refs[n_cols], refs[n_cols + 1]

        @pl.when(pl.program_id(0) == 0)
        def _init():
            init_groups(o_ref, ops, fills)

        code_block = code_ref[...]
        vals = value_fn(scal_ref, [r[...] for r in col_refs], code_block)
        assert len(vals) == n_out, (len(vals), n_out)
        accumulate_groups(o_ref, vals, code_block, ops, fills)

    spec = pl.BlockSpec((block_rows, LANES), lambda i, s: (i, 0))
    acc_shape = (num_groups, n_out, LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // block_rows,),
        in_specs=[spec] * (n_cols + 1),
        out_specs=pl.BlockSpec(acc_shape, lambda i, s: (0, 0, 0)),
    )
    acc = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(acc_shape, jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(scal, *cols, codes)
    return reduce_lanes(acc, ops)
