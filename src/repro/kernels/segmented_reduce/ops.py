"""Public wrapper: padding and block sizing for the grouped-sum kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import should_interpret
from repro.kernels.segmented_reduce import kernel as K

_should_interpret = should_interpret  # backward-compatible private alias


@functools.partial(jax.jit,
                   static_argnames=("num_groups", "block_rows", "interpret"))
def segmented_sum(values: jnp.ndarray, codes: jnp.ndarray, num_groups: int,
                  block_rows: int = K.DEFAULT_BLOCK_ROWS,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Group sums of 1-D ``values`` by 1-D int ``codes`` in [0, G).

    ``interpret=None`` picks the mode from the backend (Pallas interpret
    everywhere except TPU); pass an explicit bool to force it.  A group
    domain over ``MAX_GROUPS`` raises
    :class:`repro.kernels.KernelBudgetError`: the caller keeps the
    scatter lowering for it (``jax.ops.segment_sum``).
    """
    if interpret is None:
        interpret = should_interpret()
    n = values.shape[0]
    if n < block_rows * K.LANES:
        block_rows = max(1, n // K.LANES)
    per_block = block_rows * K.LANES
    padded = (n + per_block - 1) // per_block * per_block
    v = jnp.pad(values.astype(jnp.float32), (0, padded - n))
    c = jnp.pad(codes.astype(jnp.int32), (0, padded - n))
    out = K.segmented_sum(v.reshape(-1, K.LANES), c.reshape(-1, K.LANES),
                          num_groups, block_rows, interpret)
    return out[0]
