"""OptiML-analogue ML kernels (Flare Level 3, paper sections 5.2 / 6.2).

The paper compiles heterogeneous pipelines -- relational ETL feeding
iterative ML kernels -- into one program via Delite/DMLL.  Here the DMLL
role is played by the jaxpr: these kernels are pure jnp/lax functions
that the plan language embeds as :class:`repro.core.plan.IterativeKernel`
nodes (``df.train(...)``), so the relational operators and the training
loop compile into a single XLA program (DESIGN.md section 7,
examples/heterogeneous_kmeans.py).

Kernels reproduced from the paper's evaluation: k-means (Fig. 8), logistic
regression, Gaussian Discriminant Analysis (Fig. 13), plus the
``untilconverged`` / ``dist`` / ``group_by_reduce`` OptiML building blocks.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

#: Full f32 matmuls: at default precision a TPU multiplies f32 operands
#: in bf16 passes, which moves k-means assignments and gradients away
#: from what the same pipeline computes on other backends.
_F32 = jax.lax.Precision.HIGHEST

# ---------------------------------------------------------------------------
# OptiML building blocks
# ---------------------------------------------------------------------------


def dist(x: jnp.ndarray, y: jnp.ndarray, kind: str = "SQUARE") -> jnp.ndarray:
    """Pairwise distance of rows of x [n,d] against rows of y [k,d]."""
    if kind != "SQUARE":
        raise ValueError(kind)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)          # [n,1]
    y2 = jnp.sum(y * y, axis=-1)[None, :]                # [1,k]
    return x2 + y2 - 2.0 * jnp.dot(x, y.T, precision=_F32)


def until_converged(init, body: Callable, tol: float, max_iter: int,
                    diff: Callable = None):
    """``untilconverged_withdiff`` analogue as a lax.while_loop.

    ``body(state) -> state``; ``diff(old, new) -> scalar``.  Stops when
    diff < tol or max_iter reached.  Returns (state, iters).
    """
    if diff is None:
        diff = lambda a, b: jnp.max(jnp.abs(a - b))

    def cond(carry):
        _, it, d = carry
        return (it < max_iter) & (d >= tol)

    def step(carry):
        state, it, _ = carry
        new = body(state)
        return new, it + 1, diff(state, new)

    state, iters, _ = jax.lax.while_loop(
        cond, step, (init, jnp.int32(0), jnp.float32(jnp.inf)))
    return state, iters


#: Rows per chunk of :func:`segment_sum`: a group's values accumulate
#: one after another only within a chunk.
SUM_CHUNK_ROWS = 256
#: Most chunk partials (chunks x segments) :func:`segment_sum` keeps.
SUM_MAX_PARTIALS = 1 << 22


def segment_sum(values: jnp.ndarray, segment_ids: jnp.ndarray,
                num_segments: int) -> jnp.ndarray:
    """``jax.ops.segment_sum`` whose float rounding error is bounded by a
    chunk, not by the size of a group.

    A scatter-add accumulates each segment's values in sequence, so a
    segment of millions of f32 rows drifts: 15M values of 1..50 summed
    that way came out 2-5% off (TPC-H q1 at SF 10), on the CPU and on a
    TPU alike.  Here the rows split into chunks of
    :data:`SUM_CHUNK_ROWS`; each chunk's segment sums land in their own
    partial, and the partials meet in one reduction, which XLA does as
    a tree.  Integer sums are exact and take the plain scatter.
    """
    n = values.shape[0]
    chunks = min(-(-n // SUM_CHUNK_ROWS),
                 max(1, SUM_MAX_PARTIALS // max(num_segments, 1)))
    if chunks <= 1 or not jnp.issubdtype(values.dtype, jnp.floating):
        return jax.ops.segment_sum(values, segment_ids,
                                   num_segments=num_segments)
    rows = -(-n // chunks)
    chunk = jnp.arange(n, dtype=jnp.int32) // rows
    part = jax.ops.segment_sum(values, chunk * num_segments + segment_ids,
                               num_segments=chunks * num_segments)
    return part.reshape((chunks, num_segments) + values.shape[1:]).sum(0)


def group_by_reduce(keys: jnp.ndarray, values: jnp.ndarray,
                    num_groups: int,
                    weights: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """DMLL GroupByReduce: per-group sums and counts over dense int keys.

    With ``weights`` (0/1 validity weights from a relational mask, or
    fractional sample weights), sums and counts are weighted -- padded
    invalid rows contribute nothing, so the padded computation matches
    the compacted one exactly.
    """
    if weights is None:
        w = jnp.ones(keys.shape[0], values.dtype)
    else:
        w = weights.astype(values.dtype)
    vals = values * (w[:, None] if values.ndim > 1 else w)
    sums = segment_sum(vals, keys, num_groups)
    counts = segment_sum(w, keys, num_groups)
    return sums, counts


def _first_valid_rows(x: jnp.ndarray, w: jnp.ndarray, k: int) -> jnp.ndarray:
    """The first ``k`` rows with nonzero weight -- a deterministic,
    mask-invariant initialisation: padded-and-masked inputs pick the same
    rows as their compacted counterparts (differential testability).
    With fewer than ``k`` valid rows, surplus seeds duplicate the LAST
    valid row on both paths (never a padded invalid row)."""
    if x.shape[0] == 0:  # degenerate empty input: origin seeds
        return jnp.zeros((k,) + x.shape[1:], x.dtype)
    cw = jnp.cumsum((w > 0).astype(jnp.int32))
    n_valid = jnp.maximum(cw[-1], 1)
    targets = jnp.minimum(jnp.arange(1, k + 1, dtype=jnp.int32), n_valid)
    idx = jnp.searchsorted(cw, targets)
    return x[jnp.clip(idx, 0, x.shape[0] - 1)]


# ---------------------------------------------------------------------------
# kernels from the paper's evaluation
# ---------------------------------------------------------------------------


class KMeansResult(NamedTuple):
    centroids: jnp.ndarray
    assignments: jnp.ndarray
    iters: jnp.ndarray


def kmeans(x: jnp.ndarray, k: int, tol: float = 1e-3,
           max_iter: int = 100, seed: int = 0,
           weights: Optional[jnp.ndarray] = None) -> KMeansResult:
    """Paper Fig. 8: findNearestCluster + untilconverged + groupByReduce.

    ``weights`` (relational validity mask or sample weights) makes the
    update weighted and switches initialisation to the first k valid
    rows, so padded (compiled-engine) and compacted (volcano oracle)
    executions converge identically.
    """
    m = x.shape[0]
    if weights is None:
        key = jax.random.PRNGKey(seed)
        mu0 = x[jax.random.randint(key, (k,), 0, m)]
    else:
        mu0 = _first_valid_rows(x, weights, k)

    def assign(mu):
        return jnp.argmin(dist(x, mu), axis=1)

    def body(mu):
        c = assign(mu)
        sums, counts = group_by_reduce(c, x, k, weights)   # [k,d], [k]
        return sums / jnp.maximum(counts[:, None], 1.0)

    def mu_diff(a, b):
        return jnp.sum(dist(a, b).diagonal())

    mu, iters = until_converged(mu0, body, tol, max_iter, mu_diff)
    return KMeansResult(mu, assign(mu), iters)


class LogRegResult(NamedTuple):
    weights: jnp.ndarray
    iters: jnp.ndarray


def logreg(x: jnp.ndarray, y: jnp.ndarray, lr: float = 0.1,
           tol: float = 1e-4, max_iter: int = 200,
           weights: Optional[jnp.ndarray] = None) -> LogRegResult:
    """Batch-gradient logistic regression (paper Fig. 13 'LogReg').

    With ``weights``, the gradient is the weighted mean: zero-weight
    (masked) rows drop out exactly, so padded execution matches
    compacted execution.
    """
    n, d = x.shape
    sw = (jnp.ones((n,), x.dtype) if weights is None
          else weights.astype(x.dtype))
    n_eff = jnp.maximum(jnp.sum(sw), 1.0)

    def body(w):
        p = jax.nn.sigmoid(jnp.dot(x, w, precision=_F32))
        grad = jnp.dot(x.T, (p - y) * sw, precision=_F32) / n_eff
        return w - lr * grad

    w, iters = until_converged(jnp.zeros((d,), x.dtype), body, tol, max_iter)
    return LogRegResult(w, iters)


class GDAResult(NamedTuple):
    phi: jnp.ndarray
    mu0: jnp.ndarray
    mu1: jnp.ndarray
    sigma: jnp.ndarray


def gda(x: jnp.ndarray, y: jnp.ndarray,
        weights: Optional[jnp.ndarray] = None) -> GDAResult:
    """Gaussian Discriminant Analysis (paper Fig. 13 'GDA'); closed form."""
    n = x.shape[0]
    y1 = y.astype(x.dtype)
    sw = (jnp.ones((n,), x.dtype) if weights is None
          else weights.astype(x.dtype))
    n_eff = jnp.maximum(jnp.sum(sw), 1.0)
    n1 = jnp.sum(y1 * sw)
    n0 = n_eff - n1
    phi = n1 / n_eff
    mu0 = jnp.sum(x * ((1 - y1) * sw)[:, None], axis=0) / jnp.maximum(n0, 1)
    mu1 = jnp.sum(x * (y1 * sw)[:, None], axis=0) / jnp.maximum(n1, 1)
    centered = x - jnp.where(y1[:, None] > 0, mu1[None], mu0[None])
    sigma = jnp.dot(centered.T, centered * sw[:, None],
                    precision=_F32) / n_eff
    return GDAResult(phi, mu0, mu1, sigma)


def gene_barcode(counts: jnp.ndarray, barcodes: jnp.ndarray,
                 num_genes: int) -> jnp.ndarray:
    """Stand-in for the paper's 'Gene' app: per-gene barcode histogram via
    GroupByReduce (a pure data-parallel aggregation workload)."""
    sums, _ = group_by_reduce(barcodes, counts, num_genes)
    return sums


# ---------------------------------------------------------------------------
# the kernel registry behind df.train(...) / plan.IterativeKernel
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TrainKernel:
    """A named, plan-embeddable training kernel.

    ``fn(x, weights=..., **hyper)`` for unsupervised kernels,
    ``fn(x, y, weights=..., **hyper)`` when ``needs_labels``.  ``weights``
    carries the relational validity mask, so the same function runs
    padded (fused whole-query program) or compacted (interpreters) with
    identical results.  The name keys compile-cache fingerprints
    (``plan.IterativeKernel.fingerprint``), so register distinct logic
    under distinct names.
    """

    name: str
    fn: Callable[..., Any]
    needs_labels: bool = False

    def __call__(self, x, y=None, weights=None, **hyper):
        if self.needs_labels:
            if y is None:
                raise TypeError(f"kernel {self.name!r} needs labels; "
                                "pass label=... to df.train()")
            return self.fn(x, y, weights=weights, **hyper)
        return self.fn(x, weights=weights, **hyper)


TRAIN_KERNELS: Dict[str, TrainKernel] = {}


def register_kernel(name: str, fn: Callable[..., Any],
                    needs_labels: bool = False) -> TrainKernel:
    k = TrainKernel(name, fn, needs_labels)
    TRAIN_KERNELS[name] = k
    return k


def train_kernel(kernel) -> TrainKernel:
    """Resolve a kernel spec: a TrainKernel, a registered name, or a
    bare callable (registered ad hoc under its ``__name__``)."""
    if isinstance(kernel, TrainKernel):
        return kernel
    if isinstance(kernel, str):
        try:
            return TRAIN_KERNELS[kernel]
        except KeyError:
            raise ValueError(
                f"unknown training kernel {kernel!r}; registered: "
                f"{sorted(TRAIN_KERNELS)}") from None
    if callable(kernel):
        name = getattr(kernel, "__name__", None)
        if name in TRAIN_KERNELS and TRAIN_KERNELS[name].fn is kernel:
            return TRAIN_KERNELS[name]
        return TrainKernel(name or f"kernel@{id(kernel):x}", kernel)
    raise TypeError(f"cannot resolve training kernel from {kernel!r}")


register_kernel("kmeans", kmeans)
register_kernel("logreg", logreg, needs_labels=True)
register_kernel("gda", gda, needs_labels=True)
