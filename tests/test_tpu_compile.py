"""The native kernels compile for a TPU v5e at TPC-H SF 1 shapes.

Interpret mode accepts kernels that Mosaic (the TPU kernel compiler)
refuses, so these tests hand the kernels of the main path to the chip's
compiler against a *described* ``v5e:2x2`` topology: nothing runs and
no chip is needed, only libtpu.  Each test asserts that the program
holds the Pallas kernel (``tpu_custom_call``).

The native fragments are built by the real dispatch pass
(``rewrite_plan(..., interpret=False)``) over a small catalog, then
their emitters are traced over SF 1-sized boundary streams: the kernel
bodies are the ones the ``compiled-native`` engine runs on the chip.

The topology is described only inside the module fixtures below (never
at import), so every xdist worker collects the same tests and only the
worker that runs this file loads libtpu.
"""
import dataclasses
import importlib
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import FlareContext
from repro.core import lower as L
from repro.native import dispatch as ND
from repro.relational import queries as Q

# the package re-exports registry.patterns(), which shadows the module
PAT = importlib.import_module("repro.native.patterns")

#: official TPC-H SF 1 lineitem cardinality: the probe/scan stream of
#: every fragment below
SF1_LINEITEM = 6_001_215


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture(scope="module")
def ctx():
    c = FlareContext()
    Q.register_tpch(c, sf=0.002)
    c.preload()
    return c


def _chip_dispatch(ctx, df):
    """The dispatch pass as it runs on a TPU (``interpret=False``)."""
    return ND.rewrite_plan(ctx.optimized(df.plan), ctx.catalog,
                           interpret=False)


def _native_ops(p):
    if isinstance(p, ND.NativeOp):
        return [p]
    return [op for c in p.children() for op in _native_ops(c)]


def _compile_fragment(op, catalog, one_chip, n_rows, param_specs):
    """Trace the fragment's emitter over an ``n_rows`` boundary stream
    of described-device arrays and compile it for the chip."""
    boundary = PAT.boundary_of(op.child)
    info = dataclasses.replace(L.static_info(boundary, catalog),
                               n_rows=n_rows)
    names = sorted(info.cols)
    masked = not isinstance(boundary, L.P.Scan)

    def fn(*args):
        cols = dict(zip(names, args[:len(names)]))
        mask = args[len(names)] if masked else None
        params = dict(zip(param_specs, args[len(names) + masked:]))
        out = op.emitter(L.Stream(cols, mask, info), params, False)
        return out.cols, out.mask

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    avals = [sds((n_rows,), L._JNP_OF[info.cols[n].dtype]) for n in names]
    if masked:
        avals.append(sds((n_rows,), jnp.bool_))
    avals += [sds((), jnp.float32) for _ in param_specs]
    return jax.jit(fn).lower(*avals).compile()


def test_filter_agg_q6_compiles(one_chip, no_compile_cache):
    from repro.kernels.filter_agg import kernel as FA_K
    rows = -(-SF1_LINEITEM // (FA_K.LANES * FA_K.DEFAULT_BLOCK_ROWS)) \
        * FA_K.DEFAULT_BLOCK_ROWS

    def q6(qty, price, disc, date):
        return FA_K.filter_agg_q6(qty, price, disc, date, date_lo=8766,
                                  date_hi=9131, disc_lo=0.05,
                                  disc_hi=0.07, qty_hi=24.0)

    def sds(dtype):
        return jax.ShapeDtypeStruct((rows, FA_K.LANES), dtype,
                                    sharding=one_chip)

    compiled = jax.jit(q6).lower(sds(jnp.float32), sds(jnp.float32),
                                 sds(jnp.float32),
                                 sds(jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: SF 1 cardinalities of the fragments' boundary streams
SF1_ORDERS, SF1_CUSTOMER = 1_500_000, 150_000

#: (query, template?, pattern that fires on the chip, boundary rows at
#: SF 1, param names) -- every query whose fragment fires natively there
_FRAGMENTS = [
    ("q1", False, "grouped-agg", SF1_LINEITEM, ()),  # 8 aggs, 3 x 2 groups
    ("q6", True, "filter-scalar-agg", SF1_LINEITEM,
     ("date_lo", "date_hi", "disc_lo", "disc_hi", "qty_hi")),
    ("q14", True, "masked-filter-project", SF1_LINEITEM,
     ("date_lo", "date_hi")),
    ("q19", True, "masked-filter-project", SF1_LINEITEM,
     ("qty1", "qty2", "qty3")),
    ("q5", False, "masked-filter-project", SF1_LINEITEM, ()),  # by n_name
    ("q4", False, "masked-filter-project", SF1_ORDERS, ()),
    ("q22", True, "masked-filter-project", SF1_CUSTOMER,
     ("acctbal_min",)),
]


@pytest.mark.parametrize("qname,template,pattern,rows,params", _FRAGMENTS,
                         ids=[f[0] for f in _FRAGMENTS])
def test_native_fragment_compiles(ctx, one_chip, no_compile_cache, qname,
                                  template, pattern, rows, params):
    build = (Q.TEMPLATES if template else Q.QUERIES)[qname]
    plan, report = _chip_dispatch(ctx, build(ctx))
    assert report.fired_patterns() == [pattern], str(report)
    assert all(d.mode == "pallas" for d in report.fired), str(report)
    (op,) = _native_ops(plan)
    compiled = _compile_fragment(op, ctx.catalog, one_chip, rows,
                                 sorted(params))
    assert "tpu_custom_call" in compiled.as_text()


def test_segmented_multi_sum_q1_shape(ctx):
    """q1's fragment is the 8-aggregate grouped sum over the 3 x 2
    l_returnflag x l_linestatus domain (the shape compiled above)."""
    plan, _ = _chip_dispatch(ctx, Q.q1(ctx))
    (op,) = _native_ops(plan)
    ana = PAT._analyze(PAT.match_fragment(op.child, ctx.catalog),
                       ctx.catalog)
    assert (ana.n_out, ana.domain) == (8, 6)


@pytest.mark.parametrize("qname", ["q5", "q14", "q19"])
def test_join_probe_refused_for_the_chip(ctx, qname):
    """The join-probe kernel's probe is a data-dependent gather Mosaic
    cannot lower: dispatch for the chip records the refusal and leaves
    the fragment to another pattern or the generic lowering."""
    df = Q.QUERIES[qname](ctx)
    _, report = _chip_dispatch(ctx, df)
    assert "join-probe" not in report.fired_patterns(), str(report)
    refusal = ND.R.get_pattern("join-probe").pallas_refusal
    reasons = " ".join(d.reason for d in report.decisions)
    assert f"join-probe: {refusal}" in reasons, str(report)
    # in interpret mode the same fragment still takes the fused kernel
    _, interp = ND.rewrite_plan(ctx.optimized(df.plan), ctx.catalog,
                                interpret=True)
    assert "join-probe" in interp.fired_patterns(), str(interp)
