"""Pallas kernel sweeps: shapes x dtypes against the ref.py oracles.

All kernels run under interpret=True on this CPU container (the ops
wrappers pick the mode from the backend).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import KernelBudgetError
from repro.kernels.decode_attention import ops as DA
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.filter_agg import ops as FA
from repro.kernels.filter_agg.ref import filter_agg_q6_ref
from repro.kernels.flash_attention import ops as FL
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.segmented_reduce import ops as SR
from repro.kernels.segmented_reduce.ref import segmented_sum_ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n", [7, 127, 1000, 4096, 131072 + 13])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_filter_agg_sweep(n, dtype):
    qty = jnp.asarray(RNG.uniform(1, 50, n), dtype)
    price = jnp.asarray(RNG.uniform(900, 10000, n), dtype)
    disc = jnp.asarray(np.round(RNG.uniform(0, 0.1, n), 2), dtype)
    ship = jnp.asarray(RNG.integers(8000, 10600, n), jnp.int32)
    kw = dict(date_lo=8766, date_hi=9131, disc_lo=0.05, disc_hi=0.07,
              qty_hi=24.0)
    got = FA.filter_agg_q6(qty, price, disc, ship, **kw)
    want = filter_agg_q6_ref(qty, price, disc, ship, **kw)
    np.testing.assert_allclose(np.float64(got), np.float64(want),
                               rtol=1e-4, atol=1e-2)


def test_filter_agg_empty_predicate():
    n = 1024
    qty = jnp.full((n,), 100.0)  # nothing passes qty < 24
    z = jnp.zeros((n,))
    ship = jnp.full((n,), 9000, jnp.int32)
    got = FA.filter_agg_q6(qty, z, z, ship, date_lo=8766, date_hi=9131,
                           disc_lo=0.05, disc_hi=0.07, qty_hi=24.0)
    assert float(got) == 0.0


@pytest.mark.parametrize("n,g", [(100, 3), (1000, 6), (8192, 64),
                                 (50000, 512), (4096, 700)])
def test_segmented_sum_sweep(n, g):
    v = jnp.asarray(RNG.uniform(-5, 5, n), jnp.float32)
    c = jnp.asarray(RNG.integers(0, g, n), jnp.int32)
    if g > SR.K.MAX_GROUPS:
        # no quiet reference fallback: the caller keeps segment_sum
        with pytest.raises(KernelBudgetError, match="MAX_GROUPS"):
            SR.segmented_sum(v, c, g)
        return
    got = SR.segmented_sum(v, c, g)
    want = segmented_sum_ref(v, c, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-3)


def test_segmented_sum_over_budget_is_a_kernel_budget_error():
    """A domain over MAX_GROUPS raises the error dispatch screens for
    (and the degradation ladder may absorb), never a quiet fallback."""
    from repro.resilience import degrade
    n = SR.K.MAX_GROUPS + 1
    v = jnp.ones((256,), jnp.float32)
    c = jnp.arange(256, dtype=jnp.int32)
    with pytest.raises(KernelBudgetError) as err:
        SR.segmented_sum(v, c, n)
    assert degrade.recoverable(err.value)


@pytest.mark.parametrize("b,h,hkv,s,d", [
    (1, 2, 1, 128, 64), (2, 4, 2, 256, 64), (1, 8, 2, 128, 128),
    (2, 2, 2, 96, 32), (1, 4, 4, 64, 16),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, h, hkv, s, d, causal, dtype):
    q = jnp.asarray(RNG.standard_normal((b, h, s, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), dtype)
    got = FL.flash_attention(q, k, v, causal=causal)
    want = attention_ref(q.reshape(b * h, s, d),
                         k.reshape(b * hkv, s, d),
                         v.reshape(b * hkv, s, d),
                         causal=causal).reshape(b, h, s, d)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.float64(got), np.float64(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,h,hkv,s,d", [
    (2, 8, 2, 1024, 64), (4, 4, 4, 2048, 128), (1, 16, 8, 512, 64),
    (3, 6, 3, 96, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, h, hkv, s, d, dtype):
    q = jnp.asarray(RNG.standard_normal((b, h, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), dtype)
    lens = jnp.asarray(RNG.integers(1, s + 1, b), jnp.int32)
    got = DA.decode_attention(q, k, v, lens)
    want = decode_attention_ref(q, k, v, lens)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.float64(got), np.float64(want),
                               rtol=tol, atol=tol)


def test_decode_attention_length_masking():
    """Tokens beyond `length` must not contribute."""
    b, h, hkv, s, d = 1, 2, 1, 256, 32
    q = jnp.asarray(RNG.standard_normal((b, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
    short = DA.decode_attention(q, k, v, jnp.asarray([64], jnp.int32))
    # corrupt the tail: result must be identical
    k2 = k.at[:, :, 64:].set(99.0)
    v2 = v.at[:, :, 64:].set(-99.0)
    short2 = DA.decode_attention(q, k2, v2, jnp.asarray([64], jnp.int32))
    np.testing.assert_allclose(np.asarray(short), np.asarray(short2),
                               rtol=1e-6)


def test_flash_matches_model_attention():
    """Kernel path == the model's lax blockwise path."""
    from repro.models import layers as L
    b, h, hkv, s, d = 1, 4, 2, 256, 64
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), jnp.float32)
    cfg = L.AttnConfig(d_model=h * d, n_heads=h, n_kv=hkv, head_dim=d,
                       causal=True, block_q=64, block_k=64)
    lax_out = L._blockwise_attention(q, k, v, cfg)
    kern = FL.flash_attention(jnp.transpose(q, (0, 2, 1, 3)),
                              jnp.transpose(k, (0, 2, 1, 3)),
                              jnp.transpose(v, (0, 2, 1, 3)))
    np.testing.assert_allclose(
        np.float64(jnp.transpose(kern, (0, 2, 1, 3))),
        np.float64(lax_out), rtol=2e-3, atol=2e-3)
