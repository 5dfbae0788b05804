"""Bytes each measured query must read, against hand-computed sizes."""
import pytest

from benchmarks.flare_bench import roofline
from benchmarks.flare_bench.data import tpch as G


def _dtypes():
    t = G.generate(0.01, 0)["lineitem"]
    return {n: c.dtype for n, c in t.items()}


def test_q6_reads_four_float32_or_int32_columns():
    # l_shipdate, l_discount, l_quantity, l_extendedprice: 4 x 4 B a row
    assert roofline.query_bytes("q6", 60_000_000, _dtypes()) == 960_000_000


def test_q1_reads_seven_columns():
    # shipdate, returnflag, linestatus, quantity, extendedprice,
    # discount, tax: 7 x 4 B a row
    assert roofline.query_bytes("q1", 60_000_000, _dtypes()) \
        == 1_680_000_000


def test_share_of_the_v5e_roofline():
    # 819 GB at 819 GB/s takes 1 s: read in 2 s it is half the roofline
    assert roofline.share(819e9, 2.0, "TPU v5 lite") == pytest.approx(50.0)
    assert roofline.share(819e9, None, "TPU v5 lite") is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.share(1.0, 1.0, "TPU v9 imaginary")
