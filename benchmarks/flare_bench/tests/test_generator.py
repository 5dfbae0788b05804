"""The benchmark's generator against the TPC-H schema and formulas and
against ``relational/tpch.generate``'s columns, and its reference
against the program's ``volcano`` engine, at SF 0.01."""
import numpy as np
import pytest

from benchmarks.flare_bench.data import tpch as G
from benchmarks.flare_bench.reference import KEYS, Reference, present
from benchmarks.flare_bench.tables import to_tables

SF, SEED = 0.01, 5

#: Every column of the TPC-H schema (1.4), in the spec's order.
SPEC_COLUMNS = {
    "region": "r_regionkey r_name r_comment",
    "nation": "n_nationkey n_name n_regionkey n_comment",
    "supplier": "s_suppkey s_name s_address s_nationkey s_phone s_acctbal "
                "s_comment",
    "part": "p_partkey p_name p_mfgr p_brand p_type p_size p_container "
            "p_retailprice p_comment",
    "partsupp": "ps_partkey ps_suppkey ps_availqty ps_supplycost "
                "ps_comment",
    "customer": "c_custkey c_name c_address c_nationkey c_phone c_acctbal "
                "c_mktsegment c_comment",
    "orders": "o_orderkey o_custkey o_orderstatus o_totalprice o_orderdate "
              "o_orderpriority o_clerk o_shippriority o_comment",
    "lineitem": "l_orderkey l_partkey l_suppkey l_linenumber l_quantity "
                "l_extendedprice l_discount l_tax l_returnflag l_linestatus "
                "l_shipdate l_commitdate l_receiptdate l_shipinstruct "
                "l_shipmode l_comment",
}


@pytest.fixture(scope="module")
def both():
    from repro.relational import tpch as program_tpch
    return (G.generate(SF, SEED, full_text=("o_comment",)),
            program_tpch.generate(SF, SEED))


def test_schema_dtypes_domains_uniques_and_dictionaries(both):
    """Every spec column, and every column of the program's own
    generator with its type, its uniqueness and, for strings with a
    fixed domain, its dictionary."""
    ours, theirs = both
    tables = to_tables(ours)
    assert set(tables) == set(theirs) == set(SPEC_COLUMNS)
    for name, t in theirs.items():
        assert tables[name].schema.names == SPEC_COLUMNS[name].split()
        for f in t.schema:
            mine = tables[name].schema[f.name]
            assert (mine.dtype, mine.unique) == (f.dtype, f.unique), f.name
            assert tables[name][f.name].dtype == t[f.name].dtype, f.name
            if f.name != "o_comment" and t.dictionary(f.name) is not None:
                assert tables[name].dictionary(f.name) == t.dictionary(
                    f.name), f.name


@pytest.mark.parametrize("name", ["region", "nation", "supplier", "part",
                                  "partsupp", "customer", "orders"])
def test_same_seed_same_table(both, name):
    """A seed draws the same table again, another seed another table,
    and the dictionaries are the same for every seed."""
    ours, _ = both
    again = G.generate(SF, SEED, full_text=("o_comment",))[name]
    other = G.generate(SF, SEED + 1, full_text=("o_comment",))[name]
    moved = False
    for col, c in ours[name].items():
        np.testing.assert_array_equal(c.data, again[col].data,
                                      err_msg=f"{name}.{col}")
        assert c.dictionary == other[col].dictionary, col
        moved |= not np.array_equal(c.data, other[col].data)
    assert moved or name in ("region", "nation")


def test_lineitem_has_fixed_rows_and_the_same_value_ranges(both):
    ours, theirs = both
    li, ref = ours["lineitem"], theirs["lineitem"]
    n = len(li["l_orderkey"].data)
    assert n == G.sizes(SF)["lineitem"]
    assert n == len(G.generate(SF, SEED + 1)["lineitem"]["l_orderkey"].data)
    for col in ("l_quantity", "l_discount", "l_tax", "l_returnflag",
                "l_linestatus", "l_shipmode", "l_shipinstruct"):
        want = set(ref.column(col).decode().tolist())
        assert set(li[col].decode().tolist()) == want, col
    ship = li["l_shipdate"].data
    assert (li["l_receiptdate"].data > ship).all()
    per_order = np.bincount(G.order_index(li["l_orderkey"].data))
    assert per_order.min() == 1 and per_order.max() == 7


def test_spec_formulas(both):
    """4.2.3: retail and extended prices, part-supplier keys, sparse
    order keys, line numbers, order status and total, customer keys of
    orders, brands under their manufacturer, phones under their
    nation."""
    t, _ = both
    part, li, orders = t["part"], t["lineitem"], t["orders"]
    pk = part["p_partkey"].data.astype(np.int64)
    np.testing.assert_array_equal(
        part["p_retailprice"].data,
        (90000 + (pk // 10) % 20001 + 100 * (pk % 1000)) / 100.0)
    retail = part["p_retailprice"].data[li["l_partkey"].data - 1]
    np.testing.assert_allclose(li["l_extendedprice"].data,
                               li["l_quantity"].data * retail, rtol=1e-15)
    s = len(t["supplier"]["s_suppkey"].data)
    for tbl, p, sk in ((t["partsupp"], "ps_partkey", "ps_suppkey"),
                       (li, "l_partkey", "l_suppkey")):
        p = tbl[p].data.astype(np.int64)
        got = tbl[sk].data.astype(np.int64)
        ok = np.zeros(len(p), bool)
        for i in range(4):
            ok |= got == (p + i * (s // 4 + (p - 1) // s)) % s + 1
        assert ok.all(), sk
    okey = orders["o_orderkey"].data
    assert ((okey - 1) % 32 < 8).all() and (np.diff(okey) > 0).all()
    np.testing.assert_array_equal(G.order_index(okey), np.arange(len(okey)))
    assert (orders["o_custkey"].data % 3 != 0).all()
    rows = G.order_index(li["l_orderkey"].data)
    first = np.r_[True, rows[1:] != rows[:-1]]
    assert (li["l_linenumber"].data[first] == 1).all()
    assert (np.diff(li["l_linenumber"].data)[~first[1:]] == 1).all()
    status = li["l_linestatus"].decode()
    for k in np.random.default_rng(0).choice(len(okey), 50, replace=False):
        mine = status[rows == k]
        want = ("F" if (mine == "F").all() else "O" if (mine == "O").all()
                else "P")
        assert orders["o_orderstatus"].decode()[k] == want
        m = rows == k
        total = np.round(li["l_extendedprice"].data[m]
                         * (1 + li["l_tax"].data[m])
                         * (1 - li["l_discount"].data[m]), 2).sum()
        assert abs(orders["o_totalprice"].data[k] - total) < 0.006
    mfgr = part["p_mfgr"].decode()
    brand = part["p_brand"].decode()
    assert all(b[6] == m[-1] for b, m in zip(brand, mfgr))
    for tbl, nat, ph in (("supplier", "s_nationkey", "s_phone"),
                         ("customer", "c_nationkey", "c_phone")):
        cc = np.array([int(x[:2]) for x in t[tbl][ph].decode()])
        np.testing.assert_array_equal(cc, t[tbl][nat].data + 10)


def test_free_text_cardinality(both):
    """A ``full_text`` column holds one distinct string per row; others
    at most ``text_distinct``, the same strings for every seed."""
    t, _ = both
    oc = t["orders"]["o_comment"]
    assert len(np.unique(oc.data)) == len(oc.data) == len(oc.dictionary)
    assert all(19 <= len(s) <= 78 for s in oc.dictionary)
    small = G.generate(SF, SEED, text_distinct=64)
    for name, col in (("lineitem", "l_comment"), ("part", "p_name"),
                      ("customer", "c_address"), ("orders", "o_comment")):
        c = small[name][col]
        assert len(c.dictionary) == 64 and c.data.max() < 64, col
        assert list(c.dictionary) == sorted(c.dictionary), col
    words = t["part"]["p_name"].decode()[0].split()
    assert len(set(words)) == 5 and set(words) <= set(G.COLORS)


@pytest.mark.parametrize("query", sorted(KEYS))
def test_reference_equals_volcano(query):
    """The reference answers as the program's oracle engine does."""
    from repro.core import FlareContext
    from repro.core.compare import assert_results_equal
    from repro.relational import queries as Q
    raw = G.generate(SF, SEED, full_text=("o_comment",))
    ctx = FlareContext()
    for name, tbl in to_tables(raw).items():
        ctx.register(name, tbl)
    ref = Reference(raw)
    params = ref.q22_binding() if query == "q22" else {}
    df = Q.q22(ctx) if query == "q22" else Q.QUERIES[query](ctx)
    got = df.collect(engine="volcano", params=params or None)
    assert_results_equal(present(query, ref.run(query, params)), got,
                         rtol=1e-12, atol=0.0, msg=query)
