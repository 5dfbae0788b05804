"""Plain NumPy answers to the TPC-H queries the benchmark runs.

An implementation of the same semantics as ``repro.relational.queries``
that imports nothing of the program: it reads the generated arrays
(``data/tpch.py``) directly, joins by dense key lookups and groups with
``np.bincount``, all in float64.  It returns the *whole* answer of a
query before any ``limit``, so that the comparison can look every row
the program returned up by its key.

``Reference(tables, rnd)`` with ``rnd`` rounding a float array computes
the same answers in a lower precision: every float column, literal,
parameter and per-row float result passes through ``rnd``.  The
benchmark's control uses it with bfloat16 rounding (``BF16``).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import numpy as np

from benchmarks.flare_bench.data.tpch import Tables, date, order_index

Answer = Dict[str, np.ndarray]


def _bf16(x):
    import ml_dtypes
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


#: Rounding of the control: float values held and computed in bfloat16.
BF16: Callable[[Any], Any] = _bf16

#: Key columns of each answer (compared exactly; rows are matched by
#: them), and the row limit and ranking column of the top-k queries.
KEYS = {"q1": ("l_returnflag", "l_linestatus"), "q3": ("l_orderkey",),
        "q4": ("o_orderpriority",), "q5": ("n_name",), "q6": (),
        "q10": ("o_custkey",), "q13": ("c_count",), "q14": (), "q19": (),
        "q22": ("c_nationkey",)}
TOPK = {"q3": (10, "revenue"), "q10": (20, "revenue")}
#: Row order of each answer: (column, +1 ascending or -1 descending).
ORDER = {"q1": (("l_returnflag", 1), ("l_linestatus", 1)),
         "q3": (("revenue", -1), ("o_orderdate", 1)),
         "q4": (("o_orderpriority", 1),), "q5": (("revenue", -1),),
         "q10": (("revenue", -1),),
         "q13": (("custdist", -1), ("c_count", -1)),
         "q22": (("c_nationkey", 1),)}


def present(query: str, answer: Answer) -> Answer:
    """A whole answer as the query returns it: in its row order, cut to
    its limit."""
    spec = ORDER.get(query, ())
    n = len(next(iter(answer.values())))
    order = np.arange(n)
    for name, sign in reversed(spec):  # least significant key first
        col = answer[name][order]
        if col.dtype == object:
            ranks = np.unique(col, return_inverse=True)[1]
            keyv = ranks * sign
        else:
            keyv = np.asarray(col, np.float64) * sign
        order = order[np.argsort(keyv, kind="stable")]
    limit = TOPK.get(query, (None,))[0]
    if limit is not None:
        order = order[:limit]
    return {k: v[order] for k, v in answer.items()}


class Reference:
    def __init__(self, tables: Tables,
                 rnd: Optional[Callable[[Any], Any]] = None):
        self.t = tables
        self.r = rnd or (lambda x: np.asarray(x, np.float64))

    # -- access -----------------------------------------------------------

    def f(self, table: str, name: str) -> np.ndarray:
        """A float column, in this reference's precision."""
        return self.r(self.t[table][name].data)

    def i(self, table: str, name: str) -> np.ndarray:
        return self.t[table][name].data

    def code(self, table: str, name: str, value: str) -> int:
        return self.t[table][name].dictionary.index(value)

    def codes(self, table: str, name: str, pred) -> np.ndarray:
        d = self.t[table][name].dictionary
        return np.array([k for k, s in enumerate(d) if pred(s)], np.int64)

    def names(self, table: str, name: str, codes) -> np.ndarray:
        d = np.asarray(self.t[table][name].dictionary, dtype=object)
        return d[np.asarray(codes, np.int64)]

    def rev(self, m) -> np.ndarray:
        r = self.r
        return r(self.f("lineitem", "l_extendedprice")[m]
                 * r(1.0 - self.f("lineitem", "l_discount")[m]))

    def run(self, query: str, params: Optional[Dict[str, Any]] = None
            ) -> Answer:
        return getattr(self, query)(**(params or {}))

    # -- queries ----------------------------------------------------------

    def q1(self) -> Answer:
        r = self.r
        m = self.i("lineitem", "l_shipdate") <= date("1998-12-01") - 90
        # groups 0..5 by (returnflag, linestatus); filtered-out rows in 6
        g = np.where(m, self.i("lineitem", "l_returnflag").astype(np.int64)
                     * 2 + self.i("lineitem", "l_linestatus"), 6)
        qty = self.f("lineitem", "l_quantity")
        price = self.f("lineitem", "l_extendedprice")
        disc = self.f("lineitem", "l_discount")
        disc_price = r(price * r(1.0 - disc))
        charge = r(disc_price * r(1.0 + self.f("lineitem", "l_tax")))
        cnt = np.bincount(g, minlength=7)[:6]
        keep = np.nonzero(cnt)[0]

        def s(w):
            return np.bincount(g, weights=w, minlength=7)[keep]
        n = cnt[keep]
        sum_qty, sum_price = s(qty), s(price)
        return {"l_returnflag": self.names("lineitem", "l_returnflag",
                                           keep // 2),
                "l_linestatus": self.names("lineitem", "l_linestatus",
                                           keep % 2),
                "sum_qty": sum_qty, "sum_base_price": sum_price,
                "sum_disc_price": s(disc_price), "sum_charge": s(charge),
                "avg_qty": sum_qty / n, "avg_price": sum_price / n,
                "avg_disc": s(disc) / n, "count_order": n}

    def q3(self) -> Answer:
        cust_ok = np.concatenate([[False], self.i("customer", "c_mktsegment")
                                  == self.code("customer", "c_mktsegment",
                                               "BUILDING")])
        odate = self.i("orders", "o_orderdate")
        ord_ok = (odate < date("1995-03-15")) \
            & cust_ok[self.i("orders", "o_custkey")]
        lok = self.i("lineitem", "l_orderkey")
        row = order_index(lok)
        m = (self.i("lineitem", "l_shipdate") > date("1995-03-15")) \
            & ord_ok[row]
        revenue = np.bincount(row[m], weights=self.rev(m),
                              minlength=len(odate))
        rows = np.unique(row[m])
        return {"l_orderkey": self.i("orders", "o_orderkey")[rows],
                "revenue": revenue[rows],
                "o_orderdate": odate[rows],
                "o_shippriority": self.i("orders", "o_shippriority")[rows]}

    def q4(self) -> Answer:
        odate = self.i("orders", "o_orderdate")
        late = (self.i("lineitem", "l_commitdate")
                < self.i("lineitem", "l_receiptdate"))
        has_late = np.zeros(len(odate), bool)
        has_late[order_index(self.i("lineitem", "l_orderkey")[late])] = True
        m = ((odate >= date("1993-07-01")) & (odate < date("1993-10-01"))
             & has_late)
        pr = self.i("orders", "o_orderpriority")[m]
        cnt = np.bincount(pr, minlength=len(
            self.t["orders"]["o_orderpriority"].dictionary))
        keep = np.nonzero(cnt)[0]
        return {"o_orderpriority": self.names("orders", "o_orderpriority",
                                              keep),
                "order_count": cnt[keep]}

    def q5(self) -> Answer:
        odate = self.i("orders", "o_orderdate")
        ord_ok = (odate >= date("1994-01-01")) & (odate < date("1995-01-01"))
        row = order_index(self.i("lineitem", "l_orderkey"))
        cust = self.i("orders", "o_custkey")[row]
        c_nat = np.concatenate([[-1], self.i("customer", "c_nationkey")])[
            cust]
        s_nat = self.i("supplier", "s_nationkey")[
            self.i("lineitem", "l_suppkey") - 1]
        asia = self.code("region", "r_name", "ASIA")
        in_asia = self.i("nation", "n_regionkey")[s_nat] == asia
        m = ord_ok[row] & (c_nat == s_nat) & in_asia
        revenue = np.bincount(s_nat[m], weights=self.rev(m), minlength=25)
        keep = np.unique(s_nat[m])
        return {"n_name": self.names("nation", "n_name",
                                     self.i("nation", "n_name")[keep]),
                "revenue": revenue[keep]}

    def q6(self, date_lo=date("1994-01-01"), date_hi=date("1995-01-01"),
           disc_lo=0.05, disc_hi=0.07, qty_hi=24.0) -> Answer:
        r = self.r
        ship = self.i("lineitem", "l_shipdate")
        disc = self.f("lineitem", "l_discount")
        m = ((ship >= date_lo) & (ship < date_hi) & (disc >= r(disc_lo))
             & (disc <= r(disc_hi))
             & (self.f("lineitem", "l_quantity") < r(qty_hi)))
        w = r(self.f("lineitem", "l_extendedprice")[m] * disc[m])
        return {"revenue": np.array([w.sum()])}

    def q10(self) -> Answer:
        n_cust = len(self.i("customer", "c_custkey"))
        odate = self.i("orders", "o_orderdate")
        ord_ok = (odate >= date("1993-10-01")) & (odate < date("1994-01-01"))
        row = order_index(self.i("lineitem", "l_orderkey"))
        m = (self.i("lineitem", "l_returnflag")
             == self.code("lineitem", "l_returnflag", "R")) & ord_ok[row]
        cust = self.i("orders", "o_custkey")[row[m]]
        revenue = np.bincount(cust, weights=self.rev(m),
                              minlength=n_cust + 1)
        keys = np.unique(cust)
        nat = self.i("customer", "c_nationkey")[keys - 1]
        return {"o_custkey": keys.astype(np.int32),
                "revenue": revenue[keys],
                "c_acctbal": self.f("customer", "c_acctbal")[keys - 1],
                "n_name": self.names("nation", "n_name",
                                     self.i("nation", "n_name")[nat])}

    def q13(self) -> Answer:
        n_cust = len(self.i("customer", "c_custkey"))
        special = self.codes("orders", "o_comment",
                             lambda s: "special" in s and "requests"
                             in s[s.index("special"):])
        m = ~np.isin(self.i("orders", "o_comment"), special)
        per_cust = np.bincount(self.i("orders", "o_custkey")[m],
                               minlength=n_cust + 1)
        c_count = per_cust[self.i("customer", "c_custkey")]
        dist = np.bincount(c_count)
        keep = np.nonzero(dist)[0]
        return {"c_count": keep.astype(np.int32), "custdist": dist[keep]}

    def q14(self) -> Answer:
        r = self.r
        ship = self.i("lineitem", "l_shipdate")
        m = (ship >= date("1995-09-01")) & (ship < date("1995-10-01"))
        promo_codes = self.codes("part", "p_type",
                                 lambda s: s.startswith("PROMO"))
        ptype = self.i("part", "p_type")[
            self.i("lineitem", "l_partkey")[m] - 1]
        rev = self.rev(m)
        promo = np.where(np.isin(ptype, promo_codes), rev, 0.0).sum()
        total = rev.sum()
        return {"promo_revenue": np.array([r(100.0 * promo) / total])}

    def q19(self) -> Answer:
        r = self.r
        pk = self.i("lineitem", "l_partkey") - 1
        brand = self.i("part", "p_brand")[pk]
        cont = self.i("part", "p_container")[pk]
        size = self.i("part", "p_size")[pk]
        qty = self.f("lineitem", "l_quantity")

        def branch(b, containers, qlo, qhi, smax):
            return ((brand == self.code("part", "p_brand", b))
                    & np.isin(cont, [self.code("part", "p_container", c)
                                     for c in containers])
                    & (qty >= r(qlo)) & (qty <= r(qhi))
                    & (size >= 1) & (size <= smax))
        b1 = branch("Brand#12", ["SM CASE", "SM BOX", "SM PACK", "SM PKG"],
                    1.0, 11.0, 5)
        b2 = branch("Brand#23", ["MED BAG", "MED BOX", "MED PKG",
                                 "MED PACK"], 10.0, 20.0, 10)
        b3 = branch("Brand#34", ["LG CASE", "LG BOX", "LG PACK", "LG PKG"],
                    20.0, 30.0, 15)
        mode = np.isin(self.i("lineitem", "l_shipmode"),
                       [self.code("lineitem", "l_shipmode", s)
                        for s in ("AIR", "REG AIR")])
        instr = self.i("lineitem", "l_shipinstruct") == self.code(
            "lineitem", "l_shipinstruct", "DELIVER IN PERSON")
        m = (b1 | b2 | b3) & mode & instr
        return {"revenue": np.array([self.rev(m).sum()])}

    def q22(self, acctbal_min: float) -> Answer:
        n_cust = len(self.i("customer", "c_custkey"))
        has_order = np.zeros(n_cust + 1, bool)
        has_order[self.i("orders", "o_custkey")] = True
        bal = self.f("customer", "c_acctbal")
        m = (bal > self.r(acctbal_min)) & ~has_order[1:]
        nat = self.i("customer", "c_nationkey")[m]
        cnt = np.bincount(nat, minlength=25)
        tot = np.bincount(nat, weights=bal[m], minlength=25)
        keep = np.nonzero(cnt)[0]
        return {"c_nationkey": keep.astype(np.int32), "numcust": cnt[keep],
                "totacctbal": tot[keep]}

    def q22_binding(self) -> Dict[str, float]:
        """q22's scalar subquery (average positive account balance) as
        its parameter, moved to half a cent below the next cent: the
        balances are whole cents, so the same customers pass as with
        the average itself, and none lies within float32 rounding of
        the threshold."""
        bal = self.t["customer"]["c_acctbal"].data
        avg = bal[bal > 0.0].mean()
        return {"acctbal_min": float(np.ceil(avg * 100.0) / 100.0 - 0.005)}
