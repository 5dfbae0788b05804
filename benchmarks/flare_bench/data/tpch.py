"""TPC-H tables for the benchmark, drawn from a seed as NumPy arrays.

Every table has every column of the TPC-H schema (TPC-H Standard
Specification v3.0.1, 1.4), drawn at the domains and by the formulas of
4.2.3: sparse order keys (8 of every 32), ``PS_SUPPKEY``/``L_SUPPKEY``
from the part key, ``P_RETAILPRICE`` from the part key, ``L_EXTENDEDPRICE
= L_QUANTITY * P_RETAILPRICE``, ``O_TOTALPRICE`` and ``O_ORDERSTATUS``
from the order's lines, orders only for customer keys not divisible by
3, phone numbers under their nation's country code, and text from the
grammar of 4.2.2.10.  The columns ``repro.relational.tpch`` also has
keep its names and types.  Three things are the benchmark's own:

* String columns are int32 codes into a sorted dictionary, as the
  program holds every string.  A dictionary depends on the scale factor
  and never on the seed, so the programs compiled for one seed serve
  every seed (the program digests every dictionary of a scanned table
  into its programs' keys).
* Free text (comments, addresses, phone numbers, part names) draws from
  a dictionary of at most ``text_distinct`` strings, one distinct
  string per row up to that many rows.  A column named in ``full_text``
  takes one distinct string per row at any size, as the spec's nearly
  unique text does.  The device holds one code per row either way.
* ``lineitem`` has a fixed number of rows for a scale factor: the
  counts of lines per order (1 to 7, uniform, as in the spec) are a
  fixed multiset that the seed permutes.  Every seed then gives tables
  of the same shapes, and a compiled program serves every seed.

Each column is drawn from a stream of its own, keyed by the seed and the
column's name, so the draws run in threads and do not depend on their
order.  Nothing here imports the program: the benchmark's reference
(``reference.py``) reads these arrays directly, and ``tables.py`` wraps
them as the program's ``Table`` objects.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

#: Bumped whenever the arrays drawn for a (scale factor, seed) change.
VERSION = 2

#: Default size of a free-text dictionary.
TEXT_DISTINCT = 1 << 17

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
SHIPINSTRUCT = ["COLLECT COD", "DELIVER IN PERSON", "NONE",
                "TAKE BACK RETURN"]
TYPE_SYL1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_SYL2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_SYL3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINERS = [f"{a} {b}" for a in ["SM", "MED", "LG", "JUMBO", "WRAP"]
              for b in ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM"]]
#: P_NAME's words (4.2.3).
COLORS = """almond antique aquamarine azure beige bisque black blanched
blue blush brown burlywood burnished chartreuse chiffon chocolate coral
cornflower cornsilk cream cyan dark deep dim dodger drab firebrick floral
forest frosted gainsboro ghost goldenrod green grey honeydew hot indian
ivory khaki lace lavender lawn lemon light lime linen magenta maroon
medium metallic midnight mint misty moccasin navajo navy olive orange
orchid pale papaya peach peru pink plum powder puff purple red rose rosy
royal saddle salmon sandy seashell sienna sky slate smoke snow spring
steel tan thistle tomato turquoise violet wheat white yellow""".split()

#: The word classes of the text grammar (4.2.2.10), drawn uniformly.
NOUNS = """foxes ideas theodolites pinto_beans instructions dependencies
excuses platelets asymptotes courts dolphins multipliers sauternes
warthogs frets dinos attainments somas Tiresias' patterns forges braids
hockey_players frays warhorses dugouts notornis epitaphs pearls tithes
waters orbits gifts sheaves depths sentiments decoys realms pains
grouches escapades packages requests accounts deposits""".split()
VERBS = """sleep wake are cajole haggle nag use boost affix detect
integrate maintain nod was lose sublate solve thrash promise engage
hinder print x-ray breach eat grow impress mold poach serve run dazzle
snooze doze unwind kindle play hang believe doubt""".split()
ADJECTIVES = """furious sly careful blithe quick fluffy slow quiet
ruthless thin close dogged daring brave stealthy permanent enticing idle
busy regular final ironic even bold silent special pending unusual
express""".split()
ADVERBS = """sometimes always never furiously slyly carefully blithely
quickly fluffily slowly quietly ruthlessly thinly closely doggedly
daringly bravely stealthily permanently enticingly idly busily
regularly finally ironically evenly boldly silently""".split()
PREPOSITIONS = """about above according_to across after against along
alongside_of among around at atop before behind beneath beside besides
between beyond by despite during except for from in_place_of inside
instead_of into near of on outside over past since through throughout
to toward under until up upon without with within""".split()
AUXILIARIES = """do may might shall will would can could should ought_to
must will_have_to shall_have_to could_have_to should_have_to
must_have_to need_to try_to""".split()
TERMINATORS = [".", ";", ":", "?", "!", "--"]
#: The characters of a random v-string (4.2.2.7).
ALNUM = ("0123456789abcdefghijklmnopqrstuvwxyz"
         "ABCDEFGHIJKLMNOPQRSTUVWXYZ, ")

_EPOCH = np.datetime64("1970-01-01", "D")


def date(s: str) -> int:
    """'1994-01-01' -> days since 1970 (the program's DATE encoding)."""
    return int((np.datetime64(s, "D") - _EPOCH).astype(np.int64))


START_DATE = date("1992-01-01")
LAST_ORDER_DATE = date("1998-12-31") - 151
DATE_DOMAIN = date("1998-12-31") + 200  # receiptdate can pass the end
CURRENT_DATE = date("1995-06-17")


@dataclasses.dataclass
class Col:
    """One column: ``data`` as drawn, its logical type, and for strings
    the sorted dictionary that the int32 codes in ``data`` index."""

    data: np.ndarray
    dtype: str                       # int32 | float64 | date | string
    dictionary: Optional[Tuple[str, ...]] = None
    domain: Optional[int] = None
    unique: bool = False

    def decode(self) -> np.ndarray:
        if self.dictionary is None:
            return self.data
        return np.asarray(self.dictionary, dtype=object)[self.data]


Tables = Dict[str, Dict[str, Col]]


def _stream(seed: int, name: str) -> np.random.Generator:
    """The random stream of one column (or of one fixed dictionary when
    ``seed`` is None), independent of every other."""
    key = zlib.crc32(name.encode())
    if seed is None:
        return np.random.default_rng([VERSION, key])
    return np.random.default_rng([int(seed) % (1 << 64), key])


def _sorted(values: Sequence[str]) -> Tuple[str, ...]:
    return tuple(sorted(values))


def _fixed(values: Sequence[str], codes: np.ndarray) -> Col:
    """Codes into a dictionary given in sorted order."""
    return Col(codes.astype(np.int32), "string", tuple(values))


def _keyed(prefix: str, n: int) -> Tuple[str, ...]:
    """'Supplier#000000001' .. in key order, which is sorted order."""
    return tuple((f"{prefix}%09d\n" * n % tuple(range(1, n + 1)))
                 .split("\n")[:n])


# ---------------------------------------------------------------------------
# free text: dictionaries fixed for a scale factor
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def text_pool(size: int = 1 << 21) -> str:
    """``size`` characters of text from the grammar of 4.2.2.10, the same
    in every process: sentences of noun phrases, verb phrases and
    prepositional phrases over the spec's word classes."""
    rng = _stream(None, "text_pool")
    # a word's draw is the next number of its class's stream
    draws = {k: iter([ws[i].replace("_", " ") for i in rng.integers(
        0, len(ws), size // 4).tolist()]) for k, ws in (
            ("n", NOUNS), ("v", VERBS), ("j", ADJECTIVES), ("d", ADVERBS),
            ("p", PREPOSITIONS), ("x", AUXILIARIES), ("t", TERMINATORS))}
    n, v, j, d, p, x, t = (draws[k].__next__ for k in "nvjdpxt")
    forms = iter(rng.integers(0, 4, size // 2).tolist())

    def noun_phrase() -> str:
        return (n, lambda: f"{j()} {n()}", lambda: f"{j()}, {j()} {n()}",
                lambda: f"{d()} {j()} {n()}")[next(forms)]()

    def verb_phrase() -> str:
        return (v, lambda: f"{x()} {v()}", lambda: f"{v()} {d()}",
                lambda: f"{x()} {v()} {d()}")[next(forms)]()

    def prep_phrase() -> str:
        return f"{p()} the {noun_phrase()}"

    sentences = (
        lambda: f"{noun_phrase()} {verb_phrase()}",
        lambda: f"{noun_phrase()} {verb_phrase()} {prep_phrase()}",
        lambda: f"{noun_phrase()} {verb_phrase()} {noun_phrase()}",
        lambda: f"{noun_phrase()} {prep_phrase()} {verb_phrase()} "
                f"{noun_phrase()}",
        lambda: f"{noun_phrase()} {prep_phrase()} {verb_phrase()} "
                f"{prep_phrase()}")
    kinds = iter(rng.integers(0, len(sentences), size // 8).tolist())
    parts: List[str] = []
    total = 0
    while total < size:
        s = sentences[next(kinds)]() + t() + " "
        parts.append(s)
        total += len(s)
    return "".join(parts)[:size]


def _distinct(draw: Callable[[np.random.Generator, int], List[str]],
              rng: np.random.Generator, n: int) -> List[str]:
    """``n`` distinct strings from ``draw``, drawn until there are."""
    seen: Dict[str, None] = {}
    while len(seen) < n:
        seen.update(dict.fromkeys(draw(rng, int((n - len(seen)) * 1.1)
                                       + 16)))
    return list(seen)[:n]


def _text_draw(lo: int, hi: int):
    """Random substrings of the text pool of ``lo`` to ``hi`` characters
    (4.2.2.10)."""
    def draw(rng, k):
        pool = text_pool()
        lens = rng.integers(lo, hi + 1, k)
        offs = rng.integers(0, len(pool) - hi, k)
        return [pool[o:o + ln] for o, ln in zip(offs.tolist(),
                                                lens.tolist())]
    return draw


def _vstring_draw(lo: int, hi: int):
    """Random v-strings of ``lo`` to ``hi`` characters (4.2.2.7)."""
    def draw(rng, k):
        chars = np.frombuffer(ALNUM.encode(), np.uint8)[
            rng.integers(0, len(ALNUM), (k, hi))]
        lens = rng.integers(lo, hi + 1, k)
        rows = chars.view(f"S{hi}").ravel()
        return [r[:ln].decode() for r, ln in zip(rows.tolist(),
                                                 lens.tolist())]
    return draw


def _pname_draw(rng, k):
    """P_NAME: five distinct color words."""
    idx = rng.integers(0, len(COLORS), (k, 5))
    while True:
        s = np.sort(idx, axis=1)
        again = (s[:, 1:] == s[:, :-1]).any(axis=1)
        if not again.any():
            break
        idx[again] = rng.integers(0, len(COLORS), (int(again.sum()), 5))
    return [" ".join(COLORS[i] for i in row) for row in idx.tolist()]


def _phone_draw(rng, k):
    """The local part of a phone number (4.2.2.9)."""
    a = rng.integers(100, 1000, k)
    b = rng.integers(100, 1000, k)
    c = rng.integers(1000, 10000, k)
    return [f"{x}-{y}-{z}" for x, y, z in zip(a.tolist(), b.tolist(),
                                               c.tolist())]


@functools.lru_cache(maxsize=64)
def text_dictionary(name: str, kind: str, lo: int, hi: int,
                    n: int) -> Tuple[str, ...]:
    """The sorted dictionary of ``n`` distinct strings of column ``name``,
    the same for every seed."""
    draw = {"text": _text_draw(lo, hi), "vstring": _vstring_draw(lo, hi),
            "pname": _pname_draw}[kind]
    return _sorted(_distinct(draw, _stream(None, name), n))


@functools.lru_cache(maxsize=16)
def phone_dictionary(name: str, per_nation: int) -> Tuple[str, ...]:
    """``per_nation`` numbers under each country code 10..34 (4.2.2.9),
    in sorted order: nation ``k``'s block starts at ``k * per_nation``."""
    local = _sorted(_distinct(_phone_draw, _stream(None, name),
                              per_nation))
    return tuple(f"{k + 10}-{s}" for k in range(25) for s in local)


def supplier_comments(n: int, sf: float) -> Tuple[str, ...]:
    """S_COMMENT: ``n`` distinct texts of 25 to 100 characters, of which
    ``5 * sf`` hold 'Customer ... Complaints' and as many 'Customer ...
    Recommends' (4.2.3), in sorted order."""
    rng = _stream(None, "s_comment")
    base = _distinct(_text_draw(25, 100), rng, n)
    k = min(int(5 * sf), n // 2)
    for j, word in enumerate(["Complaints"] * k + ["Recommends"] * k):
        s = base[j]
        cut = int(rng.integers(0, max(1, len(s) - 30)))
        base[j] = (s[:cut] + "Customer " + s[cut:cut + 8] + " " + word
                   + s[cut + 8:])[:100]
    return _sorted(dict.fromkeys(base))


# ---------------------------------------------------------------------------
# the tables
# ---------------------------------------------------------------------------


def order_keys(n_ord: int) -> np.ndarray:
    """O_ORDERKEY: the first 8 of every 32 keys (4.2.3)."""
    i = np.arange(n_ord, dtype=np.int64)
    return ((i // 8) * 32 + i % 8 + 1).astype(np.int32)


def order_index(keys: np.ndarray) -> np.ndarray:
    """The row of each order key in ``orders``."""
    k = np.asarray(keys, np.int64) - 1
    return (k // 32) * 8 + k % 32


def sizes(sf: float) -> Dict[str, int]:
    """Rows of each table at scale factor ``sf``."""
    n_part = max(int(200_000 * sf), 50)
    n_supp = max(int(10_000 * sf), 10)
    n_cust = max(int(150_000 * sf), 30)
    n_ord = max(int(1_500_000 * sf), 100)
    per_order = np.arange(n_ord) % 7 + 1
    return {"region": 5, "nation": 25, "supplier": n_supp, "part": n_part,
            "partsupp": 4 * n_part, "customer": n_cust, "orders": n_ord,
            "lineitem": int(per_order.sum())}


def _cents(rng, lo: int, hi: int, n: int) -> np.ndarray:
    """A money value drawn uniformly in whole cents, ``lo``..``hi``."""
    return rng.integers(lo, hi + 1, n).astype(np.float64) / 100.0


def _supp_of(part: np.ndarray, i: np.ndarray, n_supp: int) -> np.ndarray:
    """PS_SUPPKEY / L_SUPPKEY of part key ``part``, supplier ``i`` of 4."""
    p = part.astype(np.int64)
    s = (p + i.astype(np.int64) * (n_supp // 4 + (p - 1) // n_supp)) \
        % n_supp + 1
    return s.astype(np.int32)


def generate(sf: float, seed: int, full_text: Sequence[str] = (),
             text_distinct: int = TEXT_DISTINCT) -> Tables:
    """All eight tables at scale factor ``sf`` from ``seed``."""
    n = sizes(sf)
    n_part, n_supp, n_cust, n_ord, n_li = (
        n["part"], n["supplier"], n["customer"], n["orders"],
        n["lineitem"])
    i32 = np.int32
    full = set(full_text)
    jobs: Dict[str, Callable[[], Col]] = {}

    def job(name):
        def put(fn):
            jobs[name] = fn
            return fn
        return put

    def text(name, kind, lo, hi, rows):
        """A free-text column: a permutation of a dictionary of ``rows``
        strings, or codes uniform over ``text_distinct`` of them."""
        @job(name)
        def _():
            rng = _stream(seed, name)
            d = rows if name in full else min(rows, text_distinct)
            dic = text_dictionary(name, kind, lo, hi, d)
            codes = (rng.permutation(rows) if d == rows
                     else rng.integers(0, d, rows))
            return _fixed(dic, codes)

    def phone(name, nation_col):
        @job(name)
        def _():
            rows = len(nation_col.data)
            per = max(1, min(rows, text_distinct) // 25)
            codes = (nation_col.data.astype(np.int64) * per
                     + _stream(seed, name).integers(0, per, rows))
            return _fixed(phone_dictionary(name, per), codes)

    def ints(name, lo, hi, rows, domain=None, dtype="int32"):
        """Uniform integers ``lo``..``hi``."""
        @job(name)
        def _():
            return Col(_stream(seed, name).integers(lo, hi + 1, rows,
                                                    dtype=i32),
                       dtype, domain=domain)

    def choice(name, values, rows):
        @job(name)
        def _():
            return _fixed(_sorted(values), _stream(seed, name).integers(
                0, len(values), rows))

    def money(name, lo, hi, rows):
        @job(name)
        def _():
            return Col(_cents(_stream(seed, name), lo, hi, rows), "float64")

    # draws that depend on no other column, run in threads
    ints("s_nationkey", 0, 24, n_supp, domain=25)
    money("s_acctbal", -99_999, 999_999, n_supp)
    text("s_address", "vstring", 10, 40, n_supp)
    ints("c_nationkey", 0, 24, n_cust, domain=25)
    money("c_acctbal", -99_999, 999_999, n_cust)
    choice("c_mktsegment", SEGMENTS, n_cust)
    text("c_address", "vstring", 10, 40, n_cust)
    text("c_comment", "text", 29, 116, n_cust)
    text("p_name", "pname", 0, 0, n_part)
    ints("p_mfgr_m", 1, 5, n_part)
    ints("p_brand_n", 1, 5, n_part)
    ints("p_size", 1, 50, n_part, domain=51)
    choice("p_container", CONTAINERS, n_part)
    text("p_comment", "text", 5, 22, n_part)
    ints("ps_availqty", 1, 9_999, 4 * n_part)
    money("ps_supplycost", 100, 100_000, 4 * n_part)
    text("ps_comment", "text", 49, 198, 4 * n_part)
    ints("o_orderdate", START_DATE, LAST_ORDER_DATE, n_ord,
         domain=DATE_DOMAIN, dtype="date")
    choice("o_orderpriority", PRIORITIES, n_ord)
    ints("o_clerk", 0, max(int(sf * 1000), 1) - 1, n_ord)
    text("o_comment", "text", 19, 78, n_ord)
    ints("l_partkey", 1, n_part, n_li, domain=n_part + 1)
    ints("l_supp_i", 0, 3, n_li)
    ints("l_quantity", 1, 50, n_li)
    ints("l_discount", 0, 10, n_li)
    ints("l_tax", 0, 8, n_li)
    ints("l_ship_days", 1, 121, n_li)
    ints("l_commit_days", 30, 90, n_li)
    ints("l_receipt_days", 1, 30, n_li)
    ints("l_returned", 0, 1, n_li)
    choice("l_shipmode", SHIPMODES, n_li)
    choice("l_shipinstruct", SHIPINSTRUCT, n_li)
    text("l_comment", "text", 10, 43, n_li)

    @job("p_type")
    def _():
        rng = _stream(seed, "p_type")
        names = _sorted(f"{x} {y} {z}" for x in TYPE_SYL1
                        for y in TYPE_SYL2 for z in TYPE_SYL3)
        return _fixed(names, rng.integers(0, len(names), n_part))

    @job("o_custkey")
    def _():
        # customers whose key is a multiple of 3 place no orders
        j = _stream(seed, "o_custkey").integers(0, n_cust - n_cust // 3,
                                                n_ord)
        return Col((j + j // 2 + 1).astype(i32), "int32",
                   domain=n_cust + 1)

    @job("l_per_order")
    def _():
        per = (np.arange(n_ord, dtype=i32) % 7 + 1)[
            _stream(seed, "l_per_order").permutation(n_ord)]
        return Col(per, "int32")

    text_pool()  # shared by every text column: built once, first
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        d = dict(zip(jobs, pool.map(lambda f: f(), jobs.values())))
        jobs.clear()
        phone("s_phone", d["s_nationkey"])
        phone("c_phone", d["c_nationkey"])
        d.update(zip(jobs, pool.map(lambda f: f(), jobs.values())))
        return _tables(sf, seed, d, n, pool)


def _tables(sf: float, seed: int, d: Dict[str, Col], n: Dict[str, int],
            pool: ThreadPoolExecutor) -> Tables:
    """The eight tables from the drawn columns ``d``."""
    n_part, n_supp, n_cust, n_ord = (n["part"], n["supplier"],
                                     n["customer"], n["orders"])
    i32 = np.int32
    t: Tables = {}

    t["region"] = {
        "r_regionkey": Col(np.arange(5, dtype=i32), "int32", domain=5,
                           unique=True),
        "r_name": _fixed(_sorted(REGIONS),
                         np.argsort(np.argsort(REGIONS))),
        "r_comment": _fixed(text_dictionary("r_comment", "text", 31, 115,
                                            5),
                            _stream(seed, "r_comment").permutation(5))}
    names = [nm for nm, _ in NATIONS]
    t["nation"] = {
        "n_nationkey": Col(np.arange(25, dtype=i32), "int32", domain=25,
                           unique=True),
        "n_name": _fixed(_sorted(names), np.argsort(np.argsort(names))),
        "n_regionkey": Col(np.array([r for _, r in NATIONS], i32), "int32",
                           domain=5),
        "n_comment": _fixed(text_dictionary("n_comment", "text", 31, 114,
                                            25),
                            _stream(seed, "n_comment").permutation(25))}

    s_comment = supplier_comments(n_supp, sf)
    t["supplier"] = {
        "s_suppkey": Col(np.arange(1, n_supp + 1, dtype=i32), "int32",
                         domain=n_supp + 1, unique=True),
        "s_name": _fixed(_keyed("Supplier#", n_supp),
                         np.arange(n_supp)),
        "s_address": d["s_address"],
        "s_nationkey": d["s_nationkey"],
        "s_phone": d["s_phone"],
        "s_acctbal": d["s_acctbal"],
        "s_comment": _fixed(s_comment, _stream(seed, "s_comment")
                            .permutation(n_supp))}

    keys = np.arange(1, n_part + 1, dtype=np.int64)
    # P_RETAILPRICE in cents: 90000 + (key/10 mod 20001) + 100 (key mod 1000)
    retail_cents = 90_000 + (keys // 10) % 20_001 + 100 * (keys % 1_000)
    mfgr = d["p_mfgr_m"].data
    t["part"] = {
        "p_partkey": Col(keys.astype(i32), "int32", domain=n_part + 1,
                         unique=True),
        "p_name": d["p_name"],
        "p_mfgr": _fixed([f"Manufacturer#{m}" for m in range(1, 6)],
                         mfgr - 1),
        "p_brand": _fixed([f"Brand#{m}{k}" for m in range(1, 6)
                           for k in range(1, 6)],
                          (mfgr - 1) * 5 + d["p_brand_n"].data - 1),
        "p_type": d["p_type"],
        "p_size": d["p_size"],
        "p_container": d["p_container"],
        "p_retailprice": Col(retail_cents / 100.0, "float64"),
        "p_comment": d["p_comment"]}

    ps_part = np.repeat(keys.astype(i32), 4)
    t["partsupp"] = {
        "ps_partkey": Col(ps_part, "int32", domain=n_part + 1),
        "ps_suppkey": Col(_supp_of(ps_part, np.tile(np.arange(4), n_part),
                                   n_supp), "int32", domain=n_supp + 1),
        "ps_availqty": d["ps_availqty"],
        "ps_supplycost": d["ps_supplycost"],
        "ps_comment": d["ps_comment"]}

    t["customer"] = {
        "c_custkey": Col(np.arange(1, n_cust + 1, dtype=i32), "int32",
                         domain=n_cust + 1, unique=True),
        "c_name": _fixed(_keyed("Customer#", n_cust), np.arange(n_cust)),
        "c_address": d["c_address"],
        "c_nationkey": d["c_nationkey"],
        "c_phone": d["c_phone"],
        "c_acctbal": d["c_acctbal"],
        "c_mktsegment": d["c_mktsegment"],
        "c_comment": d["c_comment"]}

    o_keys = order_keys(n_ord)
    key_domain = int(o_keys[-1]) + 1
    li, o_status, o_total = _lineitem(d, o_keys, retail_cents, n_supp, pool)

    t["orders"] = {
        "o_orderkey": Col(o_keys, "int32", domain=key_domain, unique=True),
        "o_custkey": d["o_custkey"],
        "o_orderstatus": _fixed(["F", "O", "P"], o_status),
        "o_totalprice": Col(o_total, "float64"),
        "o_orderdate": d["o_orderdate"],
        "o_orderpriority": d["o_orderpriority"],
        "o_clerk": _fixed(_keyed("Clerk#", max(int(sf * 1000), 1)),
                          d["o_clerk"].data),
        "o_shippriority": Col(np.zeros(n_ord, i32), "int32", domain=1),
        "o_comment": d["o_comment"]}

    t["lineitem"] = {
        "l_orderkey": Col(li["l_orderkey"], "int32", domain=key_domain),
        "l_partkey": d["l_partkey"],
        "l_suppkey": Col(li["l_suppkey"], "int32", domain=n_supp + 1),
        "l_linenumber": Col(li["l_linenumber"], "int32", domain=8),
        "l_quantity": Col(li["l_quantity"], "float64"),
        "l_extendedprice": Col(li["l_extendedprice"], "float64"),
        "l_discount": Col(li["l_discount"], "float64"),
        "l_tax": Col(li["l_tax"], "float64"),
        "l_returnflag": _fixed(["A", "N", "R"], li["l_returnflag"]),
        "l_linestatus": _fixed(["F", "O"], li["l_linestatus"]),
        "l_shipdate": Col(li["l_shipdate"], "date", domain=DATE_DOMAIN),
        "l_commitdate": Col(li["l_commitdate"], "date", domain=DATE_DOMAIN),
        "l_receiptdate": Col(li["l_receiptdate"], "date",
                             domain=DATE_DOMAIN),
        "l_shipinstruct": d["l_shipinstruct"],
        "l_shipmode": d["l_shipmode"],
        "l_comment": d["l_comment"]}
    return t


def _lineitem(d: Dict[str, Col], o_keys: np.ndarray,
              retail_cents: np.ndarray, n_supp: int,
              pool: ThreadPoolExecutor
              ) -> Tuple[Dict[str, np.ndarray], np.ndarray, np.ndarray]:
    """lineitem's derived columns, and O_ORDERSTATUS and O_TOTALPRICE
    from each order's lines, computed in slices of whole orders in
    threads."""
    per_order = d["l_per_order"].data
    n_ord, n_li = len(per_order), int(per_order.sum())
    starts = np.zeros(n_ord + 1, np.int64)
    np.cumsum(per_order, out=starts[1:])
    o_orderdate = d["o_orderdate"].data
    f64, i32 = np.float64, np.int32
    out = {name: np.empty(n_li, dt) for name, dt in (
        ("l_orderkey", i32), ("l_suppkey", i32), ("l_linenumber", i32),
        ("l_quantity", f64), ("l_extendedprice", f64), ("l_discount", f64),
        ("l_tax", f64), ("l_returnflag", i32), ("l_linestatus", i32),
        ("l_shipdate", i32), ("l_commitdate", i32),
        ("l_receiptdate", i32))}
    o_status = np.empty(n_ord, i32)
    o_total = np.empty(n_ord, f64)

    def part(o0: int, o1: int) -> None:
        r0, r1 = int(starts[o0]), int(starts[o1])
        rows = slice(r0, r1)
        per = per_order[o0:o1]
        o_row = np.repeat(np.arange(o0, o1, dtype=i32), per)
        first = np.repeat(starts[o0:o1] - r0, per)
        out["l_orderkey"][rows] = o_keys[o_row]
        out["l_linenumber"][rows] = np.arange(r1 - r0) - first + 1
        pk = d["l_partkey"].data[rows]
        out["l_suppkey"][rows] = _supp_of(pk, d["l_supp_i"].data[rows],
                                          n_supp)
        qty = d["l_quantity"].data[rows]
        out["l_quantity"][rows] = qty
        # L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE, in whole cents
        ext = qty * retail_cents[pk - 1] / 100.0
        out["l_extendedprice"][rows] = ext
        disc = d["l_discount"].data[rows] / 100.0
        tax = d["l_tax"].data[rows] / 100.0
        out["l_discount"][rows] = disc
        out["l_tax"][rows] = tax
        odate = o_orderdate[o_row]
        ship = odate + d["l_ship_days"].data[rows]
        receipt = ship + d["l_receipt_days"].data[rows]
        out["l_shipdate"][rows] = ship
        out["l_commitdate"][rows] = odate + d["l_commit_days"].data[rows]
        out["l_receiptdate"][rows] = receipt
        # returnflag codes in the sorted dictionary (A, N, R)
        out["l_returnflag"][rows] = np.where(
            receipt <= CURRENT_DATE,
            np.where(d["l_returned"].data[rows] == 1, 2, 0), 1)
        status = (ship > CURRENT_DATE).astype(i32)  # F 0, O 1
        out["l_linestatus"][rows] = status
        local = starts[o0:o1] - r0
        n_open = np.add.reduceat(status, local)
        o_status[o0:o1] = np.where(n_open == per, 1,
                                   np.where(n_open == 0, 0, 2))  # F O P
        line = np.round(ext * (1.0 + tax) * (1.0 - disc), 2)
        o_total[o0:o1] = np.round(np.add.reduceat(line, local), 2)

    bounds = np.unique(np.linspace(0, n_ord, 33).astype(int))
    list(pool.map(part, bounds[:-1], bounds[1:]))
    return out, o_status, o_total
