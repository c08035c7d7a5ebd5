"""TPC-H tables generated in HBM from a seed given at run time.

The benchmark's own copy of the repo's device generator
(``cylon_tpu/tpch/datagen_device.py``): the same counter-based PRNG, the
same column formulas and dictionary pools, so at a given seed it yields
the same values.  Two things differ, both so that a new seed compiles
nothing:

* the seed is an argument of the one jitted program, not a constant
  baked into it;
* every table is padded to a capacity fixed by the scale factor and the
  number of chips alone (lineitem's row count depends on the seed, so
  its rows up to the capacity carry a validity bound computed on the
  device).

It also generates every Clause 4.2 column that the repo's generator
leaves out (names, addresses, phones, clerks, line numbers, shipping
instructions and comments), as dictionary codes like its other strings,
so HBM holds the whole database.

Each table is block-distributed over the chips of the sharding's mesh:
chip ``p`` holds a contiguous block of rows, padded to one capacity.
The program under test gets the arrays as ``DTable`` s; the reference
regenerates them from the seed and reads them back (``host_frames``).
"""
from __future__ import annotations

import functools
from typing import Dict

import numpy as np

# ---------------------------------------------------------------------------
# schema constants (the repo generator's shapes, TPC-H Clause 4.2 pools)
# ---------------------------------------------------------------------------

DAYS_TOTAL = 2406
_EPOCH = np.datetime64("1992-01-01")
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
RETURN_FLAGS = ["A", "N", "R"]
SHIP_MODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
P_NAME_WORDS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished",
    "chartreuse", "chiffon", "chocolate", "coral", "cornflower", "cream",
    "cyan", "dark", "deep", "dim", "dodger", "drab", "firebrick", "floral",
    "forest", "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey",
    "honeydew", "hot", "hotpink", "indian", "ivory", "khaki",
]
P_TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
P_TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
P_TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
P_CONTAINER_1 = ["SM", "MED", "LG", "JUMBO", "WRAP"]
P_CONTAINER_2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
_COMMENT_WORDS = [
    "carefully", "quickly", "furiously", "slyly", "blithely", "ironic",
    "regular", "final", "pending", "express", "special", "unusual",
    "requests", "deposits", "packages", "accounts", "instructions",
    "theodolites", "Customer", "Complaints", "platelets", "foxes",
]
SHIP_INSTRUCT = ["COLLECT COD", "DELIVER IN PERSON", "NONE",
                 "TAKE BACK RETURN"]
SUPPLIERS_PER_PART = 4
PHONES_PER_NATION = 1000


def date_to_days(iso: str) -> int:
    """'1995-03-15' -> the int32 day offset every date column holds."""
    return int((np.datetime64(iso) - _EPOCH).astype(int))


_LINESTATUS_CUTOFF = date_to_days("1995-06-17")

# dictionary pools, built pre-sorted (codes are drawn in sorted space)
_WORDS = sorted(_COMMENT_WORDS)
_W = len(_WORDS)
COMMENT_POOL = [f"{a} {b} {c}" for a in _WORDS for b in _WORDS
                for c in _WORDS]
NAME_POOL = sorted({f"{a} {b}" for a in P_NAME_WORDS for b in P_NAME_WORDS})
TYPE_POOL = [f"{a} {b} {c}" for a in sorted(P_TYPE_S1)
             for b in sorted(P_TYPE_S2) for c in sorted(P_TYPE_S3)]
CONTAINER_POOL = [f"{a} {b}" for a in sorted(P_CONTAINER_1)
                  for b in sorted(P_CONTAINER_2)]
BRAND_POOL = [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)]
MFGR_POOL = [f"Manufacturer#{m}" for m in range(1, 6)]
STATUS_POOL = ["F", "O", "P"]


def _text_pools():
    """Address and phone pools, drawn once from a fixed stream: they are
    part of the schema, not of the seed.  Phones are ``CC-LLL-LLL-LLLL``
    with ``CC`` the nation key + 10 (Clause 4.2.2.9), so code ``nation *
    PHONES_PER_NATION + k`` is in sorted order."""
    rng = np.random.default_rng(4_2_2)
    chars = np.array(list("0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZ"
                          "abcdefghijklmnopqrstuvwxyz ,"))
    addresses = sorted({"".join(rng.choice(chars, rng.integers(10, 41)))
                        for _ in range(4096)})
    phones = []
    for cc in range(10, 35):
        for x in np.sort(rng.choice(900 * 900 * 9000, PHONES_PER_NATION,
                                    replace=False)):
            a, r = divmod(int(x), 900 * 9000)
            b, c = divmod(r, 9000)
            phones.append(f"{cc}-{a + 100}-{b + 100}-{c + 1000}")
    return addresses, phones


ADDRESS_POOL, PHONE_POOL = _text_pools()

# the repo generator's columns first, in its order, then the rest of
# Clause 4.2 (``c_phone_cc``, the phone's country code, is the repo's)
COLUMN_ORDER = {
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
                 "l_extendedprice", "l_discount", "l_tax", "l_returnflag",
                 "l_linestatus", "l_shipdate", "l_commitdate",
                 "l_receiptdate", "l_shipmode", "l_linenumber",
                 "l_shipinstruct", "l_comment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_orderpriority",
               "o_shippriority", "o_totalprice", "o_comment",
               "o_orderstatus", "o_clerk"],
    "customer": ["c_custkey", "c_nationkey", "c_acctbal", "c_mktsegment",
                 "c_phone_cc", "c_name", "c_address", "c_phone",
                 "c_comment"],
    "supplier": ["s_suppkey", "s_nationkey", "s_acctbal", "s_comment",
                 "s_name", "s_address", "s_phone"],
    "part": ["p_partkey", "p_name", "p_mfgr", "p_type", "p_brand",
             "p_container", "p_size", "p_retailprice", "p_comment"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost",
                 "ps_availqty", "ps_comment"],
}
DICTS = {
    "o_orderpriority": PRIORITIES, "o_comment": COMMENT_POOL,
    "o_orderstatus": STATUS_POOL,
    "l_returnflag": RETURN_FLAGS, "l_linestatus": ["F", "O"],
    "l_shipmode": SHIP_MODES, "l_shipinstruct": SHIP_INSTRUCT,
    "l_comment": COMMENT_POOL, "c_mktsegment": SEGMENTS,
    "c_address": ADDRESS_POOL, "c_phone": PHONE_POOL,
    "c_comment": COMMENT_POOL,
    "s_comment": COMMENT_POOL, "s_address": ADDRESS_POOL,
    "s_phone": PHONE_POOL,
    "p_name": NAME_POOL, "p_mfgr": MFGR_POOL, "p_type": TYPE_POOL,
    "p_brand": BRAND_POOL, "p_container": CONTAINER_POOL,
    "p_comment": COMMENT_POOL, "ps_comment": COMMENT_POOL,
}
# names numbered by key, as many as the scale has (``dictionaries``)
NUMBERED = {"c_name": ("Customer", "customer"),
            "s_name": ("Supplier", "supplier"), "o_clerk": ("Clerk", "clerk")}
FLOAT_COLS = {"o_totalprice", "l_quantity", "l_extendedprice",
              "l_discount", "l_tax", "c_acctbal", "s_acctbal",
              "p_retailprice", "ps_supplycost"}

# ---------------------------------------------------------------------------
# counter-based PRNG over uint32 (murmur3 finalizer); the seed is traced
# ---------------------------------------------------------------------------

_M1, _M2, _GOLD = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B9


def _mix(x, jnp):
    x = (x ^ (x >> jnp.uint32(16))) * jnp.uint32(_M1)
    x = (x ^ (x >> jnp.uint32(13))) * jnp.uint32(_M2)
    return x ^ (x >> jnp.uint32(16))


def _salt(jnp, seed, tag: int):
    """Per-draw-site salt from the (traced uint32) seed: the same value
    as the host-side ``(seed * GOLD + tag) mod 2**32`` avalanche."""
    return _mix(seed * jnp.uint32(_GOLD) + jnp.uint32(tag), jnp)


def _randint(jnp, salt, i, n: int):
    u = _mix(i.astype(jnp.uint32) + salt, jnp)
    return (u % jnp.uint32(n)).astype(jnp.int32)


def _uniform(jnp, salt, i, lo: float, hi: float):
    u = _mix(i.astype(jnp.uint32) + salt, jnp)
    r = (u >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    return (r * jnp.float32(hi - lo) + jnp.float32(lo)).astype(jnp.float32)


def _round2(jnp, x):
    return (jnp.round(x * jnp.float32(100.0)) / jnp.float32(100.0)) \
        .astype(jnp.float32)


class _T:  # draw-site tags, one per random column or decision
    LINES, ODATE, OCUST, OPRIO, OPRICE, OCA, OCB, OCC, OCHIT = range(1, 10)
    (LPART, LSUPI, LSHIP, LCOMMIT, LRECEIPT, LQTY, LPRICE, LDISC, LTAX,
     LRFLAG, LMODE) = range(10, 21)
    CNAT, CBAL, CSEG, SNAT, SBAL, SCA, SCB, SCC, SCHIT = range(21, 30)
    PNAME, PTYPE, PBRAND, PCONT, PSIZE, PPRICE, PSCOST, PSQTY = range(30, 38)
    (LCOMMENT, LINSTR, OCLERK, CADDR, CPHONE, CCOMMENT, SADDR, SPHONE,
     PCOMMENT, PSCOMMENT) = range(38, 48)


def _pick(jnp, seed, tag: int, i, pool):
    """A code drawn uniformly from ``pool`` for row ``i``."""
    return _randint(jnp, _salt(jnp, seed, tag), i, len(pool))


def _comment_code(jnp, seed, tags, i):
    ta, tb, tc, thit, wa, wc = tags
    a = _randint(jnp, _salt(jnp, seed, ta), i, _W)
    b = _randint(jnp, _salt(jnp, seed, tb), i, _W)
    c = _randint(jnp, _salt(jnp, seed, tc), i, _W)
    hit = _randint(jnp, _salt(jnp, seed, thit), i, 50) == 0
    a = jnp.where(hit, jnp.int32(_WORDS.index(wa)), a)
    c = jnp.where(hit, jnp.int32(_WORDS.index(wc)), c)
    return a * jnp.int32(_W * _W) + b * jnp.int32(_W) + c


def _part_supp_key(jnp, partkey, i, n_supp: int):
    step = max(n_supp // SUPPLIERS_PER_PART, 1)
    return (((partkey - 1) + i * jnp.int32(step)) % jnp.int32(n_supp)
            + 1).astype(jnp.int32)


def _orders_cols(jnp, seed, o_idx, starts, lines_per, n_cust: int,
                 n_clerk: int):
    n_valid_cust = max(n_cust - n_cust // 3, 1)
    ci = _randint(jnp, _salt(jnp, seed, _T.OCUST), o_idx, n_valid_cust)
    odate = _randint(jnp, _salt(jnp, seed, _T.ODATE), o_idx, DAYS_TOTAL)
    s_ship = _salt(jnp, seed, _T.LSHIP)
    n_o = jnp.zeros(o_idx.shape[0], jnp.int32)
    for j in range(7):
        ship = odate + 1 + _randint(jnp, s_ship, starts + jnp.int32(j), 121)
        is_o = (ship > _LINESTATUS_CUTOFF) & (jnp.int32(j) < lines_per)
        n_o = n_o + is_o.astype(jnp.int32)
    status = jnp.where(n_o == 0, jnp.int32(0),
                       jnp.where(n_o == lines_per, jnp.int32(1),
                                 jnp.int32(2)))
    return {
        "o_orderkey": (o_idx + 1).astype(jnp.int32),
        "o_custkey": (3 * (ci // 2) + 1 + ci % 2).astype(jnp.int32),
        "o_orderdate": odate,
        "o_orderpriority": _randint(jnp, _salt(jnp, seed, _T.OPRIO), o_idx,
                                    len(PRIORITIES)),
        "o_shippriority": jnp.zeros(o_idx.shape[0], jnp.int32),
        "o_totalprice": _round2(jnp, _uniform(
            jnp, _salt(jnp, seed, _T.OPRICE), o_idx, 900.0, 500_000.0)),
        "o_comment": _comment_code(jnp, seed, (_T.OCA, _T.OCB, _T.OCC,
                                               _T.OCHIT, "special",
                                               "requests"), o_idx),
        "o_orderstatus": status,
        "o_clerk": _randint(jnp, _salt(jnp, seed, _T.OCLERK), o_idx, n_clerk),
    }


def _lineitem_cols(jnp, seed, li_idx, order_idx, line, n_part: int,
                   n_supp: int):
    odate = _randint(jnp, _salt(jnp, seed, _T.ODATE), order_idx, DAYS_TOTAL)
    ship = odate + 1 + _randint(jnp, _salt(jnp, seed, _T.LSHIP), li_idx, 121)
    commit = odate + 30 + _randint(jnp, _salt(jnp, seed, _T.LCOMMIT),
                                   li_idx, 61)
    receipt = ship + 1 + _randint(jnp, _salt(jnp, seed, _T.LRECEIPT),
                                  li_idx, 30)
    partkey = 1 + _randint(jnp, _salt(jnp, seed, _T.LPART), li_idx, n_part)
    suppkey = _part_supp_key(jnp, partkey, _randint(
        jnp, _salt(jnp, seed, _T.LSUPI), li_idx, SUPPLIERS_PER_PART), n_supp)
    return {
        "l_orderkey": (order_idx + 1).astype(jnp.int32),
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_quantity": (1 + _randint(jnp, _salt(jnp, seed, _T.LQTY), li_idx,
                                    50)).astype(jnp.float32),
        "l_extendedprice": _round2(jnp, _uniform(
            jnp, _salt(jnp, seed, _T.LPRICE), li_idx, 900.0, 105_000.0)),
        "l_discount": (_randint(jnp, _salt(jnp, seed, _T.LDISC), li_idx, 11)
                       .astype(jnp.float32) / jnp.float32(100.0)),
        "l_tax": (_randint(jnp, _salt(jnp, seed, _T.LTAX), li_idx, 9)
                  .astype(jnp.float32) / jnp.float32(100.0)),
        "l_returnflag": _randint(jnp, _salt(jnp, seed, _T.LRFLAG), li_idx,
                                 len(RETURN_FLAGS)),
        "l_linestatus": (ship > _LINESTATUS_CUTOFF).astype(jnp.int32),
        "l_shipdate": ship,
        "l_commitdate": commit,
        "l_receiptdate": receipt,
        "l_shipmode": _randint(jnp, _salt(jnp, seed, _T.LMODE), li_idx,
                               len(SHIP_MODES)),
        "l_linenumber": line,
        "l_shipinstruct": _pick(jnp, seed, _T.LINSTR, li_idx, SHIP_INSTRUCT),
        "l_comment": _pick(jnp, seed, _T.LCOMMENT, li_idx, COMMENT_POOL),
    }


def _customer_cols(jnp, seed, i):
    nat = _randint(jnp, _salt(jnp, seed, _T.CNAT), i, 25)
    return {
        "c_custkey": (i + 1).astype(jnp.int32),
        "c_nationkey": nat,
        "c_acctbal": _round2(jnp, _uniform(jnp, _salt(jnp, seed, _T.CBAL), i,
                                           -999.99, 9999.99)),
        "c_mktsegment": _randint(jnp, _salt(jnp, seed, _T.CSEG), i,
                                 len(SEGMENTS)),
        "c_phone_cc": (nat + 10).astype(jnp.int32),
        "c_name": i.astype(jnp.int32),
        "c_address": _pick(jnp, seed, _T.CADDR, i, ADDRESS_POOL),
        "c_phone": _phone(jnp, seed, _T.CPHONE, i, nat),
        "c_comment": _pick(jnp, seed, _T.CCOMMENT, i, COMMENT_POOL),
    }


def _phone(jnp, seed, tag: int, i, nat):
    return nat * jnp.int32(PHONES_PER_NATION) + _randint(
        jnp, _salt(jnp, seed, tag), i, PHONES_PER_NATION)


def _supplier_cols(jnp, seed, i):
    nat = _randint(jnp, _salt(jnp, seed, _T.SNAT), i, 25)
    return {
        "s_suppkey": (i + 1).astype(jnp.int32),
        "s_nationkey": nat,
        "s_acctbal": _round2(jnp, _uniform(jnp, _salt(jnp, seed, _T.SBAL), i,
                                           -999.99, 9999.99)),
        "s_comment": _comment_code(jnp, seed, (_T.SCA, _T.SCB, _T.SCC,
                                               _T.SCHIT, "Customer",
                                               "Complaints"), i),
        "s_name": i.astype(jnp.int32),
        "s_address": _pick(jnp, seed, _T.SADDR, i, ADDRESS_POOL),
        "s_phone": _phone(jnp, seed, _T.SPHONE, i, nat),
    }


def _part_cols(jnp, seed, i):
    brand = _randint(jnp, _salt(jnp, seed, _T.PBRAND), i, len(BRAND_POOL))
    return {
        "p_partkey": (i + 1).astype(jnp.int32),
        "p_name": _randint(jnp, _salt(jnp, seed, _T.PNAME), i,
                           len(NAME_POOL)),
        "p_mfgr": brand // 5,
        "p_type": _randint(jnp, _salt(jnp, seed, _T.PTYPE), i,
                           len(TYPE_POOL)),
        "p_brand": brand,
        "p_container": _randint(jnp, _salt(jnp, seed, _T.PCONT), i,
                                len(CONTAINER_POOL)),
        "p_size": 1 + _randint(jnp, _salt(jnp, seed, _T.PSIZE), i, 50),
        "p_retailprice": _round2(jnp, _uniform(
            jnp, _salt(jnp, seed, _T.PPRICE), i, 900.0, 2000.0)),
        "p_comment": _pick(jnp, seed, _T.PCOMMENT, i, COMMENT_POOL),
    }


def _partsupp_cols(jnp, seed, i, n_supp: int):
    partkey = (i // SUPPLIERS_PER_PART + 1).astype(jnp.int32)
    k = (i % SUPPLIERS_PER_PART).astype(jnp.int32)
    return {
        "ps_partkey": partkey,
        "ps_suppkey": _part_supp_key(jnp, partkey, k, n_supp),
        "ps_supplycost": _round2(jnp, _uniform(
            jnp, _salt(jnp, seed, _T.PSCOST), i, 1.0, 1000.0)),
        "ps_availqty": 1 + _randint(jnp, _salt(jnp, seed, _T.PSQTY), i, 9999),
        "ps_comment": _pick(jnp, seed, _T.PSCOMMENT, i, COMMENT_POOL),
    }


# ---------------------------------------------------------------------------
# sizes, the block layout and the one jitted generator
# ---------------------------------------------------------------------------

def bucket(n: int, minimum: int = 8) -> int:
    """Quarter-step size class 2^k * {5,6,7,8}/8 at or above ``n``: the
    capacity a block of ``n`` rows is padded to."""
    cap = max(int(n), minimum)
    pow2 = 1 << (cap - 1).bit_length()
    for num in (5, 6, 7):
        if (pow2 // 8) * num >= cap:
            return (pow2 // 8) * num
    return pow2


def sizes(scale: float) -> Dict[str, int]:
    """Row counts fixed by the scale factor, and a bound on lineitem's
    (7 lines per order at most, 4 on average: the bound is 4.2 per
    order, 25 standard deviations above the mean at SF-10, and the
    generator checks it on every seed)."""
    n_ord = max(int(1_500_000 * scale), 1)
    return {
        "customer": max(int(150_000 * scale), 1),
        "orders": n_ord,
        "supplier": max(int(10_000 * scale), SUPPLIERS_PER_PART),
        "part": max(int(200_000 * scale), 1),
        "partsupp": max(int(200_000 * scale), 1) * SUPPLIERS_PER_PART,
        "clerk": max(int(1_000 * scale), 1),
        "lineitem_bound": int(n_ord * 4.2) + 64,
    }


def split(n: int, chips: int) -> np.ndarray:
    """Rows per chip of an ``n``-row table: contiguous blocks, the first
    ``n % chips`` one row longer (the engine's ``DTable`` layout)."""
    base, rem = divmod(int(n), chips)
    return np.array([base + (p < rem) for p in range(chips)], np.int32)


def capacity(scale: float, chips: int) -> Dict[str, int]:
    """Each table's rows per chip, padded: fixed by the scale factor and
    the number of chips, whatever the seed."""
    sz = sizes(scale)
    cap = {t: bucket(split(sz[t], chips).max())
           for t in ("orders", "customer", "supplier", "part", "partsupp")}
    cap["lineitem"] = bucket(-(-sz["lineitem_bound"] // chips))
    return cap


def _blocks(jnp, chips: int, cap: int, n):
    """Padded position -> (global row, valid) for ``n`` rows in blocks
    (``n`` may be traced)."""
    p = jnp.arange(chips * cap, dtype=jnp.int32)
    shard, local = p // jnp.int32(cap), p % jnp.int32(cap)
    n = jnp.asarray(n, jnp.int32)
    base, rem = n // chips, n % chips
    size = base + (shard < rem).astype(jnp.int32)
    start = shard * base + jnp.minimum(shard, rem)
    return start + local, local < size


@functools.lru_cache(maxsize=None)
def _generator(scale: float, sharding):
    """The jitted all-tables generator for ``scale`` on ``sharding``:
    ``fn(seed_u32) -> (columns per table, n_lineitem)``."""
    import jax
    import jax.numpy as jnp

    chips = len(sharding.device_set)
    sz, cap = sizes(scale), capacity(scale, chips)
    n_ord, n_cust, n_supp, n_part = (sz["orders"], sz["customer"],
                                     sz["supplier"], sz["part"])

    def zero_invalid(cols, valid):
        return {k: jnp.where(valid, v, jnp.zeros((), v.dtype))
                for k, v in cols.items()}

    def fn(seed):
        o_c = jnp.arange(n_ord, dtype=jnp.int32)
        lp_c = 1 + _randint(jnp, _salt(jnp, seed, _T.LINES), o_c, 7)
        ends_c = jnp.cumsum(lp_c).astype(jnp.int32)
        starts_c = ends_c - lp_c
        n_li = ends_c[-1]

        g_o, valid_o = _blocks(jnp, chips, cap["orders"], n_ord)
        safe = jnp.minimum(g_o, jnp.int32(n_ord - 1))
        lp_b = jnp.where(valid_o, jnp.take(lp_c, safe), 0)
        st_b = jnp.take(starts_c, safe)
        out = {"orders": zero_invalid(
            _orders_cols(jnp, seed, g_o, st_b, lp_b, n_cust, sz["clerk"]),
            valid_o)}

        span = chips * cap["lineitem"]
        g_l, valid_l = _blocks(jnp, chips, cap["lineitem"], n_li)
        g_l = jnp.minimum(g_l, jnp.int32(span - 1))
        marker = jnp.zeros(span, jnp.int32).at[starts_c].add(1, mode="drop")
        order_idx = jnp.minimum(jnp.take(jnp.cumsum(marker) - 1, g_l),
                                jnp.int32(n_ord - 1))
        line = g_l - jnp.take(starts_c, order_idx) + 1
        out["lineitem"] = zero_invalid(
            _lineitem_cols(jnp, seed, g_l, order_idx, line, n_part, n_supp),
            valid_l)
        for name, cols in (("customer", _customer_cols),
                           ("supplier", _supplier_cols),
                           ("part", _part_cols)):
            g, valid = _blocks(jnp, chips, cap[name], sz[name])
            out[name] = zero_invalid(cols(jnp, seed, g), valid)
        g, valid = _blocks(jnp, chips, cap["partsupp"], sz["partsupp"])
        out["partsupp"] = zero_invalid(_partsupp_cols(jnp, seed, g, n_supp),
                                       valid)
        return out, jnp.reshape(n_li, (1,))

    return jax.jit(fn, out_shardings=(sharding, None))


def seed_u32(seed: int):
    import jax.numpy as jnp
    return jnp.uint32(int(seed) % (1 << 32))


def generate(scale: float, seed: int, sharding):
    """Device arrays of every generated table, block-distributed over
    the chips of ``sharding``, and each table's rows per chip (host
    arrays), for ``seed``."""
    chips = len(sharding.device_set)
    cols, n_li = _generator(scale, sharding)(seed_u32(seed))
    sz, cap = sizes(scale), capacity(scale, chips)
    counts = {t: split(sz[t], chips) for t in
              ("orders", "customer", "supplier", "part", "partsupp")}
    counts["lineitem"] = split(int(n_li[0]), chips)
    if counts["lineitem"].max() > cap["lineitem"]:
        raise RuntimeError(f"lineitem: {int(n_li[0])} rows over its "
                           f"capacity {chips} x {cap['lineitem']}")
    return cols, counts


@functools.lru_cache(maxsize=None)
def _numbered(prefix: str, n: int):
    return np.array([f"{prefix}#{i:09d}" for i in range(1, n + 1)])


def dictionaries(scale: float) -> Dict[str, np.ndarray]:
    """Every string column's sorted dictionary at ``scale``."""
    sz = sizes(scale)
    out = {c: np.asarray(pool) for c, pool in DICTS.items()}
    out.update({c: _numbered(prefix, sz[table])
                for c, (prefix, table) in NUMBERED.items()})
    return out


def nation_region_frames():
    import pandas as pd
    words = np.asarray(COMMENT_POOL)
    rng = np.random.default_rng(4_2_3)  # fixed, as the text pools
    nation = pd.DataFrame({
        "n_nationkey": np.arange(25, dtype=np.int32),
        "n_name": pd.Categorical([n for n, _ in NATIONS]),
        "n_regionkey": np.array([r for _, r in NATIONS], dtype=np.int32),
        "n_comment": pd.Categorical(words[rng.integers(0, len(words), 25)]),
    })
    region = pd.DataFrame({
        "r_regionkey": np.arange(5, dtype=np.int32),
        "r_name": pd.Categorical(REGIONS),
        "r_comment": pd.Categorical(words[rng.integers(0, len(words), 5)]),
    })
    return nation, region


def device_tables(ctx, cols, counts, scale: float):
    """The generated arrays as the program's ``DTable`` s (all eight
    TPC-H tables; nation and region are 25 and 5 rows, ingested)."""
    import jax
    from cylon_tpu.dtypes import DataType, Type
    from cylon_tpu.parallel import DColumn, DTable

    dicts = dictionaries(scale)
    out = {}
    for name, order in COLUMN_ORDER.items():
        dcols = []
        for c in order:
            data = cols[name][c]
            if c in dicts:
                dcols.append(DColumn(c, DataType(Type.STRING), data,
                                     dictionary=dicts[c]))
            elif c in FLOAT_COLS:
                dcols.append(DColumn(c, DataType(Type.FLOAT), data))
            else:
                dcols.append(DColumn(c, DataType(Type.INT32), data))
        n = counts[name]
        dt = DTable(ctx, dcols, int(data.shape[0]) // len(n),
                    jax.device_put(n, ctx.sharding()))
        dt.counts_host()  # the row counts, once, before any plan needs them
        out[name] = dt
    nation, region = nation_region_frames()
    out["nation"] = DTable.from_pandas(ctx, nation)
    out["region"] = DTable.from_pandas(ctx, region)
    return out


def host_frames(cols, counts, scale: float, columns):
    """pandas frames of ``columns`` ({table: [column, ...]}) read back
    from the generated arrays, in global row order: the reference's
    input."""
    import jax
    import pandas as pd

    dicts = dictionaries(scale)
    want = {t: [c for c in COLUMN_ORDER[t] if c in cs]
            for t, cs in columns.items() if t in COLUMN_ORDER}

    def valid(t, a):
        cap = a.shape[0] // len(counts[t])
        return [a[p * cap:p * cap + int(n)] for p, n in enumerate(counts[t])]
    got = jax.device_get({t: {c: valid(t, cols[t][c]) for c in cs}
                          for t, cs in want.items()})
    out = {}
    for t, cs in want.items():
        cols_t = {c: np.concatenate(got[t][c]) for c in cs}
        out[t] = pd.DataFrame({
            c: (pd.Categorical.from_codes(v, dicts[c]) if c in dicts else v)
            for c, v in cols_t.items()})
    nation, region = nation_region_frames()
    out["nation"], out["region"] = nation, region
    return out
