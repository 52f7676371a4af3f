"""TPC-H-shaped tables from a seed, sized by scale factor.

The yardstick's own generator (it began as a copy of the program's
``benchmarks/tpch_full.py``, which PR 29 deleted). Same schema, row counts (SF 1 =
6,000,000 lineitem rows) and value distributions - not dbgen: values are
shaped to what the query texts predicate on. What differs is how it is made:
every file has a random stream of its own, keyed by (seed, table, file), so
files are made on a thread pool and the result is still a pure function of
the seed, and strings are made by pyarrow from dictionaries and integer
formatting, not by Python loops, so SF 10 takes seconds.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

ROWS_SF1 = {
    "region": 5,
    "nation": 25,
    "supplier": 10_000,
    "customer": 150_000,
    "part": 200_000,
    "partsupp": 800_000,
    "orders": 1_500_000,
    "lineitem": 6_000_000,
}
FILES = {"region": 1, "nation": 1, "supplier": 2, "customer": 4, "part": 4,
         "partsupp": 4, "orders": 8, "lineitem": 16}
TABLES = tuple(ROWS_SF1)  # position = the table's word in a file's seed

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
SHIPMODES = ["AIR", "AIR REG", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
INSTRUCT = ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]
TYPES = [
    f"{a} {b} {c}"
    for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
    for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
    for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")
]
CONTAINERS = [
    f"{a} {b}"
    for a in ("SM", "MED", "LG", "JUMBO", "WRAP")
    for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM")
]
BRANDS = [f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6)]
NAME_WORDS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished", "chartreuse",
    "chiffon", "chocolate", "coral", "cornflower", "cornsilk", "cream", "cyan",
    "dark", "deep", "dim", "dodger", "drab", "firebrick", "floral", "forest",
    "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey", "honeydew",
    "hot", "hotpink", "indian", "ivory", "khaki", "lace", "lavender", "lawn",
    "lemon", "light", "lime", "linen", "magenta", "maroon", "medium", "metallic",
    "midnight", "mint", "misty", "moccasin", "navajo", "navy", "olive", "orange",
]
# the LIKE patterns of q13/q16/q19-class texts need occupants
COMMENTS = [f"notes {i}" for i in range(97)] + [
    "special requests handle", "pending deposits accounts",
    "unusual packages wake", "express Customer Complaints",
]
EPOCH = np.datetime64("1992-01-01")


def rows_of(table: str, sf: float) -> int:
    if table in ("region", "nation"):
        return ROWS_SF1[table]
    return max(20, int(ROWS_SF1[table] * sf))


def _pick(rng, values, rows) -> pa.Array:
    """A string column drawn uniformly from ``values``."""
    return _gather(values, rng.integers(0, len(values), rows))


def _gather(values, idx) -> pa.Array:
    return pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(idx, dtype=np.int32)), pa.array(values, type=pa.string())
    ).cast(pa.string())


def _comments(rng, rows) -> pa.Array:
    idx = rng.integers(0, 97, rows)
    hits = rng.random(rows) < 0.1
    idx[hits] = 97 + rng.integers(0, 4, int(hits.sum()))
    return _gather(COMMENTS, idx)


def _numbered(prefix: str, keys: np.ndarray) -> pa.Array:
    """``f"{prefix}{key:09d}"`` for every key."""
    digits = pc.utf8_lpad(pc.cast(pa.array(keys), pa.string()), 9, "0")
    return pc.binary_join_element_wise(pa.scalar(prefix), digits, pa.scalar(""))


def _cycled(make, period: int, keys: np.ndarray) -> pa.Array:
    """A string that depends on ``key % period`` only."""
    return _gather([make(v) for v in range(period)], keys % period)


def _phone(v: int) -> str:
    return f"{13 + (v % 20)}-{v % 997:03d}-55"


def _region(rng, off, rows, n):
    return {
        "r_regionkey": np.arange(5, dtype=np.int64),
        "r_name": pa.array(REGIONS),
        "r_comment": pa.array([f"region {i}" for i in range(5)]),
    }


def _nation(rng, off, rows, n):
    return {
        "n_nationkey": np.arange(25, dtype=np.int64),
        "n_name": pa.array([name for name, _ in NATIONS]),
        "n_regionkey": np.array([r for _, r in NATIONS], dtype=np.int64),
        "n_comment": pa.array([f"nation {i}" for i in range(25)]),
    }


def _supplier(rng, off, rows, n):
    k = np.arange(off, off + rows, dtype=np.int64)
    return {
        "s_suppkey": k,
        "s_name": _numbered("Supplier#", k),
        "s_address": _cycled(lambda v: f"{v} Dock Rd", 9999, k),
        "s_nationkey": rng.integers(0, 25, rows).astype(np.int64),
        "s_phone": _cycled(_phone, 19940, k),
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, rows), 2),
        "s_comment": _comments(rng, rows),
    }


def _customer(rng, off, rows, n):
    k = np.arange(off, off + rows, dtype=np.int64)
    return {
        "c_custkey": k,
        "c_name": _numbered("Customer#", k),
        "c_address": _cycled(lambda v: f"{v} Market St", 9999, k),
        "c_nationkey": rng.integers(0, 25, rows).astype(np.int64),
        "c_phone": _cycled(_phone, 19940, k),
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, rows), 2),
        "c_mktsegment": _pick(rng, SEGMENTS, rows),
        "c_comment": _comments(rng, rows),
    }


def _part(rng, off, rows, n):
    k = np.arange(off, off + rows, dtype=np.int64)
    names = [f"{a} {b}" for a in NAME_WORDS for b in NAME_WORDS]
    return {
        "p_partkey": k,
        "p_name": _pick(rng, names, rows),
        "p_mfgr": _cycled(lambda v: f"Manufacturer#{1 + v}", 5, k),
        "p_brand": _pick(rng, BRANDS, rows),
        "p_type": _pick(rng, TYPES, rows),
        "p_size": rng.integers(1, 51, rows).astype(np.int64),
        "p_container": _pick(rng, CONTAINERS, rows),
        "p_retailprice": np.round(rng.uniform(900.0, 2000.0, rows), 2),
        "p_comment": _comments(rng, rows),
    }


def _partsupp(rng, off, rows, n):
    return {
        "ps_partkey": rng.integers(0, n["part"], rows).astype(np.int64),
        "ps_suppkey": rng.integers(0, n["supplier"], rows).astype(np.int64),
        "ps_availqty": rng.integers(1, 10_000, rows).astype(np.int64),
        "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, rows), 2),
        "ps_comment": _comments(rng, rows),
    }


def _orders(rng, off, rows, n):
    k = np.arange(off, off + rows, dtype=np.int64)
    return {
        "o_orderkey": k,
        "o_custkey": rng.integers(0, max(1, int(n["customer"] * 0.85)), rows).astype(np.int64),
        "o_orderstatus": _pick(rng, ["F", "O", "P"], rows),
        "o_totalprice": np.round(rng.uniform(800.0, 600000.0, rows), 2),
        "o_orderdate": EPOCH + rng.integers(0, 2406, rows).astype("timedelta64[D]"),
        "o_orderpriority": _pick(rng, PRIORITIES, rows),
        "o_clerk": _cycled(lambda v: f"Clerk#{v:09d}", 1000, k),
        "o_shippriority": np.zeros(rows, dtype=np.int64),
        "o_comment": _comments(rng, rows),
    }


def _lineitem(rng, off, rows, n):
    ship = EPOCH + rng.integers(366, 2526, rows).astype("timedelta64[D]")
    commit = ship + rng.integers(7, 30, rows).astype("timedelta64[D]")
    late = rng.random(rows) < 0.2
    receipt = commit + np.where(
        late, rng.integers(1, 6, rows), rng.integers(-5, 1, rows)
    ).astype("timedelta64[D]")
    okeys = rng.integers(0, n["orders"], rows).astype(np.int64)
    heavy = rng.random(rows) < 0.02  # q18's heavy orders
    okeys[heavy] = rng.integers(0, max(1, n["orders"] // 1000), int(heavy.sum()))
    return {
        "l_orderkey": okeys,
        "l_partkey": rng.integers(0, n["part"], rows).astype(np.int64),
        "l_suppkey": rng.integers(0, n["supplier"], rows).astype(np.int64),
        "l_linenumber": rng.integers(1, 8, rows).astype(np.int64),
        "l_quantity": rng.integers(1, 51, rows).astype(np.int64),
        "l_extendedprice": np.round(rng.uniform(900.0, 105000.0, rows), 2),
        "l_discount": np.round(rng.integers(0, 11, rows) / 100.0, 2),
        "l_tax": np.round(rng.integers(0, 9, rows) / 100.0, 2),
        "l_returnflag": _pick(rng, ["A", "N", "R"], rows),
        "l_linestatus": _pick(rng, ["F", "O"], rows),
        "l_shipdate": ship,
        "l_commitdate": commit,
        "l_receiptdate": receipt,
        "l_shipinstruct": _pick(rng, INSTRUCT, rows),
        "l_shipmode": _pick(rng, SHIPMODES, rows),
        "l_comment": _comments(rng, rows),
    }


_MAKERS = {"region": _region, "nation": _nation, "supplier": _supplier,
           "customer": _customer, "part": _part, "partsupp": _partsupp,
           "orders": _orders, "lineitem": _lineitem}


def file_table(table: str, file_index: int, num_files: int, sf: float, seed: int) -> pa.Table:
    """One file's rows: a pure function of its arguments."""
    n = {t: rows_of(t, sf) for t in TABLES}
    per = max(1, n[table] // num_files)
    off = file_index * per
    rows = n[table] - off if file_index == num_files - 1 else per
    rng = np.random.default_rng([int(seed), TABLES.index(table), file_index])
    return pa.table(_MAKERS[table](rng, off, rows, n))


def generate(root: str, sf: float, seed: int, tables=TABLES, threads: int = 8) -> dict:
    """Write ``tables`` under ``root`` as Parquet; returns {table: directory}."""
    jobs = []
    dirs = {}
    for table in tables:
        dirs[table] = os.path.join(root, table)
        os.makedirs(dirs[table], exist_ok=True)
        files = min(FILES[table], rows_of(table, sf))
        jobs += [(table, i, files) for i in range(files)]

    def write(job):
        table, i, files = job
        pq.write_table(file_table(table, i, files, sf, seed),
                       os.path.join(dirs[table], f"part-{i:05d}.parquet"))

    # the largest files first, so that the pool's tail is short
    jobs.sort(key=lambda j: -rows_of(j[0], sf) // j[2])
    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(write, jobs))
    return dirs


def source_files(directory: str) -> list:
    return sorted(os.path.join(directory, f) for f in os.listdir(directory) if f.endswith(".parquet"))


def load_frame(directory: str, columns=None):
    """A table's source files as one pandas frame, for the oracles."""
    import pandas as pd

    t = pq.read_table(source_files(directory), columns=columns)
    return pd.DataFrame({c: t.column(c).to_numpy(zero_copy_only=False) for c in t.column_names})
