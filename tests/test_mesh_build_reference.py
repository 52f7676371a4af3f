"""The mesh build against a plain reference (``tests/reference_build.py``:
NumPy and pyarrow, no JAX, nothing of the package): ``create_index`` with
``hyperspace.parallel.enabled`` on 4 and on 8 virtual devices has to write
exactly the reference's runs — file set, rows per bucket, row order, every
payload value — over seeded TPC-H-shaped rows. ``test_distributed_build.py``
compares the mesh build with the one-chip program, which is not independent
of the code under test; this file is.

One test ties the chips' shares to the whole, one pins the names and counters
the four-chip benchmark cell reads, one pins that the build and the query side
take their mesh from one place.
"""

import glob
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.indexes.covering import bucket_of_file
from hyperspace_tpu.obs.metrics import REGISTRY

from reference_build import bucket_of, reference_index, run_content

pytestmark = pytest.mark.mesh

EPOCH = np.datetime64("1992-01-01")
SHIPMODES = ["AIR", "AIR REG", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
LI_SD = (["l_shipdate"], ["l_extendedprice", "l_discount", "l_quantity", "l_orderkey"])
LI_OK = (["l_orderkey"], ["l_extendedprice", "l_discount", "l_quantity", "l_shipdate", "l_shipmode",
                          "l_returnflag"])


def lineitem(rng, rows: int, orders: int) -> pa.Table:
    """TPC-H lineitem's columns that the two indexes of the four-chip cell
    read, with the generator's distributions (``hsbench/datagen.py``)."""
    okeys = rng.integers(0, orders, rows).astype(np.int64)
    heavy = rng.random(rows) < 0.02
    okeys[heavy] = rng.integers(0, max(1, orders // 1000), int(heavy.sum()))
    return pa.table({
        "l_orderkey": okeys,
        "l_quantity": rng.integers(1, 51, rows).astype(np.int64),
        "l_extendedprice": np.round(rng.uniform(900.0, 105000.0, rows), 2),
        "l_discount": np.round(rng.integers(0, 11, rows) / 100.0, 2),
        "l_returnflag": pa.array(np.array(["A", "N", "R"])[rng.integers(0, 3, rows)]),
        "l_shipdate": EPOCH + rng.integers(366, 2526, rows).astype("timedelta64[D]"),
        "l_shipmode": pa.array(np.array(SHIPMODES)[rng.integers(0, 8, rows)]),
    })


def write_lake(root, seed: int, file_rows, orders=30_000, edit=None) -> str:
    os.makedirs(root, exist_ok=True)
    rng = np.random.default_rng([seed, 27])
    for i, n in enumerate(file_rows):
        t = lineitem(rng, n, orders)
        if edit is not None:
            t = edit(t, rng)
        pq.write_table(t, os.path.join(root, f"part-{i:05d}.parquet"))
    return str(root)


def session_on(tmp_path, n_dev: int, tag: str, **conf):
    sysp = tmp_path / f"idx_{tag}"
    sysp.mkdir()
    merged = {hst.keys.SYSTEM_PATH: str(sysp), hst.keys.NUM_BUCKETS: 24,
              hst.keys.PARALLEL_ENABLED: True, hst.keys.PARALLEL_MESH_DEVICES: n_dev}
    merged.update(conf)
    return hst.Session(conf=merged)


def index_files(session, name: str) -> list:
    files = glob.glob(os.path.join(session.conf.get(hst.keys.SYSTEM_PATH), name, "v__=*", "*.parquet"))
    assert files, f"no index data files for {name}"
    return files


def grown(before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in counters().items()}


def counters() -> dict:
    out = {}
    for name, entry in REGISTRY.snapshot().items():
        for series in entry["series"]:
            if "value" in series:
                labels = ",".join(f"{k}={v}" for k, v in sorted(series["labels"].items()))
                out[f"{name}{{{labels}}}"] = float(series["value"])
    return out


def assert_index_is_the_reference(session, name, lake, indexed, included, batch_rows=None):
    """File set, rows per bucket, row order and every payload value."""
    nb = session.conf.num_buckets
    want = reference_index(glob.glob(os.path.join(lake, "*.parquet")), indexed, included, nb, batch_rows)
    columns = indexed + included
    got = {}
    for f in index_files(session, name):
        t = pq.read_table(f)
        assert t.column_names == columns
        got.setdefault(bucket_of_file(f), []).append(run_content(t, columns))
    assert sorted(got) == sorted(want), "the buckets that have files"
    for b, runs in want.items():
        assert len(got[b]) == len(runs), f"bucket {b}: one file a chunk that has rows for it"
        # file names carry a random suffix: runs compare as a multiset
        assert sorted(got[b]) == sorted(run_content(r, columns) for r in runs), f"bucket {b} differs"
    return want


def big_keys(t, rng):
    """l_orderkey beyond 32 bits: the key travels as two int32 planes."""
    wide = pa.array(t.column("l_orderkey").to_numpy() * np.int64(1_000_003) + np.int64(1 << 40))
    return t.set_column(t.column_names.index("l_orderkey"), "l_orderkey", wide)


def zipf_keys(t, rng):
    """Zipf(1.2): key 1 holds a sixth of the rows, so its bucket's owner is
    sent more than an even share by every chip."""
    skewed = pa.array(np.minimum(rng.zipf(1.2, t.num_rows), 1 << 30).astype(np.int64))
    return t.set_column(t.column_names.index("l_orderkey"), "l_orderkey", skewed)


CASES = {
    # (file rows, index, conf, edit of the lake, whether the exchange is run again)
    "date-key": ([6000, 6000], LI_SD, {}, None, False),
    "int64-key": ([6000, 6000], LI_OK, {}, None, False),
    "int64-key-beyond-32-bits": ([6000, 6000], LI_OK, {}, big_keys, False),
    # eight ship modes over 24 buckets: skew by nature, the retry may or may not be taken
    "string-key": ([6000, 6000], (["l_shipmode"], ["l_quantity", "l_shipdate", "l_extendedprice"]), {}, None,
                   None),
    # chunks of 2,500, 2,500 and 1,200 rows: 1,024 and 512 rows a chip on four chips
    "two-chunk-shapes": ([5000, 1200], LI_SD, {hst.keys.TPU_BUILD_BATCH_ROWS: 3000}, None, False),
    "zipf-key-takes-the-retry": ([6000], LI_OK, {hst.keys.TPU_ROWS_PER_SHARD_CAPACITY_FACTOR: 1.0}, zipf_keys, True),
}


@pytest.mark.parametrize("n_dev", [4, 8])
@pytest.mark.parametrize("case", sorted(CASES))
def test_create_index_on_the_mesh_writes_the_reference(tmp_path, case, n_dev):
    file_rows, (indexed, included), conf, edit, retry = CASES[case]
    lake = write_lake(tmp_path / "lake", 2700 + n_dev, file_rows, edit=edit)
    session = session_on(tmp_path, n_dev, "mesh", **conf)
    assert session.mesh.devices.size == n_dev
    before = counters()
    hst.Hyperspace(session).create_index(session.read_parquet(lake),
                                         hst.CoveringIndexConfig("idx", indexed, included))
    grew = grown(before)
    # the mesh path ran: every row arrived at one chip, none twice
    assert grew["hs_build_exchange_slots_total{kind=valid}"] == sum(file_rows)
    assert retry is None or (grew["hs_build_exchange_retries_total{}"] >= 1) == retry
    assert_index_is_the_reference(session, "idx", lake, indexed, included,
                                  conf.get(hst.keys.TPU_BUILD_BATCH_ROWS, session.conf.build_batch_rows))


@pytest.mark.parametrize("n_dev", [4, 8])
def test_the_chips_shares_are_disjoint_and_add_up_to_the_reference(tmp_path, n_dev):
    """What chip ``d`` holds after the exchange is exactly the buckets ``b % n
    == d``, in the reference's order; no row is on two chips; together the
    shares are the reference's index."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hyperspace_tpu.ops import encode
    from hyperspace_tpu.ops.bucketize import distributed_bucket_sort_build
    from hyperspace_tpu.utils.x64 import ensure_x64

    ensure_x64()
    nb, rows = 24, 8000
    lake = write_lake(tmp_path / "lake", 2790 + n_dev, [rows])
    want = reference_index(glob.glob(os.path.join(lake, "*.parquet")), ["l_shipdate"], ["l_orderkey"], nb)
    source = pq.read_table(os.path.join(lake, "part-00000.parquet"))
    ship = source.column("l_shipdate").to_numpy(zero_copy_only=False)
    keys, kinds, host_hashes = encode.encode_sort_columns([ship])
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("buckets",))
    sharding = NamedSharding(mesh, P("buckets"))
    per_dev = 2048 if n_dev == 4 else 1024
    pad = per_dev * n_dev - rows
    capacity = per_dev  # always fits
    out = distributed_bucket_sort_build(
        mesh, [jax.device_put(np.pad(k, (0, pad)), sharding) for k in keys], [], kinds,
        jax.device_put(np.arange(per_dev * n_dev, dtype=np.int32), sharding), rows, nb, capacity)
    bkts, ridx, vld, ovf = (np.asarray(a) for a in out)
    assert int(ovf.sum()) == 0
    shard = n_dev * capacity
    seen = np.zeros(rows, dtype=np.int64)
    for d in range(n_dev):
        valid = vld[d * shard:(d + 1) * shard]
        nv = int(valid.sum())
        assert valid[:nv].all()  # valid rows are the shard's prefix
        b, r = bkts[d * shard:d * shard + nv], ridx[d * shard:d * shard + nv]
        assert set(np.unique(b) % n_dev) <= {d}, "a chip holds only its own buckets"
        np.add.at(seen, r, 1)
        mine = [x for x in sorted(want) if x % n_dev == d]
        assert sorted(np.unique(b)) == mine
        # the share, bucket by bucket, is the reference's run
        bounds = np.searchsorted(b, np.arange(nb + 1))
        for x in mine:
            got = source.take(pa.array(r[bounds[x]:bounds[x + 1]])).select(["l_shipdate", "l_orderkey"])
            (run,) = want[x]
            assert got.equals(run), f"chip {d}, bucket {x}"
    assert (seen == 1).all(), "the shares are disjoint and leave no row out"
    assert np.array_equal(bucket_of([ship], nb)[ridx[:1]], bkts[:1])  # the reference's own hash


def test_the_mesh_build_keeps_the_names_and_counts_the_benchmark_reads(tmp_path):
    """Stages, counters, the program's module name and its scopes as the
    cell ``sf10-build-x4`` reads them; sharded arrays count once on the link,
    not once a chip."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.ops.bucketize import _build_exchange_program

    n_dev, file_rows = 4, [5000, 1200]
    lake = write_lake(tmp_path / "lake", 2727, file_rows)
    session = session_on(tmp_path, n_dev, "names", **{hst.keys.TPU_BUILD_BATCH_ROWS: 3000})
    before = counters()
    hst.Hyperspace(session).create_index(session.read_parquet(lake), hst.CoveringIndexConfig("idx", *LI_SD))
    grew = grown(before)
    for stage in ("decode-keys", "encode-keys", "h2d-launch", "decode-payload", "combine", "exchange-drain",
                  "d2h-counts", "d2h-perm", "take-write", "take", "write", "log-commit"):
        assert grew[f"hs_stage_seconds_total{{cat=build,stage={stage}}}"] > 0, stage
    # three chunks: 2,500 + 2,500 rows at 1,024 a chip (capacity 512), 1,200 at 512 (capacity 256)
    shipped = 2 * 16 * 512 + 16 * 256
    assert grew["hs_build_exchange_slots_total{kind=shipped}"] == shipped
    assert grew["hs_build_exchange_slots_total{kind=valid}"] == sum(file_rows) == grew["hs_build_rows_total{}"]
    assert grew["hs_build_exchange_retries_total{}"] == 0
    want = reference_index(glob.glob(os.path.join(lake, "*.parquet")), *LI_SD, 24, 3000)
    for d in range(n_dev):
        owned = sum(r.num_rows for b, runs in want.items() if b % n_dev == d for r in runs)
        assert grew[f"hs_build_exchange_rows_total{{device={d}}}"] == owned
    # bytes of the whole array, once: an int32 key plane and the int32 row
    # index up; bucket, row index (int32) and the mask (1 byte) down
    padded = 2 * 4 * 1024 + 4 * 512
    assert grew["hs_h2d_bytes_total{site=build-keys}"] == padded * (4 + 4)
    assert grew["hs_d2h_bytes_total{site=build-perm}"] == shipped * (4 + 4 + 1)
    assert grew["hs_d2h_bytes_total{site=build-counts}"] == 3 * n_dev * 8

    fn = _build_exchange_program(session.mesh, ("M",), 24, 512)
    sharding = jax.sharding.NamedSharding(session.mesh, jax.sharding.PartitionSpec("buckets"))
    col = jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=sharding)
    lowered = fn.lower((col,), (), col, jax.ShapeDtypeStruct((), jnp.int64))
    assert lowered.as_text().startswith("module @jit_hs_index_build_exchange")
    scoped = lowered.as_text(debug_info=True)
    # inside shard_map the phases' scopes start the operations' names
    assert all(f'"{phase}/' in scoped for phase in ("hash", "exchange", "sort"))
    assert lowered.compile().as_text().count(" all-to-all(") == 1


def test_the_build_and_the_query_side_take_the_mesh_from_one_place(tmp_path):
    """``hyperspace.parallel.mesh.devices = 4`` of 8: the build runs over
    four chips, and the sharded executor holds the very same mesh."""
    import jax

    from hyperspace_tpu.parallel.executor import ShardedExecutor

    assert len(jax.devices()) == 8
    lake = write_lake(tmp_path / "lake", 2704, [6000])
    session = session_on(tmp_path, 4, "one")
    before = counters()
    hst.Hyperspace(session).create_index(session.read_parquet(lake), hst.CoveringIndexConfig("idx", *LI_SD))
    grew = grown(before)
    assert [grew.get(f"hs_build_exchange_rows_total{{device={d}}}", 0) > 0 for d in range(8)] == [True] * 4 + [False] * 4
    assert ShardedExecutor.maybe(session).mesh is session.mesh and session.mesh.devices.size == 4
    # the keys shape the mesh for both at once; a mesh that was set stays
    session.conf.set(hst.keys.PARALLEL_MESH_DEVICES, 2)
    assert ShardedExecutor.maybe(session).mesh is session.mesh and session.mesh.devices.size == 2
    session.conf.set(hst.keys.PARALLEL_ENABLED, False)
    assert ShardedExecutor.maybe(session) is None and session.mesh.devices.size == 8
    pinned = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("buckets",))
    session.set_mesh(pinned)
    session.conf.set(hst.keys.PARALLEL_ENABLED, True)
    assert session.mesh is pinned and ShardedExecutor.maybe(session).mesh is pinned
