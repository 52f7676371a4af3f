"""Pallas kernel numerics (interpret mode on the CPU backend).

The kernels are the device programs behind MinMaxSketch builds and bucketed
write planning (ops/kernels.py); off-TPU they run in the pallas interpreter
with identical numerics. ``copy_blocks`` is the skip of the keyed grouped
aggregate (exec/device.py): numbered blocks out of one-dimensional arrays.
"""

import numpy as np
import pytest

from hyperspace_tpu.ops.kernels import _COPY_WINDOW, bucket_histogram, copy_blocks, segmented_min_max


def test_segmented_min_max_matches_numpy():
    rng = np.random.default_rng(0)
    segs = [rng.standard_normal(int(rng.integers(1, 700))) for _ in range(13)]
    mins, maxs = segmented_min_max(segs)
    for i, s in enumerate(segs):
        assert mins[i] == s.min()
        assert maxs[i] == s.max()


def test_segmented_min_max_nulls_and_empty():
    segs = [np.array([1.0, np.nan, -3.0]), np.array([]), np.array([np.nan])]
    mins, maxs = segmented_min_max(segs)
    assert mins[0] == -3.0 and maxs[0] == 1.0
    assert np.isnan(mins[1]) and np.isnan(maxs[1])
    assert np.isnan(mins[2]) and np.isnan(maxs[2])


def test_segmented_min_max_int_segments():
    segs = [np.arange(100, dtype=np.int64), np.array([7], dtype=np.int64)]
    mins, maxs = segmented_min_max(segs)
    assert mins[0] == 0 and maxs[0] == 99
    assert mins[1] == 7 and maxs[1] == 7


@pytest.mark.parametrize("n,nb", [(10_000, 64), (5, 8), (2048, 128), (3000, 200)])
def test_bucket_histogram_matches_bincount(n, nb):
    rng = np.random.default_rng(n)
    b = rng.integers(0, nb, n)
    assert np.array_equal(bucket_histogram(b, nb), np.bincount(b, minlength=nb))


def test_bucket_histogram_empty():
    assert np.array_equal(bucket_histogram(np.array([], dtype=np.int64), 8), np.zeros(8, np.int32))


def test_minmax_sketch_build_uses_exact_int_bounds(tmp_path):
    """End-to-end: DataSkippingIndex MinMax rows equal the host oracle."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    import hyperspace_tpu as hst
    from hyperspace_tpu.indexes.dataskipping import DataSkippingIndexConfig, MinMaxSketch

    rng = np.random.default_rng(5)
    root = tmp_path / "data"
    root.mkdir()
    expected = []
    for i in range(5):
        vals = rng.integers(-(10**9), 10**9, 500).astype(np.int64)
        expected.append((int(vals.min()), int(vals.max())))
        pq.write_table(pa.table({"k": vals}), root / f"f{i}.parquet")

    sess = hst.Session(conf={hst.keys.SYSTEM_PATH: str(tmp_path / "idx")})
    hst.set_session(sess)
    try:
        hs = hst.Hyperspace(sess)
        df = sess.read_parquet(str(root))
        hs.create_index(df, DataSkippingIndexConfig("mm", MinMaxSketch("k")))
        entry = sess.index_manager.get_index("mm")
        from hyperspace_tpu.indexes.registry import index_of_entry

        idx = index_of_entry(entry)
        table = idx.read_sketch_table(entry)
        mins = table.column("MinMax_k__min").to_pylist()
        maxs = table.column("MinMax_k__max").to_pylist()
        assert sorted(zip(mins, maxs)) == sorted(expected)
    finally:
        hst.set_session(None)


@pytest.mark.parametrize("n_numbers", [1, _COPY_WINDOW - 1, _COPY_WINDOW, _COPY_WINDOW + 1, 3 * _COPY_WINDOW + 5])
def test_copy_blocks_is_the_numbered_blocks_of_every_array(n_numbers):
    """Fewer blocks than copies in flight, exactly as many, and several
    windows; a block asked for twice; a short last block never read; 4-byte
    planes and the 8-byte columns a CPU keeps whole."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)
    block, n_blocks = 1024, 70
    rng = np.random.default_rng(n_numbers)
    total = n_blocks * block + 300
    arrays = [rng.integers(0, 2**31, total).astype(dt) for dt in (np.uint32, np.int32, np.float32, np.int64, np.float64)]
    numbers = np.sort(rng.integers(0, n_blocks, n_numbers)).astype(np.int32)
    if n_numbers > 2:
        numbers[1] = numbers[0]
    got = jax.jit(lambda n, a: copy_blocks(n, a, block))(jnp.asarray(numbers), [jnp.asarray(a) for a in arrays])
    assert len(got) == len(arrays)
    for g, a in zip(got, arrays):
        assert g.dtype == a.dtype
        assert np.array_equal(np.asarray(g), a[: n_blocks * block].reshape(n_blocks, block)[numbers].reshape(-1))
