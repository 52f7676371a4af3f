"""Working set > cache capacity: the >HBM spill story (SURVEY §7 hard part,
round-2 VERDICT item 4's mechanism half).

When the index working set exceeds the byte-capped caches (HBM column cache
in exec/device.py, host batch cache in exec/io.py), BytesLRU evicts
least-recently-used entries and queries keep returning correct results —
re-decoding/re-uploading on demand rather than failing or growing without
bound. These tests pin that behavior by shrinking the caps far below the
index size and checking correctness + cap enforcement across repeated and
rotating queries. (Chip timing of the same path at SF10 is the hardware
half: not measured.)
"""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.exec import device as D
from hyperspace_tpu.exec import io as hs_io


class _CountingLRU:
    """BytesLRU wrapper recording cumulative inserted bytes, so tests can
    prove the working set really exceeded the cap (eviction happened) rather
    than just re-asserting the cap invariant."""

    def __init__(self, cap_bytes: int):
        from hyperspace_tpu.utils.lru import BytesLRU

        self._inner = BytesLRU(cap_bytes)
        self.inserted_bytes = 0

    def put(self, key, value, nbytes, **kw):
        self.inserted_bytes += nbytes
        self._inner.put(key, value, nbytes, **kw)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __len__(self):
        return len(self._inner)


@pytest.fixture()
def tiny_caches(monkeypatch):
    """Shrink every byte-capped cache far below the index working set."""
    dev = _CountingLRU(256 * 1024)
    io_ = _CountingLRU(256 * 1024)
    rank = _CountingLRU(64 * 1024)
    monkeypatch.setattr(D, "_device_cache", dev)
    monkeypatch.setattr(D, "_RANK_CACHE", rank)
    monkeypatch.setattr(hs_io, "_io_cache", io_)
    return dev, io_, rank


@pytest.fixture()
def big_indexed(session, tmp_path):
    """Two tables whose covering indexes total ~8 MB — 30x the shrunken
    caps — so every query cycles entries through eviction."""
    hs = hst.Hyperspace(session)
    rng = np.random.default_rng(0)
    n = 200_000
    f = pa.table(
        {
            "k": rng.integers(0, 50_000, n).astype(np.int64),
            "v": rng.standard_normal(n),
            "w": rng.standard_normal(n),
        }
    )
    g = pa.table(
        {
            "gk": np.arange(50_000, dtype=np.int64),
            "gv": rng.standard_normal(50_000),
        }
    )
    for name, t in (("f", f), ("g", g)):
        root = tmp_path / name
        root.mkdir()
        pq.write_table(t, root / "p.parquet")
    fdf = session.read_parquet(str(tmp_path / "f"))
    gdf = session.read_parquet(str(tmp_path / "g"))
    hs.create_index(fdf, hst.CoveringIndexConfig("f_k_cp", ["k"], ["v", "w"]))
    hs.create_index(gdf, hst.CoveringIndexConfig("g_gk_cp", ["gk"], ["gv"]))
    session.enable_hyperspace()
    return fdf, gdf, f.to_pandas(), g.to_pandas()


class TestCachePressure:
    def test_filter_correct_under_eviction(self, session, tiny_caches, big_indexed):
        dev, io_, _ = tiny_caches
        fdf, _, fpd, _ = big_indexed
        for key in (7, 4321, 49_000, 7):  # repeat 7: hits after eviction too
            q = fdf.filter(hst.col("k") == key).select("v")
            assert "IndexScan" in q.optimized_plan().pretty()
            got = np.sort(q.collect()["v"])
            want = np.sort(fpd[fpd.k == key].v.to_numpy())
            np.testing.assert_allclose(got, want)
        assert io_.total_bytes <= io_.cap
        assert dev.total_bytes <= dev.cap

    def test_join_correct_under_eviction(self, session, tiny_caches, big_indexed):
        dev, io_, rank = tiny_caches
        fdf, gdf, fpd, gpd = big_indexed
        q = fdf.join(gdf, on=hst.col("k") == hst.col("gk")).select("v", "gv")
        for _ in range(2):  # second run re-loads whatever was evicted
            got = q.collect()
            merged = fpd.merge(gpd, left_on="k", right_on="gk")
            assert len(got["v"]) == len(merged)
            np.testing.assert_allclose(np.sort(got["gv"]), np.sort(merged.gv.to_numpy()))
        assert io_.total_bytes <= io_.cap
        assert dev.total_bytes <= dev.cap
        assert rank.total_bytes <= rank.cap

    def test_eviction_actually_happened(self, session, tiny_caches, big_indexed):
        """The working set really exceeds the caps: cumulative bytes offered
        to the cache are many times the cap, yet the residency invariant
        holds — i.e. entries were actually evicted under pressure."""
        _, io_, _ = tiny_caches
        fdf, _, fpd, _ = big_indexed
        got = fdf.filter(hst.col("k") >= 0).select("v").collect()
        assert len(got["v"]) == len(fpd)
        assert 0 < io_.total_bytes <= io_.cap
        # the scan pushed far more bytes through than fit: eviction proven
        assert io_.inserted_bytes > 4 * io_.cap
        evicted = io_.inserted_bytes - io_.total_bytes
        assert evicted > 0


class TestColdEntries:
    """A cold put is kept while there is room and goes first when there is
    not: the per-file pieces of a read whose concatenation is cached too are
    put cold (exec/io.py), so a pass of whole-scan reads cannot push a live
    entry out to keep a second copy of its own rows."""

    def test_cold_put_goes_first_and_a_get_warms_it(self):
        from hyperspace_tpu.utils.lru import BytesLRU

        lru = BytesLRU(100)
        lru.put("live", 1, 40)
        lru.put("piece-a", 2, 30, cold=True)
        lru.put("piece-b", 3, 30, cold=True)
        assert lru.keys() == ["piece-b", "piece-a", "live"] and lru.evictions == 0  # room: all kept
        lru.put("next", 4, 40)
        assert lru.keys() == ["live", "next"] and lru.evictions == 2
        lru.put("piece-c", 5, 20, cold=True)
        assert lru.get("piece-c") == 5  # used: as recent as any
        lru.put("more", 6, 30)
        assert lru.keys() == ["next", "piece-c", "more"]
        lru.put("piece-d", 7, 30, cold=True)  # no room: it is the one that goes
        assert lru.keys() == ["next", "piece-c", "more"] and lru.total_bytes == 90

    @pytest.mark.parametrize("reader", ["native-rg-scan", "per-file"])
    def test_whole_scan_reads_keep_live_entries(self, tmp_path, monkeypatch, reader):
        """Three multi-file reads of 24 KB each, pieces and concatenation,
        against a cap that holds their concatenations and one live entry but
        not their pieces as well."""
        from hyperspace_tpu.utils.lru import BytesLRU

        if reader == "per-file":
            monkeypatch.setenv("HS_NATIVE_RG", "0")
        lru = BytesLRU(100_000)
        monkeypatch.setattr(hs_io, "_io_cache", lru)
        lru.put("live", {}, 20_000)
        scans = []
        for s in range(3):
            files = []
            for i in range(3):
                f = str(tmp_path / f"s{s}_f{i}.parquet")
                pq.write_table(pa.table({"x": np.arange(1000, dtype=np.int64) + 1000 * i}), f)
                files.append(f)
            scans.append(files)
            got = hs_io.read_parquet_batch(files, ["x"])
            np.testing.assert_array_equal(got["x"], np.arange(3000))
        assert lru.get("live") is not None
        concats = [k for k in lru.keys() if isinstance(k, tuple) and k[0] == "concat"]
        assert len(concats) == 3 and lru.evictions > 0 and lru.total_bytes <= lru.cap
        for files in scans:  # each answered from its concatenation
            before = lru.hits
            hs_io.read_parquet_batch(files, ["x"])
            assert lru.hits == before + 1
