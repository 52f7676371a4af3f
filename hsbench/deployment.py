"""A configuration brought up through the entry points a user calls:
``Session`` -> ``read_parquet`` -> ``Hyperspace.create_index``. This file and
``loops.py`` are the only ones that import the program."""

from __future__ import annotations

import glob
import json
import os

from hsbench import datagen

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_config(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


class Deployment:
    """The tables of one configuration on disk and a session over them."""

    def __init__(self, config: dict, workdir: str, seed: int, scale_factor=None):
        import hyperspace_tpu as hst

        self.hst = hst
        self.config = config
        self.seed = int(seed)
        self.sf = float(config["scale_factor"] if scale_factor is None else scale_factor)
        self.workdir = workdir
        self.dirs = datagen.generate(
            os.path.join(workdir, "lake"), self.sf, self.seed, tables=tuple(config["tables"])
        )
        self.system_path = os.path.join(workdir, "indexes")
        conf = dict(config.get("conf", {}))
        conf[hst.keys.SYSTEM_PATH] = self.system_path
        self.session = hst.Session(conf=conf)
        hst.set_session(self.session)
        self.hs = hst.Hyperspace(self.session)
        self.frames = {}
        for table, d in self.dirs.items():
            self.frames[table] = self.session.read_parquet(d)
            self.frames[table].create_or_replace_temp_view(table)
        self.index_specs = {i["name"]: i for i in config["indexes"]}
        self.num_buckets = self.session.conf.num_buckets

    def build(self, name: str, as_name=None):
        """``create_index`` for one index of the roster; returns its log entry."""
        spec = self.index_specs[name]
        cfg = self.hst.CoveringIndexConfig(as_name or name, spec["indexed"], spec["included"])
        return self.hs.create_index(self.frames[spec["table"]], cfg)

    def drop(self, name: str) -> None:
        self.hs.delete_index(name)
        self.hs.vacuum_index(name)

    def index_dir(self, name: str) -> str:
        return os.path.join(self.system_path, name)

    def index_files(self, name: str) -> list:
        """The data files of an index, found on disk and not asked of the program."""
        return sorted(glob.glob(os.path.join(self.index_dir(name), "**", "*.parquet"), recursive=True))

    def source_rows(self, table: str) -> int:
        return datagen.rows_of(table, self.sf)

    def column_values(self, table: str, column: str):
        import pyarrow.parquet as pq

        t = pq.read_table(datagen.source_files(self.dirs[table]), columns=[column])
        return t.column(column).to_numpy(zero_copy_only=False)

    def plan_text(self, sql: str) -> str:
        return self.session.sql(sql).optimized_plan().pretty()

    def close(self) -> None:
        self.hst.set_session(None)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
