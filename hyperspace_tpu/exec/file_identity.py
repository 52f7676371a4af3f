"""What identifies a file a scan reads: ``(path, size, mtime_ns)``, and where
that comes from.

Every cache key and size gate of the query path asks the same question of the
same files — the host decode cache (``exec/io.py``), the device column cache,
the rank, re-bucket and build-side caches, the footer-rows memo, the
streaming gates — and this module is the one place that answers it:

- A file of an ``IndexScan`` has it from the scan's log entry, which recorded
  each data file's size and mtime when the index version was committed
  (``Content.file_keys()``, memoized): no syscall. Index files are written
  once under fresh names; a refresh or optimize commits other files, a
  vacuumed and rebuilt index other mtimes, and commits purge what they
  replace — so a key made of the log's triple changes whenever the committed
  content does. On a lake's filesystem a stat is a round trip: 136 us a file
  on the 9p mount of the benchmark's machine, 0.1 s over one 800-file index.
- Any other file (a ``FileScan``, a ``Scan`` over source files, hybrid scan's
  appended files, an index file no committed content of the entry knows) is
  stat'ed: its owner may rewrite it in place, and a rewrite changes mtime or
  size and so misses every cache. A file that cannot be stat'ed has no
  identity (None): callers then do not cache — a path-only key could serve
  stale data after an in-place rewrite.

The choice is made from what the code observes (the leaf's type and its
entry); nothing here remembers a stat.
"""

from __future__ import annotations

import os
from typing import List, Mapping, Optional, Sequence, Tuple

from hyperspace_tpu.models.log_entry import FileKey  # (absolute path, size, mtime_ns)
from hyperspace_tpu.plan import logical as L

SCAN_LEAVES = (L.Scan, L.FileScan, L.IndexScan)


def _count(source: str, n: int) -> None:
    if n:
        from hyperspace_tpu.obs.metrics import REGISTRY

        REGISTRY.counter(
            "hs_file_identity_total",
            "Scan files identified on the query path, by where size and mtime "
            "came from: the index log entry (no syscall) or an os.stat",
            source=source,
        ).inc(n)


def file_identities(
    files: Sequence[str], committed: Optional[Mapping[str, FileKey]] = None
) -> List[Optional[FileKey]]:
    """The identity of each of ``files``, in order: ``committed``'s (a log
    entry's ``Content.file_keys()``) where it knows the file, else one
    ``os.stat``; None for a file that cannot be stat'ed."""
    out: List[Optional[FileKey]] = []
    from_log = 0
    for f in files:
        key = committed.get(f) if committed is not None else None
        if key is not None:
            from_log += 1
        else:
            try:
                st = os.stat(f)
                key = (f, st.st_size, st.st_mtime_ns)
            except OSError:
                key = None
        out.append(key)
    _count("log", from_log)
    _count("stat", len(out) - from_log)
    return out


def committed_keys(leaf) -> Optional[Mapping[str, FileKey]]:
    """What the log recorded of a scan leaf's files: an ``IndexScan``'s
    entry has it; no other leaf does."""
    if isinstance(leaf, L.IndexScan):
        return leaf.entry.content.file_keys()
    return None


def leaf_files(leaf: L.LogicalPlan) -> List[str]:
    if isinstance(leaf, L.Scan):
        return [fi.name for fi in leaf.relation.all_file_infos()]
    return list(leaf.files)


def leaf_identities(leaf: L.LogicalPlan) -> List[Optional[FileKey]]:
    """``file_identities`` of a scan leaf's files, in scan order."""
    return file_identities(leaf_files(leaf), committed_keys(leaf))


def scan_identity(leaf: L.LogicalPlan) -> Optional[Tuple[FileKey, ...]]:
    """Cache identity of a scan leaf's file set, or None (= don't cache)
    when one of its files has none."""
    keys = leaf_identities(leaf)
    return None if None in keys else tuple(keys)


def plan_identity(plan: L.LogicalPlan, leaves=SCAN_LEAVES) -> Optional[Tuple[FileKey, ...]]:
    """``scan_identity`` over every scan leaf (of the given types) under
    ``plan``, concatenated in plan order."""
    out: List[FileKey] = []
    for leaf in L.collect(plan, lambda p: isinstance(p, leaves)):
        keys = scan_identity(leaf)
        if keys is None:
            return None
        out.extend(keys)
    return tuple(out)
