"""Bytes of the index files written in the window per byte of the source
columns' files they were built from. Percent."""


def read(run, params):
    if not run.source_bytes:
        return None
    return 100.0 * run.index_bytes / run.source_bytes
