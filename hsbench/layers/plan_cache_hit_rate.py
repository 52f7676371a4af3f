"""Share of the window's requests whose plan came from QueryServer's plan
cache, from the difference of two ``stats()`` readings. Percent."""


def read(run, params):
    before, after = run.server_stats
    if not before or "planCache" not in after:
        return None
    hits = after["planCache"]["hits"] - before["planCache"]["hits"]
    misses = after["planCache"]["misses"] - before["planCache"]["misses"]
    return 100.0 * hits / (hits + misses) if hits + misses else None
