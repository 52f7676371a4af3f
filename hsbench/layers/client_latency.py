"""A percentile of the window's latencies on the benchmark's own client
clock, from the due time. Milliseconds."""

from hsbench import stats


def read(run, params):
    done = [o for o in run.outcomes if o.done is not None]
    if not done:
        return None
    return stats.percentile(stats.latencies_ms([o.due for o in done], [o.done for o in done]),
                            float(params["percentile"]))
