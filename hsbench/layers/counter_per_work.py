"""Growth of one of the program's registry counters over the window, per
unit of work done in it (a query, or a million source rows indexed)."""


def read(run, params):
    if not run.work:
        return None
    return run.counter_delta(params["counter"]) / run.work
