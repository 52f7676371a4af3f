"""Seconds the program spent in the given stages over the window
(``hs_stage_seconds_total{cat,stage}``, each stage's own seconds), per unit
of work done in it (a million source rows indexed, or a query)."""


def read(run, params):
    if not run.work:
        return None
    keys = {f"hs_stage_seconds_total{{cat={params['cat']},stage={s}}}" for s in params["stages"]}
    found = [v for k, v in run.growth().items() if k in keys]
    if not found:
        return None
    return sum(found) / run.work
