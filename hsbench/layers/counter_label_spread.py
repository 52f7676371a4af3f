"""How evenly the growth of one counter over the window is spread over the
values of one of its labels: the least value's growth over the mean. 100 is
flat. Nothing where the counter has fewer than two such series (a program
without it, a mesh of one). Percent."""


def read(run, params):
    prefix = params["counter"] + "{"
    per_value = {}
    for key, grown in run.growth().items():
        if key.startswith(prefix):
            labels = dict(p.split("=", 1) for p in key[len(prefix):-1].split(",") if "=" in p)
            if params["label"] in labels:
                value = labels[params["label"]]
                per_value[value] = per_value.get(value, 0.0) + grown
    total = sum(per_value.values())
    if len(per_value) < 2 or not total:
        return None
    return 100.0 * min(per_value.values()) * len(per_value) / total
