"""Growth over the window of the series of one counter whose label has one
of the ``numerator`` values, over the same for the ``denominator`` values.
Percent."""


def read(run, params):
    growth = run.growth()

    def total(values):
        want = {f"{params['label']}={v}" for v in values}
        return sum(v for k, v in growth.items() if k.startswith(params["counter"] + "{")
                   and want & set(k[len(params["counter"]) + 1:-1].split(",")))

    below = total(params["denominator"])
    if not below:
        return None
    return 100.0 * total(params["numerator"]) / below
