"""The one traffic generator. A mix is a data file under ``hsbench/traffic/``
that names its loop kind and its parameters; a query template is
``hsbench/templates/<name>.sql`` with ``{placeholders}``, and
``hsbench/templates/<name>.json`` says how each placeholder is drawn. Nothing
here knows a template, a mix or a cell by name.

Everything drawn comes from ``numpy.random.default_rng([seed, stream])``: the
same seed gives the same requests at the same times. A served mix asks every
seed for the same set of requests, drawn from its own ``param_seed``: the
run's seed changes the data under them and their order, and nothing else.
"""

from __future__ import annotations

import datetime
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
STREAM_ARRIVALS, STREAM_MIX, STREAM_PARAMS, STREAM_KEYS, STREAM_POOL, STREAM_ORDER = range(6)


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


class Template:
    def __init__(self, name: str):
        self.name = name
        with open(os.path.join(HERE, "templates", f"{name}.sql")) as f:
            self.sql = f.read().strip()
        with open(os.path.join(HERE, "templates", f"{name}.json")) as f:
            spec = json.load(f)
        self.params = spec.get("params", {})
        self.ordered = bool(spec.get("ordered", False))

    def text(self, params: dict) -> str:
        return self.sql.format(**params)


def _add_months(day: datetime.date, months: int) -> datetime.date:
    m = day.month - 1 + months
    return day.replace(year=day.year + m // 12, month=m % 12 + 1)


def _date_steps(spec: dict) -> list:
    start = datetime.date.fromisoformat(spec["from"])
    step, count = spec["step"], int(spec["count"])
    if step == "day":
        return [(start + datetime.timedelta(days=i)).isoformat() for i in range(count)]
    months = {"month": 1, "year": 12}[step]
    return [_add_months(start, i * months).isoformat() for i in range(count)]


def zipf_ranks(rng, n: int, s: float, size: int) -> np.ndarray:
    """``size`` ranks in [0, n) with P(rank r) ~ 1/(r+1)**s; ``s`` 0 is uniform."""
    if s == 0:
        return rng.integers(0, n, size)
    cdf = np.cumsum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** s)
    return np.searchsorted(cdf, rng.random(size) * cdf[-1], side="left").clip(0, n - 1)


class ParamDrawer:
    """Draws one template's placeholders. ``key`` placeholders come from the
    distinct values of a source column, ranked by a seeded permutation and
    drawn with the mix's skew; the rest from the template's own lists."""

    def __init__(self, template: Template, seed: int, skew: float, column_values):
        self.template = template
        self.skew = float(skew)
        self.domains = {}
        for pname, spec in template.params.items():
            if spec["kind"] == "key":
                values = np.unique(column_values(spec["table"], spec["column"]))
                perm_rng = np.random.default_rng([int(seed), STREAM_KEYS, len(self.domains)])
                self.domains[pname] = values[perm_rng.permutation(len(values))]

    def draw(self, rng) -> dict:
        out = {}
        for pname, spec in self.template.params.items():
            kind = spec["kind"]
            if kind == "key":
                domain = self.domains[pname]
                v = domain[int(zipf_ranks(rng, len(domain), self.skew, 1)[0])]
                out[pname] = str(v.astype("datetime64[D]")) if v.dtype.kind == "M" else int(v)
            elif kind == "choice":
                values = [v for v in spec["values"] if v != out.get(spec.get("distinct_from"))]
                out[pname] = values[int(rng.integers(0, len(values)))]
            elif kind == "date":
                steps = _date_steps(spec)
                out[pname] = steps[int(rng.integers(0, len(steps)))]
            elif kind == "int":
                out[pname] = int(rng.integers(int(spec["from"]), int(spec["to"]) + 1))
            elif kind == "decimal":
                places = int(spec["places"])
                n = int(round((spec["to"] - spec["from"]) / spec["step"])) + 1
                out[pname] = f"{spec['from'] + spec['step'] * int(rng.integers(0, n)):.{places}f}"
            elif kind == "plus":
                base = out[spec["of"]]
                if "days" in spec:
                    day = datetime.date.fromisoformat(base) + datetime.timedelta(days=int(spec["days"]))
                    out[pname] = day.isoformat()
                else:
                    out[pname] = f"{float(base) + float(spec['amount']):.{int(spec['places'])}f}"
            else:
                raise ValueError(f"{self.template.name}: unknown placeholder kind {kind!r}")
        return out


class Request:
    __slots__ = ("template", "params", "text", "tenant", "due_s")

    def __init__(self, template: Template, params: dict, tenant: str, due_s: float = 0.0):
        self.template = template
        self.params = params
        self.text = template.text(params)
        self.tenant = tenant
        self.due_s = due_s

    @property
    def key(self) -> tuple:
        return (self.template.name, json.dumps(self.params, sort_keys=True))


def _shares(mix: dict) -> tuple:
    names = [t["name"] for t in mix["templates"]]
    w = np.array([float(t.get("share", 1.0)) for t in mix["templates"]])
    return names, w / w.sum()


def arrival_times(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times in [0, seconds): Poisson arrivals at ``rate_per_s``, or with
    ``burst`` {on_s, off_s, rate_per_s} alternating that rate and silence."""
    rng = np.random.default_rng([int(seed), STREAM_ARRIVALS])
    burst = mix.get("burst")
    rate = float(burst["rate_per_s"] if burst else mix["rate_per_s"])
    n = int(rate * seconds * 1.5) + 64
    if mix.get("arrivals", "poisson") == "poisson":
        t = np.cumsum(rng.exponential(1.0 / rate, n))
    else:  # "uniform": evenly spaced
        t = (np.arange(n) + 0.5) / rate
    if burst:  # stretch the busy clock over the silences
        on, off = float(burst["on_s"]), float(burst["off_s"])
        t = t + np.floor(t / on) * off
    return t[t < seconds]


def _exact_shares(p: np.ndarray, n: int) -> np.ndarray:
    """``n`` template indexes with every template as near its share as whole
    requests allow (largest remainders), in no particular order."""
    counts = np.floor(p * n).astype(int)
    for k in np.argsort(-(p * n - counts), kind="stable")[:n - counts.sum()]:
        counts[k] += 1
    return np.repeat(np.arange(len(p)), counts)


def open_schedule(mix: dict, templates: dict, drawers: dict, seed: int, seconds: float) -> list:
    """The requests of an open loop, each with its due time. Every seed offers
    the same set of requests at the same due times, in another order: the
    arrivals, exact template shares and the draws (a key by its rank) come
    from the MIX's ``param_seed``, and the run's seed deals the requests out
    over the due times. What a rank's key is comes from the run's seed, with
    the data (``ParamDrawer``). Drawn from the run's seed, two windows differed
    in how many requests they offered, of which templates, and in how often a
    key came again: the median latency moved 5.2 -> 8.6 ms with the seed."""
    fixed = int(mix["param_seed"])
    times = arrival_times(mix, fixed, seconds)
    names, p = _shares(mix)
    par_rng = np.random.default_rng([fixed, STREAM_PARAMS])
    asked = [(names[k], drawers[names[k]].draw(par_rng)) for k in _exact_shares(p, len(times))]
    order = np.random.default_rng([int(seed), STREAM_ORDER]).permutation(len(asked))
    tenants = mix.get("tenants", ["default"])
    return [Request(templates[asked[k][0]], asked[k][1], tenants[i % len(tenants)], float(t))
            for i, (t, k) in enumerate(zip(times, order))]


def pool(mix: dict, templates: dict, drawers: dict) -> list:
    """The closed loop's fixed set of queries: ``params_per_template`` draws of
    every template from the MIX's ``param_seed``, not from the run's seed, so
    that every seed asks for the same set of sizes."""
    rng = np.random.default_rng([int(mix["param_seed"]), STREAM_POOL])
    out = []
    for t in mix["templates"]:
        for _ in range(int(mix["params_per_template"])):
            out.append((t["name"], drawers[t["name"]].draw(rng)))
    return out


def closed_sequences(mix: dict, templates: dict, drawers: dict, length: int) -> list:
    """One endless-enough list of requests per client: rotations of the
    templates, each taking the next of its pooled parameter sets. The order
    too comes from the mix's ``param_seed``: with two clients in step, which
    queries run side by side moved the rate by a tenth from seed to seed, so
    the seed changes the data under the queries and nothing else."""
    by_template = {}
    for name, params in pool(mix, templates, drawers):
        by_template.setdefault(name, []).append(params)
    names = list(by_template)
    tenants = mix.get("tenants", ["default"])
    sequences = []
    for c in range(int(mix["clients"])):
        rng = np.random.default_rng([int(mix["param_seed"]), STREAM_MIX, c])
        nxt = {n: int(rng.integers(0, len(by_template[n]))) for n in names}
        seq = []
        while len(seq) < length:
            for k in rng.permutation(len(names)):
                name = names[k]
                params = by_template[name][nxt[name] % len(by_template[name])]
                nxt[name] += 1
                seq.append(Request(templates[name], params, tenants[c % len(tenants)]))
        sequences.append(seq)
    return sequences
