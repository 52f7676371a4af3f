"""What a request waits for when it waits for the device: every ``device-wait``
of the traced window split, on the trace's clock, into four pieces that add up
to it exactly. Milliseconds a query (a piece summed over the waits, over the
queries finished in the profiler's window).

Read from ``run.planes`` alone (host planes whole, the device planes clipped to
the traced window, one clock):

- launches are the host planes' ``hs:device:device-launch module=<m>
  request=<id>`` events, waits their ``hs:device:device-wait request=<id>``
  events (``hyperspace_tpu/exec/device.py``: ``launch``, ``fetch``); a program
  that writes neither has nothing to read here (``None``);
- programs are the ``XLA Modules`` events of the first device plane. The device
  runs one queue in order, so the launches of one module name, in trace order,
  are its module events in trace order: a launch that began inside the window
  takes the first event of its name that no launch before it took and that did
  not start before the launch began (such a one belongs to an earlier launch:
  one from before the window, whose module may lie outside it and is left to
  no one, or, after a wrong match, the one before, which ends the error). The
  device plane's clock is not quite the host planes': ``SKEW_NS`` is the room
  "before the launch began" is given;
- of a wait ``[w0, w1]`` of request R, cut to the window: ``run`` = a module R
  launched runs; ``queued`` = a module of another request, or one matched to no
  launch, runs; ``start_gap`` = nothing runs, before the last module of R that
  the wait overlaps has begun; ``tail`` = nothing runs after that (the download
  and the thread's wake-up), and every idle moment of a wait that overlaps no
  module of R.

``params``: ``{"piece": "run" | "queued" | "start_gap" | "tail"}``. The first
read of a run prints the join's counts and the pieces by template.
"""

from hsbench import tracing

LAUNCH = "hs:device:device-launch "
WAIT = "hs:device:device-wait "
MODULES_LINE = "XLA Modules"
PIECES = ("run", "queued", "start_gap", "tail")
#: a module may be recorded as starting this long before its launch's annotation
#: began: the device plane's clock ran 0.05-1.02 ms ahead of the host planes' in
#: PR 39's runs on a v5e (PERF.md, section 7); with no room a third of the modules
#: of sf1-analytic found no launch, and 5 ms would still have been safe in both cells
SKEW_NS = 2e6

_last = (None, None)  # (the planes last split, their split): four metrics read one run


def _fields(name: str) -> dict:
    return dict(tok.split("=", 1) for tok in name.split(" ")[1:] if "=" in tok)


def annotations(planes: dict) -> tuple:
    """``(launches, waits)`` of the host planes' threads, each sorted by start:
    ``(start, end, request, module)`` and ``(start, end, request)``."""
    devices = set(tracing.device_planes(planes))
    launches, waits = [], []
    for plane, lines in planes.items():
        if plane in devices:
            continue
        for events in lines.values():
            for name, start, dur in events:
                if name.startswith(WAIT):
                    request = _fields(name).get("request")
                    if request:
                        waits.append((start, start + dur, request))
                elif name.startswith(LAUNCH):
                    f = _fields(name)
                    if f.get("request") and f.get("module"):
                        launches.append((start, start + dur, f["request"], f["module"]))
    return sorted(launches), sorted(waits)


def join(launches, modules, lo: float) -> tuple:
    """``(owner, lags)``: the request of each of ``modules`` (``[(name, start,
    dur)]`` sorted by start), None for one matched to no launch; and of every
    match the ns from the launch's start to the module's. ``lo``: where the
    window opens."""
    owner, lags = [None] * len(modules), []
    by_name = {}
    for i, (name, _, _) in enumerate(modules):
        by_name.setdefault(name.split("(", 1)[0], []).append(i)
    at = dict.fromkeys(by_name, 0)
    for l0, _, request, module in launches:
        events = by_name.get(module)
        if not events or l0 < lo:
            continue
        j = at[module]
        while j < len(events) and modules[events[j]][1] < l0 - SKEW_NS:
            j += 1  # started before this launch began: an earlier launch's
        if j < len(events):
            owner[events[j]] = request
            lags.append(modules[events[j]][1] - l0)
            j += 1
        at[module] = j
    return owner, lags


def split_wait(w0: float, w1: float, request: str, modules, owner) -> dict:
    """The four pieces of one wait, in ns; they add up to ``w1 - w0``. Also
    ``queued_after_own``: the part of ``queued`` after the request's last
    module ended (the request's result was ready, another's program ran)."""
    inside = [(max(s, w0), min(s + d, w1), owner[i] == request)
              for i, (_, s, d) in enumerate(modules) if s + d > w0 and s < w1]
    own = [(a, b) for a, b, mine in inside if mine]
    last_began = max((a for a, _ in own), default=w0)
    own_ended = max((b for _, b in own), default=w0)
    out = dict.fromkeys(PIECES + ("queued_after_own",), 0.0)
    cuts = sorted({w0, w1, *(a for a, _, _ in inside), *(b for _, b, _ in inside)})
    for a, b in zip(cuts, cuts[1:]):
        over = [mine for s, e, mine in inside if s <= a and e >= b]
        if any(over):
            out["run"] += b - a
        elif over:
            out["queued"] += b - a
            if own and a >= own_ended:
                out["queued_after_own"] += b - a
        elif own and b <= last_began:
            out["start_gap"] += b - a
        else:
            out["tail"] += b - a
    return out


def split(run) -> dict:
    """``{"waits": [(request, pieces)], "modules": ..., ...}`` of the traced
    window, or None where the trace holds no wait that names its request."""
    planes = run.planes
    launches, waits = annotations(planes)
    if not waits:
        return None
    lo, hi = tracing.trace_window(planes, run.trace_window_s)
    devices = tracing.device_planes(planes)
    modules = sorted(planes[devices[0]].get(MODULES_LINE, []), key=lambda e: e[1]) if devices else []
    owner, lags = join(launches, modules, lo)
    per_wait = [(r, split_wait(max(w0, lo), min(w1, hi), r, modules, owner))
                for w0, w1, r in waits if w1 > lo and w0 < hi]
    last_wait = {}
    for _, w1, r in waits:
        last_wait[r] = max(w1, last_wait.get(r, w1))
    late = sum(1 for (_, s, d), o in zip(modules, owner) if o is not None and s + d > last_wait.get(o, hi) and s + d < hi)
    return {"waits": per_wait, "launches": len(launches), "modules": len(modules), "lags": sorted(lags), "late": late,
            "unmatched_ns": sum(d for (_, _, d), o in zip(modules, owner) if o is None),
            "busy_ns": sum(e - s for s, e in tracing.union((s, s + d) for _, s, d in modules))}


def _log(run, found: dict) -> None:
    work = run.traced_work
    total = dict.fromkeys(PIECES + ("queued_after_own",), 0.0)
    by_request = {}
    for request, pieces in found["waits"]:
        mine = by_request.setdefault(request, dict.fromkeys(total, 0.0))
        for k, v in pieces.items():
            total[k] += v
            mine[k] += v
    busy, lags = found["busy_ns"], found["lags"]
    print(f"wait split: {len(found['waits'])} device-wait annotations of {len(by_request)} requests inside the window, "
          f"{sum(total[p] for p in PIECES) / 1e6:.6f} ms in all; {found['launches']} launches, {found['modules']} module "
          f"events, {len(lags)} matched to a launch, {found['modules'] - len(lags)} to none "
          f"({found['unmatched_ns'] / 1e6:.6f} ms, {100.0 * found['unmatched_ns'] / busy if busy else 0.0:.3f} % of "
          f"{busy / 1e6:.3f} ms busy); launch to module start, ms: least {lags[0] / 1e6 if lags else 0.0:.3f}, "
          f"median {lags[len(lags) // 2] / 1e6 if lags else 0.0:.3f}; {found['late']} matched modules end after their "
          "request's last wait (a request waits for all it launched: a wrong match, or a wait that outlasted the trace)",
          flush=True)
    print("wait split, ms a query: " + ", ".join(f"{k} {v / 1e6 / work:.6f}" for k, v in total.items()), flush=True)
    template = {}
    for o in getattr(run, "outcomes", []):
        ident = getattr(getattr(getattr(o, "root", None), "trace", None), "id", None)
        if ident is not None:
            template[ident] = o.request.template.name
    by_template = {}
    for request, pieces in by_request.items():
        row = by_template.setdefault(template.get(request, "(no outcome)"), dict.fromkeys(total, 0.0) | {"requests": 0})
        row["requests"] += 1
        for k, v in pieces.items():
            row[k] += v
    for name, row in sorted(by_template.items()):
        n = row.pop("requests")
        print(f"wait split by template, ms a request with a wait in the window: {name} ({n}): "
              + ", ".join(f"{k} {v / 1e6 / n:.3f}" for k, v in row.items()), flush=True)


def read(run, params):
    global _last
    if run.planes is None or not run.traced_work or not run.trace_window_s:
        return None
    if _last[0] is not run.planes:
        _last = (run.planes, split(run))
        if _last[1] is not None:
            _log(run, _last[1])
    found = _last[1]
    if found is None:
        return None
    return sum(pieces[params["piece"]] for _, pieces in found["waits"]) / 1e6 / run.traced_work
