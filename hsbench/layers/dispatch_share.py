"""Share of the dispatch events of the given kinds (``exec.trace.record``
annotations on the requests' span trees) whose detail names a device path.
Percent."""


def read(run, params):
    kinds = set(params["kinds"])
    prefixes = tuple(params["device_prefixes"])
    events = [(k, d) for o in run.outcomes if o.root is not None
              for s in o.root.walk() for k, d in s.events if k in kinds]
    if not events:
        return None
    return 100.0 * sum(1 for _, d in events if d.startswith(prefixes)) / len(events)
