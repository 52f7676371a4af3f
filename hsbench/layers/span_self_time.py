"""Self time of the program's spans with the given names, per request: a
span's duration minus what its children cover. Milliseconds."""


def self_seconds(span) -> float:
    covered = sum(c.duration_s for c in span.children)
    return max(0.0, span.duration_s - covered)


def read(run, params):
    roots = [o.root for o in run.outcomes if o.root is not None and o.done is not None]
    if not roots:
        return None
    names = set(params["spans"])
    total = sum(self_seconds(s) for r in roots for s in r.walk() if s.name in names)
    return 1e3 * total / len(roots)
