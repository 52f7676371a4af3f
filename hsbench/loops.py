"""The three loops a mix can name: ``open``, ``closed`` and ``build``. Each is
generic over what the mix file gives it and returns plain records; the metrics
are worked out from those in ``run.py``."""

from __future__ import annotations

import threading
import time


class Outcome:
    """What became of one request. Times are ``time.perf_counter()`` seconds."""

    __slots__ = ("request", "due", "sent", "done", "answer", "error", "root")

    def __init__(self, request, due):
        self.request = request
        self.due = due
        self.sent = None
        self.done = None
        self.answer = None
        self.error = None
        self.root = None  # the program's span tree, in a traced run


def _submit(server, outcome: Outcome, timeout, annotate) -> None:
    """Send one request; the future's callback stamps its completion on the
    worker's thread, so no client thread's scheduling is in the latency."""
    r = outcome.request
    outcome.sent = time.perf_counter()
    try:
        with annotate(f"submit:{r.template.name}"):
            fut = server.submit(r.text, timeout=timeout, tenant=r.tenant)
    except Exception as exc:  # AdmissionRejected, ServerClosed: the request failed
        outcome.error = f"{type(exc).__name__}: {exc}"
        return None

    def finished(f):
        outcome.done = time.perf_counter()
        outcome.root = getattr(f, "request_root", None)
        exc = f.exception()
        if exc is not None:
            outcome.error, outcome.done = f"{type(exc).__name__}: {exc}", None
        else:
            outcome.answer = f.result()

    fut.add_done_callback(finished)
    return fut


def _wait(futures, grace: float) -> None:
    for f in futures:
        if f is not None:
            try:
                f.exception(timeout=grace)
            except Exception:  # a timeout of the wait itself: the callback never ran
                pass


def open_loop(server, schedule, timeout, annotate, start=None) -> list:
    """Send every request of ``schedule`` at its due time whatever the server
    is doing, then wait for the stragglers. Latency counts from the due time."""
    t0 = time.perf_counter() if start is None else start
    outcomes, futures = [], []
    for r in schedule:
        due = t0 + r.due_s
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        o = Outcome(r, due)
        outcomes.append(o)
        futures.append(_submit(server, o, timeout, annotate))
    _wait(futures, (timeout or 30.0) + 5.0)
    return outcomes


def closed_loop(server, sequences, seconds: float, timeout, annotate, start=None) -> list:
    """One thread per client; each sends its next request when the last one
    has answered, and starts none after ``seconds``."""
    t0 = time.perf_counter() if start is None else start
    per_client = [[] for _ in sequences]

    def client(seq, out):
        for r in seq:
            now = time.perf_counter()
            if now - t0 >= seconds:
                return
            o = Outcome(r, now)
            out.append(o)
            _wait([_submit(server, o, timeout, annotate)], (timeout or 30.0) + 5.0)

    threads = [threading.Thread(target=client, args=(s, o), daemon=True)
               for s, o in zip(sequences, per_client)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [o for out in per_client for o in out]


def build_loop(deployment, rotation, seconds: float, estimate_s: dict, annotate, start=None) -> list:
    """One client builds the rotation's indexes one after another, again while
    time remains. A build that its warm-up time says cannot finish inside the
    window is not started: cut by the end it would count for nothing, and
    would only make the run longer. Returns one record per build started."""
    t0 = time.perf_counter() if start is None else start
    builds = []
    i = 0
    while True:
        name = rotation[i % len(rotation)]
        began = time.perf_counter()
        if began - t0 + estimate_s[name] >= seconds:
            break
        as_name = f"{name}_w{i}"
        rec = {"index": name, "as": as_name, "began_s": began - t0, "ended_s": None, "error": None}
        builds.append(rec)
        try:
            with annotate(f"create_index:{name}"):
                deployment.build(name, as_name=as_name)
            rec["ended_s"] = time.perf_counter() - t0
        except Exception as exc:
            rec["error"] = f"{type(exc).__name__}: {exc}"
        i += 1
    return builds
