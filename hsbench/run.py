"""One cell, once: ``python3 -m hsbench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``. Prints the contract's JSON object as the last
line of stdout; with ``--trace 0`` the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics and the trace's breakdown.

It fails, with no result line, unless ``jax.devices()[0].platform == "tpu"``.
``--rehearse-on-cpu`` (with ``JAX_PLATFORMS=cpu``) debugs the script at a tiny
scale factor: it prints readings as log lines, puts no number under a metric's
name, and exits with code 3.

Which configuration, mix, templates, oracles and per-layer readers a cell uses
is found by name from ``BENCHMARK.json``: see ``hsbench/README.md``.
"""

from __future__ import annotations

import time

_IMPORTED_AT = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

from hsbench import check, datagen, deployment, layers, loops, stats, tracing, traffic  # noqa: E402

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
REHEARSAL_EXIT = 3


def process_age_s() -> float:
    """Seconds since this process was started, interpreter start-up included."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED_AT


def log(text: str) -> None:
    print(text, flush=True)


class Stage:
    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        log(f"setup {self.name}: {time.perf_counter() - self.t0:.1f} s")


def device_gate(chips: int, rehearse: bool) -> dict:
    """This is the first thing in the process to touch JAX."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device: {device}")
    if rehearse:
        if device["platform"] != "cpu":
            raise SystemExit("--rehearse-on-cpu needs JAX_PLATFORMS=cpu")
        log("REHEARSAL on the CPU backend: debugs the benchmark, measures nothing")
    elif device["platform"] != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0].platform == {device['platform']!r}")
    elif device["count"] < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX finds {device['count']}")
    return device


class CompileWatch:
    """Counts what JAX compiles while ``armed``: every backend compile, and the
    persistent cache's misses (a compile of a second or more that was not in it)."""

    def __init__(self):
        import jax

        self.armed = False
        self.compiles = 0
        self.compile_s = 0.0
        self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_):
        if self.armed and event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, seconds: float, **_):
        if self.armed and event == BACKEND_COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += seconds


def all_counters() -> dict:
    """Every counter of the program's registry, one total per name and label set."""
    from hyperspace_tpu.obs.metrics import REGISTRY

    out = {}
    for name, entry in REGISTRY.snapshot().items():
        for series in entry["series"]:
            if "value" in series:
                labels = ",".join(f"{k}={v}" for k, v in sorted(series["labels"].items()) if k != "server")
                out[f"{name}{{{labels}}}"] = out.get(f"{name}{{{labels}}}", 0.0) + float(series["value"])
    return out


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0


class TracedRun:
    """What the per-layer readers may read. Filled by the window."""

    def __init__(self, device_kind: str):
        self.device_kind = device_kind
        self.outcomes = []
        self.builds = []
        self.work = 0.0           # queries finished, or million source rows indexed
        self.traced_work = 0.0    # the same inside the profiler's window
        self.server_stats = (None, None)
        self.planes = None
        self.trace_busy_s = 0.0
        self.trace_window_s = 0.0
        self.source_bytes = 0
        self.index_bytes = 0
        self._before = {}

    def mark(self) -> None:
        """The program's counters as the window opens."""
        self._before = all_counters()

    def growth(self) -> dict:
        return {k: v - self._before.get(k, 0.0) for k, v in all_counters().items()}

    def counter_delta(self, name: str) -> float:
        """Growth of a counter since ``mark``, summed over its label sets."""
        return sum(v for k, v in self.growth().items() if k.startswith(name + "{"))

    def log_growth(self) -> None:
        log("counters that grew in the window: " + json.dumps(
            {k: v for k, v in sorted(self.growth().items()) if v}))


def _host_spans(outcomes) -> list:
    """The program's span trees as ``request:<template> > <category>:<span>``
    pieces in perf_counter seconds: each span's own time, without what its
    children cover, so that a moment has the label of the deepest span."""
    out = []

    def pieces(span, label):
        at = span.t0
        for c in sorted((c for c in span.children if c.t1 is not None), key=lambda c: c.t0):
            if c.t0 > at:
                out.append((label(span), at, c.t0))
            pieces(c, label)
            at = max(at, c.t1)
        if span.t1 > at:
            out.append((label(span), at, span.t1))

    for o in outcomes:
        if o.root is not None and o.root.t1 is not None:
            head = f"request:{o.request.template.name} > "
            pieces(o.root, lambda s, head=head: head + (f"{s.cat}:{s.name}" if s.cat else s.name))
    return out


def _reduce_trace(run: TracedRun, profiler, xplane: str, host_spans) -> dict:
    """Everything the device did is read inside the one traced window: from
    ``profiler.started`` (the anchor) to ``profiler.stopped``, on the trace's clock."""
    whole = tracing.read_planes(xplane)
    window = tracing.trace_window(whole, profiler.window_s)
    run.planes = planes = tracing.clip(whole, window)
    run.trace_busy_s = tracing.busy_seconds(planes)
    run.trace_window_s = (window[1] - window[0]) / 1e9  # its own length: a plane busy for all of it reads this
    ops = tracing.op_seconds(planes)
    log(f"trace window: device busy {tracing.busy_seconds(whole):.9f} s in the whole file (the old reading), "
        f"{run.trace_busy_s:.9f} s inside the window of {run.trace_window_s:.9f} s; operations summed "
        f"{sum(tracing.op_seconds(whole).values()):.9f} s in the file, {sum(ops.values()):.9f} s inside")
    offset = tracing.anchor_offset_ns(planes, profiler.anchor_perf_ns)
    return {
        "device_ops": tracing.top(ops),
        "idle_gaps": tracing.top(tracing.idle_gaps(planes, host_spans, offset, window)),
    }


def serve_cell(args, config, mix, dep, device, watch, workdir):
    from hyperspace_tpu.serving import QueryServer

    traced = bool(args.trace)
    names = [t["name"] for t in mix["templates"]]
    templates = {n: traffic.Template(n) for n in names}
    with Stage("key domains of the templates' placeholders"):
        drawers = {n: traffic.ParamDrawer(templates[n], args.seed, mix.get("key_skew_zipf_s", 0.0),
                                          dep.column_values) for n in names}
    if mix["loop"] == "open":
        schedule = traffic.open_schedule(mix, templates, drawers, args.seed, args.seconds)
        # shapes follow the template (a scan runs over its whole index), not the
        # literal: the first few distinct requests of each template compile them all
        distinct, seen = [], {}
        for r in {r.key: r for r in schedule}.values():
            if seen.setdefault(r.template.name, 0) < int(mix.get("warmup_per_template", 8)):
                seen[r.template.name] += 1
                distinct.append(r)
    else:
        # more than any client can finish: a query takes tens of milliseconds at least
        sequences = traffic.closed_sequences(mix, templates, drawers, length=int(args.seconds * 50) + 16)
        distinct = [traffic.Request(templates[n], p, "warmup")
                    for n, p in traffic.pool(mix, templates, drawers)]

    with Stage(f"{len(dep.index_specs)} index builds"):
        for name in dep.index_specs:
            dep.build(name)
    dep.session.enable_hyperspace()
    with Stage("plans checked for IndexScan"):
        first = {}
        for r in distinct:
            first.setdefault(r.template.name, r)
        no_index_scan = [n for n, r in first.items() if "IndexScan" not in dep.plan_text(r.text)]
        for n in no_index_scan:
            log(f"plan of {n} holds no IndexScan")

    if traced:
        dep.session.conf.set("hyperspace.obs.tracing.enabled", True)
    timeout = mix.get("request_timeout_s")
    annotate = tracing.annotation if traced else tracing.null_annotation
    run = TracedRun(device["kind"])
    server = QueryServer(dep.session, **config.get("server", {}))
    with server:
        with Stage(f"warm-up through the server, {len(distinct)} distinct queries"):
            width = int(config.get("server", {}).get("workers", 4))
            chunks = [distinct[i::width] for i in range(width)]
            warm = loops.closed_loop(server, [c for c in chunks if c], 1e9, timeout, tracing.null_annotation)
            burst = int(mix.get("warmup_burst", 0))
            if burst:  # queued requests of one template share a scan: walk that path too
                for r in first.values():
                    warm += loops.open_loop(server, [traffic.Request(r.template, r.params, r.tenant)
                                                     for _ in range(burst)], timeout, tracing.null_annotation)
            bad = [o.error for o in warm if o.error]
            if bad:
                raise RuntimeError(f"{len(bad)} warm-up requests failed, first: {bad[0]}")
        run.mark()
        stats0 = server.stats()
        setup_s = process_age_s()
        watch.armed = True
        t0 = time.perf_counter()
        profiler = tracing.Profiler(os.path.join(workdir, "profile")) if traced else None
        xplane = []

        def profile_slice():
            time.sleep(float(mix.get("trace_lead_s", 1.0)))
            profiler.start()
            time.sleep(float(mix["trace_seconds"]))
            xplane.append(profiler.stop())

        slicer = threading.Thread(target=profile_slice, daemon=True) if traced else None
        if slicer:
            slicer.start()
        if mix["loop"] == "open":
            outcomes = loops.open_loop(server, schedule, timeout, annotate, start=t0)
        else:
            outcomes = loops.closed_loop(server, sequences, args.seconds, timeout, annotate, start=t0)
        if slicer:
            slicer.join()
        watch.armed = False
        window_compiles = run.counter_delta("hs_xla_compiles_total")
        run.server_stats = (stats0, server.stats())
        memory = memory_peak_bytes()
    log(f"window: {watch.compiles} backend compiles of any size, {watch.compile_s:.3f} s in all "
        "(not gated: an eager operation on a new shape compiles in milliseconds); "
        f"{watch.misses} persistent-cache misses, {window_compiles:.0f} first-seen device programs (both gated)")

    done = [o for o in outcomes if o.done is not None]
    failed = [o for o in outcomes if o.done is None]
    for o in failed[:5]:
        log(f"failed request {o.request.template.name}: {o.error}")
    run.outcomes = outcomes
    in_window = [o for o in done if o.done - t0 <= args.seconds]
    run.work = float(len(done))
    end_to_end = {}
    if mix["loop"] == "open":
        lat = stats.latencies_ms([o.due for o in done], [o.done for o in done])
        late = stats.lateness_ms([o.due for o in outcomes], [o.sent for o in outcomes])
        end_to_end["query_p50_ms"] = stats.percentile(lat, 50)
        end_to_end["query_p95_ms"] = stats.percentile(lat, 95)
        log(f"open loop: {len(outcomes)} requests offered in {args.seconds} s, {len(done)} answered; "
            f"generator lateness p50 {stats.percentile(late, 50):.2f} ms, max {max(late, default=0):.2f} ms; "
            f"latency p50 {stats.percentile(lat, 50):.1f} p95 {stats.percentile(lat, 95):.1f} "
            f"p99 {stats.percentile(lat, 99):.1f} max {max(lat, default=0):.1f} ms")
    else:
        end_to_end["queries_per_s"] = len(in_window) / args.seconds
        lat = stats.latencies_ms([o.due for o in done], [o.done for o in done])
        log(f"closed loop: {mix['clients']} clients, {len(in_window)} queries answered inside "
            f"{args.seconds} s, {len(done) - len(in_window)} after it; latency p50 "
            f"{stats.percentile(lat, 50):.0f} ms, max {max(lat, default=0):.0f} ms")

    by_template = {}
    for o in done:
        by_template.setdefault(o.request.template.name, []).append((o.done - o.due) * 1e3)
    log("latency by template, p50/p95 ms: " + ", ".join(
        f"{n} {stats.percentile(v, 50):.0f}/{stats.percentile(v, 95):.0f}" for n, v in sorted(by_template.items())))

    breakdown = None
    if traced:
        run.log_growth()
        run.traced_work = float(sum(1 for o in done if profiler.started <= o.done <= profiler.stopped))
        breakdown = _reduce_trace(run, profiler, xplane[0], _host_spans(outcomes))

    # -- the reference, after the window: the program's device state is idle
    t_ref = time.perf_counter()
    oracles = {n: importlib.import_module(f"hsbench.oracles.{n}") for n in names}
    wanted = {}
    for orc in oracles.values():
        for table, cols in orc.COLUMNS.items():
            wanted.setdefault(table, set()).update(cols)
    frames = {t: datagen.load_frame(dep.dirs[t], sorted(c)) for t, c in wanted.items()}
    answers, mismatches, gap = {}, 0, 0.0
    for o in done:
        if o.request.key not in answers:
            answers[o.request.key] = oracles[o.request.template.name].answer(frames, o.request.params)
        wrong, g = check.compare_answer(o.answer, answers[o.request.key], o.request.template.ordered)
        if wrong:
            log(f"answer differs: {o.request.template.name} {o.request.params}")
        mismatches += wrong
        gap = max(gap, g)
    log(f"oracle: {len(answers)} computations for {len(done)} answers, {time.perf_counter() - t_ref:.1f} s")
    control = None
    if getattr(args, "control", False):
        from hsbench import control as controls

        control = controls.float32_control(oracles, frames, {o.request.key: o.request for o in done},
                                           answers, config["limits"])
    numbers = {
        "answer.exact_mismatches": mismatches,
        "answer.float_rel_gap": gap,
        "plan.templates_without_IndexScan": len(no_index_scan),
        "window.persistent_cache_misses": watch.misses,
        "window.xla_compiles": window_compiles,
        "window.failed_requests": len(failed),
    }
    return {
        "numbers": numbers, "attempted": len(outcomes), "failed": len(failed), "setup_s": setup_s,
        "end_to_end": end_to_end, "memory": memory, "run": run, "breakdown": breakdown,
        "control": control,
    }


def build_cell(args, config, mix, dep, device, watch, workdir):
    traced = bool(args.trace)
    rotation = list(mix["rotation"])
    estimate = {}
    with Stage("one warm build of each index of the rotation"):
        for name in rotation:
            t = time.perf_counter()
            dep.build(name, as_name=f"{name}_warm")
            estimate[name] = time.perf_counter() - t
            dep.drop(f"{name}_warm")
    log("warm builds: " + ", ".join(f"{n} {s:.1f} s" for n, s in estimate.items()))
    run = TracedRun(device["kind"])
    run.mark()
    profiler = tracing.Profiler(os.path.join(workdir, "profile")) if traced else None
    xplane, marks = [], []
    trace_builds = int(mix.get("trace_builds", 1))

    class Annotate:
        """Starts the profiler before build 0 and stops it after build
        ``trace_builds - 1``; each build is one host span of the trace."""

        def __init__(self, name):
            self.name, self.cm = name, None

        def __enter__(self):
            if traced and not marks:
                profiler.start()
            self.t0 = time.perf_counter()
            self.cm = tracing.annotation(self.name) if traced else tracing.null_annotation(self.name)
            self.cm.__enter__()

        def __exit__(self, *exc):
            self.cm.__exit__(*exc)
            marks.append((self.name, self.t0, time.perf_counter()))
            if traced and len(marks) == trace_builds:
                xplane.append(profiler.stop())

    setup_s = process_age_s()
    watch.armed = True
    builds = loops.build_loop(dep, rotation, args.seconds, estimate, Annotate)
    watch.armed = False
    window_compiles = run.counter_delta("hs_xla_compiles_total")
    memory = memory_peak_bytes()
    log(f"window: {watch.compiles} backend compiles of any size, {watch.compile_s:.3f} s in all (not gated); "
        f"{watch.misses} persistent-cache misses, {window_compiles:.0f} first-seen device programs (both gated)")
    done = [b for b in builds if b["ended_s"] is not None and b["ended_s"] <= args.seconds]
    failed = [b for b in builds if b["error"]]
    log("builds: " + ", ".join(f"{b['index']} {b['ended_s'] - b['began_s']:.1f} s" for b in done))
    rows = sum(dep.source_rows(dep.index_specs[b["index"]]["table"]) for b in done)
    end_to_end = {"build_rows_per_s": rows / max(b["ended_s"] for b in done)} if done else {}
    run.builds = builds
    run.work = rows / 1e6
    breakdown = None
    if traced:
        run.log_growth()
    if traced and xplane:
        traced_builds = done[:trace_builds]
        run.traced_work = sum(dep.source_rows(dep.index_specs[b["index"]]["table"]) for b in traced_builds) / 1e6
        breakdown = _reduce_trace(run, profiler, xplane[0], [(f"build:{n}", a, b) for n, a, b in marks])
    for b in done:
        spec = dep.index_specs[b["index"]]
        run.index_bytes += sum(os.path.getsize(f) for f in dep.index_files(b["as"]))
        run.source_bytes += sum(os.path.getsize(f) for f in datagen.source_files(dep.dirs[spec["table"]]))

    # -- the check, after the window
    t_ref = time.perf_counter()
    import numpy as np

    nb = dep.num_buckets
    sample = np.random.default_rng([args.seed, 99]).choice(
        nb, size=min(nb, int(config.get("check_sample_buckets", 12))), replace=False)
    numbers = {k: 0 for k in config["limits"] if k.startswith("index.")}
    facts = {}
    # one build of each index of the window, drawn from the seed: a sample, so
    # that the check stays shorter than the window
    pick_rng = np.random.default_rng([args.seed, 98])
    by_index = {}
    for b in done:
        by_index.setdefault(b["index"], []).append(b)
    checked = [bs[int(pick_rng.integers(0, len(bs)))] for bs in by_index.values()]
    for b in checked:
        spec = dep.index_specs[b["index"]]
        key, columns = spec["indexed"][0], spec["indexed"] + spec["included"]
        if b["index"] not in facts:
            facts[b["index"]] = check.source_facts(
                datagen.source_files(dep.dirs[spec["table"]]), key, columns, nb, sample)
        got = check.index_numbers(dep.index_files(b["as"]), dep.index_dir(b["as"]), key, columns, nb,
                                  facts[b["index"]])
        for k, v in got.items():
            numbers[k] += v
    log(f"index checks: {[b['as'] for b in checked]} of {len(done)} builds against their sources, "
        f"{time.perf_counter() - t_ref:.1f} s")
    control = None
    if getattr(args, "control", False) and checked:
        from hsbench import control as controls

        b = checked[0]
        spec = dep.index_specs[b["index"]]
        control = controls.index_controls(
            dep.index_files(b["as"]), dep.index_dir(b["as"]), spec["indexed"][0],
            spec["indexed"] + spec["included"], nb, facts[b["index"]], config["limits"])
    numbers["window.persistent_cache_misses"] = watch.misses
    numbers["window.xla_compiles"] = window_compiles
    numbers["window.failed_builds"] = len(failed)
    return {
        "numbers": numbers, "attempted": len(builds), "failed": len(failed), "setup_s": setup_s,
        "end_to_end": end_to_end, "memory": memory, "run": run, "breakdown": breakdown,
        "control": control,
    }


LOOPS = {"open": serve_cell, "closed": serve_cell, "build": build_cell}


def execute(args) -> tuple:
    """Run the cell; returns ``(result line as a dict, exit code)``."""
    manifest = deployment.manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no cell {args.workload!r} in BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[args.workload]
    config_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = deployment.load_config(config_entry["file"])
    mix = traffic.load_mix(cell["traffic"])
    for item in args.mix_set:
        key, _, value = item.partition("=")
        mix[key] = json.loads(value)
        log(f"mix override by hand: {key} = {mix[key]!r}")
    device = device_gate(int(cell["chips"]), args.rehearse_on_cpu)
    watch = CompileWatch()

    def reports(m) -> bool:
        return "workloads" not in m or cell["name"] in m["workloads"]

    layer_names = [m["name"] for m in manifest["per_layer"] if reports(m)]
    e2e_spec = {m["name"]: m for m in manifest["end_to_end"] if reports(m)}
    layer_spec = {m["name"]: m for m in manifest["per_layer"] if reports(m)}

    with Stage("imports, native decoder"):
        import hyperspace_tpu  # noqa: F401  (sets the compile cache's fixed path)
        from hyperspace_tpu import native

        native._load()
    workdir = tempfile.mkdtemp(prefix="hsbench_")
    try:
        sf = args.rehearse_sf if args.rehearse_on_cpu else None
        with Stage("datagen, session"):
            dep = deployment.Deployment(config, workdir, args.seed, scale_factor=sf)
        try:
            out = LOOPS[mix["loop"]](args, config, mix, dep, device, watch, workdir)
        finally:
            dep.close()
        correct = check.verdict(out["numbers"], config["limits"])
        readings = dict(out["end_to_end"], setup_s=out["setup_s"])
        if args.trace:
            readings = {}
            for name in layer_names:
                value = layers.read_metric(name, out["run"])
                if value is not None:
                    readings[name] = value
        spec = layer_spec if args.trace else e2e_spec
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    device = dict(device, memory_peak_bytes=out["memory"])
    if args.trace:
        device.update(busy_s=out["run"].trace_busy_s, window_s=out["run"].trace_window_s)
    result = {"correct": bool(correct), "attempted": out["attempted"], "failed": out["failed"]}
    if out.get("control") is not None:
        result["control"] = out["control"]
    if args.rehearse_on_cpu:
        for name, value in readings.items():
            log(f"rehearsal reading, CPU, not a measurement: {name} = {value!r}")
        result.update(metrics={}, device=device, rehearsal="CPU backend; no number of it is a metric")
        return result, REHEARSAL_EXIT
    missing = [n for n in spec if n not in readings] if not args.trace else []
    if missing:
        raise SystemExit(f"the window gave no reading for {missing}")
    result["metrics"] = {n: {"value": v, "unit": spec[n]["unit"]} for n, v in readings.items() if n in spec}
    result["device"] = device
    if args.trace and out["breakdown"]:
        result["breakdown"] = out["breakdown"]
    return result, 0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None, help="default: run_seconds of BENCHMARK.json")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--rehearse-sf", type=float, default=0.01)
    ap.add_argument("--mix-set", action="append", default=[], metavar="KEY=JSON",
                    help="override one key of the mix for a sweep by hand, e.g. rate_per_s=8; never in a check")
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = float(deployment.manifest()["run_seconds"])
    return args


def main(argv=None) -> int:
    result, code = execute(parse(argv))
    print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
