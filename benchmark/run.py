"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process owns the chip and starts no child. It configures the compile
cache by the repo's one rule (``trino_tpu.compile_cache``), starts an
in-process ``CoordinatorServer`` and one ``WorkerServer``, and drives only
``POST /v1/statement``, drained through ``nextUri``, by
``trino_tpu.client.remote.StatementClient``. It builds the window's
statements from ``--seed``, warms exactly those (each distinct statement
once: generator fill, staging, every compile), measures for ``--seconds``,
then compares the rows the window's own statements returned with the plain
reference, prints one JSON object as the last line of its standard output
and exits. A run that finds no TPU, or fewer chips than the cell asks for,
exits with code 3 and prints no result: there is no CPU fallback (the CPU
rehearsal is an argument of :func:`run`, which the tests pass; the command
line has none).

``setup_s`` runs from the start of this process to the window's first
statement. The reference's answers are computed once the window has closed,
the device's peak has been read and the servers are stopped; they are in
neither ``setup_s`` nor the window.

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1`` wraps a
steady part of the window in ``jax.profiler`` (at least one whole statement
of each template where the cap allows), reads the kernel ledger of the
window's statements once the window has closed, and reports the per-layer
metrics, ``device.busy_s`` / ``device.window_s`` and ``breakdown``.
"""
from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import check, loadgen, spec, trace  # noqa: E402

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
NO_CHIP_EXIT = 3
TRACE_START_AFTER_S = 1.0     # let the window reach its steady state first
TRACE_MIN_S = 5.0
TRACE_MAX_S = 20.0            # the cap: a longer trace does not come back whole
PROFILES_KEPT = 64            # devprofiler.MAX_QUERY_PROFILES: an LRU


class CompileCounter:
    """XLA backend compiles, from ``jax.monitoring`` (copied from
    ``chip_smoke.py``): (when, seconds) of each."""

    def __init__(self) -> None:
        import jax

        self.events: List[Tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self.events.append((time.perf_counter(), duration))

    def between(self, start: float, end: float) -> List[float]:
        return [d for at, d in self.events if start <= at <= end]


@dataclasses.dataclass
class RunData:
    """What the per-layer readers read."""
    cell: spec.Cell
    window: loadgen.Window
    compiles_in_window: List[float]
    profiles: Dict[str, List[dict]]       # query id -> kernel-ledger rows
    trace: Optional[dict]                 # trace.reduce_events, or None
    traced: List[Tuple[loadgen.Record, float]]  # (record, share inside trace)
    peaks: Optional[dict]
    type_bytes: Dict[str, int]


def device_info() -> Dict[str, object]:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def configure_cache() -> str:
    """The repo's one rule for where the compile cache lives
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``: a
    fixed path inside the checkout), and, wherever it lives, every program
    kept: the served tier is hundreds of programs that compile in under a
    second each, which JAX's default threshold would compile anew in every
    run's set-up (169 of them, 4.6 s, in a warm scan_agg run on the v5e
    before this was set: my chip run, PR 26)."""
    import jax

    from trino_tpu.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats()  # None on the CPU backend
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# --------------------------------------------------------------- the servers
class Served:
    """The system under test: an in-process coordinator and one worker."""

    def __init__(self) -> None:
        from trino_tpu.server.coordinator import CoordinatorServer
        from trino_tpu.server.worker import WorkerServer

        self.coord = CoordinatorServer()
        self.coord.start()
        self.worker = WorkerServer(coordinator_url=self.coord.base_url,
                                   node_id="bench0")
        self.worker.start()
        if not self.coord.registry.wait_for_workers(1, timeout=30.0):
            self.stop()
            raise RuntimeError("the worker never announced itself")
        self.url = self.coord.base_url

    def client(self, props: Dict[str, str]):
        from trino_tpu.client.remote import StatementClient

        return StatementClient(self.url, props)

    def get(self, path: str):
        from trino_tpu.server import wire

        return wire.json_request("GET", f"{self.url}{path}")

    def stop(self) -> None:
        self.worker.stop()
        self.coord.stop()


# ------------------------------------------------------------------- tracing
class WindowTracer(threading.Thread):
    """Wraps a steady part of the window in ``jax.profiler``: starts a
    little after the window's start, stops once every template has had one
    whole statement inside it (and TRACE_MIN_S have passed), at the cap, or
    a little before the window's end, whichever comes first."""

    def __init__(self, trace_dir: str, templates: List[str], seconds: float,
                 max_s: float) -> None:
        super().__init__(name="window-tracer", daemon=True)
        self.trace_dir = trace_dir
        self.templates = set(templates)
        self.seconds = seconds
        self.max_s = max_s
        self.done: List[loadgen.Record] = []   # appended by loadgen's threads
        self.wall: Optional[Tuple[float, float]] = None  # time.time() span
        self.error: Optional[str] = None

    def run(self) -> None:
        import jax

        t0 = time.perf_counter()
        time.sleep(min(TRACE_START_AFTER_S, self.seconds / 4))
        try:
            # no Python function events: they slow the host under trace and
            # fill the trace; the harness's annotations need none of them
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1   # the harness's annotations only
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        except Exception as e:  # noqa: BLE001 — the run then reports no trace
            self.error = f"start_trace: {type(e).__name__}: {e}"
            return
        started, wall0 = time.perf_counter(), time.time()
        stop_by = min(started + self.max_s, t0 + self.seconds - 0.5)
        while time.perf_counter() < stop_by:
            time.sleep(0.05)
            whole = {r.template for r in list(self.done) if r.sent >= started}
            if (whole >= self.templates
                    and time.perf_counter() - started >= TRACE_MIN_S):
                break
        wall1 = time.time()
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            self.error = f"stop_trace: {type(e).__name__}: {e}"
            return
        self.wall = (wall0, wall1)


def _phase_spans(served: Served, records, offset_ns: int
                 ) -> List[Tuple[int, int, str]]:
    """The program's spans of ``records``, as (start, end, ledger phase) on
    the trace's clock; leaf phases only (an enclosing window would cover
    every gap)."""
    from trino_tpu.obs.timeline import SPAN_PHASE

    spans = []

    def walk(node: dict) -> None:
        mapped = SPAN_PHASE.get(node.get("name"))
        start, dur = node.get("start"), node.get("durationS")
        if mapped and start is not None and dur is not None:
            a = int(float(start) * 1e9) + offset_ns
            spans.append((a, a + int(float(dur) * 1e9), mapped[1], mapped[0]))
        for child in node.get("children", ()):
            walk(child)

    for rec in records:
        if rec.query_id is None:
            continue
        try:
            tree = served.get(f"/v1/query/{rec.query_id}/trace")
        except Exception:  # noqa: BLE001 — aged out of the coordinator
            continue
        root = tree.get("root")
        for node in (root if isinstance(root, list) else [root]):
            if node:
                walk(node)
    # the most specific phase first, so that it wins a tie in trace._cover
    spans.sort(key=lambda s: s[3])
    return [(a, b, phase) for a, b, phase, _prio in spans]


def reduce_trace(tracer: WindowTracer, served: Served, window: loadgen.Window):
    """(reduced trace or None, [(record, share of it inside the trace)])."""
    if tracer.wall is None:
        return None, []
    path = trace.find_xplane(tracer.trace_dir)
    if path is None:
        return None, []
    events = trace.read_xplane(path)
    wall0, wall1 = tracer.wall
    traced = []
    for rec in window.records:
        a, b = rec.wall_sent, rec.wall_done
        inside = min(b, wall1) - max(a, wall0)
        if inside > 0 and b > a and rec.error is None:
            traced.append((rec, inside / (b - a)))
    offset = trace.clock_offset_ns(events, window.records)
    window_ns, host_spans, in_flight = None, [], None
    if offset is not None:
        window_ns = (int(wall0 * 1e9) + offset, int(wall1 * 1e9) + offset)
        host_spans = _phase_spans(served, [r for r, _ in traced], offset)
        in_flight = [(int(r.wall_sent * 1e9) + offset,
                      int(r.wall_done * 1e9) + offset)
                     for r in window.records]
    reduced = trace.reduce_events(events, window_ns, host_spans, in_flight)
    if reduced is not None:
        reduced["clock_laid_on_trace"] = offset is not None
        reduced["xplane_bytes"] = os.path.getsize(path)
    return reduced, traced


# ------------------------------------------------------------------- metrics
def end_to_end_values(cell: spec.Cell, window: loadgen.Window,
                      setup_s: float) -> Dict[str, Optional[float]]:
    """Every end-to-end number the harness knows, by name; a cell reports
    those that BENCHMARK.json gives it."""
    records = window.records
    values: Dict[str, Optional[float]] = {"setup_s": setup_s}
    by_template: Dict[str, List[float]] = {}
    for r in records:
        by_template.setdefault(r.template, []).append(1000.0 * r.latency_s)
    if by_template:
        # TPC-H Power's arithmetic: per template the MEAN of all its
        # statements in the window, then the geometric mean over templates
        means = [sum(v) / len(v) for v in by_template.values()]
        values["geomean_ms"] = math.exp(
            sum(math.log(m) for m in means) / len(means))
    scanned = sum(cell.templates[r.template].scan_rows(cell.row_counts)
                  for r in records if r.error is None)
    values["rows_per_s"] = (scanned / window.elapsed_s
                            if window.elapsed_s > 0 and scanned else None)
    values["stmt_p50_ms"] = spec.percentile(
        sorted(r.counted_ms for r in records), 0.50)
    return values


def per_layer_values(cell: spec.Cell, data: RunData) -> Dict[str, float]:
    out = {}
    for metric in cell.per_layer:
        body = spec.load_layer_metric(metric["name"])
        reader = importlib.import_module(f"benchmark.readers.{body['reader']}")
        value = reader.read(body, data)
        if value is not None:
            out[metric["name"]] = value
    return out


# ----------------------------------------------------------------------- run
def warm_up(served: Served, cell: spec.Cell, plan: spec.Plan) -> List[loadgen.Record]:
    """Each distinct statement once, through the same entry as the window:
    generator fill, staging, every compile."""
    client = served.client(cell.session_properties())
    records = []
    for template in cell.templates.values():
        if template.mode == "prepared":
            client.execute(spec.prepare_text(template))
    for n, stmt in enumerate(plan.distinct):
        now = time.perf_counter()
        # no client-side deadline: a cold run compiles for minutes inside one
        # statement, and a client that gave up would leave the server
        # compiling under the next one (how long a run may take is the
        # driver's rule)
        records.append(loadgen.send(client, stmt, -1, n, now,
                                    timeout=float("inf")))
    return records


def read_profiles(served: Served, records, keep: int) -> Dict[str, List[dict]]:
    """Kernel-ledger rows of the last ``keep`` answered statements."""
    out = {}
    answered = [r for r in records if r.error is None and r.query_id]
    for rec in sorted(answered, key=lambda r: r.done)[-keep:]:
        try:
            out[rec.query_id] = served.get(
                f"/v1/query/{rec.query_id}/profile")["kernels"]
        except Exception:  # noqa: BLE001 — aged out: nothing to read
            continue
    return out


def run(argv=None, require_chip: bool = True, root: str = spec.ROOT,
        out=sys.stdout, err=sys.stderr) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, root)

    device = device_info()  # first touch: a backend that cannot start raises
    if require_chip and (device["platform"] != "tpu"
                         or device["count"] < cell.chips):
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"reports {device['count']} x {device['platform']}", file=err)
        return NO_CHIP_EXIT
    peaks = (spec.load_peaks(device["kind"])
             if device["platform"] == "tpu" else None)
    cache_dir = configure_cache()
    counter = CompileCounter()
    plan = spec.build_plan(cell, args.seed, args.seconds)
    props = cell.session_properties()
    trace_dir = os.path.join(root, check.CACHE_DIR_NAME, "trace")

    served = Served()
    try:
        warm = warm_up(served, cell, plan)
        warm_profiles = read_profiles(served, warm, len(warm))
        tracer = None
        observer = None
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            tracer = WindowTracer(
                trace_dir, list(cell.templates), args.seconds,
                float(cell.traffic.get("trace_max_s", TRACE_MAX_S)))
            observer = tracer.done.append
        setup_s = time.perf_counter() - _PROCESS_START
        if tracer:
            tracer.start()
        window = loadgen.run_window(
            plan, lambda: served.client(props), args.seconds,
            senders=int(cell.traffic.get("senders", 32)),
            annotate=bool(args.trace), observer=observer)
        if tracer:
            tracer.join()
        peak = memory_peak_bytes(cell.chips)
        compiles = counter.between(window.start, window.end)
        # the ledgers are read once the window has closed, never inside it
        profiles = read_profiles(served, window.records, PROFILES_KEPT)
        reduced, traced = (reduce_trace(tracer, served, window)
                           if tracer else (None, []))
    finally:
        served.stop()
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the reference: after the window, the peak's reading and the servers
    t_ref = time.perf_counter()
    everything = warm + window.records
    answers = check.reference_answers(
        cell, {(r.template, r.binding_key): (
            r.template, r.binding_key, json.loads(r.binding_key))
            for r in everything}.values(), root)
    reference_s = time.perf_counter() - t_ref
    # warm-up statements are checked like the window's, and a fault among
    # them fails the run; attempted and failed count the window alone
    platform = device["platform"]
    numbers, failed_window, notes = check.compare(
        cell, window.records, answers, profiles, platform)
    warm_numbers, _failed, warm_notes = check.compare(
        cell, warm, answers, warm_profiles, platform)
    numbers = {k: numbers[k] + warm_numbers[k] for k in numbers}
    notes = (warm_notes + notes)[:8]
    correct = check.verdict(numbers) and bool(window.records)

    data = RunData(cell, window, compiles, profiles, reduced, traced, peaks,
                   spec.type_bytes())
    if args.trace:
        values = per_layer_values(cell, data)
        units = {m["name"]: m["unit"] for m in cell.per_layer}
    else:
        known = end_to_end_values(cell, window, setup_s)
        values = {m["name"]: known[m["name"]] for m in cell.end_to_end
                  if known.get(m["name"]) is not None}
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
    device_block = dict(device)
    device_block["memory_peak_bytes"] = peak
    result = {
        "correct": correct,
        "attempted": len(window.records),
        "failed": failed_window,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items()},
        "device": device_block,
    }
    if args.trace and reduced is not None:
        device_block["busy_s"] = reduced["busy_s"]
        device_block["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    per_template: Dict[str, int] = {}
    for r in window.records:
        per_template[r.template] = per_template.get(r.template, 0) + 1
    setup_compiles = counter.between(0.0, window.start)
    result["info"] = {
        "workload": cell.name, "seed": args.seed, "seconds": args.seconds,
        "elapsed_s": window.elapsed_s, "setup_s": setup_s,
        "reference_s": reference_s, "compile_cache": cache_dir,
        "statements": per_template,
        "xla_compiles_setup": len(setup_compiles),
        "xla_compile_s_setup": sum(setup_compiles),
        "xla_compiles_window": len(compiles),
        "profiles_read": len(profiles),
        "trace": None if reduced is None else {
            k: reduced[k] for k in ("chips", "op_events", "idle_share",
                                    "clock_laid_on_trace", "xplane_bytes")},
        "trace_error": tracer.error if tracer else None,
        "notes": notes,
    }
    result["compared"] = check.compared_block(numbers)
    for line in notes:
        print(f"benchmark: {line}", file=err)
    for name, pair in result["compared"].items():
        print(f"benchmark: compared {name} = {pair['value']} "
              f"(limit {pair['limit']})", file=err)
    print(json.dumps(result), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
