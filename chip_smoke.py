"""Chip smoke: the served SQL path on the accelerator, at TPC-H sf1.

    python chip_smoke.py                 # one chip, tpch.sf1 (what CI runs)
    python chip_smoke.py --schema tiny   # rehearsal size
    python chip_smoke.py --chips 4       # only the SPMD tier, on four chips

One process owns the chip and starts no child. It builds an in-process
``CoordinatorServer`` + one ``WorkerServer`` and talks to them only through
``StatementClient`` over HTTP:

- phase ``served``: TPC-H q1, q6, q3 and q18 through ``POST /v1/statement``
  (drained through ``nextUri``), each checked to have EXECUTED (no result
  cache; kernel launches in ``GET /v1/query/{id}/profile``, every one of
  which left its output on the accelerator) through WORKER TASKS
  (``fastPath == "distributed"``); one point query on ``orders`` through
  the coordinator-local fast path; then q3 again, cold vs warm.
- phase ``compiled``: ``CompiledQuery.build`` + run of q1, with its staged
  inputs and result columns checked to live on the accelerator.
- ``--chips 4`` runs ONLY phase ``spmd``: q3 as ``DistributedQuery`` on a
  mesh of the four devices, default plan and hash-partitioned plan.

Every result must EQUAL ``tpch_reference`` (numpy, exact integers). Any
phase that raises, or any row mismatch, ends the run non-zero. The same
phases run on a machine without the chip — that is the rehearsal — but
only a TPU run exits 0 and prints to stdout; elsewhere the per-phase lines
go to stderr and the exit code is 1. There is no option that changes that.

stdout on a TPU: one JSON object per query, then as the LAST line exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List, Optional

import jax
import numpy as np

import tpch_reference as ref
from tests.tpch_sql import QUERIES as TPCH_SQL
from trino_tpu.compile_cache import configure_compile_cache

SERVED = {
    "q1": (TPCH_SQL[1], ref.q1),
    "q6": (TPCH_SQL[6], ref.q6),
    "q3": (TPCH_SQL[3], ref.q3),
    "q18": (TPCH_SQL[18], ref.q18),
}
WARM = "q3"  # served a second time: cold (compiles included) vs warm
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def device_info() -> Dict[str, object]:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _ledger_compiles() -> int:
    """Compilations in the device profiler's compile ledger so far (its
    ``hit`` events are reuses of an executable, not compilations)."""
    from trino_tpu.obs.devprofiler import DEVICE_PROFILER

    return sum(1 for e in DEVICE_PROFILER.compile_rows()
               if e["cache"] == "miss")


class CompileCounter:
    """What a phase compiled: ``compiles`` from the device profiler's
    compile ledger (the compiled and SPMD tiers), and the XLA backend
    compiles ``jax.monitoring`` reports — the eager tier's per-operator
    programs never reach the ledger, and a cold start is mostly those."""

    def __init__(self) -> None:
        self.durations: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self.durations.append(duration)

    def mark(self):
        return len(self.durations), _ledger_compiles()

    def since(self, mark) -> Dict[str, object]:
        """Compile counts since ``mark`` + the device's peak bytes now."""
        took = self.durations[mark[0]:]
        stats = jax.devices()[0].memory_stats()  # None on the CPU backend
        return {"compiles": _ledger_compiles() - mark[1],
                "xla_compiles": len(took),
                "xla_compiles_under_1s": sum(1 for d in took if d < 1.0),
                "xla_compile_s": round(sum(took), 3),
                "peak_device_bytes":
                    int(stats["peak_bytes_in_use"]) if stats else None}


def _assert_on_device(what: str, array, platform: str) -> None:
    """A host numpy array (or anything that is not a committed device
    array of the expected backend) means a path quietly computed on the
    host."""
    if not isinstance(array, jax.Array):
        raise AssertionError(f"{what} is {type(array).__name__}, "
                             "not a device array")
    platforms = {d.platform for d in array.devices()}
    if platforms != {platform}:
        raise AssertionError(f"{what} lives on {platforms}, not {platform}")


def _assert_rows(query: str, got, want) -> None:
    if got != want:
        raise AssertionError(
            f"{query}: {len(got)} rows differ from the reference's "
            f"{len(want)}: got {got[:2]} want {want[:2]}")


# ------------------------------------------------------------------ served
def _served_query(coord_url: str, schema: str, query: str, sql: str,
                  want: List[list], path: str, counter: CompileCounter,
                  extra_props: Optional[Dict[str, str]] = None) -> dict:
    """One statement over HTTP, checked: rows equal the reference, the
    query executed (no cache hit; kernel launches in its profile, all of
    whose outputs — the staged scan pages, every operator's page, the
    result page — sit on this process's accelerator) and took ``path``."""
    from trino_tpu.client.remote import StatementClient
    from trino_tpu.server import wire

    props = {"catalog": "tpch", "schema": schema,
             "result_cache_enabled": "false"}
    props.update(extra_props or {})
    client = StatementClient(coord_url, props)
    mark = counter.mark()
    t0 = time.perf_counter()
    # no client-side deadline: how long a run may take is its caller's rule
    _columns, rows = client.execute(sql, timeout=float("inf"))
    wall = time.perf_counter() - t0
    _assert_rows(query, rows, want)
    if client.cache_status == "HIT":
        raise AssertionError(f"{query}: served from the result cache")
    base = f"{coord_url}/v1/query/{client.query_id}"
    info = wire.json_request("GET", base)
    if info["state"] != "FINISHED" or info["fastPath"] != path:
        raise AssertionError(
            f"{query}: state {info['state']}, fastPath {info['fastPath']!r}"
            f" (expected FINISHED over {path!r})")
    kernels = wire.json_request("GET", base + "/profile")["kernels"]
    on_workers = sum(k["launches"] for k in kernels
                     if k["nodeId"] != "coordinator")
    launches = sum(k["launches"] for k in kernels)
    if launches == 0 or (path == "distributed" and on_workers == 0):
        raise AssertionError(
            f"{query}: kernel ledger shows {launches} launches, "
            f"{on_workers} on workers")
    platform = jax.devices()[0].platform
    elsewhere = [(k["nodeId"], k["operator"], k["platform"])
                 for k in kernels if k["platform"] != platform]
    if elsewhere:
        raise AssertionError(
            f"{query}: launches left their output off the {platform}: "
            f"{elsewhere}")
    record = {"phase": "served", "query": query, "rows": len(rows),
              "cold_s": wall, "warm_s": None, "fast_path": info["fastPath"],
              "kernel_launches": launches, "launch_platform": platform}
    record.update(counter.since(mark))
    return record


def run_served(schema: str, emit, counter: CompileCounter) -> List[dict]:
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    coord = CoordinatorServer()
    coord.start()
    worker = WorkerServer(coordinator_url=coord.base_url, node_id="smoke0")
    worker.start()
    records = []
    wants = {}
    try:
        if not coord.registry.wait_for_workers(1, timeout=30.0):
            raise RuntimeError("the worker never announced itself")
        for query, (sql, reference) in SERVED.items():
            wants[query] = ref.wire_rows(reference(schema))
            records.append(_served_query(
                coord.base_url, schema, query, sql, wants[query],
                "distributed", counter))
            emit(records[-1])
        # the other branch of the coordinator: lineitem at sf1 is over the
        # fast path's scan-row cap, a point lookup on orders is not
        key = ref.point_order_key(schema)
        records.append(_served_query(
            coord.base_url, schema, "point",
            f"select {', '.join(ref.POINT_COLUMNS)} from orders "
            f"where o_orderkey = {key}",
            ref.wire_rows(ref.point_order(schema, key)), "fast-path",
            counter, {"short_query_fast_path": "true"}))
        emit(records[-1])
        warm = _served_query(coord.base_url, schema, WARM, SERVED[WARM][0],
                             wants[WARM], "distributed", counter)
        cold = next(r for r in records if r["query"] == WARM)
        warm.update(cold_s=cold["cold_s"], warm_s=warm["cold_s"])
        records.append(warm)
        emit(warm)
    finally:
        worker.stop()
        coord.stop()
    return records


# ---------------------------------------------------------------- compiled
def run_compiled(schema: str, emit, counter: CompileCounter) -> dict:
    from trino_tpu import Session
    from trino_tpu.exec.compiled import CompiledQuery
    from trino_tpu.exec.query import plan_sql

    platform = jax.devices()[0].platform
    session = Session(properties={"catalog": "tpch", "schema": schema})
    want = ref.q1(schema)
    mark = counter.mark()
    t0 = time.perf_counter()
    cq = CompiledQuery.build(session, plan_sql(session, TPCH_SQL[1]))
    for i, staged in enumerate(cq.input_arrays):
        _assert_on_device(f"compiled q1 staged input {i}", staged, platform)
    page = cq.run()
    rows = page.to_pylist()
    cold = time.perf_counter() - t0
    for i, col in enumerate(page.columns):
        _assert_on_device(f"compiled q1 result column {i}", col.values,
                          platform)
    _assert_rows("compiled q1", rows, want)
    t0 = time.perf_counter()
    _assert_rows("compiled q1 (second run)", cq.run().to_pylist(), want)
    warm = time.perf_counter() - t0
    record = {"phase": "compiled", "query": "q1", "rows": len(rows),
              "cold_s": cold, "warm_s": warm, "fast_path": None,
              "kernel_launches": sum(
                  k["launches"] for k in cq.kernel_stats.values())}
    record.update(counter.since(mark))
    emit(record)
    return record


# -------------------------------------------------------------------- spmd
def run_spmd(schema: str, chips: int, emit,
             counter: CompileCounter) -> List[dict]:
    """q3 as one shard_map program over ``chips`` devices: the plan of the
    default thresholds (small builds broadcast; at sf1 orders is already
    too large for that) and the hash-partitioned plan (thresholds forced
    low, every exchange an ``all_to_all``), each against the local
    single-device rows and the numpy reference."""
    from jax.sharding import Mesh

    from trino_tpu import Session
    from trino_tpu.exec.compiled import CompiledQuery
    from trino_tpu.exec.query import plan_sql
    from trino_tpu.parallel.spmd import DistributedQuery
    from trino_tpu.sql.planner import stats

    devices = jax.devices()[:chips]
    if len(devices) < chips:
        raise RuntimeError(f"--chips {chips} needs {chips} devices, "
                           f"JAX reports {len(devices)}")
    mesh = Mesh(np.array(devices), ("d",))
    session = Session(properties={"catalog": "tpch", "schema": schema})
    sql = TPCH_SQL[3]
    want = ref.q3(schema)
    # local = the same body on ONE device (the compiled tier: one program;
    # the eager tier would compile each of its sorts separately first)
    t0 = time.perf_counter()
    local = CompiledQuery.build(session, plan_sql(session, sql)).run(
        ).to_pylist()
    local_s = time.perf_counter() - t0
    _assert_rows("local q3", local, want)
    records = []
    saved = (stats.GATHER_AGG_MAX_ROWS_PER_DEVICE, stats.BROADCAST_BUILD_MAX)
    try:
        for plan in ("default", "hash-partitioned"):
            if plan == "hash-partitioned":
                stats.GATHER_AGG_MAX_ROWS_PER_DEVICE = 8
                stats.BROADCAST_BUILD_MAX = 8
            mark = counter.mark()
            t0 = time.perf_counter()
            dq = DistributedQuery.build(session, plan_sql(session, sql), mesh)
            for i, staged in enumerate(dq.inputs):
                homes = {s.device for s in staged.addressable_shards}
                if len(homes) != chips:
                    raise AssertionError(
                        f"{plan} q3 staged input {i} sits on {len(homes)} "
                        f"device(s), not {chips}: {sorted(map(str, homes))}")
            xchg = sorted(k for k in dq.capacity_hints if k.startswith("xchg"))
            if plan == "hash-partitioned" and not xchg:
                raise AssertionError("the repartition path was not taken: "
                                     "no xchg capacity hints")
            rows = dq.run().to_pylist()
            cold = time.perf_counter() - t0
            _assert_rows(f"{plan} q3 vs local", rows, local)
            _assert_rows(f"{plan} q3 vs reference", rows, want)
            t0 = time.perf_counter()
            _assert_rows(f"{plan} q3 (second run)", dq.run().to_pylist(), want)
            warm = time.perf_counter() - t0
            record = {"phase": "spmd", "query": "q3", "plan": plan,
                      "rows": len(rows), "cold_s": cold, "warm_s": warm,
                      "local_s": local_s, "exchanges": len(xchg),
                      "shard_devices": chips, "recompiles": dq.recompiles}
            record.update(counter.since(mark))
            records.append(record)
            emit(record)
    finally:
        stats.GATHER_AGG_MAX_ROWS_PER_DEVICE, stats.BROADCAST_BUILD_MAX = saved
    return records


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--schema", default="sf1",
                    help="tpch schema (tiny for a rehearsal)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the SPMD tier on a four-device mesh")
    args = ap.parse_args(argv)

    device = device_info()  # first touch: a backend that cannot start raises
    on_chip = device["platform"] == "tpu"
    out = sys.stdout if on_chip else sys.stderr

    def emit(record: dict) -> None:
        print(json.dumps(record), file=out, flush=True)

    emit({"phase": "start", "schema": args.schema, "device": device,
          "compile_cache": configure_compile_cache()})
    counter = CompileCounter()
    if args.chips == 4:
        run_spmd(args.schema, 4, emit, counter)
    else:
        run_served(args.schema, emit, counter)
        run_compiled(args.schema, emit, counter)
    if not on_chip:
        print(f"chip_smoke: every phase passed, but on {device['platform']}"
              " — not a chip run", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
