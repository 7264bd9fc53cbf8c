"""Benchmark: TPC-H throughput on the flagship compiled path.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Queries: TPC-H Q1 (headline, BASELINE config #1 scaled to sf1), Q3 and Q18
at sf1 (round-over-round continuity), Q3 at sf10 (BASELINE config #2), and
TPC-DS q95 (BASELINE config #4 shape) at the largest compiler-surviving
sf. Rows/sec = LOGICAL scanned input rows / steady-state device time per
run — dynamic filtering is IN-PROGRAM since round 5 (collect + apply both
inside the one compiled body), so repeated runs repeat zero host work;
the one-time staging narrowing is reported as staging_df_s.

Measurement design (round-3; the round-2 failure mode was unfinished runs):
- The persistent XLA compile cache (trino_tpu/compile_cache.py) makes reruns
  cheap; a cold cache pays one real compile per query, so a hard DEADLINE
  guard emits the JSON line with whatever finished.
- Per-run time comes from a device-side ``fori_loop`` harness (one dispatch
  and one sync for K repetitions, so the host<->device sync does not swamp
  fast queries). The loop body
  perturbs one element per scan with an i-dependent never-taken select and
  reduces EVERY output into the carry, so XLA can neither hoist the body
  nor dead-code-eliminate operators. A K-vs-2K scaling check validates it.
- Some query bodies hit an XLA TPU compiler bug inside fori_loop (scoped
  vmem overflow on int64 scan ops); those fall back to a K-dispatch train
  with one trailing sync (accurate when device time >> sync noise, which
  holds for exactly the queries big enough to fail the fori compile).
- A bandwidth sanity bound: implied input bytes/s must stay below the v5e
  HBM roofline, else the number is reported as suspect (sanity="fail").
- ``vs_baseline`` divides by a MEASURED anchor: the same engine + queries on
  the host CPU backend, run CONCURRENTLY in a subprocess (zero wall cost).

Reference perf role: testing/trino-benchto-benchmarks/.../tpch.yaml:1-30.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_SQL = {
    "q1": """
select
    l_returnflag, l_linestatus,
    sum(l_quantity) as sum_qty,
    sum(l_extendedprice) as sum_base_price,
    sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
    sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
    avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
    avg(l_discount) as avg_disc, count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
""",
    "q3": """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10
""",
    "q18": """
select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
       sum(l_quantity)
from customer, orders, lineitem
where o_orderkey in (
        select l_orderkey from lineitem
        group by l_orderkey having sum(l_quantity) > 300)
  and c_custkey = o_custkey and o_orderkey = l_orderkey
group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
order by o_totalprice desc, o_orderdate limit 100
""",
    "q95": """
WITH ws_wh AS (
   SELECT ws1.ws_order_number, ws1.ws_warehouse_sk wh1, ws2.ws_warehouse_sk wh2
   FROM web_sales ws1, web_sales ws2
   WHERE ws1.ws_order_number = ws2.ws_order_number
     AND ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk
)
SELECT
  count(DISTINCT ws_order_number) "order count",
  sum(ws_ext_ship_cost) "total shipping cost",
  sum(ws_net_profit) "total net profit"
FROM web_sales ws1, date_dim, customer_address, web_site
WHERE cast(d_date AS date) BETWEEN cast('1999-2-01' AS date)
      AND (cast('1999-2-01' AS date) + INTERVAL '60' DAY)
  AND ws1.ws_ship_date_sk = d_date_sk
  AND ws1.ws_ship_addr_sk = ca_address_sk
  AND ca_state = 'IL'
  AND ws1.ws_web_site_sk = web_site_sk
  AND web_company_name = 'pri'
  AND ws1.ws_order_number IN (SELECT ws_order_number FROM ws_wh)
  AND ws1.ws_order_number IN (
      SELECT wr_order_number FROM web_returns, ws_wh
      WHERE wr_order_number = ws_wh.ws_order_number)
ORDER BY count(DISTINCT ws_order_number) ASC
LIMIT 100
""",
}

# name -> (catalog, schema, sql key). sf1 trio = round-over-round
# continuity; q3_sf10 = BASELINE config #2; q95_sf02 = BASELINE config #4
# at the LARGEST sf whose program the TPU compiler accepted in round 5:
# q95's plain body failed to compile (scoped-memory failure tiling a
# ~720K-row u32 sort) at sf0.5 and above; sf0.2 compiled and ran.
SPECS = {
    "q1": ("tpch", "sf1", "q1"),
    "q3": ("tpch", "sf1", "q3"),
    "q18": ("tpch", "sf1", "q18"),
    "q3_sf10": ("tpch", "sf10", "q3"),
    "q95_sf02": ("tpcds", "sf0.2", "q95"),
}
CPU_ANCHOR = ["q1", "q3", "q18"]

# q18's, q95's and sf10 q3's whole-body fori programs are large enough that
# the TPU compile of the loop-wrapped body failed or exceeded any sane
# budget in round 5 (scoped-vmem compiler limits); measure them with the
# dispatch train on the (smaller, also cacheable) plain program
TRAIN_ONLY = {"q18", "q95", "q3_sf10"}
DEADLINE_S = float(os.environ.get("BENCH_DEADLINE_S", "900"))
CHILD_TIMEOUT_S = 700.0
# HBM bandwidth by ``device_kind``; a device that is not listed is an error,
# not a default. v5e: Google Cloud documentation, "TPU v5e" — 819 GB/s.
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}

_START = time.time()


def _remaining() -> float:
    return DEADLINE_S - (time.time() - _START)


def _log(msg: str) -> None:
    print(f"[bench +{time.time() - _START:6.1f}s] {msg}", file=sys.stderr)


def _hbm_bytes_per_s() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in HBM_BYTES_PER_S:
        raise SystemExit(f"no HBM bandwidth on record for device kind "
                         f"{kind!r}: add it to HBM_BYTES_PER_S with its source")
    return HBM_BYTES_PER_S[kind]


def _setup_jax(platform: str) -> None:
    import jax

    from trino_tpu.compile_cache import configure_compile_cache

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    configure_compile_cache()


def _session_for(name: str):
    from trino_tpu import Session

    catalog, schema, _key = SPECS[name]
    # device cache ON: the cold build populates the warm-HBM table cache,
    # and a second build measures the warm staging path (warm_seconds) —
    # the repeat-traffic story BENCH tracks round over round
    return Session(properties={"catalog": catalog, "schema": schema,
                               "device_cache_enabled": True})


def _build(session, name: str):
    """-> (cq, profile dict, scan_starts). Profile distinguishes STAGED
    (what phase-1 dynamic filtering let through to the device) from LOGICAL
    (full scanned-table inputs): throughput reports logical rows over
    device + host-DF time; the HBM sanity bound applies to staged bytes
    over device time (only those bytes ride the chip)."""
    from trino_tpu.exec.compiled import CompiledQuery
    from trino_tpu.exec.query import plan_sql
    from trino_tpu.sql.planner import plan as P

    catalog, schema, key = SPECS[name]
    root = plan_sql(session, _SQL[key])
    cq = CompiledQuery.build(session, root)
    # Dynamic filtering is IN-PROGRAM since round 5 (PreloadedExecutor
    # collects build-side domains and masks probe scans inside the single
    # compiled program), so repeated runs repeat ZERO host work — the only
    # remaining host DF cost is the one-time staging narrowing (phase-1
    # numpy + domain application), reported as staging_df_s, a storage-read
    # cost like generation itself.
    scans_by_id = {
        n.id: n for n in P.walk_plan(root) if isinstance(n, P.TableScanNode)
    }
    conn = session.catalogs[catalog]
    staged_rows = logical_rows = 0
    staged_bytes = logical_bytes = 0.0
    i = 0
    starts = []
    for nid, spec in cq.input_specs.items():
        starts.append(i)
        n_arrays = spec.array_count()
        srows = int(cq.input_arrays[i].shape[0])
        sbytes = sum(
            int(a.size) * a.dtype.itemsize
            for a in cq.input_arrays[i : i + n_arrays]
        )
        node = scans_by_id[nid]
        lrows = int(conn.table_row_count(node.schema, node.table) or srows)
        staged_rows += srows
        logical_rows += lrows
        staged_bytes += sbytes
        logical_bytes += sbytes * (lrows / srows if srows else 1.0)
        i += n_arrays
    prof = {
        "rows": logical_rows,
        "staged_rows": staged_rows,
        "bytes": logical_bytes,
        "staged_bytes": staged_bytes,
        "staging_df_s": round(cq.phase1_s + cq.df_apply_s, 3),  # one-time
    }
    return cq, prof, set(starts)


def _fori_harness(cq, scan_starts):
    """jit(f)(flat, k) -> (acc, flags): run the query body k times
    device-side. The body perturbs element 0 of each scan's first column
    with an i-dependent select whose branches differ (never taken, not
    foldable: defeats loop-invariant hoisting) and folds every output into
    the carry (defeats dead-code elimination of unconsumed operators).
    Deferred error flags OR across iterations and return with the result,
    so this ONE program also drives the capacity-growth loop and the child
    compiles exactly one (large) program."""
    import jax
    import jax.numpy as jnp

    body = cq.raw_fn

    def repeated(flat, k):
        def step(i, carry):
            acc, fbits, x = carry
            xi = [
                a.at[0].set(jnp.where(i < 0, a[0] + 1, a[0]))
                if j in scan_starts else a
                for j, a in enumerate(x)
            ]
            outs, step_flags = body(xi)
            tot = jnp.float32(0)
            for o in outs:
                tot = tot + jnp.sum(o, dtype=jnp.float32) if o.dtype != jnp.bool_ \
                    else tot + jnp.sum(o).astype(jnp.float32)
            # deferred error flags OR into an int64 BITMASK: the carry
            # structure stays fixed no matter how many flags the body has
            # (the count is only known while tracing this step), keeping
            # the whole harness to ONE body instantiation (one trace of
            # the query body, not one per flag count).
            bits = jnp.int64(0)
            for j, sf in enumerate(step_flags[:63]):
                bits = bits | (jnp.any(sf).astype(jnp.int64) << j)
            if len(step_flags) > 63:  # collapse the overflow conservatively
                rest = jnp.zeros((), bool)
                for sf in step_flags[63:]:
                    rest = rest | jnp.any(sf)
                bits = bits | (rest.astype(jnp.int64) << 63)
            return acc + tot, fbits | bits, x

        acc, fbits, _ = jax.lax.fori_loop(
            0, k, step, (jnp.float32(0), jnp.int64(0), flat)
        )
        return acc, fbits

    return jax.jit(repeated)


def _measure_fori(cq, scan_starts):
    """((seconds_per_run, mode), None) via the fori harness, or (None,
    why) when the harness could not be used (XLA scoped-vmem bug on some
    bodies) — ``why`` rides the result JSON as ``fori_fallback``. Runs the
    capacity-growth loop through the harness itself (see _fori_harness)."""
    import numpy as np

    from trino_tpu.exec.executor import raise_query_errors
    from trino_tpu.sql.planner import stats

    from trino_tpu.obs.devprofiler import DEVICE_PROFILER, shape_signature

    grown = None
    for _attempt in range(6):
        f = _fori_harness(cq, scan_starts)
        try:
            t0 = time.time()
            acc, fbits = f(cq.input_arrays, 1)
            bits = int(np.asarray(fbits))
            np.asarray(acc)
            compile_first_s = time.time() - t0
            _log(f"fori compile+first: {compile_first_s:.1f}s")
            # the fori harness jits OUTSIDE CompiledQuery.run(), so its
            # compile would be invisible to the compile ledger — record it
            # here (compile + one run; the run is noise next to a cold
            # compile, and a persistent-cache hit reports honestly small)
            try:
                from trino_tpu.cache.plan_key import plan_fingerprint

                DEVICE_PROFILER.record_compile(
                    "compiled", plan_fingerprint(cq.root),
                    shape_signature(cq.input_arrays), compile_first_s,
                    "miss")
            except Exception:  # noqa: BLE001 — accounting never fails work
                pass
        except Exception as e:  # noqa: BLE001 — compiler bug fallback
            _log(f"fori harness failed ({str(e)[:120]}); falling back to train")
            return None, f"fori harness failed: {str(e)[:300]}"
        codes = cq.error_codes_cell[0]
        flags = [
            np.asarray(bool(bits >> min(j, 63) & 1)) for j in range(len(codes))
        ]
        grown = stats.grow_overflowed_hints(cq.capacity_hints, codes, flags)
        if grown is not None:
            _log(f"capacity overflow; growing {grown} and recompiling")
            cq.capacity_hints = grown
            cq._jit()
            continue
        raise_query_errors(codes, flags)
        break
    else:
        raise RuntimeError(
            "capacity still exceeded after recompiles — refusing to time a "
            "truncating program")
    t0 = time.time(); r = f(cq.input_arrays, 1); np.asarray(r[0]); t1 = time.time() - t0
    # pick K so the loop dominates sync noise, then scale-check with 2K
    k = max(4, min(400, int(10.0 / max(t1, 0.01))))
    t0 = time.time(); r = f(cq.input_arrays, k); np.asarray(r[0]); ta = time.time() - t0
    t0 = time.time(); r = f(cq.input_arrays, 2 * k); np.asarray(r[0]); tb = time.time() - t0
    per = (tb - ta) / k
    if per <= 0:
        return None, f"fori K-vs-2K scaling check failed: {ta:.4f}s vs {tb:.4f}s"
    return (per, f"fori(k={k})"), None


def _join_fraction(session, name: str):
    """Fraction of per-operator EXCLUSIVE wall spent in join kernels,
    from one eager-tier profiled run (per-operator stats sync per node —
    the only tier that can attribute time inside the fused body, since
    XLA fuses across operator boundaries in the compiled program). The
    scans ride the device cache the timed build already warmed, so this
    costs roughly one device pass, not a re-staging."""
    from trino_tpu.exec.executor import Executor
    from trino_tpu.exec.query import plan_sql

    _catalog, _schema, key = SPECS[name]
    root = plan_sql(session, _SQL[key])
    ex = Executor(session)
    ex.execute_checked(root)
    join_wall = sum(s.wall_s for s in ex.node_stats.values()
                    if s.operator == "Join")
    total = sum(s.wall_s for s in ex.node_stats.values())
    return (join_wall / total) if total > 0 else 0.0


def _measure_train(cq, k=6):
    """K-dispatch train: k dispatches queued back-to-back, one trailing
    sync; per-run = (t_1+k - t_1) / k."""
    import numpy as np

    def train(n):
        t0 = time.time()
        for _ in range(n):
            outs, _f = cq.fn(cq.input_arrays)
        np.asarray(outs[0].ravel()[0])
        return time.time() - t0

    train(1)
    t1 = min(train(1) for _ in range(3))
    tk = train(1 + k)
    per = (tk - t1) / k
    if per <= 0:
        per = t1  # noise swamped the train; report the (upper-bound) single call
        return per, "single-call-upper-bound"
    return per, f"train(k={k})"


def _bench_query(session, name: str):
    from trino_tpu.obs.devprofiler import DEVICE_PROFILER

    t0 = time.time()
    cq, prof, scan_starts = _build(session, name)
    _log(f"{name}: staged {prof['staged_rows']}/{prof['rows']} rows "
         f"({int(prof['staged_bytes']) // 1048576} MiB) in {time.time() - t0:.1f}s "
         f"staging_df={prof['staging_df_s'] * 1000:.0f}ms hints={cq.capacity_hints}")
    compiles_before = len(DEVICE_PROFILER.compile_rows())
    res = fori_fallback = None
    if name not in TRAIN_ONLY and SPECS[name][2] not in TRAIN_ONLY \
            and _remaining() > 120:
        res, fori_fallback = _measure_fori(cq, scan_starts)
    if res is None:
        # fallback program: compile + first run + growth + error check,
        # then a dispatch train on that same program
        t0 = time.time()
        cq.run()
        _log(f"{name}: first run {time.time() - t0:.1f}s "
             f"hints={cq.capacity_hints}")
        res = _measure_train(cq)
    per, mode = res
    # compile cost from the compile LEDGER (obs/devprofiler.py) — the
    # events this query's measurement produced, not a first-minus-warm
    # wall inference, so compile can no longer be confused with staging
    compile_events = DEVICE_PROFILER.compile_rows()[compiles_before:]
    compile_s = sum(e.get("compileS", 0.0) for e in compile_events
                    if e.get("cache") == "miss")
    # per-run = device time alone: dynamic filtering is in-program (traced
    # collect->mask inside the one compiled body), so repeated executions
    # repeat no host work; staging_df_s (one-time, storage-read-class) is
    # reported separately in the profile
    total = per
    device_bw = prof["staged_bytes"] / per
    sanity = "ok" if device_bw <= _hbm_bytes_per_s() else "fail"
    if sanity == "fail":
        _log(f"{name}: device {device_bw / 1e9:.0f} GB/s exceeds HBM roofline "
             f"— reporting as suspect")
    out = {
        "rows": prof["rows"],
        "staged_rows": prof["staged_rows"],
        "seconds": round(total, 5),
        "device_seconds": round(per, 5),
        "staging_df_s": prof["staging_df_s"],
        "cold_staging_s": round(getattr(cq, "staging_s", 0.0), 4),
        "compile_seconds": round(compile_s, 3),
        "compile_events": len(compile_events),
        "rows_per_sec": round(prof["rows"] / total, 1),
        "input_gbytes_per_sec": round(prof["bytes"] / total / 1e9, 2),
        "device_gbytes_per_sec": round(device_bw / 1e9, 2),
        "mode": mode,
        "fori_fallback": fori_fallback,
        "sanity": sanity,
    }
    # warm staging: rebuild against the now-populated device cache and
    # time the staging loop alone — the BENCH_r* trajectory's warm-serving
    # signal (trino_tpu/devcache/; budget permitting this is ~0). All
    # keys are always set together so the per-query record shape is
    # stable across success, failure, and budget-skip.
    out["warm_seconds"] = None
    out["warm_cache_hits"] = None
    out["warm_over_device_ratio"] = None
    out["warm_within_2x_device"] = None
    out["warm_error"] = None
    if _remaining() > 45:
        try:
            t0 = time.time()
            cq2, _prof2, _ = _build(session, name)
            out["warm_seconds"] = round(getattr(cq2, "staging_s", 0.0), 4)
            out["warm_cache_hits"] = int(getattr(cq2, "cache_hits", 0))
            # the ROADMAP item-1 target: a WARM repeat run (cached staging
            # + steady-state device time) within ~2x of pure device time
            ratio = (out["warm_seconds"] + per) / per if per > 0 else None
            out["warm_over_device_ratio"] = round(ratio, 3) if ratio else None
            out["warm_within_2x_device"] = (ratio is not None
                                            and ratio <= 2.0)
            _log(f"{name}: warm rebuild {time.time() - t0:.1f}s "
                 f"(staging {out['warm_seconds'] * 1000:.0f}ms, "
                 f"{out['warm_cache_hits']} cache hits, "
                 f"warm/device {out['warm_over_device_ratio']}x)")
        except Exception as e:  # noqa: BLE001 — warm probe must not lose the run
            out["warm_error"] = str(e)[:300]
            _log(f"{name}: warm rebuild failed: {str(e)[:120]}")
    # join-phase attribution: split join_seconds out of device_seconds so
    # BENCH_r06 can pin the q3/q18 trajectory on the join kernels rather
    # than staging. The fraction comes from an eager profiled run (warm
    # scans); join_seconds = device_seconds * fraction.
    out["join_fraction"] = None
    out["join_seconds"] = None
    out["join_fraction_error"] = None
    # eager profiling pays a per-operator host sync per node and cannot be
    # cut short once started: profile only the sf1-class queries (the plan
    # SHAPE carries the attribution; q3_sf10 shares q3's) and only with
    # real budget left
    if SPECS[name][1] == "sf1" and _remaining() > 120:
        try:
            t0 = time.time()
            frac = _join_fraction(session, name)
            out["join_fraction"] = round(frac, 4)
            out["join_seconds"] = round(per * frac, 5)
            _log(f"{name}: join fraction {frac:.1%} "
                 f"(profile run {time.time() - t0:.1f}s) -> "
                 f"join {out['join_seconds'] * 1000:.1f} ms of "
                 f"{per * 1000:.1f} ms device")
        except Exception as e:  # noqa: BLE001 — profiling must not lose the run
            out["join_fraction_error"] = str(e)[:300]
            _log(f"{name}: join-fraction profile failed: {str(e)[:120]}")
    _log(f"{name}: {total * 1000:.1f} ms/run ({per * 1000:.1f} device)  "
         f"{prof['rows'] / total / 1e6:.1f}M rows/s  [{mode}]")
    return out


def _run_child(spec: str) -> subprocess.Popen:
    env = dict(os.environ, _BENCH_CHILD=spec)
    if spec.startswith("cpu"):
        # set BEFORE python starts: the anchor must never touch the chip
        # the tpu child owns
        env["JAX_PLATFORMS"] = "cpu"
    # tpu child stderr goes to a file so a dead/timed-out child is
    # DIAGNOSABLE: its tail rides into the result JSON (round-4's "child
    # produced no result" artifacts were unactionable). cpu anchors stay on
    # DEVNULL (their only failure mode is a timeout, already labeled).
    if spec.startswith("cpu"):
        stderr, errf = subprocess.DEVNULL, None
    else:
        stderr = errf = open(f"/tmp/bench_child_{spec.replace(':', '_')}.err", "w+")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        stdout=subprocess.PIPE, stderr=stderr, text=True, env=env,
    )
    proc._errf = errf  # noqa: SLF001 — read+closed by _stderr_tail/_collect
    return proc


def _stderr_tail(proc, limit: int = 1200) -> str:
    """Read (once) and close the child's stderr capture file."""
    if getattr(proc, "_errtail", None) is not None:
        return proc._errtail
    errf = getattr(proc, "_errf", None)
    if errf is None:
        return ""
    try:
        errf.flush()
        errf.seek(0, 2)
        size = errf.tell()
        errf.seek(max(0, size - 8192))
        txt = errf.read()
    except Exception:  # noqa: BLE001
        txt = ""
    finally:
        try:
            errf.close()
        except Exception:  # noqa: BLE001
            pass
        proc._errf = None
    lines = [ln for ln in txt.splitlines() if ln.strip()]
    proc._errtail = "\n".join(lines)[-limit:]
    return proc._errtail


def _collect_child(proc: subprocess.Popen, timeout: float):
    timed_out = False
    try:
        out, _ = proc.communicate(timeout=max(timeout, 5))
    except subprocess.TimeoutExpired:
        timed_out = True
        proc.kill()
        try:
            out, _ = proc.communicate(timeout=10)
        except Exception:  # noqa: BLE001
            out = ""
    try:
        for line in (out or "").splitlines():
            if line.startswith("BENCH_CHILD_RESULT "):
                return json.loads(line[len("BENCH_CHILD_RESULT "):])
        why = "child timed out" if timed_out else "child died without a result"
        return {"error": why, "stderr_tail": _stderr_tail(proc)}
    finally:
        _stderr_tail(proc)  # reads once and closes the capture file


def _child_main(spec: str) -> None:
    """spec = 'cpu' (anchor: all queries, one process) or 'tpu:<query>'
    (one query per process: one crash or timeout can't lose other queries'
    results, and each child starts with an empty HBM). The parent never
    imports jax, so its one-at-a-time tpu children can each own the chip."""
    platform, _, only = spec.partition(":")
    _setup_jax(platform)

    from trino_tpu import Session

    import jax

    devs = jax.devices()  # a backend that cannot start fails here, at once
    if platform != "cpu":
        _hbm_bytes_per_s()  # an unlisted device kind ends the child now
    _log(f"child[{spec}]: devices {devs}")
    results = {"platform": devs[0].platform}
    for name in SPECS if not only else [only]:
        try:
            session = _session_for(name)
            if platform == "cpu":
                results[name] = _cpu_single(session, name)
            else:
                results[name] = _bench_query(session, name)
        except Exception as e:  # noqa: BLE001
            import traceback

            traceback.print_exc(file=sys.stderr)
            results[name] = {"error": str(e)[:300]}
    print("BENCH_CHILD_RESULT " + json.dumps(results))


def _cpu_single(session, name: str):
    """CPU anchor: compile + one timed run (the anchor only needs the right
    order of magnitude; CPU compiles are seconds, runs are seconds). Host
    DF work is charged identically to the TPU side."""
    import numpy as np

    cq, prof, _starts = _build(session, name)
    outs, _f = cq.fn(cq.input_arrays)  # compile + run
    np.asarray(outs[0].ravel()[0])
    t0 = time.time()
    outs, _f = cq.fn(cq.input_arrays)
    np.asarray(outs[0].ravel()[0])
    per = time.time() - t0
    return {"rows": prof["rows"], "seconds": round(per, 4),
            "rows_per_sec": round(prof["rows"] / per, 1)}


def main() -> None:
    child = os.environ.get("_BENCH_CHILD")
    if child:
        _child_main(child)
        return

    # CPU anchors run in a background thread, one child at a time (one
    # query per process — two compiled queries in one CPU process has
    # produced buffer-count mismatches; running all three at once would
    # contend with each other and understate the anchor). TPU queries run
    # one child each, sequentially: partial results survive any single
    # query's crash or timeout.
    import threading

    cpu: dict = {}

    def _cpu_anchor():
        for name in CPU_ANCHOR:
            res = _collect_child(_run_child(f"cpu:{name}"), max(_remaining(), 60))
            cpu[name] = res.get(name, res)

    anchor_thread = threading.Thread(target=_cpu_anchor, daemon=True)
    anchor_thread.start()
    tpu = {}
    for name in SPECS:
        for attempt in (1, 2):
            if _remaining() < 90:
                # keep a real attempt-1 diagnostic if one exists
                tpu.setdefault(name, {"error": "skipped: bench deadline"})
                break
            # five children share the budget. Warm-cache children take
            # 20-120s; a cold compile can eat its cap without starving
            # everyone after it. The big programs (sf10 / TPC-DS) compile
            # slowest and run LAST, so they may take most of what remains.
            frac = 0.8 if name in ("q3_sf10", "q95_sf02") else 0.45
            cap = min(CHILD_TIMEOUT_S, max(90.0, _remaining() * frac))
            proc = _run_child(f"tpu:{name}")
            res = _collect_child(proc, min(cap, _remaining()))
            tpu[name] = res.get(name, res if "error" in res else
                                {"error": "child result missing query"})
            if "error" in tpu[name] and "stderr_tail" not in tpu[name]:
                tpu[name]["stderr_tail"] = _stderr_tail(proc)
            _log(f"tpu:{name} (attempt {attempt}) -> {tpu[name]}")
            if "error" not in tpu[name]:
                break
    anchor_thread.join(timeout=max(_remaining(), 60))
    for name in CPU_ANCHOR:
        cpu.setdefault(name, {"error": "anchor did not finish"})

    headline = (tpu.get("q1") or {}).get("rows_per_sec") or 0
    cpu_q1 = (cpu.get("q1") or {}).get("rows_per_sec")
    vs = round(headline / cpu_q1, 3) if headline and cpu_q1 else None
    out = {
        "metric": "tpch_sf1_q1_rows_per_sec_per_chip",
        "value": headline,
        "unit": "rows/sec/chip",
        # measured anchor: same engine, host CPU backend; vs_baseline =
        # TPU Q1 throughput / CPU Q1 throughput
        "vs_baseline": vs,
        "tpu": tpu,
        "cpu_anchor": cpu,
        "wall_s": round(time.time() - _START, 1),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
