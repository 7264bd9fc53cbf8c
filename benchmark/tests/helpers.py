"""What the benchmark's CPU rehearsals share: the cells at ``tpch.tiny``.

The rehearsal size is an argument of these helpers, not of ``run.py``: the
command line has no CPU flag and no size flag."""
import contextlib
import io
import json

from benchmark import spec

TINY_ROWS = {"lineitem": 59837, "orders": 15000, "customer": 1500}
CELLS = {
    "tpch_sf1.join_agg": ("tpch_sf1", "join_agg"),
    "tpch_sf1_serving.point_lookup": ("tpch_sf1_serving", "point_lookup"),
    "tpch_sf10.q3": ("tpch_sf10", "q3"),
    "tpch_sf1.scan_agg": ("tpch_sf1", "scan_agg"),
}


def bench_entries() -> dict:
    """BENCHMARK.json's metric entries, with every cell of CELLS listed as a
    workload whether or not it was proven on the chip (an unproven cell keeps
    its files and its rehearsal)."""
    bench = spec.load_benchmark_json()
    closed = [n for n, (_c, t) in CELLS.items() if t != "point_lookup"]
    point = [n for n, (_c, t) in CELLS.items() if t == "point_lookup"]
    for m in bench["end_to_end"]:
        if m["name"] in ("geomean_ms", "rows_per_s"):
            m["workloads"] = closed
        if m["name"] == "stmt_p50_ms":
            m["workloads"] = point
    return bench


def tiny_cell(name: str, session_properties=None) -> spec.Cell:
    config, traffic = CELLS[name]
    cell = spec.build_cell(name, config, traffic, 1, bench_entries())
    cell.config = dict(cell.config, schema="tiny", row_counts=TINY_ROWS)
    if session_properties:
        cell.config["session_properties"] = dict(
            cell.config["session_properties"], **session_properties)
    return cell


def rehearse(monkeypatch, name: str, trace: int = 0, seconds: float = 3.0,
             seed: int = 3000000007, require_chip: bool = False,
             session_properties=None):
    """``run.run`` on the CPU at tiny: (exit code, last stdout line parsed or
    None, stderr text)."""
    from benchmark import run

    monkeypatch.setattr(
        spec, "load_cell",
        lambda workload, root=spec.ROOT: tiny_cell(workload, session_properties))
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(io.StringIO()):
        rc = run.run(["--workload", name, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace)],
                     require_chip=require_chip, out=out, err=err)
    lines = [ln for ln in out.getvalue().splitlines() if ln.strip()]
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
