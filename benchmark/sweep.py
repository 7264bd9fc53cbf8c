"""The knee sweep of an open-loop cell: one process, one set-up, several
fixed rates one after the other.

    python3 benchmark/sweep.py --workload <cell> --rates 5,10,20,40 --seconds 15

For each rate: statements attempted, p50 and p95 latency from the due time,
the share of statements sent late (more than 1 ms after they were due), the
p95 lateness, and how the backlog moved: the mean latency of the window's
last fifth over that of its first fifth (about 1 where the backlog does not
grow). The knee is the highest rate at which the backlog does not grow; the
cell's traffic file then fixes four fifths of it as a number. Run on the chip;
the benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import loadgen, run as bench_run, spec  # noqa: E402


def main(argv=None, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=2600000001)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    device = bench_run.device_info()
    if require_chip and device["platform"] != "tpu":
        print(f"sweep: needs a TPU, JAX reports {device['platform']}",
              file=sys.stderr)
        return bench_run.NO_CHIP_EXIT
    bench_run.configure_cache()
    served = bench_run.Served()
    rows = []
    try:
        plan = spec.build_plan(cell, args.seed, args.seconds)
        bench_run.warm_up(served, cell, plan)
        props = cell.session_properties()
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            at_rate = copy.copy(cell)
            at_rate.traffic = dict(cell.traffic, rate_per_s=rate)
            plan = spec.build_plan(at_rate, args.seed + i, args.seconds)
            window = loadgen.run_window(
                plan, lambda: served.client(props), args.seconds,
                senders=int(cell.traffic.get("senders", 32)))
            recs = sorted(window.records, key=lambda r: r.due)
            lat = sorted(1000.0 * r.latency_s for r in recs)
            late = sorted(1000.0 * (r.sent - r.due) for r in recs)
            fifth = max(1, len(recs) // 5)
            head = sum(r.latency_s for r in recs[:fifth]) / fifth
            tail = sum(r.latency_s for r in recs[-fifth:]) / fifth
            row = {"rate_per_s": rate, "attempted": len(recs),
                   "failed": sum(1 for r in recs if r.error),
                   "completed_per_s": len(recs) / window.elapsed_s,
                   "p50_ms": spec.percentile(lat, 0.5),
                   "p95_ms": spec.percentile(lat, 0.95),
                   "late_share": sum(1 for x in late if x > 1.0) / len(late),
                   "late_p95_ms": spec.percentile(late, 0.95),
                   "drain_s": window.elapsed_s - args.seconds,
                   "backlog_growth": tail / head if head > 0 else None}
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        served.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
