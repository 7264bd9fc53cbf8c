"""Runs a cell several times and prints the spreads a bound is set from.

    python3 benchmark/measure.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--sets 2] [--trace-seeds 21,22] [--out <dir>]

Each run is ``benchmark/run.py`` in a process of its own, one after the
other (this parent never touches JAX, so each child takes the chip in turn).
``--sets 2`` makes the seeds' runs twice, the second set after the first,
with the same seeds in both. Every result line is appended to
``<out>/<cell>.jsonl``. The summary gives, for each set and each end-to-end
metric, the median and the spread: the distance between the first and third
quartile (``statistics.quantiles(values, n=4)``) as a share of the median.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def one_run(workload: str, seed: int, seconds: float, trace: int,
            timeout: float) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.time()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired as e:
        return {"_rc": 124, "_wall_s": time.time() - t0, "_trace": trace,
                "_seed": seed, "_stderr_tail": (
                    e.stderr.decode(errors="replace")[-3000:]
                    if isinstance(e.stderr, bytes) else str(e.stderr)[-3000:])}
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    try:
        result = json.loads(lines[-1]) if lines else {}
    except ValueError:
        result = {}
    result["_rc"] = proc.returncode
    result["_wall_s"] = time.time() - t0
    result["_trace"] = trace
    result["_seed"] = seed
    if proc.returncode != 0 or not result.get("correct"):
        result["_stderr_tail"] = proc.stderr[-3000:]
    return result


def spread(values):
    if len(values) < 2:
        return None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--timeout", type=float, default=1500.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    trace_seeds = [int(s) for s in args.trace_seeds.split(",") if s]
    os.makedirs(args.out, exist_ok=True)
    log = os.path.join(args.out, f"{args.workload}.jsonl")
    sets = []
    bad = 0

    def record(result: dict, tag: str) -> None:
        nonlocal bad
        result["_tag"] = tag
        with open(log, "a", encoding="utf-8") as f:
            f.write(json.dumps(result) + "\n")
        ok = result.get("correct") is True and result["_rc"] == 0
        bad += 0 if ok else 1
        brief = {k: round(v["value"], 4) for k, v in
                 result.get("metrics", {}).items()}
        info = result.get("info", {})
        print(f"{tag} seed={result['_seed']} rc={result['_rc']} "
              f"correct={result.get('correct')} wall={result['_wall_s']:.1f}s "
              f"attempted={result.get('attempted')} failed={result.get('failed')} "
              f"compiles_setup={info.get('xla_compiles_setup')} "
              f"compiles_window={info.get('xla_compiles_window')} "
              f"ref_s={info.get('reference_s')} stmts={info.get('statements')} "
              f"peak={result.get('device', {}).get('memory_peak_bytes')} "
              f"{brief}", flush=True)
        if not ok:
            print(result.get("_stderr_tail", "")[-1500:], flush=True)

    for s in range(args.sets):
        results = []
        for seed in seeds:
            r = one_run(args.workload, seed, args.seconds, 0, args.timeout)
            record(r, f"set{s}")
            results.append(r)
        sets.append(results)
    for seed in trace_seeds:
        r = one_run(args.workload, seed, args.seconds, 1, args.timeout)
        record(r, "trace")
        print(json.dumps({"device": r.get("device"),
                          "breakdown": r.get("breakdown"),
                          "trace": r.get("info", {}).get("trace"),
                          "trace_error": r.get("info", {}).get("trace_error")}),
              flush=True)
    for s, results in enumerate(sets):
        names = sorted({k for r in results for k in r.get("metrics", {})})
        for name in names:
            values = [r["metrics"][name]["value"] for r in results
                      if name in r.get("metrics", {})]
            if name == "setup_s":
                values = values[1:] if s == 0 else values  # first run compiles
            if values:
                sp = spread(values)
                print(f"summary set{s} {name}: n={len(values)} "
                      f"median={statistics.median(values):.4f} "
                      f"spread={'n/a' if sp is None else f'{100 * sp:.2f}%'} "
                      f"min={min(values):.4f} max={max(values):.4f}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
