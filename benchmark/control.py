"""The control of ``correct``: the reference put in the program's place,
computed in the precision below the one the configurations state.

The configurations state exact decimal results. The step that would tempt a
later PR on this chip is float32 arithmetic in a sum, a product or a value
carried through the device (the TPU has no native int64 or float64), so the
control is ``benchmark/reference`` with ``dtype=numpy.float32``. It has to
come out as NOT correct: ``wrong_answers`` above its limit of 0.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 30

For each seed: the statements the window would send (each distinct one once),
the exact reference's rows and the control's, and how many statements' rows
differ. Numpy on the host only; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import check, spec  # noqa: E402


def control_numbers(cell: spec.Cell, seed: int, seconds: float,
                    root: str = spec.ROOT, memoise: bool = True) -> dict:
    import numpy as np

    plan = spec.build_plan(cell, seed, seconds)
    statements = {(s.template, s.binding_key): s for s in plan.distinct}
    for _due, s in plan.arrivals:
        statements[(s.template, s.binding_key)] = s
    wanted = [(s.template, s.binding_key, s.binding)
              for s in statements.values()]
    exact = check.reference_answers(cell, wanted, root, memoise=memoise)
    lower = check.reference_answers(cell, wanted, root, dtype=np.float32)
    wrong = sum(1 for key in exact if exact[key] != lower[key])
    return {"seed": seed, "statements": len(exact), "wrong_answers": wrong,
            "limit": check.LIMITS["wrong_answers"],
            "correct": wrong <= check.LIMITS["wrong_answers"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    failed_to_fail = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        row = control_numbers(cell, seed, args.seconds)
        row["workload"] = cell.name
        print(json.dumps(row), flush=True)
        failed_to_fail += 1 if row["correct"] else 0
    return 1 if failed_to_fail else 0


if __name__ == "__main__":
    sys.exit(main())
