"""Host-clock time per layer from the program's phase ledger: the
``timeline`` of the final ``stats`` block that each statement's own protocol
response carried (``obs/timeline.py``: exclusive phases of the query's
wall). ``device-execute`` there is host wall around device work, not device
time."""
from typing import Optional


def read(spec: dict, run) -> Optional[float]:
    """Mean per statement, in ms, of the sum of ``spec['phases']``."""
    totals = []
    for rec in run.window.records:
        phases = ((rec.stats or {}).get("timeline") or {}).get("phases")
        if rec.error is None and phases:
            totals.append(sum(float(phases.get(p, 0.0))
                              for p in spec["phases"]))
    if not totals:
        return None
    return 1000.0 * sum(totals) / len(totals)
