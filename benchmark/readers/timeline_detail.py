"""Host-clock time inside a layer from level two of the program's phase
ledger: ``timeline.detail`` of the final ``stats`` block that each
statement's own protocol response carried (``obs/timeline.py``:
``"<phase>/<label>"`` -> exclusive seconds, each phase's detail summing to
the phase). A program from before the ledger had a second level carries no
``detail``: there is nothing to read, and the metric stays out of the
line."""
import fnmatch
from typing import Optional


def read(spec: dict, run) -> Optional[float]:
    """Mean per statement, in ms, of the ``detail`` entries whose key
    matches one of ``spec['patterns']`` (shell wildcards); ``None`` where
    no statement of the window carries ``detail``."""
    totals = []
    for rec in run.window.records:
        detail = ((rec.stats or {}).get("timeline") or {}).get("detail")
        if rec.error is None and detail is not None:
            totals.append(sum(
                float(seconds) for key, seconds in detail.items()
                if any(fnmatch.fnmatchcase(key, p) for p in spec["patterns"])))
    if not totals:
        return None
    return 1000.0 * sum(totals) / len(totals)
