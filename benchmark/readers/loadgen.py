"""The benchmark's own generator: how late statements were sent."""
from typing import Optional

from benchmark.spec import percentile


def read(spec: dict, run) -> Optional[float]:
    late = sorted(1000.0 * (r.sent - r.due) for r in run.window.records)
    if not late:
        return None
    return percentile(late, float(spec["quantile"]))
