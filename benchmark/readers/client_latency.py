"""The client's own clock: a quantile of the window's statement latencies,
each from its due time; a failed statement counts as the statement
timeout. Taken in the traced run, so it carries the tracer's cost: a tail
too unsteady for a bound stands here, beside the bounded median."""
from typing import Optional

from benchmark.spec import percentile


def read(spec: dict, run) -> Optional[float]:
    return percentile(sorted(r.counted_ms for r in run.window.records),
                      float(spec["quantile"]))
