"""The device's busy union from the profiler trace of the traced part of the
window (``benchmark/trace.py``)."""
from typing import Optional


def read(spec: dict, run) -> Optional[float]:
    if run.trace is None:
        return None
    if spec["field"] == "idle_share":
        return 100.0 * run.trace["idle_share"]
    raise ValueError(f"device_trace has no field {spec['field']!r}")
