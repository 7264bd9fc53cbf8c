"""XLA backend compiles (``jax.monitoring``'s ``backend_compile_duration``
events) between the window's first statement and its last: should read 0."""
from typing import Optional


def read(spec: dict, run) -> Optional[float]:
    return float(len(run.compiles_in_window))
