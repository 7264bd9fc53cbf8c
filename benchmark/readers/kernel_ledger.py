"""Counts from the program's kernel ledger (``GET /v1/query/{id}/profile``),
read once the window has closed for as many of its statements as the
coordinator still holds (an LRU of 64). ``launches`` and ``platform`` are
sound; the ledger's ``deviceS`` is an estimate from wall and is never read."""
from typing import Optional


def read(spec: dict, run) -> Optional[float]:
    """Mean per statement of ``spec['field']`` summed over its kernels."""
    per_statement = [sum(k[spec["field"]] for k in kernels)
                     for kernels in run.profiles.values()]
    if not per_statement:
        return None
    return sum(per_statement) / len(per_statement)
