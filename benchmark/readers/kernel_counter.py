"""A counter of the program's kernel ledger that a program may lack
(``GET /v1/query/{id}/profile``, read once the window has closed for as
many of its statements as the coordinator still holds). ``kernel_ledger``
reads fields every row has had since the ledger began and raises on any
other; this reader is for a field a later PR added (``hostSyncs``, the
blocking device->host reads): on a program whose rows lack it there is
nothing to read, and the metric stays out of the line."""
from typing import Optional


def read(spec: dict, run) -> Optional[float]:
    """Mean per statement of ``spec['field']`` summed over its kernels;
    ``None`` where no profile was read or no row carries the field."""
    field = spec["field"]
    per_statement = [sum(k[field] for k in kernels if field in k)
                     for kernels in run.profiles.values()
                     if any(field in k for k in kernels)]
    if not per_statement:
        return None
    return sum(per_statement) / len(per_statement)
