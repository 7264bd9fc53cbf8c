"""Share of the HBM roofline: the bytes of the columns each traced statement
must read once (template files x the configuration's row counts x
``widths.json``), over the chip's peak bytes per second, divided by the
device's busy seconds in the traced window. The same work whatever
implements it. A statement that lies partly inside the traced window counts
by the share of its time that lies inside. Above 105% the bytes are counted
too high or the busy time leaves out work: an error, never clipped."""
from typing import Optional


def read(spec: dict, run) -> Optional[float]:
    if run.trace is None or not run.traced or run.trace["busy_s"] <= 0:
        return None
    need = sum(
        share * run.cell.templates[rec.template].scan_bytes(
            run.cell.row_counts, run.type_bytes)
        for rec, share in run.traced)
    if need <= 0:
        return None
    least_s = need / run.peaks[spec["peak"]]
    return 100.0 * least_s / run.trace["busy_s"]
