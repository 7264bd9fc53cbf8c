"""From a profiler trace to busy seconds, idle share, top operations and
the longest idle gaps.

Two steps, kept apart so that the second can be checked on a small
recorded trace (``benchmark/tests/data/recorded_trace.json``):

1. :func:`read_xplane` turns the ``.xplane.pb`` that ``jax.profiler`` wrote
   into plain events ``{"plane", "line", "name", "start_ns", "dur_ns"}``
   with nothing but JAX (``jax.profiler.ProfileData``).
2. :func:`reduce_events` works on those events alone.

Device events: on a TPU the device planes are named ``/device:TPU:<n>`` and
the operations sit on the line ``XLA Ops`` (``XLA Modules`` holds the
programs that enclose them, gaps between their operations included, and
``Async XLA Ops`` the copies; neither counts as busy). Busy time is the
UNION of the operation intervals of one chip, averaged over the chips that
ran anything.

Host events: the harness wraps each statement in a
``jax.profiler.TraceAnnotation`` named ``bench/statement/<template>/<tag>``,
after an instant one named ``bench/sent/<template>/<tag>`` (no ``#``: the
profiler reads what follows one as metadata); they
land on the host plane on the same clock as the device's, and give the
offset between the trace's clock and the wall clock of the program's spans.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
STATEMENT_PREFIX = "bench/"
TOP = 10
NAME_CHARS = 160


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def read_xplane(path: str) -> List[dict]:
    """Device operation events and the harness's statement annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events: List[dict] = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name != OP_LINE:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(STATEMENT_PREFIX):
                    continue
                events.append({"plane": plane.name, "line": line.name,
                               "name": ev.name, "start_ns": int(ev.start_ns),
                               "dur_ns": int(ev.duration_ns)})
    return events


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Disjoint, ascending union of (start, end) intervals."""
    merged: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _device_ops(events: List[dict]) -> Dict[str, List[dict]]:
    """plane -> its operation events."""
    out: Dict[str, List[dict]] = {}
    for ev in events:
        if (ev["plane"].startswith(DEVICE_PLANE_PREFIX)
                and ev["line"] == OP_LINE):
            out.setdefault(ev["plane"], []).append(ev)
    return out


IDLE_SERVER = "no statement in flight"


def reduce_events(events: List[dict], window_ns: Optional[Tuple[int, int]] = None,
                  host_spans: Optional[List[Tuple[int, int, str]]] = None,
                  in_flight: Optional[List[Tuple[int, int]]] = None) -> dict:
    """``busy_s`` (mean over the chips that ran anything), ``window_s``,
    ``idle_share`` (0..1), ``device_ops`` (the TOP names by summed seconds,
    over all chips) and ``idle_gaps`` (the TOP longest gaps of the first
    chip's busy union inside the window, each named by the host span
    ``host_spans`` = (start_ns, end_ns, name) that covers most of it; else
    ``no statement in flight`` where ``in_flight``, the statements' own
    (start_ns, end_ns), is given and none of them touches the gap; else
    ``unattributed``).

    ``window_ns`` is the traced window on the trace's clock; without it the
    window runs from the first device operation's start to the last one's
    end. Returns ``None`` where no operation ran on a device: there is
    nothing to read, and 0 would be a lie."""
    ops = _device_ops(events)
    if not ops:
        return None
    if window_ns is None:
        window_ns = (min(e["start_ns"] for evs in ops.values() for e in evs),
                     max(e["start_ns"] + e["dur_ns"]
                         for evs in ops.values() for e in evs))
    w0, w1 = window_ns
    if w1 <= w0:
        return None
    busy_per_chip, unions = [], {}
    for plane, evs in sorted(ops.items()):
        u = union((max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1))
                  for e in evs)
        unions[plane] = u
        busy_per_chip.append(sum(b - a for a, b in u))
    busy_ns = sum(busy_per_chip) / len(busy_per_chip)
    by_name: Dict[str, int] = {}
    for evs in ops.values():
        for e in evs:
            by_name[e["name"]] = by_name.get(e["name"], 0) + e["dur_ns"]
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    # the trace names an operation by its whole HLO line; its head says which
    top_ops = [(name[:NAME_CHARS], ns) for name, ns in top_ops]
    first = unions[sorted(unions)[0]]
    gaps, cursor = [], w0
    for a, b in first:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if w1 > cursor:
        gaps.append((cursor, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_cover(g, host_spans or (), in_flight), (g[1] - g[0]) / 1e9]
             for g in gaps[:TOP]]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "idle_share": 1.0 - busy_ns / (w1 - w0),
        "device_ops": [[name, ns / 1e9] for name, ns in top_ops],
        "idle_gaps": named,
        "chips": len(ops),
        "op_events": sum(len(evs) for evs in ops.values()),
    }


def _cover(gap: Tuple[int, int], host_spans, in_flight=None) -> str:
    """The name of the host span that covers most of ``gap``; a span counts
    only where it covers at least half of it."""
    best, best_ns = None, (gap[1] - gap[0]) / 2.0
    for start, end, name in host_spans:
        overlap = min(end, gap[1]) - max(start, gap[0])
        if overlap > best_ns:
            best, best_ns = name, overlap
    if best is not None:
        return best
    if in_flight is not None and not any(
            start < gap[1] and end > gap[0] for start, end in in_flight):
        return IDLE_SERVER
    return "unattributed"


def statement_annotations(events: List[dict]) -> List[dict]:
    return [e for e in events if e["name"].startswith(STATEMENT_PREFIX)]


def clock_offset_ns(events: List[dict], records) -> Optional[int]:
    """trace clock minus wall clock, in ns, from the harness's own
    annotations: each is named ``bench/<kind>/<template>/<tag>``, and the
    record with that tag knows the wall clock at which it was sent. The
    median over the pairs; ``None`` where the trace holds none."""
    by_tag = {r.tag: r for r in records}
    offsets = []
    for e in statement_annotations(events):
        rec = by_tag.get(e["name"].rpartition("/")[2])
        if rec is not None:
            offsets.append(e["start_ns"] - int(rec.wall_sent * 1e9))
    if not offsets:
        return None
    offsets.sort()
    return offsets[len(offsets) // 2]
