"""What decides ``correct``: the rows the window's own statements returned to
the client, against the plain reference with the same bindings.

Exact comparisons, so every limit is 0 (the configurations state exact
decimal results and every row drained). The numbers compared:

- ``wrong_answers``: statements whose rows differ from the reference's in
  any value, row count or order;
- ``unanswered``: statements that raised, were refused or never finished;
- ``not_executed``: statements served from a cache, or by another path than
  the template names (``distributed`` worker tasks or the coordinator's
  ``fast-path``), by the final ``stats`` block each statement's own
  protocol response carried;
- ``no_device_launch``: of the statements whose kernel profile the
  coordinator still held once the window had closed, those with no kernel
  launch (or none on a worker, for ``distributed``), or with a launch whose
  output was left off the accelerator.

The reference's answers may be memoised under ``<checkout>/.bench_cache``,
keyed by a hash of the generator's source, the reference's source, the
schema, the template and the binding, so that later runs of a cell in a
checkout do not pay numpy over 60 M rows again. The key holds everything the
answer depends on; a changed generator or reference misses.
"""
from __future__ import annotations

import hashlib
import importlib
import json
import os
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.spec import Cell

CACHE_DIR_NAME = ".bench_cache"
LIMITS = {"wrong_answers": 0, "unanswered": 0, "not_executed": 0,
          "no_device_launch": 0}


def _file_hash(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _memo_path(root: str, module, schema: str, template: str,
               key: str) -> str:
    from trino_tpu.connector.tpch import generator

    h = hashlib.sha256("\n".join([
        _file_hash(generator.__file__), _file_hash(module.__file__),
        schema, template, key]).encode()).hexdigest()[:32]
    return os.path.join(root, CACHE_DIR_NAME, "reference", f"{h}.json")


def reference_answers(cell: Cell, wanted: Iterable[Tuple[str, str, dict]],
                      root: str, memoise: bool = True, dtype=None
                      ) -> Dict[Tuple[str, str], List[list]]:
    """(template, binding_key) -> the reference's rows, for every wanted
    (template, binding_key, binding). ``dtype`` is the control's."""
    by_template: Dict[str, Dict[str, dict]] = {}
    for template, key, binding in wanted:
        by_template.setdefault(template, {})[key] = binding
    out: Dict[Tuple[str, str], List[list]] = {}
    for name, bindings in by_template.items():
        template = cell.templates[name]
        module = importlib.import_module(
            f"benchmark.reference.{template.reference_module}")
        fn = getattr(module, template.reference)
        missing = []
        for key in bindings:
            path = _memo_path(root, module, cell.schema, name, key)
            if memoise and dtype is None and os.path.exists(path):
                with open(path, encoding="utf-8") as f:
                    out[(name, key)] = json.load(f)
            else:
                missing.append(key)
        if not missing:
            continue
        kwargs = {} if dtype is None else {"dtype": dtype}
        answers = fn(cell.schema, [bindings[k] for k in missing], **kwargs)
        for key, rows in zip(missing, answers):
            out[(name, key)] = rows
            if memoise and dtype is None:
                path = _memo_path(root, module, cell.schema, name, key)
                os.makedirs(os.path.dirname(path), exist_ok=True)
                tmp = f"{path}.{os.getpid()}.tmp"
                with open(tmp, "w", encoding="utf-8") as f:
                    json.dump(rows, f)
                os.replace(tmp, path)
    return out


def profile_faults(kernels: List[dict], path: str, platform: str
                   ) -> Optional[str]:
    """Why a statement's kernel profile does not show a run on the device,
    or ``None`` where it does."""
    launches = sum(k["launches"] for k in kernels)
    on_workers = sum(k["launches"] for k in kernels
                     if k["nodeId"] != "coordinator")
    if launches == 0:
        return "no kernel launch in its profile"
    if path == "distributed" and on_workers == 0:
        return "no kernel launch on a worker"
    elsewhere = sorted({k["platform"] for k in kernels
                        if k["platform"] != platform})
    if elsewhere:
        return f"launches left their output on {elsewhere}, not {platform}"
    return None


def compare(cell: Cell, records, answers: Dict[Tuple[str, str], List[list]],
            profiles: Dict[str, List[dict]], platform: str
            ) -> Tuple[Dict[str, int], int, List[str]]:
    """The numbers compared, how many statements failed any of them, and a
    few lines saying what the first faults were."""
    numbers = {k: 0 for k in LIMITS}
    failed, notes = 0, []

    def note(rec, what: str) -> None:
        if len(notes) < 8:
            notes.append(f"{rec.template} {rec.binding_key} "
                         f"[{rec.query_id}]: {what}")

    for rec in records:
        faults = 0
        path = cell.templates[rec.template].path
        if rec.error is not None:
            numbers["unanswered"] += 1
            faults += 1
            note(rec, rec.error)
        else:
            want = answers[(rec.template, rec.binding_key)]
            if rec.rows != want:
                numbers["wrong_answers"] += 1
                faults += 1
                note(rec, f"{len(rec.rows)} rows differ from the reference's "
                          f"{len(want)}: got {rec.rows[:1]} want {want[:1]}")
            stats = rec.stats or {}
            if (rec.cache_status == "HIT" or stats.get("state") != "FINISHED"
                    or stats.get("fastPath") != path):
                numbers["not_executed"] += 1
                faults += 1
                note(rec, f"cache {rec.cache_status}, state "
                          f"{stats.get('state')}, path {stats.get('fastPath')!r}"
                          f" (wanted an execution over {path!r})")
            if rec.query_id in profiles:
                why = profile_faults(profiles[rec.query_id], path, platform)
                if why:
                    numbers["no_device_launch"] += 1
                    faults += 1
                    note(rec, why)
        failed += 1 if faults else 0
    return numbers, failed, notes


def verdict(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def compared_block(numbers: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
