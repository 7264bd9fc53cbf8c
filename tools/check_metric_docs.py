#!/usr/bin/env python
"""Fail when a registered metric, a kernel-ledger row field or a label of
the phase ledger's detail is missing from the README.

Every exported metric is DECLARED module-level in ``trino_tpu/obs/
metrics.py`` (the registry is the single source of truth), so doc coverage
is a set comparison: load the module, read ``REGISTRY.names()``, and
require each name to appear in README.md's Observability section. Wired as
a tier-1 test (tests/test_metric_docs.py) and into ``tools/lint.py --all``
(shared plumbing: tools/gates.py).

The counters that are not registry series are held to the same rule: the
fields of a kernel-ledger row (``obs/devprofiler.py: new_kernel_row``, the
``kernels`` of ``GET /v1/query/{id}/profile``) and the labels of
``timeline.detail`` (``obs/timeline.py: DETAIL_LABELS``) must each be
mentioned in backticks.

Usage: ``python tools/check_metric_docs.py [--readme PATH]`` — exit 0 when
every name is documented, 1 with the missing names otherwise.
"""
from __future__ import annotations

import re
import sys

if __package__ in (None, ""):  # script mode: tools/ on sys.path
    import gates
else:  # imported as tools.check_metric_docs
    from tools import gates


def registered_metric_names() -> list:
    """Names declared in trino_tpu/obs/metrics.py (loaded as a standalone
    module file — no jax import; see gates.load_module_file)."""
    mod = gates.load_module_file("trino_tpu/obs/metrics.py",
                                 "_obs_metrics_standalone")
    return sorted(mod.REGISTRY.names())


def documented_metric_names(readme_path: str) -> set:
    """Metric-shaped identifiers mentioned in the README (the table cells
    use backticks, but any mention counts — the check is for presence)."""
    text = gates.read_readme(readme_path)
    return set(re.findall(r"\btrino_tpu_[a-z0-9_]+\b", text))


def ledger_names() -> list:
    """Kernel-row fields and detail labels (both modules are stdlib-only
    at import, loaded as files like the registry)."""
    prof = gates.load_module_file("trino_tpu/obs/devprofiler.py",
                                  "_obs_devprofiler_standalone")
    timeline = gates.load_module_file("trino_tpu/obs/timeline.py",
                                      "_obs_timeline_standalone")
    labels = {label for _rank, label in timeline.DETAIL_LABELS.values()}
    return sorted(set(prof.new_kernel_row("", "", "")) | labels
                  | {timeline.REMAINDER, "op:<Kind>"})


def check(readme_path: str | None = None) -> list:
    """Missing names (empty means the docs are complete)."""
    documented = documented_metric_names(readme_path)
    backticked = gates.backticked_names(gates.read_readme(readme_path))
    return ([name for name in registered_metric_names()
             if name not in documented]
            + [name for name in ledger_names() if name not in backticked])


def main() -> int:
    return gates.gate_main(
        __doc__, check,
        "metrics, kernel-row fields or detail labels in code but missing "
        "from the README Observability section:",
        "add each to README.md (## Observability): a metric to the metric "
        "table, a field or label in backticks",
        lambda: (f"ok: all {len(registered_metric_names())} registered "
                 "metrics are documented"))


if __name__ == "__main__":
    sys.exit(main())
