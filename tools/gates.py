#!/usr/bin/env python
"""Shared infrastructure for the tier-1 gates in tools/.

The five ``check_*_docs.py`` gates share one shape — collect required
names from the source of truth (a registry module, or a source scan),
collect documented names from README.md, report the difference, exit
non-zero on drift — and before this module each had its own copy of the
module-file loader, the README reader, and the argparse/report ``main``.
This module is that shape, written once:

- :func:`load_module_file` — load a module by FILE so docs-only
  environments (and every gate run) never import the trino_tpu package,
  which would pull in jax;
- :func:`read_readme` / :func:`backticked_names` — README access and the
  standard "any backticked mention counts" identifier extraction;
- :func:`missing_readme_paths` — the files the README names that are not
  in the tree;
- :func:`iter_source_files` — the ``trino_tpu/`` walk used by every
  source-scanning gate and linter (skips ``__pycache__``);
- :func:`gate_main` — the argparse ``--readme`` CLI + stderr report +
  exit-code contract every gate exposes;
- :data:`ALL_GATES` — the registry ``tools/lint.py --all`` runs, so a new
  gate is wired into CI by adding one row here.

Each ``check_*_docs.py`` keeps its public ``check()``/``main()`` surface
(the tests/test_*_docs.py suites import those directly) and implements
them through these helpers.
"""
from __future__ import annotations

import argparse
import os
import re
import sys
from typing import Callable, Iterator, List, Optional, Sequence

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_module_file(rel_path: str, name: str):
    """Load ``REPO_ROOT/rel_path`` as a standalone module FILE. Importing
    the package instead would execute ``trino_tpu/__init__`` and pull in
    jax — a multi-second dependency no docs gate needs. The module is
    registered in sys.modules during exec (dataclass processing resolves
    the defining module through sys.modules at class-creation time) and
    removed after."""
    import importlib.util

    path = os.path.join(REPO_ROOT, *rel_path.split("/"))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.modules.pop(spec.name, None)
    return mod


def read_readme(readme_path: Optional[str] = None) -> str:
    readme_path = readme_path or os.path.join(REPO_ROOT, "README.md")
    with open(readme_path, encoding="utf-8") as f:
        return f.read()


def backticked_names(text: str) -> set:
    """Backtick-quoted identifiers — the standard "documented" test for
    vocabularies whose members are ordinary words (span names, columns)."""
    return set(re.findall(r"`([^`\n]+)`", text))


_README_PATH = re.compile(r"[\w./-]+\.(?:py|json|md)")


def missing_readme_paths(readme_path: Optional[str] = None) -> List[str]:
    """Repository paths README.md puts in backticks (whatever ends in
    ``.py``, ``.json`` or ``.md``; a glob is not a path) that no file
    answers to. A path counts from the repo root or from ``trino_tpu/``,
    the README's shorthand for engine modules; a bare file name may also
    be any module under ``trino_tpu/``."""
    engine_modules = {os.path.basename(p) for p in iter_source_files()}
    missing = []
    for name in sorted(backticked_names(read_readme(readme_path))):
        if not _README_PATH.fullmatch(name):
            continue
        if any(os.path.exists(os.path.join(REPO_ROOT, base, name))
               for base in ("", "trino_tpu")):
            continue
        if "/" not in name and name in engine_modules:
            continue
        missing.append(name)
    return missing


def iter_source_files(root: Optional[str] = None) -> Iterator[str]:
    """Every ``.py`` file under ``trino_tpu/`` (or ``root``), skipping
    ``__pycache__`` — the shared walk for source-scanning gates/linters."""
    root = root or os.path.join(REPO_ROOT, "trino_tpu")
    for dirpath, _dirs, files in os.walk(root):
        if "__pycache__" in dirpath:
            continue
        for fn in sorted(files):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def gate_main(doc: str, check: Callable[[Optional[str]], List[str]],
              missing_header: str, hint: str,
              ok_message: Callable[[], str],
              argv: Optional[Sequence[str]] = None) -> int:
    """The CLI contract every gate exposes: ``--readme PATH`` override,
    exit 0 + one "ok" line when clean, exit 1 + itemized stderr report
    (header, one indented line per missing name, actionable hint) on
    drift."""
    ap = argparse.ArgumentParser(description=doc)
    ap.add_argument("--readme", default=None,
                    help="README path (default: repo root README.md)")
    args = ap.parse_args(argv)
    missing = check(args.readme)
    if missing:
        print(missing_header, file=sys.stderr)
        for name in missing:
            print(f"  {name}", file=sys.stderr)
        print(hint, file=sys.stderr)
        return 1
    print(ok_message())
    return 0


# ------------------------------------------------------------- registry
#
# Everything `tools/lint.py --all` runs. Each row: (name, module basename
# in tools/, human description). The module must expose `check()` -> list
# of problem strings (empty = pass). The two lint analyzers are listed by
# their package path; lint.py resolves both forms.
ALL_GATES = (
    ("metric-docs", "check_metric_docs",
     "every registered metric documented in README"),
    ("session-property-docs", "check_session_property_docs",
     "every session property documented in README"),
    ("endpoint-docs", "check_endpoint_docs",
     "every served HTTP endpoint documented in README"),
    ("span-docs", "check_span_docs",
     "every emitted span name documented in README"),
    ("system-table-docs", "check_system_table_docs",
     "every system table/column/procedure documented in README"),
    ("memledger-docs", "check_memledger_docs",
     "every memory-ledger event kind and pool documented in README"),
    ("flow-docs", "check_flow_docs",
     "every flow-ledger link class, stall site, straggler cause, and "
     "flow-table column documented in README"),
    ("resource-group-docs", "check_resource_group_docs",
     "every selector field, group knob, and resource_groups column "
     "documented in README"),
    ("tracer-leak", "lint.tracer_leak",
     "no import-time jnp evaluation; no jnp in repr/property/host modules"),
    ("lock-discipline", "lint.lock_discipline",
     "no lock-order inversions, re-entry, or blocking calls under locks"),
)


if __name__ == "__main__":
    print(__doc__)
