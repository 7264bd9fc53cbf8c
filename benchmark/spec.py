"""The benchmark's data files, and the statements a run sends.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: a cell names a configuration and a traffic mix;
``configs/<configuration>.json``, ``traffic/<mix>.json``,
``templates/<template>.sql`` + ``.json`` and
``layer_metrics/<metric>.json`` hold the rest. A later PR adds files and
entries and edits none, so nothing here knows a cell, a template or a
metric by name.

The seed: TPC-H's population is fixed by the specification, so the seed
draws what the specification lets vary. A traffic file says where the
substitution parameters come from (``binding_seed``: a fixed number gives
every run the same bindings, so that every seed does the same work in
another order; ``null`` draws them from the run's seed) and the run's seed
always draws the order of statements, each stream's offset, the point
cell's keys and the order of its arrival gaps.
"""
from __future__ import annotations

import dataclasses
import datetime
import json
import math
import os
import random
from typing import Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_benchmark_json(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


# ----------------------------------------------------------------- templates
@dataclasses.dataclass(frozen=True)
class Template:
    name: str
    sql: str                    # text with {param} places, or ? when prepared
    mode: str                   # "literal" | "prepared"
    params: Tuple[dict, ...]
    using: Tuple[str, ...]      # prepared: the parameters EXECUTE ... USING sends
    reference: str              # function of benchmark/reference/<module>.py
    reference_module: str
    reads: Dict[str, Dict[str, str]]   # table -> column -> SQL type
    path: str                   # "distributed" | "fast-path"

    def scan_rows(self, row_counts: Dict[str, int]) -> int:
        """Base-table rows one statement has to scan: each table it names,
        once (a table a plan reads twice still counts once: the same work
        whatever implements it)."""
        return sum(int(row_counts[t]) for t in self.reads)

    def scan_bytes(self, row_counts: Dict[str, int],
                   type_bytes: Dict[str, int]) -> int:
        """Bytes of the columns one statement must read once."""
        return sum(int(row_counts[t]) * sum(type_bytes[ty] for ty in cols.values())
                   for t, cols in self.reads.items())


def load_template(name: str) -> Template:
    meta = load_json("templates", f"{name}.json")
    with open(os.path.join(BENCH_DIR, "templates", f"{name}.sql"),
              encoding="utf-8") as f:
        sql = f.read().strip()
    return Template(
        name=name, sql=sql, mode=meta["mode"], params=tuple(meta["params"]),
        using=tuple(meta.get("using", ())), reference=meta["reference"],
        reference_module=meta.get("reference_module", "tpch"),
        reads=meta["reads"], path=meta["path"])


def _draw(param: dict, rng: random.Random, row_counts: Dict[str, int]):
    kind = param["kind"]
    if kind == "int":
        hi = (int(row_counts[param["max_rows_of"]]) if "max_rows_of" in param
              else int(param["max"]))
        return rng.randint(int(param["min"]), hi)
    if kind == "choice":
        return rng.choice(param["values"])
    if kind == "date":
        lo = datetime.date.fromisoformat(param["min"])
        hi = datetime.date.fromisoformat(param["max"])
        return (lo + datetime.timedelta(days=rng.randint(0, (hi - lo).days))
                ).isoformat()
    if kind == "decimal":   # a string at the stated scale, e.g. "0.06"
        scale = int(param["scale"])
        lo = round(float(param["min"]) * 10 ** scale)
        hi = round(float(param["max"]) * 10 ** scale)
        return f"{rng.randint(lo, hi) / 10 ** scale:.{scale}f}"
    raise ValueError(f"unknown parameter kind {kind!r} of {param['name']}")


def draw_binding(template: Template, rng: random.Random,
                 row_counts: Dict[str, int]) -> Dict[str, object]:
    return {p["name"]: _draw(p, rng, row_counts) for p in template.params}


def render(template: Template, binding: Dict[str, object]) -> str:
    """The statement text one binding gives. A prepared template's text is
    ``EXECUTE <name> USING ...``; its PREPARE is :func:`prepare_text`."""
    if template.mode == "prepared":
        args = ", ".join(str(binding[p]) for p in template.using)
        return f"EXECUTE bench_{template.name} USING {args}"
    return template.sql.format(**binding)


def prepare_text(template: Template) -> str:
    return f"PREPARE bench_{template.name} FROM {template.sql}"


# --------------------------------------------------------------------- cells
@dataclasses.dataclass(frozen=True)
class Statement:
    template: str
    binding_key: str            # canonical JSON of the binding
    binding: Dict[str, object]
    sql: str


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    templates: Dict[str, Template]
    end_to_end: List[dict]      # BENCHMARK.json entries this cell reports
    per_layer: List[dict]

    @property
    def schema(self) -> str:
        return self.config["schema"]

    @property
    def row_counts(self) -> Dict[str, int]:
        return self.config["row_counts"]

    def session_properties(self) -> Dict[str, str]:
        props = {"catalog": self.config["catalog"], "schema": self.schema}
        props.update(self.config.get("session_properties", {}))
        props.update(self.traffic.get("session_properties", {}))
        return {k: str(v) for k, v in props.items()}


def _reports(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def _layer_reports(metric: dict, cell_name: str, end_to_end: List[dict]) -> bool:
    """A per-layer metric without ``workloads`` is reported by every cell
    that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return any(m["name"] == metric["moves"] for m in end_to_end)


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = load_benchmark_json(root)
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    return build_cell(entry["name"], entry["config"], entry["traffic"],
                      int(entry["chips"]), bench)


def build_cell(name: str, config_name: str, traffic_name: str, chips: int,
               bench: dict) -> Cell:
    """A cell from its two file names; ``bench`` gives the metric entries
    (a cell that BENCHMARK.json does not list yet reports the metrics whose
    ``workloads`` name it)."""
    config = load_json("configs", f"{config_name}.json")
    traffic = load_json("traffic", f"{traffic_name}.json")
    templates = {t["name"]: load_template(t["name"])
                 for t in traffic["templates"]}
    end_to_end = [m for m in bench["end_to_end"] if _reports(m, name)]
    return Cell(
        name=name, chips=chips, config_name=config_name, config=config,
        traffic_name=traffic_name, traffic=traffic, templates=templates,
        end_to_end=end_to_end,
        per_layer=[m for m in bench["per_layer"]
                   if _layer_reports(m, name, end_to_end)])


def binding_key(binding: Dict[str, object]) -> str:
    return json.dumps(binding, sort_keys=True)


def _statement(template: Template, binding: Dict[str, object]) -> Statement:
    return Statement(template.name, binding_key(binding), binding,
                     render(template, binding))


def cell_bindings(cell: Cell, seed: int) -> Dict[str, List[Dict[str, object]]]:
    """``bindings`` distinct bindings of each template of the mix."""
    fixed = cell.traffic.get("binding_seed")
    out = {}
    for t in cell.traffic["templates"]:
        template = cell.templates[t["name"]]
        rng = random.Random(f"{seed if fixed is None else fixed}/{t['name']}")
        seen, drawn = set(), []
        want = int(t["bindings"])
        for _ in range(1000 * want):
            b = draw_binding(template, rng, cell.row_counts)
            if binding_key(b) not in seen:
                seen.add(binding_key(b))
                drawn.append(b)
            if len(drawn) == want:
                break
        if len(drawn) < want:
            raise ValueError(f"{t['name']}: its domain holds fewer than "
                             f"{want} bindings")
        out[t["name"]] = drawn
    return out


@dataclasses.dataclass
class Plan:
    """What one run sends. Closed loop: ``streams[i]`` is the list stream
    ``i`` cycles through. Open loop: ``arrivals`` are (due seconds from the
    window's start, statement). ``distinct`` is what set-up warms."""
    kind: str
    streams: List[List[Statement]]
    arrivals: List[Tuple[float, Statement]]
    distinct: List[Statement]
    turn: int = 1               # closed loop: statements in one turn of the mix


def build_plan(cell: Cell, seed: int, seconds: float) -> Plan:
    traffic = cell.traffic
    rng = random.Random(f"{seed}/order")
    if traffic["loop"] == "closed":
        bindings = cell_bindings(cell, seed)
        # templates in turn, weight w meaning w places in each turn
        turn = [t["name"] for t in traffic["templates"]
                for _ in range(int(t.get("weight", 1)))]
        rounds = max(len(b) for b in bindings.values())
        base = [_statement(cell.templates[name],
                           bindings[name][r % len(bindings[name])])
                for r in range(rounds) for name in turn]
        distinct = list({(s.template, s.binding_key): s for s in base}.values())
        n = int(traffic["streams"])
        # each stream: the same statements, rotated to its own offset (one
        # place further for each stream, so that neighbours are not on the
        # same template at the same time), the rotation's origin drawn from
        # the seed; templates stay in turn
        origin = rng.randrange(len(base))
        streams = []
        for i in range(n):
            at = (origin + i * max(1, len(base) // n) + i) % len(base)
            streams.append(base[at:] + base[:at])
        return Plan("closed", streams, [], distinct, turn=len(turn))
    if traffic["loop"] == "open":
        rate = float(traffic["rate_per_s"])
        count = max(1, int(round(rate * seconds)))
        # the same set of gaps for every seed (the quantiles of the
        # exponential law at this rate), in an order the seed draws: Poisson
        # arrivals whose number and total length do not change with the seed
        gaps = [-math.log(1.0 - (i + 0.5) / count) / rate for i in range(count)]
        rng.shuffle(gaps)
        names = [t["name"] for t in traffic["templates"]
                 for _ in range(int(t.get("weight", 1)))]
        arrivals, due = [], 0.0
        krng = random.Random(f"{seed}/keys")
        for i, gap in enumerate(gaps):
            due += gap
            if due >= seconds:
                break
            template = cell.templates[names[i % len(names)]]
            arrivals.append((due, _statement(
                template, draw_binding(template, krng, cell.row_counts))))
        # set-up warms one statement of each template: its shapes do not
        # depend on the key
        wrng = random.Random(f"{seed}/warm")
        distinct = [_statement(cell.templates[n_], draw_binding(
            cell.templates[n_], wrng, cell.row_counts))
            for n_ in dict.fromkeys(names)]
        return Plan("open", [], arrivals, distinct)
    raise ValueError(f"unknown loop kind {traffic['loop']!r}")


def load_layer_metric(name: str) -> dict:
    return load_json("layer_metrics", f"{name}.json")


def load_peaks(device_kind: str) -> dict:
    peaks = load_json("peaks.json")["devices"]
    if device_kind not in peaks:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         "benchmark/peaks.json: no peak, no roofline")
    return peaks[device_kind]


def type_bytes() -> Dict[str, int]:
    return load_json("widths.json")["type_bytes"]


def percentile(sorted_values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of an ascending list."""
    if not sorted_values:
        return None
    rank = max(1, math.ceil(q * len(sorted_values)))
    return sorted_values[rank - 1]
