"""CPU rehearsal of the benchmark's pieces at ``tpch.tiny``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Nothing here is a device number: a CPU run is never reported as a device
run (``test_cpu_run_is_refused``)."""
import datetime
import json
import os
from decimal import Decimal

import pytest

from benchmark import check, control, spec, trace
from benchmark.reference import tpch as ref
from benchmark.tests import helpers

HERE = os.path.dirname(os.path.abspath(__file__))


# ------------------------------------------------------------ statement lists
@pytest.mark.parametrize("name", list(helpers.CELLS))
def test_statement_list_builds_from_seed_and_differs(name):
    cell = helpers.tiny_cell(name)

    def listing(seed):
        plan = spec.build_plan(cell, seed, 20.0)
        return ([[s.sql for s in stream] for stream in plan.streams],
                [(round(due, 9), s.sql) for due, s in plan.arrivals])

    assert listing(2 ** 31 + 5) == listing(2 ** 31 + 5)
    seeds = [listing(s) for s in (1, 2, 3, 2 ** 31 + 5)]
    assert any(a != b for a in seeds for b in seeds), "the seed changes nothing"
    plan = spec.build_plan(cell, 7, 20.0)
    assert plan.distinct and (plan.streams or plan.arrivals)
    for stmt in plan.distinct:
        assert "{" not in stmt.sql


@pytest.mark.parametrize("name", [n for n, (_c, t) in helpers.CELLS.items()
                                  if t != "point_lookup"])
def test_closed_loop_seeds_send_the_same_work(name):
    """Every seed: the same set of statements, in another order."""
    cell = helpers.tiny_cell(name)
    sets = [sorted(s.sql for s in spec.build_plan(cell, seed, 20.0).streams[0])
            for seed in (1, 2, 99)]
    assert sets[0] == sets[1] == sets[2]


def test_open_loop_seeds_send_the_same_gaps():
    cell = helpers.tiny_cell("tpch_sf1_serving.point_lookup")

    def gaps(seed):
        dues = [0.0] + [due for due, _s in spec.build_plan(cell, seed, 20.0).arrivals]
        return sorted(round(b - a, 9) for a, b in zip(dues, dues[1:]))

    a, b = gaps(1), gaps(2)
    # the last gaps may fall past the window's end in one order and not in
    # the other; what both sent is the same set
    n = min(len(a), len(b)) - 5
    assert n > 100 and a[:n] == b[:n]
    keys = {s.sql for _d, s in spec.build_plan(cell, 1, 20.0).arrivals}
    assert len(keys) > 100, "the keys are drawn from the seed"


# ------------------------------------------- the reference against the oracle
def _oracle_rows(rows):
    return ref.wire_rows(rows)


def test_reference_equals_oracle_at_validation_bindings():
    from tests import tpch_oracle as oracle

    assert ref.q1("tiny", [{"delta": 90}])[0] == _oracle_rows(oracle.q1("tiny"))
    assert ref.q6("tiny", [{"year": 1994, "discount": "0.06", "quantity": 24}]
                  )[0] == _oracle_rows(oracle.q6("tiny"))
    assert ref.q3("tiny", [{"segment": "BUILDING", "date": "1995-03-15"}]
                  )[0] == _oracle_rows(oracle.q3("tiny"))
    # tiny has no order over 300: the oracle's q18 is empty there, so its
    # threshold is lowered in the second test
    assert ref.q18("tiny", [{"quantity": 300}])[0] == _oracle_rows(
        oracle.q18("tiny"))


def _rows(table, columns):
    from tests import tpch_oracle as oracle

    return oracle.load_table("tiny", table, columns)


def test_reference_equals_row_at_a_time_oracle_at_a_second_binding():
    """Row-at-a-time ``Decimal`` over ``tests/tpch_oracle.load_table``, with
    other substitution values than the validation ones."""
    d = datetime.date.fromisoformat
    li = _rows("lineitem", ["l_orderkey", "l_quantity", "l_extendedprice",
                            "l_discount", "l_shipdate"])
    # q6: year 1996, discount 0.03, quantity 25
    total = Decimal(0)
    for r in li:
        if (d("1996-01-01") <= r["l_shipdate"] < d("1997-01-01")
                and Decimal("0.02") <= r["l_discount"] <= Decimal("0.04")
                and r["l_quantity"] < 25):
            total += r["l_extendedprice"] * r["l_discount"]
    assert ref.q6("tiny", [{"year": 1996, "discount": "0.03", "quantity": 25}]
                  )[0] == _oracle_rows([(total,)])
    # q18: quantity 250 (tiny has no order over 300)
    qty = {}
    for r in li:
        qty[r["l_orderkey"]] = qty.get(r["l_orderkey"], Decimal(0)) + r["l_quantity"]
    cust = {c["c_custkey"]: c["c_name"] for c in _rows("customer", ["c_custkey", "c_name"])}
    rows = [(cust[o["o_custkey"]], o["o_custkey"], o["o_orderkey"],
             o["o_orderdate"], o["o_totalprice"], qty[o["o_orderkey"]])
            for o in _rows("orders", ["o_orderkey", "o_custkey", "o_orderdate",
                                      "o_totalprice"])
            if qty.get(o["o_orderkey"], 0) > 250]
    rows.sort(key=lambda t: (-t[4], t[3]))
    got = ref.q18("tiny", [{"quantity": 250}])[0]
    assert got and got == _oracle_rows(rows[:100])
    # q3: MACHINERY, 1995-03-07
    seg = {c["c_custkey"] for c in _rows("customer", ["c_custkey", "c_mktsegment"])
           if c["c_mktsegment"] == "MACHINERY"}
    cut = d("1995-03-07")
    omap = {o["o_orderkey"]: o for o in _rows(
        "orders", ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"])
        if o["o_custkey"] in seg and o["o_orderdate"] < cut}
    rev = {}
    for r in li:
        if r["l_shipdate"] > cut and r["l_orderkey"] in omap:
            rev[r["l_orderkey"]] = (rev.get(r["l_orderkey"], Decimal(0))
                                    + r["l_extendedprice"] * (1 - r["l_discount"]))
    top = sorted(((k, v, omap[k]["o_orderdate"], omap[k]["o_shippriority"])
                  for k, v in rev.items()), key=lambda t: (-t[1], t[2], t[0]))[:10]
    assert ref.q3("tiny", [{"segment": "MACHINERY", "date": "1995-03-07"}]
                  )[0] == _oracle_rows(top)
    # q1: delta 118 -- the count of rows and the sum of quantities
    cutoff = d("1998-12-01") - datetime.timedelta(days=118)
    kept = [r for r in li if r["l_shipdate"] <= cutoff]
    got = ref.q1("tiny", [{"delta": 118}])[0]
    assert 0 < len(kept) <= len(li)  # this generator ships nothing after 1998-08-02
    assert sum(r[-1] for r in got) == len(kept)
    assert sum(Decimal(r[2]) for r in got) == sum(r["l_quantity"] for r in kept)
    # point: one row, the orders row itself
    o = _rows("orders", ["o_orderkey", "o_custkey", "o_totalprice", "o_orderdate"])[122]
    assert ref.point_order("tiny", [{"key": o["o_orderkey"]}])[0] == _oracle_rows(
        [(o["o_orderkey"], o["o_custkey"], o["o_totalprice"], o["o_orderdate"])])


def test_reference_answers_are_memoised_by_what_they_depend_on(tmp_path):
    cell = helpers.tiny_cell("tpch_sf1.scan_agg")
    wanted = [("q6", "k", {"year": 1994, "discount": "0.06", "quantity": 24})]
    first = check.reference_answers(cell, wanted, str(tmp_path))
    files = list((tmp_path / check.CACHE_DIR_NAME / "reference").iterdir())
    assert len(files) == 1
    files[0].write_text(json.dumps([["tampered"]]))
    assert check.reference_answers(cell, wanted, str(tmp_path)) == {
        ("q6", "k"): [["tampered"]]}, "a hit reads the file"
    assert check.reference_answers(cell, wanted, str(tmp_path), memoise=False
                                   ) == first


# --------------------------------------------------------- bytes and roofline
def test_q3_bytes_at_sf1_by_hand():
    """customer 150,000 x (8 + 4) + orders 1,500,000 x (8 + 8 + 4 + 4) +
    lineitem 5,996,584 (this generator's count at SF 1) x (8 + 8 + 8 + 4) =
    205,704,352 bytes, about 206 MB."""
    config = spec.load_json("configs", "tpch_sf1.json")
    q3 = spec.load_template("q3")
    by_hand = 150000 * 12 + 1500000 * 24 + 5996584 * 28
    assert by_hand == 205704352
    assert q3.scan_bytes(config["row_counts"], spec.type_bytes()) == by_hand
    assert q3.scan_rows(config["row_counts"]) == 150000 + 1500000 + 5996584


def test_roofline_reader_counts_partial_statements_by_their_share():
    from benchmark.readers import roofline
    from benchmark.run import RunData
    from benchmark.loadgen import Record

    cell = helpers.tiny_cell("tpch_sf1.join_agg")
    cell.config = spec.load_json("configs", "tpch_sf1.json")
    rec = Record("q3", "k", "sql", 0, "0.0", 0.0, 0.0)
    peaks = spec.load_peaks("TPU v5 lite")
    data = RunData(cell, None, [], {}, {"busy_s": 0.1}, [(rec, 1.0), (rec, 0.5)],
                   peaks, spec.type_bytes())
    share = roofline.read({"peak": "hbm_bytes_per_s"}, data)
    assert share == pytest.approx(100 * (1.5 * 205704352 / 819e9) / 0.1)
    assert roofline.read({"peak": "hbm_bytes_per_s"}, RunData(
        cell, None, [], {}, None, [], peaks, spec.type_bytes())) is None


def test_unlisted_device_is_an_error():
    with pytest.raises(SystemExit):
        spec.load_peaks("TPU v9 imaginary")


# ------------------------------------------------------------ trace reduction
def _recorded():
    with open(os.path.join(HERE, "data", "recorded_trace.json"), encoding="utf-8") as f:
        return json.load(f)


def test_union_of_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9), (20, 20)]) == [
        (0, 3), (5, 9)]


def test_trace_reduction_on_the_recorded_trace():
    recorded = _recorded()
    reduced = trace.reduce_events(recorded["events"])
    want = recorded["by_hand"]
    assert reduced["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert reduced["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert reduced["idle_share"] == pytest.approx(
        1 - want["busy_s"] / want["window_s"], rel=1e-9)
    assert 0 < reduced["idle_share"] < 1
    assert [round(s, 9) for _n, s in reduced["idle_gaps"][:3]] == [
        round(s, 9) for s in want["longest_gaps_s"]]
    assert reduced["device_ops"][0][0] == want["top_op"]
    assert all(name == "unattributed" for name, _s in reduced["idle_gaps"])


def test_trace_reduction_by_hand_on_a_made_up_trace():
    ev = lambda line, name, start, dur: {  # noqa: E731
        "plane": "/device:TPU:0", "line": line, "name": name,
        "start_ns": start, "dur_ns": dur}
    events = [ev("XLA Ops", "fusion.1", 100, 50), ev("XLA Ops", "fusion.2", 120, 60),
              ev("XLA Ops", "sort.3", 400, 100), ev("XLA Modules", "jit_f", 90, 500),
              {"plane": "/host:CPU", "line": "python",
               "name": "bench/statement/q3/0.1", "start_ns": 50, "dur_ns": 600}]
    reduced = trace.reduce_events(events, window_ns=(0, 1000), host_spans=[
        (150, 420, "device-staging"), (0, 99, "dispatch")])
    assert reduced["busy_s"] == pytest.approx(180e-9)     # [100,180) + [400,500)
    assert reduced["window_s"] == pytest.approx(1000e-9)
    assert reduced["idle_share"] == pytest.approx(0.82)
    assert reduced["idle_gaps"][0] == ["unattributed", pytest.approx(500e-9)]
    assert reduced["idle_gaps"][1] == ["device-staging", pytest.approx(220e-9)]
    assert reduced["idle_gaps"][2] == ["dispatch", pytest.approx(100e-9)]
    assert reduced["device_ops"][0] == ["sort.3", pytest.approx(100e-9)]
    named = trace.reduce_events(events, window_ns=(0, 1000), host_spans=[],
                                in_flight=[(50, 450)])["idle_gaps"]
    assert named[0] == [trace.IDLE_SERVER, pytest.approx(500e-9)]
    assert named[1][0] == "unattributed"
    assert trace.reduce_events([e for e in events if e["plane"] == "/host:CPU"]
                               ) is None, "nothing on a device: nothing to read"


def test_clock_offset_from_the_harness_annotations():
    from benchmark.loadgen import Record

    rec = Record("q3", "k", "sql", 0, "0.1", 0.0, 0.0, wall_sent=1000.0)
    events = [{"plane": "/host:CPU", "line": "python",
               "name": "bench/statement/q3/0.1", "start_ns": 777, "dur_ns": 5}]
    assert trace.clock_offset_ns(events, [rec]) == 777 - int(1000.0 * 1e9)
    assert trace.clock_offset_ns([], [rec]) is None


# ------------------------------------------------------------- whole rehearsal
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("name,trace_flag", [
    ("tpch_sf1.scan_agg", 0), ("tpch_sf1.join_agg", 1),
    ("tpch_sf1_serving.point_lookup", 0), ("tpch_sf1_serving.point_lookup", 1),
    ("tpch_sf10.q3", 0)])
def test_rehearsal_last_line_has_the_contract_keys(monkeypatch, name, trace_flag):
    rc, result, err = helpers.rehearse(monkeypatch, name, trace=trace_flag)
    assert rc == 0, err[-2000:]
    assert list(result)[:5] == CONTRACT_KEYS and list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu", "named for what it ran on"
    cell = helpers.tiny_cell(name)
    listed = cell.per_layer if trace_flag else cell.end_to_end
    units = {m["name"]: m["unit"] for m in listed}
    assert result["metrics"], "no metric at all"
    for metric, body in result["metrics"].items():
        assert body["unit"] == units[metric]
        assert isinstance(body["value"], float)
    if not trace_flag:
        assert set(result["metrics"]) == set(units)
        assert "setup_s" in result["metrics"]
    else:
        # no device plane on the CPU: the device readers find nothing to
        # read and stay out of the line, they never report 0
        assert not any("idle" in m or "roofline" in m for m in result["metrics"])
        assert result["metrics"][[m for m in units if m.startswith(
            "compiles_in_window")][0]]["value"] == 0.0
    for pair in result["compared"].values():
        assert pair["value"] <= pair["limit"]
    assert err.strip().splitlines()[-1].startswith("benchmark: compared ")


def test_cpu_run_is_refused(monkeypatch):
    rc, result, err = helpers.rehearse(monkeypatch, "tpch_sf1.scan_agg",
                                       require_chip=True)
    assert rc != 0 and result is None
    assert "TPU" in err


# ----------------------------------------------- the control and the faults
@pytest.mark.parametrize("name", list(helpers.CELLS))
def test_float32_control_is_not_correct(name, tmp_path):
    cell = helpers.tiny_cell(name)
    for seed in (11, 12, 13):
        row = control.control_numbers(cell, seed, 10.0, root=str(tmp_path))
        assert row["wrong_answers"] > row["limit"] and not row["correct"], row


def _break_client(monkeypatch, fault):
    """The timed path broken underneath: the program's own statement client
    hands back rows altered after the server produced them."""
    from trino_tpu.client import remote

    real = remote.StatementClient.execute
    calls = {"n": 0}

    def broken(self, sql, *a, **kw):
        columns, rows = real(self, sql, *a, **kw)
        calls["n"] += 1
        if sql.startswith("PREPARE") or not rows or calls["n"] % 3:
            return columns, rows
        return columns, fault([list(r) for r in rows])

    monkeypatch.setattr(remote.StatementClient, "execute", broken)


def _alter_a_value(rows):
    rows[-1][1] = rows[-1][1] + 1 if isinstance(rows[-1][1], int) else "0.00"
    return rows


@pytest.mark.parametrize("name,fault,number", [
    ("tpch_sf1.scan_agg", _alter_a_value, "wrong_answers"),
    ("tpch_sf1_serving.point_lookup", _alter_a_value, "wrong_answers"),
    ("tpch_sf1.join_agg", lambda rows: rows[:-1], "wrong_answers"),
    ("tpch_sf10.q3", lambda rows: rows[::-1], "wrong_answers"),
])
def test_broken_answers_come_out_not_correct(monkeypatch, name, fault, number):
    _break_client(monkeypatch, fault)
    rc, result, _err = helpers.rehearse(monkeypatch, name)
    assert rc == 0 and result["correct"] is False
    assert result["compared"][number]["value"] > 0
    assert result["failed"] > 0


def test_a_statement_served_from_a_cache_is_not_correct(monkeypatch):
    rc, result, _err = helpers.rehearse(
        monkeypatch, "tpch_sf1.scan_agg",
        session_properties={"result_cache_enabled": "true"})
    assert rc == 0 and result["correct"] is False
    assert result["compared"]["not_executed"]["value"] > 0
    assert result["compared"]["wrong_answers"]["value"] == 0


def test_a_statement_that_raises_is_not_correct(monkeypatch):
    from trino_tpu.client import remote

    real = remote.StatementClient.execute
    calls = {"n": 0}

    def flaky(self, sql, *a, **kw):
        calls["n"] += 1
        if calls["n"] % 5 == 0:
            raise remote.RemoteQueryError("injected")
        return real(self, sql, *a, **kw)

    monkeypatch.setattr(remote.StatementClient, "execute", flaky)
    rc, result, _err = helpers.rehearse(monkeypatch, "tpch_sf1_serving.point_lookup")
    assert rc == 0 and result["correct"] is False
    assert result["compared"]["unanswered"]["value"] > 0


# --------------------------------------------------------------- the data files
def test_benchmark_json_names_files_that_exist():
    bench = spec.load_benchmark_json()
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(spec.ROOT, c["file"]))
        body = spec.load_json("configs", f"{c['name']}.json")
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2
    names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        body = spec.load_layer_metric(m["name"])
        assert body["unit"] == m["unit"] and body["moves"] == m["moves"]
        assert body["layer"] == m["layer"] and m["moves"] in names
        __import__(f"benchmark.readers.{body['reader']}")
