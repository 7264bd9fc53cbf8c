"""``tpch_sf10_q18`` through ``POST /v1/statement`` at tiny, on the CPU: Q18
equal to both oracles, the aggregation finished in its source fragment equal
to the partial / final plan's rows over several splits, the two kernel-row
counters, and ``query_max_execution_time``."""
import time

import pytest

from tests import tpch_oracle as oracle
from tests.test_q18_deployment import Q18

PROPS = {"catalog": "tpch", "schema": "tiny", "result_cache_enabled": "false"}


@pytest.fixture(scope="module")
def cluster():
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    coord = CoordinatorServer()
    coord.start()
    workers = [WorkerServer(coordinator_url=coord.base_url, node_id=f"q18w{i}")
               for i in range(2)]
    for w in workers:
        w.start()
    try:
        assert coord.registry.wait_for_workers(2, timeout=15.0)
        yield coord, workers
    finally:
        for w in workers:
            w.stop()
        coord.stop()


def _client(coord, **props):
    from trino_tpu.client.remote import StatementClient

    return StatementClient(coord.base_url, {**PROPS, **props})


def _profile(coord, query_id):
    from trino_tpu.server import wire

    return wire.json_request(
        "GET", f"{coord.base_url}/v1/query/{query_id}/profile")["kernels"]


# ------------------------------------------------ (d) served q18, both oracles
@pytest.mark.parametrize("quantity", [312, 313, 314, 315, 250, 200])
def test_served_q18_equals_both_oracles(cluster, quantity):
    """The cell's four QUANTITY values (no order of tiny reaches them: the
    answer is empty, and has to be) and two lower ones that leave rows."""
    from benchmark.reference import tpch as reference

    coord, _workers = cluster
    client = _client(coord, device_cache_enabled="true",
                     query_max_execution_time="15m")
    _cols, rows = client.execute(Q18.format(quantity=quantity))
    assert client.stats["state"] == "FINISHED"
    assert client.stats["fastPath"] == "distributed"
    want = reference.q18("tiny", [{"quantity": quantity}])[0]
    assert rows == want
    assert [[r[0], r[1], r[2], str(r[3]), str(r[4]), str(r[5])]
            for r in oracle.q18(quantity=quantity)] == want
    assert bool(want) == (quantity < 300)
    kernels = _profile(coord, client.query_id)
    # the subquery's group-by ran whole on each worker: once a task
    colocated = [k for k in kernels if k["colocatedAggs"]]
    assert {k["operator"] for k in colocated} == {"Aggregation"}
    assert sum(k["colocatedAggs"] for k in colocated) == 2
    # what crossed an exchange: customer whole, and the rows the filtered
    # join kept, twice (out of its fragment, out of the customer join's)
    crossed = sum(k["exchangedRows"] for k in kernels)
    assert 1500 <= crossed <= 1500 + 3 * 7 * max(len(want), 1), crossed


# --------------------- (c) the colocated aggregation's rows, over two workers
CASES = [
    "select l_orderkey, sum(l_quantity), count(*), min(l_shipdate) "
    "from lineitem group by l_orderkey order by l_orderkey",
    "select l_orderkey, l_returnflag, avg(l_extendedprice), "
    "count(distinct l_suppkey) from lineitem where l_discount > 0.02 "
    "group by l_orderkey, l_returnflag order by l_orderkey, l_returnflag",
    "select o_orderkey, max(o_totalprice) from orders where o_orderkey < 900 "
    "group by o_orderkey order by o_orderkey",
]


@pytest.mark.parametrize("sql", CASES)
def test_rows_equal_the_partial_final_plans(cluster, sql, monkeypatch):
    from trino_tpu.sql.planner import fragmenter

    coord, _workers = cluster
    client = _client(coord)
    _cols, rows = client.execute(sql)
    taken = _profile(coord, client.query_id)
    assert sum(k["colocatedAggs"] for k in taken) == 2      # one a worker
    monkeypatch.setattr(fragmenter, "_colocated_aggregation",
                        lambda *a, **k: False)
    _cols, cut_rows = client.execute(sql)
    cut = _profile(coord, client.query_id)
    assert sum(k["colocatedAggs"] for k in cut) == 0
    assert rows == cut_rows and len(rows) > 100
    # nothing crosses an exchange under the aggregation: only its groups
    # leave the fragment (the cut plan ships partial states, or, for a
    # DISTINCT aggregate, the raw rows)
    assert sum(k["exchangedRows"] for k in taken) == len(rows)
    assert sum(k["exchangedRows"] for k in cut) >= len(rows)
    assert ("distinct" in sql) == (
        sum(k["exchangedRows"] for k in cut) > len(rows))
    # a page that crosses leaves the device once: the rows that hand rows
    # on fetched each page whole, and no statement reads a column again to
    # measure, partition or serialise it
    for kernels in (taken, cut):
        assert all(k["outputFetches"] for k in kernels if k["exchangedRows"])
        sites = {s for k in kernels for s in k["hostSyncSites"]}
        assert "output-fetch" in sites
        assert not sites & {"serialize", "partition", "row-byte-estimate"}
    # the same two counters as system.runtime.kernels serves them
    _cols, table = client.execute(
        "select sum(exchanged_rows), sum(output_fetches) "
        "from system.runtime.kernels "
        f"where query_id = '{client.query_id}'")
    assert table == [[sum(k["exchangedRows"] for k in cut),
                      sum(k["outputFetches"] for k in cut)]]


# ------------------------------------------- (e) query_max_execution_time
def test_a_statement_past_its_limit_is_ended_and_the_server_keeps_serving(
        cluster):
    from trino_tpu.client.remote import RemoteQueryError
    from trino_tpu.obs import metrics as M

    coord, workers = cluster
    before = M.QUERIES_TIME_LIMITED.value()
    client = _client(coord, slow_injection=".0.:5",
                     query_max_execution_time="500ms")
    t0 = time.monotonic()
    with pytest.raises(RemoteQueryError, match="EXCEEDED_TIME_LIMIT"):
        client.execute("select count(*) from lineitem")
    assert time.monotonic() - t0 < 4.0           # ended, not waited out
    q = coord.get_query(client.query_id)
    assert q.state.get() == "FAILED"
    assert "maximum execution time limit of 500ms" in q.failure
    assert M.QUERIES_TIME_LIMITED.value() == before + 1
    root = coord.query_trace(client.query_id)["root"]
    root = root[0] if isinstance(root, list) else root
    assert root["name"] == "query"
    assert root["attributes"]["time-limit"] == "500ms"
    # its tasks are gone
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        states = {t["state"] for w in workers for t in w.tasks.list_info()
                  if t["taskId"].startswith(client.query_id)}
        if states <= {"CANCELED", "FAILED", "FINISHED", "ABORTED"}:
            break
        time.sleep(0.1)
    assert states <= {"CANCELED", "FAILED", "FINISHED", "ABORTED"}, states
    # and the next statement is answered
    _cols, rows = _client(coord).execute("select count(*) from nation")
    assert rows == [[25]]


@pytest.mark.parametrize("limit", [None, "10m", "1.5h"])
def test_an_unset_or_unreached_limit_changes_nothing(cluster, limit):
    coord, _workers = cluster
    props = {} if limit is None else {"query_max_execution_time": limit}
    client = _client(coord, **props)
    _cols, rows = client.execute(
        "select o_orderpriority, count(*) from orders "
        "group by o_orderpriority order by o_orderpriority")
    assert len(rows) == 5 and sum(r[1] for r in rows) == 15000
    q = coord.get_query(client.query_id)
    assert q.state.get() == "FINISHED"
    assert (q._time_limit_timer is None) == (limit is None)
    if limit is not None:
        # run() cancels the timer as it returns, after FINISHED is visible
        q._time_limit_timer.join(timeout=5.0)
        assert not q._time_limit_timer.is_alive()        # disarmed


@pytest.mark.parametrize("bad", ["15", "soon", "0s", "-1m", "15 minutes", ""])
def test_a_malformed_duration_is_refused(cluster, bad):
    from trino_tpu.client.properties import validate_property
    from trino_tpu.client.remote import RemoteQueryError

    with pytest.raises(ValueError, match="query_max_execution_time"):
        validate_property("query_max_execution_time", bad)
    coord, _workers = cluster
    with pytest.raises(RemoteQueryError, match="query_max_execution_time"):
        _client(coord, query_max_execution_time=bad).execute("select 1")


@pytest.mark.parametrize("text,seconds", [
    ("15m", 900.0), ("90s", 90.0), ("1.5h", 5400.0), ("100ms", 0.1),
    (" 2 d ", 172800.0), ("250us", 0.00025)])
def test_durations_parse_as_the_reference_s(text, seconds):
    from trino_tpu.client.properties import parse_duration

    assert parse_duration(text) == pytest.approx(seconds)
