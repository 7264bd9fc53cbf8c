"""Data-plane flow ledger (trino_tpu/obs/flowledger.py) + its producers.

Covers the PR's acceptance matrix:

- ledger unit contract: bounded transfer ring, typed link classes and
  stall sites (unknown names are rejected), per-(link, owner) rollups
  with derived MB/s, directional net totals, the rollup-only ``ring``
  escape the control link uses, and the flight-recorder mirror for
  retried transfers;
- straggler detector unit matrix: a uniform stage flags nothing, one
  10x task flags with the correct dominant cause (transfer- vs device-
  vs queue-bound), a one-task stage never flags, and the absolute
  elapsed floor keeps millisecond stages quiet;
- backpressure sampling: a producer blocked on a full output buffer
  under a slow consumer lands ``buffer-enqueue`` stall samples keyed by
  (stage, partition);
- live cluster (2 workers, tiny): byte conservation — the serde
  decode-side wire bytes of a distributed query are covered by
  exchange-pull ledger records (>= 95%, the ISSUE acceptance bound) —
  plus every read surface: ``GET /v1/query/{id}/flows``,
  ``system.runtime.transfers`` / ``system.runtime.stragglers``, the
  ``net_bytes_*`` columns on ``system.runtime.nodes``, the CLI summary's
  ``drain: N MB/s`` tag, EXPLAIN ANALYZE's "Data flow:" section, and
  the postmortem flow block;
- a uniform run (q3 three times, then a spooled export) through a DBAPI
  client: conservation over cold and warm rounds, every link class it
  crosses recorded, no straggler flagged, ``system.runtime.transfers``
  filled;
- ``tools/check_flow_docs.py`` green against the shipped README.
"""
import json
import threading
import time
import urllib.request

import pytest

from trino_tpu.client.remote import StatementClient
from trino_tpu.obs.flowledger import (
    FLOW_LEDGER, DEFAULT_STRAGGLER_MIN_ELAPSED_S, FlowLedger,
    detect_stragglers, straggler_cause)
from trino_tpu.server.coordinator import CoordinatorServer
from trino_tpu.server.worker import WorkerServer

Q3_SQL = """
select l_orderkey, o_orderdate, o_shippriority,
       sum(l_extendedprice * (1 - l_discount)) as revenue
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate, l_orderkey limit 10
"""


# ----------------------------------------------------------- unit contract
def test_transfer_ring_bounded_rollup_complete():
    led = FlowLedger(capacity=8)
    for _ in range(50):
        led.record_transfer("exchange-pull", "task:q.1", 10, 0.001, pages=1)
    assert len(led) == 8
    assert len(led.snapshot()) == 8
    # the rollup keeps the FULL history even after ring wrap
    row = next(r for r in led.transfer_rows() if r["owner"] == "task:q.1")
    assert row["transfers"] == 50
    assert row["bytes"] == 500 and row["pages"] == 50


def test_unknown_link_and_stall_site_rejected():
    led = FlowLedger()
    with pytest.raises(ValueError, match="unknown flow-ledger link"):
        led.record_transfer("carrier-pigeon", "task:q", 1, 0.0)
    with pytest.raises(ValueError, match="unknown flow-ledger stall site"):
        led.record_stall("disk-flush", 1, 0, 0.1)


def test_rollup_rates_net_totals_and_owner_bytes():
    led = FlowLedger(node_id="n1")
    led.record_transfer("exchange-pull", "task:qa.1", 4_000_000, 2.0,
                        direction="recv")
    led.record_transfer("client-drain", "drain:qa", 1_000_000, 1.0,
                        direction="send")
    led.record_transfer("exchange-pull", "task:qb.1", 500, 0.1)
    pull = next(r for r in led.transfer_rows()
                if r["owner"] == "task:qa.1")
    assert pull["mbPerS"] == pytest.approx(2.0)
    assert led.net_totals() == {"sent": 1_000_000, "received": 4_000_500}
    assert led.owner_bytes("task:qa.") == 4_000_000
    assert led.owner_bytes("task:", links=("exchange-pull",)) == 4_000_500
    assert led.owner_bytes("drain:qa") == 1_000_000
    snap = led.flow_snapshot()
    assert snap["nodeId"] == "n1"
    assert snap["links"]["exchange-pull"]["bytes"] == 4_000_500


def test_control_records_skip_the_ring():
    """``ring=False`` (the control link's mode): rollup/net totals only,
    so 2/s announce heartbeats never evict data-plane records."""
    led = FlowLedger()
    led.record_transfer("control", "control", 256, 0.001, ring=False)
    assert len(led) == 0
    row = next(r for r in led.transfer_rows() if r["link"] == "control")
    assert row["bytes"] == 256 and row["transfers"] == 1


def test_retried_transfer_mirrors_to_flight_recorder():
    class FakeRecorder:
        def __init__(self):
            self.records = []

        def record(self, category, name, **attrs):
            self.records.append((category, name, attrs))

    led = FlowLedger()
    rec = FakeRecorder()
    led.attach_recorder(rec)
    led.record_transfer("exchange-pull", "task:q.1", 10, 0.1)  # not mirrored
    led.record_transfer("exchange-pull", "task:q.1", 10, 0.1,
                        retries=3, status="504")
    assert rec.records == [("flow", "flow/retry",
                            {"link": "exchange-pull", "owner": "task:q.1",
                             "bytes": 10, "retries": 3, "status": "504"})]
    row = next(r for r in led.transfer_rows() if r["owner"] == "task:q.1")
    assert row["retries"] == 3 and row["lastStatus"] == "504"


# ------------------------------------------------- straggler detector matrix
def _task(tid, stage, elapsed, transfer=0.0, device=0.0, stall=0.0):
    return {"taskId": tid, "fragment": stage, "workerUri": f"http://w{tid}",
            "stats": {"elapsedS": elapsed, "transferS": transfer,
                      "deviceS": device, "stallS": stall,
                      "completedSplits": 4}}


def test_uniform_stage_flags_nothing():
    tasks = [_task(f"q.1.{i}", 1, 1.0 + 0.01 * i) for i in range(4)]
    assert detect_stragglers(tasks) == []


@pytest.mark.parametrize("transfer,device,stall,cause", [
    (8.0, 1.0, 0.5, "transfer-bound"),
    (1.0, 8.0, 0.5, "device-bound"),
    (0.5, 1.0, 8.0, "queue-bound"),
])
def test_10x_task_flags_with_dominant_cause(transfer, device, stall, cause):
    tasks = [_task(f"q.1.{i}", 1, 1.0) for i in range(3)]
    tasks.append(_task("q.1.3", 1, 10.0, transfer, device, stall))
    flagged = detect_stragglers(tasks)
    assert len(flagged) == 1
    f = flagged[0]
    assert f["taskId"] == "q.1.3"
    assert f["cause"] == cause
    assert f["ratio"] == pytest.approx(10.0)
    assert f["stageMedianS"] == pytest.approx(1.0)


def test_one_task_stage_never_flags():
    assert detect_stragglers([_task("q.1.0", 1, 100.0)]) == []


def test_millisecond_stage_never_flags():
    """The absolute elapsed floor: a 10x skew at millisecond scale is
    ratio noise, not a straggler."""
    tasks = [_task(f"q.1.{i}", 1, 0.002) for i in range(3)]
    tasks.append(_task("q.1.3", 1, 0.02))
    assert 0.02 < DEFAULT_STRAGGLER_MIN_ELAPSED_S  # the premise
    assert detect_stragglers(tasks) == []


def test_stages_grouped_independently():
    """A slow task is judged against ITS stage's median, not the query's."""
    tasks = ([_task(f"q.1.{i}", 1, 10.0) for i in range(2)]
             + [_task(f"q.2.{i}", 2, 1.0) for i in range(3)]
             + [_task("q.2.3", 2, 9.0, transfer=5.0)])
    flagged = detect_stragglers(tasks)
    assert [f["taskId"] for f in flagged] == ["q.2.3"]
    assert flagged[0]["stageId"] == 2


def test_cause_ties_resolve_to_device_bound():
    assert straggler_cause({}) == "device-bound"
    assert straggler_cause({"transferS": 1.0, "deviceS": 1.0}) == (
        "device-bound")


# --------------------------------------------------- backpressure sampling
def test_buffer_full_wait_samples_stall_under_slow_consumer():
    from trino_tpu.server.buffer import OutputBuffer

    buf = OutputBuffer(1, max_buffer_bytes=64,
                       stall_key=("stall-ut", 7))
    page = b"x" * 64

    def produce():
        for _ in range(3):
            buf.enqueue(page, timeout=30.0)
        buf.set_complete()

    t = threading.Thread(target=produce)
    t.start()
    time.sleep(0.15)  # let the producer hit the full buffer and block
    token, got = 0, 0
    while True:
        pages, token, complete, _ = buf.poll(token, timeout=1.0)
        got += len(pages)
        time.sleep(0.05)  # the slow consumer
        if complete and not pages:
            break
    t.join(timeout=10)
    assert got == 3
    assert buf.stalled_seconds > 0.1
    roll = next(r for r in FLOW_LEDGER.stall_rows()
                if r["site"] == "buffer-enqueue"
                and r["stage"] == "stall-ut")
    assert roll["partition"] == 7
    assert roll["waits"] >= 1 and roll["stallS"] > 0.1
    sample = next(s for s in FLOW_LEDGER.stall_samples()
                  if s.get("stage") == "stall-ut")
    assert sample["depthBytes"] >= 64
    assert sample["limitBytes"] == 64


# ------------------------------------------------- acceptance, live cluster
@pytest.fixture(scope="module")
def cluster():
    coord = CoordinatorServer()
    coord.start()
    workers = [
        WorkerServer(coordinator_url=coord.base_url, node_id=f"flow-w{i}")
        for i in range(2)
    ]
    for w in workers:
        w.start()
    assert coord.registry.wait_for_workers(2, timeout=15.0)
    yield coord, workers
    for w in workers:
        w.stop()
    coord.stop()


def _wait_terminal(q, timeout=90.0):
    deadline = time.time() + timeout
    while not q.state.is_terminal() and time.time() < deadline:
        time.sleep(0.02)
    return q.state.get()


def _decode_wire_bytes():
    from trino_tpu.obs import metrics as M

    return (M.SERDE_BYTES.value("decode", "zlib")
            + M.SERDE_BYTES.value("decode", "none"))


def _link_bytes():
    totals = {}
    for r in FLOW_LEDGER.transfer_rows():
        totals[r["link"]] = totals.get(r["link"], 0) + int(r["bytes"])
    return totals


def _pull_bytes():
    return _link_bytes().get("exchange-pull", 0)


def test_distributed_q3_byte_conservation(cluster):
    """The acceptance bound: >= 95% of the bytes the page codec decoded
    (serde wire bytes) during a 2-worker query are attributed to
    exchange-pull ledger records. Framing (length prefix + page headers)
    makes the ledger side a strict superset, so a shortfall means a pull
    path stopped recording."""
    coord, _ = cluster
    serde0, pull0 = _decode_wire_bytes(), _pull_bytes()
    q = coord.submit(Q3_SQL, {"catalog": "tpch", "schema": "tiny"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    serde_delta = _decode_wire_bytes() - serde0
    pull_delta = _pull_bytes() - pull0
    assert serde_delta > 0, "q3 never crossed the page codec"
    assert pull_delta >= 0.95 * serde_delta, (
        f"exchange-pull ledger {pull_delta}B covers only "
        f"{pull_delta / serde_delta:.2%} of {serde_delta}B serde wire")
    # ...and the query's OWN flow rows see those bytes (the owner filter)
    assert FLOW_LEDGER.owner_bytes(f"task:{q.query_id}.",
                                   links=("exchange-pull",)) > 0


def test_flows_endpoint_and_system_tables(cluster):
    coord, _ = cluster
    q = coord.submit(Q3_SQL, {"catalog": "tpch", "schema": "tiny"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    req = urllib.request.Request(
        f"{coord.base_url}/v1/query/{q.query_id}/flows",
        headers={"X-Trino-User": "test"})
    payload = json.loads(urllib.request.urlopen(req).read())
    assert payload["queryId"] == q.query_id
    assert {r["link"] for r in payload["transfers"]} >= {"exchange-pull"}
    for row in payload["transfers"]:
        assert (row["owner"].startswith(f"task:{q.query_id}.")
                or row["owner"] in (f"query:{q.query_id}",
                                    f"drain:{q.query_id}"))
    assert payload["stragglers"] == []  # uniform tiny never flags
    # announce must deliver worker flow/net blocks (0.5 s cadence)
    time.sleep(1.2)
    client = StatementClient(coord.base_url,
                             {"catalog": "tpch", "schema": "tiny"})
    _, rows = client.execute(
        "select node_id, link, bytes, transfers from "
        "system.runtime.transfers where bytes > 0")
    assert rows, "system.runtime.transfers returned nothing"
    links = {r[1] for r in rows}
    assert "exchange-pull" in links and "control" in links
    _, rows = client.execute(
        "select count(*) from system.runtime.stragglers")
    assert rows[0][0] == 0
    _, rows = client.execute(
        "select node_id, net_bytes_sent, net_bytes_received "
        "from system.runtime.nodes")
    assert rows
    assert any(int(r[1] or 0) > 0 and int(r[2] or 0) > 0 for r in rows), (
        f"no node announced non-zero net totals: {rows}")


def test_cli_summary_shows_drain_rate(cluster):
    from trino_tpu.client.cli import render_summary

    coord, _ = cluster
    client = StatementClient(coord.base_url,
                             {"catalog": "tpch", "schema": "tiny"})
    _, rows = client.execute("select o_orderkey, o_totalprice from orders "
                             "where o_orderkey <= 8000")
    assert rows
    flows = (client.stats or {}).get("flows") or {}
    assert flows.get("drainBytes", 0) > 0
    assert flows.get("drainMbPerS") is not None
    summary = render_summary(client.stats)
    assert "drain: " in summary and "MB/s" in summary
    assert "stragglers" not in summary  # zero never renders


def test_explain_analyze_data_flow_section(cluster):
    coord, _ = cluster
    client = StatementClient(coord.base_url,
                             {"catalog": "tpch", "schema": "tiny"})
    _, rows = client.execute("explain analyze " + Q3_SQL)
    text = "\n".join(r[0] for r in rows)
    assert "Data flow: " in text
    flow_line = next(line for line in text.split("\n")
                     if "Data flow: " in line)
    assert "exchange-pull" in flow_line and "MB/s" in flow_line


def test_postmortem_carries_flow_snapshot(cluster):
    coord, _ = cluster
    q = coord.submit(Q3_SQL, {"catalog": "tpch", "schema": "tiny"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    pm = q.capture_postmortem(store=False)
    flows = pm["coordinator"]["flows"]
    assert set(flows) >= {"nodeId", "links", "net", "recent", "stalls"}
    assert flows["links"], "coordinator postmortem has no link rollups"
    # worker rings ride the same pull with their own flow blocks
    assert pm["workers"]
    for w in pm["workers"]:
        if "error" not in w:
            assert "flows" in w


# -------------------------------------------------------------------- docs
def test_flow_docs_gate_green():
    from tools.check_flow_docs import check

    assert check() == []


# --------------------------------------------- a uniform run, link by link
UNIFORM_ROUNDS = 3  # q3 repeats: one cold round, then warm ones
# wide rows, no aggregate: enough result bytes to cross the spool threshold
# (bounded by key, not LIMIT: a limit a worker under worker-direct spooling
# would make the returned row count ambiguous)
EXPORT_SQL = ("select o_orderkey, o_custkey, o_totalprice, o_orderdate "
              "from orders where o_orderkey <= 60000")


@pytest.fixture(scope="module")
def uniform_run(cluster):
    """What a uniform workload leaves in the ledger: q3 three times through
    a DBAPI client, then one spooled export (segments written by the
    workers and fetched by the client)."""
    from trino_tpu.client import dbapi

    coord, _ = cluster
    cur = dbapi.connect(coordinator_url=coord.base_url,
                        catalog="tpch", schema="tiny").cursor()
    links0, serde0 = _link_bytes(), _decode_wire_bytes()
    straggler_flags = 0
    for _ in range(UNIFORM_ROUNDS):
        cur.execute(Q3_SQL)
        cur.fetchall()
        straggler_flags += int(
            (cur.stats.get("flows") or {}).get("stragglers") or 0)
    pull_delta = _pull_bytes() - links0.get("exchange-pull", 0)
    serde_delta = _decode_wire_bytes() - serde0
    spool = dbapi.connect(
        coordinator_url=coord.base_url, catalog="tpch", schema="tiny",
        spooled_results_enabled="true",
        spooled_results_threshold_bytes="1024",
        spooled_results_segment_bytes="65536").cursor()
    spool.execute(EXPORT_SQL)
    export_rows = len(spool.fetchall())
    straggler_flags += int(
        (spool.stats.get("flows") or {}).get("stragglers") or 0)
    ledger_links = {link: n - links0.get(link, 0)
                    for link, n in _link_bytes().items()}
    # the announce loop (0.5 s cadence) must deliver the workers' flow rows
    # before the coordinator-side table is read
    time.sleep(1.5)
    cur.execute("select link, sum(bytes) from system.runtime.transfers "
                "group by link")
    table_links = {r[0]: int(r[1]) for r in cur.fetchall()}
    cur.execute("select count(*) from system.runtime.stragglers")
    straggler_flags += int(cur.fetchall()[0][0])
    return {"pull_bytes": pull_delta, "serde_bytes": serde_delta,
            "export_rows": export_rows, "spooled": spool.stats.get("spooled"),
            "straggler_flags": straggler_flags,
            "ledger_links": ledger_links, "table_links": table_links}


def test_uniform_run_conserves_bytes_over_cold_and_warm_rounds(uniform_run):
    """The bound of ``test_distributed_q3_byte_conservation`` over the
    window a client sees: three drained q3 rounds, two of them warm."""
    assert uniform_run["serde_bytes"] > 0
    assert uniform_run["pull_bytes"] >= 0.95 * uniform_run["serde_bytes"]


@pytest.mark.parametrize("link", [
    "exchange-pull", "staging-transfer", "spool-write", "segment-fetch",
    "client-drain"])
def test_uniform_run_records_every_link_it_crosses(uniform_run, link):
    assert uniform_run["export_rows"] > 0 and uniform_run["spooled"]
    assert uniform_run["ledger_links"].get(link, 0) > 0, (
        f"{link} never recorded: {uniform_run['ledger_links']}")


def test_uniform_run_flags_no_straggler(uniform_run):
    """Zero false positives: not in any statement's ``flows`` stats block,
    the spooled export's included, and not in
    ``system.runtime.stragglers``."""
    assert uniform_run["straggler_flags"] == 0


def test_uniform_run_reaches_the_transfers_table(uniform_run):
    links = uniform_run["table_links"]
    assert links, "system.runtime.transfers came up empty"
    assert links.get("exchange-pull", 0) > 0 and links.get("spool-write", 0) > 0
