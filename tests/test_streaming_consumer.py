"""Streaming consumer loop (round-4 verdict item 4): a hash-stage task
folds arriving partial-state pages through the INTERMEDIATE merge instead
of buffering its whole input, and row-local chains execute per micro-batch.

Reference test-strategy analog: the WorkProcessor/Driver blocked-future
pipeline tests (operator/TestWorkProcessor, Driver.java:449) — assert the
consumer makes progress while the producer is still emitting, and that
consumer memory stays bounded by the batch size, not the input size.
"""
import threading
import time
from typing import List

import pytest

from trino_tpu import Session
from trino_tpu.data.page import Page
from trino_tpu.data.serde import deserialize_page, serialize_page
from trino_tpu.exec.executor import Executor
from trino_tpu.exec.query import plan_sql
from trino_tpu.server.task import SqlTask, TaskRequest
from trino_tpu.sql.planner import plan as P
from trino_tpu.sql.planner.fragmenter import RemoteSourceNode, fragment_plan

SQL = ("select o_custkey, count(*) c, sum(o_totalprice) s, min(o_orderdate) d "
       "from orders group by o_custkey")


def _hash_fragment(session):
    """(hash fragment, source fragment) of the distributed plan for SQL."""
    root = plan_sql(session, SQL)
    frags = fragment_plan(root, session)
    hashes = [f for f in frags if f.partitioning == "hash"]
    assert hashes, [f.kind for f in frags]
    return hashes[0], frags


def _partial_state_pages(session, chunks=8) -> List[Page]:
    """Real partial-state pages: run the partial aggregation over row
    slices of the orders scan (what source tasks would ship)."""
    root = plan_sql(session, SQL)
    (agg,) = [n for n in P.walk_plan(root)
              if isinstance(n, P.AggregationNode)]
    ex = Executor(session)
    scan_page = ex.execute(agg.source)
    scan_page = scan_page.compact()
    n = scan_page.num_rows
    step = max(1, n // chunks)
    partial = P.AggregationNode(
        agg.source, list(agg.group_channels), agg.aggregates, step="partial")
    pages = []
    for lo in range(0, n, step):
        sl = scan_page.slice_rows(lo, min(n, lo + step))
        ex2 = Executor(session)
        # execute partial agg over the slice via a tiny adapter: swap the
        # source result in by executing the node functions directly
        pages.append(ex2.aggregate_partial(partial, sl).compact())
    return pages


class FakeExchangeClient:
    """Drip-feeds pre-built pages; records consumption order so the test
    can prove interleaving (consumer folded page i before page i+1 was
    even made available)."""

    instances: List["FakeExchangeClient"] = []
    pages_to_serve: List[Page] = []

    def __init__(self, locations, max_buffered_pages: int = 64,
                 owner: str = "", stall_key=None):
        self.consumed_at: List[float] = []
        self.served = 0
        FakeExchangeClient.instances.append(self)

    def start(self):
        pass

    def iter_pages(self):
        for p in FakeExchangeClient.pages_to_serve:
            self.served += 1
            self.consumed_at.append(time.time())
            yield p

    def pages(self):
        return list(self.iter_pages())


@pytest.fixture()
def patched_client(monkeypatch):
    import trino_tpu.server.exchange_client as xc

    FakeExchangeClient.instances = []
    monkeypatch.setattr(xc, "ExchangeClient", FakeExchangeClient)
    yield FakeExchangeClient


def test_final_agg_fragment_streams_via_intermediate_fold(patched_client, monkeypatch):
    session = Session({"catalog": "tpch", "schema": "tiny",
                       "gather_max_rows_per_device": 1})
    hash_frag, _ = _hash_fragment(session)
    assert isinstance(hash_frag.root, P.AggregationNode)
    assert hash_frag.root.step == "final"
    assert isinstance(hash_frag.root.source, RemoteSourceNode)

    pages = _partial_state_pages(session)
    assert len(pages) >= 6
    FakeExchangeClient.pages_to_serve = pages

    fold_sizes: List[int] = []
    orig = Executor.aggregate_intermediate

    def counting(self, node, page):
        fold_sizes.append(page.num_rows)
        return orig(self, node, page)

    monkeypatch.setattr(Executor, "aggregate_intermediate", counting)
    # tiny batch threshold -> one fold per arriving page
    monkeypatch.setattr(SqlTask, "STREAM_BATCH_ROWS", 1)

    req = TaskRequest(
        task_id="t_fold", query_id="q_fold", fragment_root=hash_frag.root,
        splits={}, upstream={hash_frag.root.source.fragment_id:
                             [("http://fake", "up.0", 0)]},
        session_properties=dict(session.properties))
    task = SqlTask(req, session_factory=lambda p: Session(p))
    task.start()
    deadline = time.time() + 120
    while task.state.get() not in ("FINISHED", "FAILED") and time.time() < deadline:
        time.sleep(0.05)
    assert task.state.get() == "FINISHED", task.failure

    # the fold ran per micro-batch (streaming: a batch is folded once it
    # has grown to the running state's size), not once over the whole
    # input — and each fold held only running-state + one batch
    assert 1 < len(fold_sizes) <= len(pages)
    total_input = sum(p.live_count() for p in pages)
    assert max(fold_sizes) < total_input

    # results identical to the local single-process engine
    frames = []
    token = 0
    for _ in range(1000):
        got, token, complete, failure = task.output.poll(
            token, 0, max_pages=100, timeout=5.0)
        assert failure is None, failure
        frames.extend(got)
        if complete:
            break
    out_rows = []
    for f in frames:
        out_rows.extend(deserialize_page(f).to_pylist())
    local = Session({"catalog": "tpch", "schema": "tiny"}).execute(
        SQL + " order by o_custkey")
    assert sorted(out_rows) == sorted(tuple(r) for r in local.rows)


def test_rowlocal_chain_streams_output_before_input_exhausted(patched_client, monkeypatch):
    """A filter/project consumer fragment emits its first output chunk
    BEFORE the upstream has served its last page — pipelining, not
    bulk-buffering — and never holds more than one batch of input."""
    session = Session({"catalog": "tpch", "schema": "tiny"})
    root = plan_sql(session, "select o_custkey, o_totalprice from orders "
                             "where o_totalprice > 1000")
    # consumer fragment: the filter/project chain re-rooted on a remote
    # source fed by raw scan pages
    (scan,) = [n for n in P.walk_plan(root) if isinstance(n, P.TableScanNode)]
    remote = RemoteSourceNode(
        fragment_id=7, types=list(scan.output_types),
        names=list(scan.column_names))

    def reroot(node):
        if node is scan:
            return remote
        for attr in ("source",):
            if hasattr(node, attr):
                setattr(node, attr, reroot(getattr(node, attr)))
        return node

    frag_root = reroot(root.source)  # drop OutputNode wrapper

    ex = Executor(session)
    scan_page = ex.execute(scan).compact()
    n = scan_page.num_rows
    chunks = [scan_page.slice_rows(lo, min(n, lo + n // 10))
              for lo in range(0, n, n // 10)]

    first_output_after_serves: List[int] = []

    class RecordingClient(FakeExchangeClient):
        def iter_pages(self):
            for p in FakeExchangeClient.pages_to_serve:
                self.served += 1
                yield p

    import trino_tpu.server.exchange_client as xc

    monkeypatch.setattr(xc, "ExchangeClient", RecordingClient)
    FakeExchangeClient.pages_to_serve = chunks
    monkeypatch.setattr(SqlTask, "STREAM_BATCH_ROWS", 1)

    req = TaskRequest(
        task_id="t_chain", query_id="q_chain", fragment_root=frag_root,
        splits={}, upstream={7: [("http://fake", "up.1", 0)]},
        session_properties=dict(session.properties))
    task = SqlTask(req, session_factory=lambda p: Session(p))

    client_ref: List[RecordingClient] = []

    orig_enqueue = task.output.enqueue

    def recording_enqueue(pb, **kw):
        if FakeExchangeClient.instances:
            first_output_after_serves.append(
                FakeExchangeClient.instances[-1].served)
        return orig_enqueue(pb, **kw)

    task.output.enqueue = recording_enqueue
    task.start()
    deadline = time.time() + 120
    while task.state.get() not in ("FINISHED", "FAILED") and time.time() < deadline:
        time.sleep(0.05)
    assert task.state.get() == "FINISHED", task.failure
    # first output chunk was enqueued after the FIRST upstream page, while
    # 9 more pages were still unserved — the consumer pipelines
    assert first_output_after_serves, "no output enqueued"
    assert first_output_after_serves[0] < len(chunks)

    frames, token = [], 0
    for _ in range(1000):
        got, token, complete, failure = task.output.poll(
            token, 0, max_pages=100, timeout=5.0)
        assert failure is None, failure
        frames.extend(got)
        if complete:
            break
    total = sum(deserialize_page(f).live_count() for f in frames)
    want = Session({"catalog": "tpch", "schema": "tiny"}).execute(
        "select count(*) from orders where o_totalprice > 1000").rows[0][0]
    assert total == want


def test_scan_task_streams_split_at_a_time(monkeypatch):
    """A scan-rooted fragment with several splits enqueues output after
    EACH split (the per-split driver loop) — the first chunk is pullable
    while later splits still scan."""
    session = Session({"catalog": "tpch", "schema": "tiny",
                       "task_output_chunk_bytes": 1 << 20,
                       "sink_max_buffer_bytes": 64 << 20})
    root = plan_sql(session, "select o_orderkey, o_totalprice from orders "
                             "where o_totalprice > 1000")
    (scan,) = [n for n in P.walk_plan(root) if isinstance(n, P.TableScanNode)]
    conn = session.catalogs["tpch"]
    splits = conn.get_splits("tiny", "orders", 6)
    assert len(splits) > 1
    enq_after_splits: List[int] = []
    seen_splits = [0]

    req = TaskRequest(
        task_id="t_splits", query_id="q_splits", fragment_root=root.source,
        splits={scan.id: splits}, upstream={},
        session_properties=dict(session.properties))
    task = SqlTask(req, session_factory=lambda p: Session(p))
    orig_enqueue = task.output.enqueue

    def recording_enqueue(pb, **kw):
        enq_after_splits.append(seen_splits[0])
        return orig_enqueue(pb, **kw)

    task.output.enqueue = recording_enqueue

    from trino_tpu.server import task as task_mod

    orig_fe = task_mod.FragmentExecutor

    class CountingFE(orig_fe):
        def __init__(self, *a, **kw):
            seen_splits[0] += 1
            super().__init__(*a, **kw)

    monkeypatch.setattr(task_mod, "FragmentExecutor", CountingFE)
    task.start()
    deadline = time.time() + 120
    while task.state.get() not in ("FINISHED", "FAILED") and time.time() < deadline:
        time.sleep(0.05)
    assert task.state.get() == "FINISHED", task.failure
    # one executor per split, and the FIRST enqueue happened before the
    # LAST split's executor was built: per-split pipelining
    assert seen_splits[0] == len(splits)
    assert enq_after_splits and enq_after_splits[0] < len(splits)
    # row totals equal a bulk execution
    frames, token = [], 0
    for _ in range(1000):
        got, token, complete, failure = task.output.poll(
            token, 0, max_pages=100, timeout=5.0)
        assert failure is None, failure
        frames.extend(got)
        if complete:
            break
    total = sum(deserialize_page(f).live_count() for f in frames)
    want = Session({"catalog": "tpch", "schema": "tiny"}).execute(
        "select count(*) from orders where o_totalprice > 1000").rows[0][0]
    assert total == want
