"""The worker's output path (``SqlTask._write_output`` / ``_enqueue_chunks``):
a page bound for an exchange leaves the device ONCE, is written as it is
(raw into an output buffer, zlib to disk), and lands where the parent put it."""
import os
import struct
import threading
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import Session
from trino_tpu import types as T
from trino_tpu.data.page import Page
from trino_tpu.data.serde import (
    CODEC_NONE, CODEC_ZLIB, VERSION, deserialize_page)
from trino_tpu.obs.devprofiler import charge_to, new_kernel_row
from trino_tpu.server import wire
from trino_tpu.server.segments import SegmentStore
from trino_tpu.server.task import (
    SqlTask, TaskRequest, _canonical_partition_ids)
from trino_tpu.sql.planner import plan as P

N = 48
SCHEMA = {
    "k": T.BIGINT,
    "s": T.VARCHAR,
    "d": T.parse_type("decimal(38,2)"),
    "a": T.parse_type("array(integer)"),
    "q": T.INTEGER,
}
# what the commit before this path was rebuilt answered for ``_device_page()``
# (its ``_canonical_partition_ids(page, channels, 3)`` and
# ``row_byte_estimate()``, run on that commit's tree): the placement of a key
# has to stay byte for byte, across producers of both versions
PARENT_PIDS = {
    (0,): [1, 1, 0, 2, 1, 0, 0, 0, 2, 2, 1, 2, 2, 1, 2, 2, 0, 1, 0, 1, 0, 1,
           2, 0, 0, 2, 2, 2, 0, 2, 1, 1, 1, 0, 2, 1, 0, 2, 0, 2, 2, 1, 2, 0,
           2, 1, 0, 1],
    (1, 0): [1, 0, 0, 0, 2, 1, 2, 0, 0, 1, 0, 1, 1, 0, 1, 2, 2, 2, 2, 2, 1,
             1, 2, 1, 0, 0, 0, 2, 1, 0, 1, 0, 0, 0, 2, 2, 1, 0, 1, 1, 1, 0,
             2, 2, 1, 1, 2, 1],
}
PARENT_ROW_BYTES = 37


def _device_page() -> Page:
    """A selection mask, a two-limb decimal, NULLs in every column, a
    varchar with empty and non-ASCII strings and a nested column."""
    data = {
        "k": [i * 7 + 1 for i in range(N)],
        "s": [None if i % 11 == 5 else ("" if i % 13 == 0 else f"nm-{i % 9}-ż")
              for i in range(N)],
        "d": [None if i % 10 == 3 else
              (str(10 ** 30 + i) + ".25" if i % 4 == 0 else f"{i}.50")
              for i in range(N)],
        "a": [None if i % 8 == 6 else list(range(i % 4)) for i in range(N)],
        "q": [None if i % 5 == 2 else i for i in range(N)],
    }
    page = Page.from_pydict(SCHEMA, data)
    page.sel = jnp.asarray(np.array([i % 3 != 1 for i in range(N)]))
    assert page.columns[2].hi is not None and page.columns[3].children
    return page


def _task(consumers=1, channels=None, segment_store=None, **props) -> SqlTask:
    root = P.ValuesNode(types=list(SCHEMA.values()), names=list(SCHEMA),
                        rows=[])
    req = TaskRequest(
        task_id="q.0.0.a0", query_id="q", fragment_root=root, splits={},
        upstream={}, session_properties=props, consumer_count=consumers,
        output_partition_channels=channels,
        spool_results=segment_store is not None)
    return SqlTask(req, session_factory=lambda p: Session(p),
                   segment_store=segment_store)


def _write(task: SqlTask, page: Page) -> dict:
    """``_write_output`` as ``_run_body`` calls it; the root's kernel row."""
    with task._output_path(page):
        task._write_output(page)
    (row,) = task.kernel_stats.values()
    return row


def _frames(task: SqlTask, buffer_id: int = 0) -> list:
    frames, token = [], 0
    while True:
        got, token, complete, failure = task.output.poll(
            token, buffer_id, max_pages=100, timeout=5.0)
        assert failure is None, failure
        frames.extend(got)
        if complete:
            return frames


def _rows(frames) -> list:
    return [r for f in frames for r in deserialize_page(f).to_pylist()]


def _blocks(frame: bytes):
    """(codec, bytes) of each column block of a frame."""
    _magic, version, _codec, ncols, _n = struct.unpack_from("<IBBHI", frame, 0)
    assert version == VERSION
    off, out = 12, []
    for _ in range(ncols):
        codec, size = struct.unpack_from("<BI", frame, off)
        out.append((codec, frame[off + 5:off + 5 + size]))
        off += 5 + size
    assert off == len(frame)
    return out


def _tree_bytes(page: Page) -> int:
    def col(c):
        arrays = [c.values, c.nulls, c.hi]
        return (sum(int(np.asarray(a).nbytes) for a in arrays if a is not None)
                + sum(col(k) for k in c.children or ()))

    return int(np.asarray(page.sel).nbytes) + sum(map(col, page.columns))


def _assert_one_fetch(row: dict, page: Page, live: int) -> None:
    assert row["outputFetches"] == 1
    assert row["exchangedRows"] == live
    assert row["hostSyncs"] == 1
    assert list(row["hostSyncSites"]) == ["output-fetch"]
    count, _seconds, nbytes = row["hostSyncSites"]["output-fetch"]
    assert count == 1 and nbytes == row["d2hBytes"] == _tree_bytes(page)
    assert row["platform"] == "cpu"     # not "cpu+host": see _charge_root


# ------------------------------------------------------------- placement
@pytest.mark.parametrize("channels", sorted(PARENT_PIDS))
def test_a_key_lands_where_the_parent_put_it(channels):
    page = _device_page()
    assert _canonical_partition_ids(
        page, list(channels), 3).tolist() == PARENT_PIDS[channels]
    # and the same from the output path's host copy
    assert _canonical_partition_ids(
        page.to_host("output-fetch"), list(channels), 3
    ).tolist() == PARENT_PIDS[channels]


@pytest.mark.parametrize("channels", sorted(PARENT_PIDS))
def test_partitioned_output_is_one_fetch_and_the_parents_rows(channels):
    page = _device_page()
    want = page.compact().to_pylist()
    live = np.asarray(page.sel)
    all_rows = Page(page.columns).to_pylist()
    task = _task(3, list(channels))
    row = _write(task, page)
    _assert_one_fetch(row, page, len(want))
    got = []
    for pid in range(3):
        frames = _frames(task, pid)
        assert _rows(frames) == [
            r for r, p, keep in zip(all_rows, PARENT_PIDS[channels], live)
            if keep and p == pid]
        assert frames and all(
            codec == CODEC_NONE for f in frames for codec, _b in _blocks(f))
        got += _rows(frames)
    assert sorted(map(repr, got)) == sorted(map(repr, want))
    assert task.partition_rows == [
        sum(1 for p, keep in zip(PARENT_PIDS[channels], live)
            if keep and p == pid) for pid in range(3)]
    assert task.output_rows == len(want)


def test_replicated_hot_partitions_follow_each_partitions_own_rows():
    page = _device_page()
    live = np.asarray(page.sel)
    all_rows = Page(page.columns).to_pylist()
    own = [[r for r, p, keep in zip(all_rows, PARENT_PIDS[(0,)], live)
            if keep and p == pid] for pid in range(3)]
    task = _task(3, [0])
    task.request.skew_replicate_partitions = [1]
    row = _write(task, page)
    _assert_one_fetch(row, page, int(live.sum()))
    assert _rows(_frames(task, 0)) == own[0] + own[1]
    assert _rows(_frames(task, 1)) == own[1]
    assert _rows(_frames(task, 2)) == own[2] + own[1]
    # the skew signal counts a row once, where its key hashed
    assert task.partition_rows == [len(p) for p in own]


# ------------------------------------------------ plain, spooled, segments
def test_plain_output_is_one_fetch_and_raw_frames():
    page = _device_page()
    want = page.compact().to_pylist()
    task = _task(2, task_output_chunk_bytes=256)     # several chunks
    row = _write(task, page)
    _assert_one_fetch(row, page, len(want))
    frames = _frames(task, 1)
    assert len(frames) > 3 and _rows(frames) == want
    assert all(codec == CODEC_NONE
               for f in frames for codec, _b in _blocks(f))


def _assert_zlib_where_it_shrinks(frames) -> None:
    codecs = set()
    for f in frames:
        for codec, block in _blocks(f):
            codecs.add(codec)
            if codec == CODEC_ZLIB:
                assert len(block) < len(zlib.decompress(block))
            else:
                assert len(zlib.compress(block, 1)) >= len(block)
    assert CODEC_ZLIB in codecs


@pytest.mark.parametrize("channels", [None, [0]])
def test_spooled_output_is_durable_first_and_zlib(channels, tmp_path,
                                                  monkeypatch):
    monkeypatch.setenv("TRINO_TPU_SPOOL_DIR", str(tmp_path))
    page = _device_page()
    want = page.compact().to_pylist()
    task = _task(3, channels)
    seen = []
    enqueue = (task.output.enqueue_partition if channels
               else task.output.enqueue)

    def spooled_first(*args, **kw):      # durable before visible
        seen.append(sorted(os.listdir(tmp_path)))
        return enqueue(*args, **kw)

    monkeypatch.setattr(
        task.output, "enqueue_partition" if channels else "enqueue",
        spooled_first)
    row = _write(task, page)
    _assert_one_fetch(row, page, len(want))
    files = ([f"q.0.0.a0.p{p}.pages" for p in range(3)] if channels
             else ["q.0.0.a0.pages"])
    assert seen and all(s == files for s in seen)
    got = []
    for pid, name in enumerate(files if channels else files * 3):
        with open(tmp_path / name, "rb") as f:
            on_disk = wire.unframe_pages(f.read())
        assert on_disk == _frames(task, pid)      # encoded once
        _assert_zlib_where_it_shrinks(on_disk)
        got.append(_rows(on_disk))
    if channels:
        assert sorted(map(repr, sum(got, []))) == sorted(map(repr, want))
    else:
        assert got == [want] * 3


def test_result_segments_are_one_fetch_and_zlib(tmp_path):
    store = SegmentStore(base_dir=str(tmp_path))
    page = _device_page()
    want = page.compact().to_pylist()
    task = _task(segment_store=store)
    row = _write(task, page)
    _assert_one_fetch(row, page, len(want))
    assert task.output.buffered_bytes == 0
    frames = [f for seg in task.result_segments
              for f in wire.unframe_pages(store.read(seg["id"]))]
    assert _rows(frames) == want
    _assert_zlib_where_it_shrinks(frames)


# --------------------------------------------------- the streaming shapes
@pytest.mark.parametrize("channels", [None, [0]])
def test_the_streaming_shapes_fetch_a_page_once(channels):
    """``_host_compacted`` inside the shape's own ``task/output`` span,
    then ``_enqueue_out`` of the host copy: one fetch between them."""
    page = _device_page()
    want = page.compact().to_pylist()
    task = _task(3 if channels else 1, channels)
    with task._output_path(page):
        out = task._host_compacted(page)
    assert out.sel is None and all(
        isinstance(c.values, np.ndarray) for c in out.columns)
    task._enqueue_out(out, channels, task.request.consumer_count)
    task.output.set_complete()
    (row,) = task.kernel_stats.values()
    _assert_one_fetch(row, page, len(want))
    got = sum((_rows(_frames(task, p)) for p in range(3 if channels else 1)),
              [])
    assert sorted(map(repr, got)) == sorted(map(repr, want))


def test_an_all_dead_page_is_fetched_and_hands_nothing_on():
    page = Page.all_dead(list(SCHEMA.values()))
    task = _task()
    task._enqueue_out(page, None, 1)
    task.output.set_complete()
    (row,) = task.kernel_stats.values()
    assert row["outputFetches"] == 1 and row["exchangedRows"] == 0
    assert _frames(task) == []


# ------------------------------------------------------ row_byte_estimate
def test_row_byte_estimate_reads_nothing():
    page = _device_page()
    row = new_kernel_row("0", "Values", "eager")
    with jax.transfer_guard_device_to_host("disallow"), charge_to(row):
        assert page.row_byte_estimate() == PARENT_ROW_BYTES
    assert row["hostSyncs"] == 0 and not row["hostSyncSites"]
    assert page.to_host("output-fetch").row_byte_estimate() == PARENT_ROW_BYTES


# ---------------------------------------------------------- the watermark
@pytest.mark.parametrize("channels", [None, [0]])
def test_frames_past_the_watermark_drain_with_a_consumer_attached(channels):
    """Raw frames cross ``max_buffer_bytes`` where zlib's did not: the
    producer parks in ``enqueue`` until its consumers acknowledge."""
    n = 60_000
    page = Page.from_pydict(
        {"k": T.BIGINT, "v": T.BIGINT},
        {"k": list(range(n)), "v": [i * 3 for i in range(n)]})
    page.sel = jnp.asarray(np.arange(n) % 2 == 0)
    consumers = 2 if channels else 1
    task = _task(consumers, channels, sink_max_buffer_bytes=128 * 1024,
                 task_output_chunk_bytes=16 * 1024)
    got = [None] * consumers

    def consume(pid):
        got[pid] = _frames(task, pid)

    threads = [threading.Thread(target=consume, args=(p,), daemon=True)
               for p in range(consumers)]
    for t in threads:
        t.start()
    row = _write(task, page)            # would time out with nobody pulling
    for t in threads:
        t.join(30)
    assert all(not t.is_alive() for t in threads)
    frames = [f for fs in got for f in fs]
    assert sum(map(len, frames)) > 3 * 128 * 1024      # well past the mark
    assert task.output.stalled_seconds > 0
    assert sorted(_rows(frames)) == [(k, k * 3) for k in range(0, n, 2)]
    assert row["outputFetches"] == 1


def test_a_full_buffer_reads_full_until_its_consumer_acknowledges():
    from trino_tpu.server.buffer import OutputBuffer, PartitionedOutputBuffer

    buf = OutputBuffer(1, max_buffer_bytes=10)
    assert not buf.full
    buf.enqueue(b"x" * 10)
    assert buf.full
    _pages, token, _complete, _failure = buf.poll(0, timeout=0.1)
    assert buf.full                     # pulled, not yet acknowledged
    buf.poll(token, timeout=0.01)
    assert not buf.full
    parts = PartitionedOutputBuffer(2, max_buffer_bytes=1 << 17)
    parts.enqueue_partition(1, b"x" * (1 << 16))
    assert parts.full and not parts._parts[0].full


def test_a_leaf_build_past_its_watermark_does_not_hold_its_consumer_back():
    """Phased execution holds a join's fragment back until its leaf build
    has finished executing. A build that streams split by split parks at
    its output watermark while still RUNNING once its raw frames pass it
    (customer in Q18 at SF 10: 45 MB against 32 MB), and only the fragment
    held back could drain it: the wait ends at a FULL buffer too."""
    from trino_tpu.client.remote import StatementClient
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    coord = CoordinatorServer()
    coord.start()
    worker = WorkerServer(coordinator_url=coord.base_url, node_id="opw0")
    worker.start()
    try:
        assert coord.registry.wait_for_workers(1, timeout=15.0)
        client = StatementClient(coord.base_url, {
            "catalog": "tpch", "schema": "tiny",
            "result_cache_enabled": "false",
            # several splits of customer, and 30 KB of frames against 16
            "staging_split_bytes": "40000",
            "sink_max_buffer_bytes": "16384",
            "task_output_chunk_bytes": "4096"})
        done = []
        t = threading.Thread(target=lambda: done.append(client.execute(
            "select count(*), sum(c_acctbal), sum(c_nationkey) "
            "from orders, customer where o_custkey = c_custkey")),
            daemon=True)
        t.start()
        t.join(90)
        assert done, "the build parked at its watermark and nobody came"
        assert done[0][1] == [[15000, "66494579.20", 185464]]
        query = coord.get_query(client.query_id)
        assert query.phase_waits == [(1, [0])]
        (loc,) = query.fragment_tasks[0]
        build = worker.tasks.get(loc.task_id)
        assert build.output.stalled_seconds > 0     # parked, then drained
        # a terminal task is kept for its status, not for its pages
        assert build.state.get() == "FINISHED"
        build._thread.join(5)
        assert build._live_executor is None
    finally:
        worker.stop()
        coord.stop()
