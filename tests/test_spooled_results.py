"""Spooled result protocol: segment store lifecycle, the serde's blocks, the
worker-direct/coordinator spool paths, parallel client fetch, faults.

Reference: Trino 455's spooled client protocol — result segments are
written by the producers, the statement response carries a manifest,
clients fetch the segments directly (the coordinator leaves the data
path), and segments are reclaimed by ack/TTL/orphan sweeps like the FTE
exchange's spool files.
"""
import os
import struct
import time
import zlib

import numpy as np
import pytest

import jax.numpy as jnp

from trino_tpu import types as T
from trino_tpu.client import dbapi
from trino_tpu.client.remote import SegmentFetchError, StatementClient
from trino_tpu.data.dictionary import Dictionary
from trino_tpu.data.page import Column, Page
from trino_tpu.data.serde import (
    CODEC_NONE, CODEC_ZLIB, MAGIC, VERSION, deserialize_page, serialize_page)
from trino_tpu.obs import metrics as M
from trino_tpu.server import wire
from trino_tpu.server.segments import SegmentStore, parse_range


# ----------------------------------------------------------- serde tier
def _segment_scale_page(n=50_000):
    """A page exercising every encoding the segment path must carry:
    dictionary varchar, long-decimal two-limb, null bitmaps, and an
    incompressible float column."""
    rng = np.random.default_rng(7)
    vocab = [f"name-{i}" for i in range(257)]
    codes = rng.integers(0, len(vocab), n).astype(np.int32)
    nulls = (rng.random(n) < 0.1)
    lo = rng.integers(-(10 ** 12), 10 ** 12, n).astype(np.int64)
    hi = rng.integers(-5, 5, n).astype(np.int64)
    entropy = rng.standard_normal(n)
    return Page([
        Column(T.parse_type("bigint"),
               jnp.asarray(np.arange(n, dtype=np.int64))),
        Column(T.parse_type("varchar"), jnp.asarray(codes),
               jnp.asarray(nulls), Dictionary(vocab)),
        Column(T.parse_type("decimal(30,2)"), jnp.asarray(lo),
               hi=jnp.asarray(hi)),
        Column(T.parse_type("double"), jnp.asarray(entropy)),
    ])


def _pages_equal(a: Page, b: Page):
    assert a.num_rows == b.num_rows and a.channel_count == b.channel_count
    for ca, cb in zip(a.columns, b.columns):
        np.testing.assert_array_equal(np.asarray(ca.values),
                                      np.asarray(cb.values))
        if ca.hi is not None:
            np.testing.assert_array_equal(np.asarray(ca.hi),
                                          np.asarray(cb.hi))
        if ca.nulls is not None:
            np.testing.assert_array_equal(np.asarray(ca.nulls),
                                          np.asarray(cb.nulls))
        if ca.dictionary is not None:
            assert list(ca.dictionary.values) == list(cb.dictionary.values)


def test_serde_segment_scale_roundtrip():
    page = _segment_scale_page()
    _pages_equal(page, deserialize_page(serialize_page(page)))


def test_serde_incompressible_column_stores_raw():
    """Entropy float data must ship as a RAW block (codec byte NONE) and
    the per-codec counters must move — the compression ratio is
    observable."""
    rng = np.random.default_rng(3)
    # full-range random int64: every byte is entropy (Gaussian doubles
    # still compress a little through their exponent bytes)
    ints = rng.integers(np.iinfo(np.int64).min, np.iinfo(np.int64).max,
                        20_000, dtype=np.int64)
    page = Page([Column(T.parse_type("bigint"), jnp.asarray(ints))])
    raw0 = M.SERDE_BYTES.value("encode", "none")
    zlib0 = M.SERDE_BYTES.value("encode", "zlib")
    blob = serialize_page(page)
    assert M.SERDE_BYTES.value("encode", "none") > raw0
    # header: magic/version/codec/ncols/nrows, then block codec byte
    magic, version, codec, ncols, nrows = struct.unpack_from("<IBBHI",
                                                             blob, 0)
    assert (magic, version, ncols) == (MAGIC, VERSION, 1)
    block_codec, block_len = struct.unpack_from("<BI", blob, 12)
    assert block_codec == CODEC_NONE  # zlib did not shrink it -> raw
    _pages_equal(page, deserialize_page(blob))
    # a compressible page still compresses (and counts under zlib)
    rep = Page([Column(T.parse_type("bigint"),
                       jnp.asarray(np.zeros(20_000, np.int64)))])
    blob2 = serialize_page(rep)
    assert M.SERDE_BYTES.value("encode", "zlib") > zlib0
    block_codec2, block_len2 = struct.unpack_from("<BI", blob2, 12)
    assert block_codec2 == CODEC_ZLIB and block_len2 < 20_000 * 8
    _pages_equal(rep, deserialize_page(blob2))


def test_serde_reads_legacy_v2_frames():
    """Spool files written by the previous (whole-body zlib) format must
    still deserialize: at segment scale a frame of fixed-width columns, whose
    blocks no later version changed, under one zlib pass; with a vocabulary,
    the bytes an older process wrote (tests/legacy_frames.py)."""
    from tests.legacy_frames import FRAMES, ROWS
    from trino_tpu.data.serde import _serialize_column

    whole = _segment_scale_page(5_000)
    page = Page([c for c in whole.columns if c.dictionary is None])
    parts = []
    for col in page.columns:
        _serialize_column(col, page.num_rows, parts)
    body = zlib.compress(b"".join(parts), 1)
    v2 = struct.pack("<IBBHI", MAGIC, 2, CODEC_ZLIB, page.channel_count,
                     page.num_rows) + body
    _pages_equal(page, deserialize_page(v2))
    assert FRAMES["v2"][4] == 2
    assert deserialize_page(FRAMES["v2"]).to_pylist() == ROWS


# ---------------------------------------------------- segment store tier
def test_segment_store_write_read_range_ack(tmp_path):
    store = SegmentStore(base_dir=str(tmp_path))
    w = store.writer("q1", target_bytes=80, ttl_s=60.0)
    w.add(b"a" * 80, 10)   # reaches the target -> rolls segment 0
    w.add(b"b" * 30, 5)    # partial -> rolled by finish()
    metas = w.finish()
    assert len(metas) == 2
    assert [m.rows for m in metas] == [10, 5]
    sid = metas[0].segment_id
    full = store.read(sid)
    assert full == struct.pack("<I", 80) + b"a" * 80
    # range semantics
    assert parse_range("bytes=0-3", 100) == (0, 4)
    assert parse_range("bytes=-10", 100) == (90, 10)
    with pytest.raises(ValueError):
        parse_range("bytes=200-", 100)
    assert store.read(sid, 4, 8) == b"a" * 8
    # ack deletes the file and the registry entry, idempotently
    acked0 = M.RESULT_SEGMENTS_RECLAIMED.value("ack")
    assert store.ack(sid)
    assert not store.ack(sid)
    assert store.read(sid) is None
    assert not os.path.exists(metas[0].path)
    assert M.RESULT_SEGMENTS_RECLAIMED.value("ack") == acked0 + 1


def test_segment_store_ttl_and_orphan_sweep(tmp_path):
    store = SegmentStore(base_dir=str(tmp_path), default_ttl_s=60.0)
    w = store.writer("q2", target_bytes=1 << 20, ttl_s=0.05)
    w.add(b"x" * 100, 1)
    (meta,) = w.finish()
    ttl_bytes0 = M.RESULT_SEGMENT_RECLAIMED_BYTES.value("ttl")
    time.sleep(0.06)
    reclaimed = store.sweep()
    assert reclaimed == meta.bytes and len(store) == 0
    assert not os.path.exists(meta.path)
    assert M.RESULT_SEGMENT_RECLAIMED_BYTES.value("ttl") == (
        ttl_bytes0 + meta.bytes)
    # orphan sweep at construction: stale files (older than the TTL) left
    # by a dead process are reclaimed; fresh files are left alone
    stale = tmp_path / "deadq.s0-ff.seg"
    stale.write_bytes(b"z" * 64)
    os.utime(stale, (time.time() - 3600, time.time() - 3600))
    # a LIVE long-TTL segment owned by another server: its mtime is its
    # expiry (stamped at write), far in the future — must survive any
    # other store's boot sweep
    live = tmp_path / "liveq.s0-aa.seg"
    live.write_bytes(b"y" * 64)
    os.utime(live, (time.time() + 1800, time.time() + 1800))
    store2 = SegmentStore(base_dir=str(tmp_path), default_ttl_s=60.0)
    assert store2.orphans_reclaimed_bytes == 64
    assert not stale.exists() and live.exists()


def test_segment_writer_abandon(tmp_path):
    store = SegmentStore(base_dir=str(tmp_path))
    w = store.writer("q3", target_bytes=10, ttl_s=60.0)
    w.add(b"p" * 50, 3)
    w.abandon()
    assert len(store) == 0 and w.finish() == []


# -------------------------------------------------------- cluster tier
EXPORT_SQL = ("select o_orderkey, o_custkey, o_totalprice, o_orderdate "
              "from orders")
SORTED_SQL = EXPORT_SQL + " order by o_orderkey"

SPOOL_PROPS = {
    "spooled_results_enabled": "true",
    "spooled_results_threshold_bytes": "1024",
    "spooled_results_segment_bytes": "65536",
}


@pytest.fixture(scope="module")
def cluster():
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    coord = CoordinatorServer()
    coord.start()
    workers = [WorkerServer(coordinator_url=coord.base_url,
                            node_id=f"spool{i}") for i in range(2)]
    for w in workers:
        w.start()
    assert coord.registry.wait_for_workers(2, timeout=30.0)
    yield coord, workers
    for w in workers:
        w.stop()
    coord.stop()


@pytest.fixture(scope="module")
def inline_rows(cluster):
    coord, _ = cluster
    cur = dbapi.connect(coordinator_url=coord.base_url).cursor()
    cur.execute(SORTED_SQL)
    return cur.fetchall()


def test_worker_direct_spool_row_equality(cluster, inline_rows):
    """The export shape: workers write the segments, the manifest URIs
    point at the WORKERS, and parallel fetch returns the same multiset
    of rows as the inline protocol."""
    coord, workers = cluster
    client = StatementClient(coord.base_url,
                             {"catalog": "tpch", "schema": "tiny",
                              **SPOOL_PROPS}, fetch_streams=4)
    columns, rows = client.execute(EXPORT_SQL)
    assert client.stats["spooled"] == "worker-direct"
    assert client.spooled_segments >= 2  # one per worker at least
    assert sorted(tuple(r) for r in rows) == [
        tuple(r) for r in inline_rows]
    # the data plane bypassed the coordinator: every URI is a worker's
    worker_urls = {w.base_url for w in workers}
    q = coord.get_query(client.query_id)
    assert q is not None and q.result_segments
    for entry in q.result_segments:
        assert any(entry["uri"].startswith(u) for u in worker_urls)
        assert entry["ackUri"].startswith(coord.base_url)
    assert len(coord.segments) == 0  # nothing spooled coordinator-side
    # acks reclaimed the worker-held segments
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and any(
            len(w.segments) for w in workers):
        time.sleep(0.05)
    assert all(len(w.segments) == 0 for w in workers)
    # the ledger attributes segment fetch explicitly, post-wall
    info = wire.json_request(
        "GET", f"{coord.base_url}/v1/query/{client.query_id}")
    tl = info["queryStats"]["timeline"]
    assert tl["phases"]["segment-fetch"] >= 0.0
    assert tl["coverage"] >= 0.95


def test_coordinator_spool_preserves_order(cluster, inline_rows):
    """ORDER BY makes the root fragment non-trivial: the coordinator
    spools from its own store, and fetch (1 stream and 4) preserves
    exact row order vs inline."""
    coord, _ = cluster
    for streams in (1, 4):
        client = StatementClient(coord.base_url,
                                 {"catalog": "tpch", "schema": "tiny",
                                  **SPOOL_PROPS}, fetch_streams=streams)
        _, rows = client.execute(SORTED_SQL)
        assert client.stats["spooled"] == "coordinator"
        assert [tuple(r) for r in rows] == [tuple(r) for r in inline_rows]


def test_fast_path_and_prepared_spool(cluster, inline_rows):
    """Plan-shape independence: the short-query fast path and a prepared
    EXECUTE both spool, with identical rows."""
    coord, _ = cluster
    conn = dbapi.connect(coordinator_url=coord.base_url,
                         short_query_fast_path="true", **SPOOL_PROPS)
    cur = conn.cursor()
    cur.execute(SORTED_SQL)
    assert cur.stats["spooled"] is not None
    assert cur.stats["fastPath"] == "fast-path"
    assert cur.fetchall() == inline_rows
    # prepared EXECUTE (the DBAPI qmark path PREPAREs server-side)
    cur.execute(SORTED_SQL.replace("order by", "where o_orderkey > ? "
                                               "order by"), (0,))
    assert cur.stats["spooled"] is not None
    assert cur.fetchall() == inline_rows


def test_local_catalog_spool(cluster):
    """Coordinator-local (process-local catalog) queries spool from the
    coordinator's own store too."""
    coord, _ = cluster
    # stable columns only: the memory/heartbeat gauges move between scans
    sql = ("select node_id, http_uri, state from system.runtime.nodes "
           "order by node_id")
    base = dbapi.connect(coordinator_url=coord.base_url,
                         catalog="system").cursor()
    base.execute(sql)
    inline = base.fetchall()
    cur = dbapi.connect(coordinator_url=coord.base_url, catalog="system",
                        spooled_results_enabled="true",
                        spooled_results_threshold_bytes="1").cursor()
    cur.execute(sql)
    assert cur.stats["spooled"] == "coordinator"
    assert cur.fetchall() == inline


def test_segment_fetch_retries_once_on_transient_failure(
        cluster, inline_rows, monkeypatch):
    coord, _ = cluster
    orig = wire.http_request
    fails = {"n": 0}

    def flaky(method, url, *a, **k):
        if method == "GET" and "/v1/segment/" in url and fails["n"] == 0:
            fails["n"] += 1
            raise ConnectionError("injected transient segment failure")
        return orig(method, url, *a, **k)

    monkeypatch.setattr(wire, "http_request", flaky)
    client = StatementClient(coord.base_url,
                             {"catalog": "tpch", "schema": "tiny",
                              **SPOOL_PROPS})
    _, rows = client.execute(SORTED_SQL)
    assert fails["n"] == 1  # the failure happened and was retried
    assert [tuple(r) for r in rows] == [tuple(r) for r in inline_rows]


def test_missing_and_truncated_segment_raise_typed(cluster):
    """A segment that vanished (acked/TTL'd) or truncated on disk fails
    the fetch with a typed SegmentFetchError after the one retry."""
    coord, _ = cluster
    q = coord.submit(SORTED_SQL, dict(SPOOL_PROPS,
                                      catalog="tpch", schema="tiny"))
    deadline = time.monotonic() + 60.0
    while not q.state.is_terminal() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert q.state.get() == "FINISHED", q.failure
    assert q.result_segments
    client = StatementClient(coord.base_url)
    # truncated: overwrite the file with garbage
    meta = coord.segments.get(q.result_segments[0]["id"])
    with open(meta.path, "wb") as f:
        f.write(b"\x00" * 16)
    with pytest.raises(SegmentFetchError):
        client._fetch_one_segment(q.result_segments[0])
    # missing: acked away before the fetch
    if len(q.result_segments) > 1:
        gone = q.result_segments[1]
    else:
        gone = q.result_segments[0]
    coord.segments.ack(gone["id"])
    with pytest.raises(SegmentFetchError):
        client._fetch_one_segment(gone)


def test_inline_result_memory_guard(cluster, inline_rows):
    """Over inline_result_max_bytes: fails loudly with spooling off,
    auto-spools with it on."""
    coord, _ = cluster
    rejected0 = M.INLINE_RESULT_REJECTIONS.value()
    cur = dbapi.connect(coordinator_url=coord.base_url,
                        inline_result_max_bytes="2000").cursor()
    with pytest.raises(dbapi.DatabaseError, match="INLINE_RESULT_TOO_LARGE"):
        cur.execute(SORTED_SQL)
    assert M.INLINE_RESULT_REJECTIONS.value() == rejected0 + 1
    # the export (pass-through) shape fails DURING the gather — before
    # the coordinator has accumulated the whole result in memory
    with pytest.raises(dbapi.DatabaseError, match="INLINE_RESULT_TOO_LARGE"):
        cur.execute(EXPORT_SQL)
    assert M.INLINE_RESULT_REJECTIONS.value() == rejected0 + 2
    # same cap, protocol enabled: auto-spool instead of failing (the
    # threshold is set ABOVE the cap to prove the cap triggers the spool)
    cur2 = dbapi.connect(coordinator_url=coord.base_url,
                         inline_result_max_bytes="2000",
                         spooled_results_enabled="true",
                         spooled_results_threshold_bytes="1073741824"
                         ).cursor()
    cur2.execute(SORTED_SQL)
    assert cur2.stats["spooled"] is not None
    assert cur2.fetchall() == inline_rows


def test_small_results_stay_inline(cluster):
    """Below the threshold the protocol is untouched — point lookups on
    a spool-enabled session still answer inline."""
    coord, _ = cluster
    cur = dbapi.connect(coordinator_url=coord.base_url,
                        spooled_results_enabled="true",
                        spooled_results_threshold_bytes="1073741824"
                        ).cursor()
    cur.execute("select o_orderkey from orders where o_orderkey = 7")
    assert cur.stats["spooled"] is None
    assert cur.fetchall() == [(7,)]


def test_segment_http_range_fetch(cluster):
    """GET /v1/segment/{id} honors Range headers (206 + Content-Range) —
    the resume semantics of the segment endpoint."""
    coord, _ = cluster
    q = coord.submit(SORTED_SQL, dict(SPOOL_PROPS,
                                      catalog="tpch", schema="tiny"))
    deadline = time.monotonic() + 60.0
    while not q.state.is_terminal() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert q.state.get() == "FINISHED", q.failure
    seg = q.result_segments[0]
    status, full, headers = wire.http_request("GET", seg["uri"])
    assert status == 200 and len(full) == seg["bytes"]
    assert headers.get("X-Segment-Rows") == str(seg["rows"])
    status, part, headers = wire.http_request(
        "GET", seg["uri"], headers={"Range": "bytes=4-11"})
    assert status == 206 and part == full[4:12]
    assert headers.get("Content-Range") == f"bytes 4-11/{seg['bytes']}"
    # out-of-range is a 416, not data
    status, _, _ = wire.http_request(
        "GET", seg["uri"], headers={"Range": f"bytes={seg['bytes']}-"})
    assert status == 416
