"""A kernel profile that aged out is not a statement without a launch.

The device profiler keeps the folded kernel rows of the last
``MAX_QUERY_PROFILES`` statements, ordered by the SERVER's terminal
transitions; a reader (the benchmark's ``read_profiles``) asks for the last
64 by the CLIENT's clock, from 32 sender threads. ``GET
/v1/query/{id}/profile`` of a statement whose rows have left the LRU must
answer 404 ("aged out"), never 200 with ``"kernels": []``, which is what a
statement that truly launched nothing answers and what
``benchmark.check.profile_faults`` exists to flag.
"""
from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

import tests.conftest  # noqa: F401 — cpu mesh config
from benchmark.check import profile_faults
from trino_tpu.obs.devprofiler import (
    DEVICE_PROFILER, MAX_QUERY_PROFILES, DeviceProfiler, new_kernel_row)

STATEMENTS = 600
SENDERS = 16
SEQUENTIAL_FIRST = 10
PROFILES_KEPT = 64          # benchmark/run.py's
LOOKUP = "select o_orderkey, o_totalprice from orders where o_orderkey = {}"


def _row(launches: int) -> dict:
    row = new_kernel_row("1", "TableScan", "eager")
    row["launches"] = launches
    return row


def test_profiler_lru_returns_nothing_for_an_evicted_query():
    prof = DeviceProfiler(max_query_profiles=2)
    for qid in ("a", "b", "c"):
        prof.record_query_kernels(qid, [_row(3)])
    assert prof.kernel_rows("a") == []
    assert [r["launches"] for r in prof.kernel_rows("c")] == [3]
    assert {r["queryId"] for r in prof.kernel_rows()} == {"b", "c"}


def test_the_lru_holds_what_the_harness_asks_for_whatever_the_order():
    # 64 profiles asked for, 32 senders whose statements may fold in any
    # order: the store has to be several times the two together
    assert MAX_QUERY_PROFILES == 512 >= 4 * (PROFILES_KEPT + 32)


def _get_profile(coord, query_id):
    req = urllib.request.Request(
        f"{coord.base_url}/v1/query/{query_id}/profile",
        headers={"X-Trino-User": "test"})
    try:
        return 200, json.loads(urllib.request.urlopen(req).read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture(scope="module")
def aged():
    """A coordinator at ``tpch.tiny`` set up as tests/test_fast_path.py
    does, a statement without any launch, then 600 fast-path lookups: ten
    in turn, the rest from 16 threads. What every profile answers after."""
    from trino_tpu.client.remote import StatementClient
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    coord = CoordinatorServer()
    # keep every statement of the fixture reachable by id: the registry's
    # own pruning (100 terminal queries) answers 404 "no such query"
    coord.MAX_QUERY_HISTORY = 4 * STATEMENTS
    coord.start()
    worker = WorkerServer(coordinator_url=coord.base_url, node_id="age-w0")
    worker.start()
    assert coord.registry.wait_for_workers(1, timeout=15.0)
    props = {"catalog": "tpch", "schema": "tiny",
             "short_query_fast_path": "true"}
    try:
        first = StatementClient(coord.base_url, props)
        first.execute("set session join_max_broadcast_rows = 1000")
        no_launch = first.query_id
        done = []
        for i in range(SEQUENTIAL_FIRST):
            first.execute(LOOKUP.format(1 + i))
            done.append((time.perf_counter(), first.query_id))
        lock = threading.Lock()
        per_sender = (STATEMENTS - SEQUENTIAL_FIRST) // SENDERS + 1

        def sender(k: int) -> None:
            client = StatementClient(coord.base_url, props)
            for i in range(per_sender):
                with lock:
                    if len(done) >= STATEMENTS:
                        return
                client.execute(LOOKUP.format(1 + (k * 997 + i * 37) % 15000))
                with lock:
                    done.append((time.perf_counter(), client.query_id))

        threads = [threading.Thread(target=sender, args=(k,))
                   for k in range(SENDERS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        by_client_clock = [qid for _at, qid in sorted(done)]
        yield {
            "coord": coord, "no_launch": no_launch, "ids": by_client_clock,
            "answers": {qid: _get_profile(coord, qid)
                        for qid in by_client_clock},
        }
    finally:
        worker.stop()
        coord.stop()


def test_an_aged_out_profile_answers_404_and_says_so(aged):
    code, body = aged["answers"][aged["ids"][0]]
    assert code == 404
    assert "aged out" in body["error"]
    # the stores behind the other read surfaces keep returning nothing
    assert DEVICE_PROFILER.kernel_rows(aged["ids"][0]) == []


def test_the_last_512_folded_answer_rows_with_launches(aged):
    assert len(aged["ids"]) >= STATEMENTS
    held = {qid: body for qid, (code, body) in aged["answers"].items()
            if code == 200}
    assert len(held) == MAX_QUERY_PROFILES
    for qid, body in held.items():
        assert sum(k["launches"] for k in body["kernels"]) > 0, qid
    gone = [body for code, body in aged["answers"].values() if code != 200]
    assert len(gone) == len(aged["ids"]) - MAX_QUERY_PROFILES
    assert all("aged out" in body["error"] for body in gone)


def test_a_statement_that_launched_nothing_still_answers_no_rows(aged):
    # it folded no row, so nothing of it can have aged out
    code, body = _get_profile(aged["coord"], aged["no_launch"])
    assert code == 200 and body["kernels"] == []
    assert profile_faults(body["kernels"], "fast-path", "cpu") \
        == "no kernel launch in its profile"


def test_the_last_64_by_client_clock_pass_the_harness_check(aged):
    """``benchmark/run.py:read_profiles`` skips a profile whose GET raises
    and hands the rest to ``profile_faults``: none may read as launchless."""
    last = aged["ids"][-PROFILES_KEPT:]
    read = {qid: aged["answers"][qid][1] for qid in last
            if aged["answers"][qid][0] == 200}
    assert len(read) == PROFILES_KEPT       # 512 holds all 64 of 16 senders
    for qid, body in read.items():
        assert profile_faults(body["kernels"], "fast-path", "cpu") is None, (
            qid, body["kernels"])


def test_system_runtime_kernels_has_no_row_of_an_evicted_query(aged):
    from trino_tpu.client.remote import StatementClient

    client = StatementClient(aged["coord"].base_url,
                             {"catalog": "system", "schema": "runtime"})
    _cols, rows = client.execute(
        "select count(*) from system.runtime.kernels "
        f"where query_id = '{aged['ids'][0]}'")
    assert rows == [[0]]
    _cols, rows = client.execute(
        "select count(*) from system.runtime.kernels "
        f"where query_id = '{aged['ids'][-1]}'")
    assert rows[0][0] > 0
