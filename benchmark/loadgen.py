"""The load generator: client threads over ``StatementClient``.

Every statement is ``POST /v1/statement`` drained through ``nextUri`` by
``trino_tpu.client.remote.StatementClient``; nothing here reaches under the
HTTP surface. One record per statement, kept in memory and reduced when the
window has closed.

- closed loop: ``streams`` threads, each its own client, each cycling its
  list. At ``seconds`` no new turn of the mix starts: a stream finishes the
  turn it is in (one statement of each template, by weight), so that every
  window holds the templates in the mix's own proportions whatever the
  moment it ends at. The window closes when every stream has drained, and
  rates divide by the time that really passed: all the work over all the
  time.
- open loop: a dispatcher hands each arrival to a pool of ``senders``
  threads at its due time, whether or not earlier ones have finished; a
  statement is timed from when it was DUE, and how late it was sent is kept.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Dict, List, Optional

from benchmark.spec import Plan, Statement

# a window's statement that has not answered by then is counted as failed: a
# run has 360 s in all
STATEMENT_TIMEOUT_S = 300.0


@dataclasses.dataclass
class Record:
    template: str
    binding_key: str
    sql: str
    stream: int
    tag: str                    # "<stream>.<n>": unique in a window
    due: float                  # perf_counter; closed loop: = sent
    sent: float
    done: float = 0.0
    wall_sent: float = 0.0      # time.time() at sent, to lay spans on a trace
    rows: Optional[List[list]] = None
    error: Optional[str] = None
    query_id: Optional[str] = None
    stats: Optional[dict] = None
    cache_status: Optional[str] = None

    @property
    def latency_s(self) -> float:
        return self.done - self.due

    @property
    def counted_ms(self) -> float:
        """The latency a percentile counts: a failed or refused statement
        counts as the statement timeout, beyond any answered one."""
        return 1000.0 * (self.latency_s if self.error is None
                         else STATEMENT_TIMEOUT_S)

    @property
    def wall_done(self) -> float:
        return self.wall_sent + (self.done - self.sent)


def _annotation(name: str):
    """A host span in the profiler's own trace, so that the traced run can
    lay the window's statements on the device's clock."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def send(client, stmt: Statement, stream: int, n: int, due: float,
         annotate: bool = False,
         timeout: float = STATEMENT_TIMEOUT_S) -> Record:
    """Statement ``n`` of ``stream`` through ``client``; never raises."""
    rec = Record(stmt.template, stmt.binding_key, stmt.sql, stream,
                 f"{stream}.{n}", due, sent=time.perf_counter(),
                 wall_sent=time.time())
    try:
        if annotate:
            # the profiler keeps an annotation only if it began and ended
            # inside the trace: the instant one marks when this was sent
            # even where the statement outlasts the trace
            with _annotation(f"bench/sent/{stmt.template}/{rec.tag}"):
                pass
            with _annotation(f"bench/statement/{stmt.template}/{rec.tag}"):
                _cols, rec.rows = client.execute(stmt.sql, timeout=timeout)
        else:
            _cols, rec.rows = client.execute(stmt.sql, timeout=timeout)
    except Exception as e:  # noqa: BLE001 — counted under failed, not fatal
        rec.error = f"{type(e).__name__}: {e}"[:300]
    rec.done = time.perf_counter()
    rec.query_id = client.query_id
    rec.stats = client.stats
    rec.cache_status = client.cache_status
    return rec


@dataclasses.dataclass
class Window:
    records: List[Record]
    start: float                # perf_counter at the window's first statement
    end: float                  # perf_counter when the last one had drained
    wall_start: float           # time.time() at start

    @property
    def elapsed_s(self) -> float:
        return self.end - self.start


def run_closed(plan: Plan, new_client: Callable[[], object], seconds: float,
               annotate: bool = False, observer=None) -> Window:
    records: List[List[Record]] = [[] for _ in plan.streams]
    clients = [new_client() for _ in plan.streams]
    go = threading.Barrier(len(plan.streams) + 1)
    t0: Dict[str, float] = {}

    def stream(i: int) -> None:
        go.wait()
        stop_at = t0["start"] + seconds
        n = 0
        while time.perf_counter() < stop_at or n % plan.turn:
            stmt = plan.streams[i][n % len(plan.streams[i])]
            now = time.perf_counter()
            records[i].append(send(clients[i], stmt, i, n, now, annotate))
            if observer:
                observer(records[i][-1])
            n += 1

    threads = [threading.Thread(target=stream, args=(i,), name=f"stream-{i}",
                                daemon=True) for i in range(len(plan.streams))]
    for t in threads:
        t.start()
    t0["start"] = time.perf_counter()
    wall_start = time.time()
    go.wait()
    for t in threads:
        t.join()
    end = time.perf_counter()
    return Window([r for rs in records for r in rs], t0["start"], end,
                  wall_start)


def run_open(plan: Plan, new_client: Callable[[], object], seconds: float,
             senders: int, annotate: bool = False, observer=None) -> Window:
    work: "queue.Queue" = queue.Queue()
    records: List[Record] = []
    lock = threading.Lock()

    def sender(i: int) -> None:
        client = new_client()
        n = 0
        while True:
            item = work.get()
            if item is None:
                return
            due, stmt = item
            rec = send(client, stmt, i, n, due, annotate)
            n += 1
            with lock:
                records.append(rec)
            if observer:
                observer(rec)

    threads = [threading.Thread(target=sender, args=(i,), name=f"sender-{i}",
                                daemon=True) for i in range(senders)]
    for t in threads:
        t.start()
    start = time.perf_counter()
    wall_start = time.time()
    for offset, stmt in plan.arrivals:
        due = start + offset
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        work.put((due, stmt))
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join()
    end = time.perf_counter()
    return Window(records, start, max(end, start + seconds), wall_start)


def run_window(plan: Plan, new_client, seconds: float, senders: int = 32,
               annotate: bool = False, observer=None) -> Window:
    """``observer`` is called with each record as its statement ends (the
    traced run's tracer watches for whole statements through it)."""
    if plan.kind == "closed":
        return run_closed(plan, new_client, seconds, annotate, observer)
    return run_open(plan, new_client, seconds, senders, annotate, observer)
