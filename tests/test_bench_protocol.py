"""Bench child protocol: a dead or timed-out child must be DIAGNOSABLE
from the artifact (round-4's 'child produced no result' postmortem)."""
import importlib.util
import os
import subprocess
import sys

_BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "bench.py")


def _bench():
    spec = importlib.util.spec_from_file_location("bench_mod", _BENCH_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_collect_child_captures_stderr_of_dead_child(tmp_path):
    bench = _bench()
    proc = subprocess.Popen(
        [sys.executable, "-c",
         "import sys; print('boom: scoped vmem exhausted', file=sys.stderr); "
         "sys.exit(1)"],
        stdout=subprocess.PIPE, text=True)
    errf = open(tmp_path / "err", "w+")
    errf.write("line one\nboom: scoped vmem exhausted\n")
    proc._errf = errf
    out = bench._collect_child(proc, timeout=10)
    assert "error" in out
    assert "scoped vmem exhausted" in out["stderr_tail"]
    assert errf.closed  # capture file released


def test_collect_child_timeout_labeled(tmp_path):
    bench = _bench()
    proc = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(30)"],
        stdout=subprocess.PIPE, text=True)
    errf = open(tmp_path / "err2", "w+")
    errf.write("still compiling fragment 3...\n")
    proc._errf = errf
    out = bench._collect_child(proc, timeout=0.5)
    assert out["error"] == "child timed out"
    assert "compiling" in out["stderr_tail"]


def test_train_only_covers_compiler_crashers():
    """The queries whose fori bodies failed to compile for the TPU in
    round 5 stay on the train path."""
    bench = _bench()
    assert {"q18", "q95", "q3_sf10"} <= set(bench.TRAIN_ONLY)
    # the five round-5 roster entries stay present (additions are fine)
    assert {"q1", "q3", "q18", "q3_sf10", "q95_sf02"} <= set(bench.SPECS)
