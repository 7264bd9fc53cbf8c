"""``chip_smoke.py`` rehearsed in-process on the CPU at ``--schema tiny``.

The chip run itself is the driver's; what is checkable here: the numpy
reference agrees with the row-at-a-time oracle, every phase runs and holds
the engine to that reference, a CPU run is never a pass, and the compile
cache rule.
"""
import json
import os

import pytest

import jax

import chip_smoke
import tpch_reference
from tests import tpch_oracle
from trino_tpu import compile_cache


@pytest.fixture(scope="module")
def counter():
    return chip_smoke.CompileCounter()


@pytest.mark.parametrize("query", ["q1", "q6", "q3", "q18"])
def test_numpy_reference_equals_oracle(query):
    assert (getattr(tpch_reference, query)("tiny")
            == getattr(tpch_oracle, query)("tiny"))


def test_served_phase_matches_reference_on_tiny(counter):
    """Engine rows == numpy reference is asserted inside the phase (it
    raises on any mismatch); here: every query ran, down the path it was
    meant to take, and returned as many rows as the oracle."""
    records = chip_smoke.run_served("tiny", lambda record: None, counter)
    assert [(r["query"], r["fast_path"]) for r in records] == [
        ("q1", "distributed"), ("q6", "distributed"), ("q3", "distributed"),
        ("q18", "distributed"), ("point", "fast-path"),
        ("q3", "distributed")]
    for r in records[:4]:
        assert r["rows"] == len(getattr(tpch_oracle, r["query"])("tiny"))
        assert r["kernel_launches"] > 0
        assert r["launch_platform"] == "cpu"  # where the tests run
    assert records[-1]["warm_s"] is not None


def test_compiled_phase_matches_reference_on_tiny(counter):
    record = chip_smoke.run_compiled("tiny", lambda record: None, counter)
    assert record["rows"] == len(tpch_oracle.q1("tiny"))
    assert record["compiles"] == 1


def test_spmd_phase_matches_reference_on_tiny(counter):
    """``--chips 4`` on four of the CPU mesh's devices: both plans equal
    local and reference, inputs sharded over four distinct devices."""
    records = chip_smoke.run_spmd("tiny", 4, lambda record: None, counter)
    assert [r["plan"] for r in records] == ["default", "hash-partitioned"]
    assert records[0]["exchanges"] == 0 and records[1]["exchanges"] > 0
    assert all(r["shard_devices"] == 4 for r in records)


def test_a_cpu_run_is_never_ok(monkeypatch, capsys):
    """Phases stubbed out (the tests above run them): whatever they do, a
    run that is not on a TPU exits non-zero and prints no result line."""
    monkeypatch.setattr(chip_smoke, "run_served", lambda *a: [])
    monkeypatch.setattr(chip_smoke, "run_compiled", lambda *a: {})
    monkeypatch.setattr(chip_smoke, "configure_compile_cache", lambda: "-")
    assert chip_smoke.main(["--schema", "tiny"]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert '"ok"' not in captured.err


def test_a_tpu_run_ends_with_the_contract_line(monkeypatch, capsys):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "device_info", lambda: device)
    monkeypatch.setattr(chip_smoke, "run_served", lambda *a: [])
    monkeypatch.setattr(chip_smoke, "run_compiled", lambda *a: {})
    monkeypatch.setattr(chip_smoke, "configure_compile_cache", lambda: "-")
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": device}


_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_compilation_cache_max_size",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restored_cache_dir():
    """Run with no cache directory configured; put back what was there."""
    before = {name: getattr(jax.config, name) for name in _CACHE_OPTIONS}
    jax.config.update(_CACHE_OPTIONS[0], None)
    yield
    for name, value in before.items():
        jax.config.update(name, value)


def test_cache_rule_environment_wins(monkeypatch, tmp_path,
                                     restored_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = {name: getattr(jax.config, name) for name in _CACHE_OPTIONS}
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    # the environment owns the cache: no place, size or threshold was set
    assert {name: getattr(jax.config, name)
            for name in _CACHE_OPTIONS} == before
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_rule_checkout_dir_otherwise(monkeypatch, restored_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, ".jax_cache")
    assert compile_cache.configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # every executable is worth keeping (the eager tier's are sub-second),
    # up to a bound
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_compilation_cache_max_size == \
        compile_cache.MAX_CACHE_BYTES
