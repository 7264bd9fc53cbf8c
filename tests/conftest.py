"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's single-process multi-node testing strategy
(DistributedQueryRunner boots N servers in one JVM — SURVEY.md §4): we boot an
8-device CPU topology in one process via XLA host-platform device count, so
all sharding/collective paths compile and execute without TPU hardware.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# tests run on the CPU
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def compacting_plans(monkeypatch):
    """tiny's pages are under the optimizer's size gate for a CompactNode
    (2^17 slots); lower it so q3 plans the three SF 1 and SF 10 plan."""
    from trino_tpu.sql.planner import optimizer

    monkeypatch.setattr(optimizer, "COMPACT_MIN_SLOTS", 1 << 10)
