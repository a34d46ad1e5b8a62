"""The counter-read metric: moved elements per key, from the program's
own counters (CPU only)."""

import collections
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness.cell import load_module
from repro.core import bucket_sort, telemetry
from repro.core.plan import build_plan
from repro.core.sort_config import SortConfig

CFG = SortConfig(impl="xla", tile=256, s=16, direct_max=256)


@pytest.fixture
def fresh_counts(monkeypatch):
    monkeypatch.setattr(telemetry, "_COUNTS", collections.Counter())


def _read():
    return load_module("metrics", "moved_elements_per_key").read(None)


@pytest.mark.parametrize("n", [4096, 3000])
def test_reads_the_plans_moved_elements_per_key(fresh_counts, n):
    x = jnp.asarray(np.random.default_rng(n).integers(0, 99, n), jnp.int32)
    for _ in range(2):
        jax.block_until_ready(bucket_sort.argsort(x, CFG))
    plan = build_plan(n, jnp.int32, CFG)
    assert _read() == plan.moved_elements / n


def test_no_call_no_reading(fresh_counts):
    assert _read() is None


def test_program_without_counters_no_reading(monkeypatch):
    import repro.core

    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert _read() is None
