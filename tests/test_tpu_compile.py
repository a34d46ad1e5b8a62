"""Compile the main-path kernels for a described TPU v5e, without a chip.

The TPU compiler is installed even where no chip is attached: it
compiles for a topology that is only described, and refuses what Mosaic
cannot lower or what does not fit VMEM.  Interpret mode accepts both, so
these compiles are the only tier-1 guard on the native kernels.  Every
compile runs at the widths ``chip_smoke.py`` uses (T=4096, s=64) on one
described device.  This is the only test file that describes a topology:
the description loads the TPU library, which one process at a time may
hold, so it happens inside a fixture and never at import.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import autotune, bucket_sort
from repro.core.plan import build_plan
from repro.core.sort_config import SortConfig
from repro.kernels import bitonic, splitter, topk

T, S = 4096, 64
NATIVE = SortConfig(impl="pallas", interpret=False)
U32, I32 = jnp.uint32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described device, with the persistent compile cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("num_words", [1, 2])
def test_tile_sort_compiles(one_chip, num_words):
    words = [jax.ShapeDtypeStruct((64, T), U32, sharding=one_chip)
             for _ in range(num_words)]
    vals = jax.ShapeDtypeStruct((64, T), I32, sharding=one_chip)
    _compile(lambda w, v: bitonic.sort_tiles_kv(
        tuple(w), v, interpret=False), words, vals)


def test_tile_sort_with_samples_compiles(one_chip):
    k = jax.ShapeDtypeStruct((64, T), U32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((64, T), I32, sharding=one_chip)
    _compile(lambda k, v: bitonic.sort_tiles_sample_kv(
        k, v, num_samples=S, interpret=False), k, v)


def test_splitter_partition_compiles(one_chip):
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in [((64, T), U32), ((64, T), I32),
                              ((64, S - 1), U32), ((64, S - 1), I32)]]
    _compile(lambda k, v, sk, sv: splitter.splitter_partition(
        k, v, sk, sv, interpret=False), *args)


def test_splitter_ranks_wide_row_compiles(one_chip):
    """A whole shard as one row (the distributed sort's ranks) folds
    into VMEM-sized rows."""
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in [((1, 1 << 20), U32), ((1, 1 << 20), I32),
                              ((1, 3), U32), ((1, 3), I32)]]
    _compile(lambda k, v, sk, sv: splitter.splitter_ranks(
        k, v, sk, sv, interpret=False), *args)


def test_topk_compiles(one_chip):
    k = jax.ShapeDtypeStruct((256, 2048), U32, sharding=one_chip)
    _compile(lambda k: topk.topk_desc(k, k=S, interpret=False), k)


def test_sort_planned_compiles(one_chip):
    """One whole program: a bucket round with fused sampling and
    partition, a direct sample sort, and the bucket sort."""
    n = 1 << 14
    plan = build_plan(n, jnp.int32, NATIVE)
    assert plan.impl == "pallas" and plan.interpret is False
    assert plan.root.kind == "bucket"
    x = jax.ShapeDtypeStruct((n,), I32, sharding=one_chip)
    compiled = _compile(lambda a: bucket_sort.sort_planned(a, plan), x)
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("strategy", ["radix", "merge"])
def test_strategy_without_native_kernel_raises(strategy):
    """radix and merge gather inside the kernel, which Mosaic cannot
    lower: a native plan names the strategy at build time."""
    cfg = SortConfig(impl="pallas", interpret=False, strategy=strategy)
    with pytest.raises(ValueError, match=strategy):
        build_plan(1 << 14, jnp.int32, cfg)
    labels = [c.label for c in autotune.candidate_space(NATIVE, 1 << 20)]
    assert not any(strategy in label for label in labels), labels
