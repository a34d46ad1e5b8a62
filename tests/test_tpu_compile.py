"""Compile the main-path kernels for a described TPU v5e, without a chip.

The TPU compiler is installed even where no chip is attached: it
compiles for a topology that is only described, and refuses what Mosaic
cannot lower or what does not fit VMEM.  Interpret mode accepts both, so
these compiles are the only tier-1 guard on the native kernels.  Every
compile runs at the widths ``chip_smoke.py`` uses (T=4096, s=64) on one
described device, and the 4-chip benchmark cell's sharded sort on all
four.  This is the only test file that describes a topology:
the description loads the TPU library, which one process at a time may
hold, so it happens inside a fixture and never at import.
"""

from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import bucket_sort, distributed_sort
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
    n = 1 << 15
    plan = build_plan(n, jnp.int32, NATIVE)
    assert plan.impl == "pallas" and plan.interpret is False
    assert plan.root.kind == "bucket"
    x = jax.ShapeDtypeStruct((n,), I32, sharding=one_chip)
    compiled = _compile(lambda a: bucket_sort.sort_planned(a, plan), x)
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_sort_planned_gathers_carry_their_step_scope(one_chip):
    """The whole-array gathers, and the fusions the compiler builds
    round them, keep the executor's step scope in their op_name, so a
    trace joined with the compiled text names relocation and
    compaction."""
    n = 1 << 15
    plan = build_plan(n, jnp.int32, NATIVE)
    x = jax.ShapeDtypeStruct((n,), I32, sharding=one_chip)
    text = _compile(lambda a: bucket_sort.sort_planned(a, plan), x).as_text()
    steps = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = [a-z0-9]+\[([\d,]*)\]\S* "
                     r"(gather|fusion)\(.*op_name=\"([^\"]*)\"", line)
        if m is None or not m.group(3).endswith("/gather"):
            continue
        if math.prod(int(d) for d in m.group(1).split(",") if d) < n:
            continue
        assert re.search(r"/sort\.level\d+/", m.group(3)), m.group(3)
        step = re.findall(r"sort\.(relocate|compact)/", m.group(3))
        assert step, m.group(3)
        steps.append((m.group(2), step[-1]))
    assert {s for _, s in steps} == {"relocate", "compact"}
    assert {k for k, _ in steps} == {"gather", "fusion"}


def _bucket_nodes(node):
    if node is None or node.kind != "bucket":
        return []
    return ([node] + _bucket_nodes(node.sample_plan)
            + _bucket_nodes(node.bucket_plan))


@pytest.fixture(scope="module")
def default_8m(one_chip):
    """The default 2^23 program, as a benchmark call runs it: its plan
    and its compiled text."""
    n = 1 << 23
    plan = build_plan(n, jnp.int32, NATIVE)
    word = jax.ShapeDtypeStruct((1, n), U32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((1, n), I32, sharding=one_chip)
    text = bucket_sort._sort_canonical_packed.lower(
        (word,), vals, plan=plan, pad_base0=n).compile().as_text()
    return plan, text


def _gathers(text, step):
    """(output elements, slice sizes, data operand's shape and layout)
    of every gather under the step's scope.  A gather inside a fusion
    reads a parameter of the fused computation."""
    params, found = {}, []
    for line in text.splitlines():
        m = re.match(r"\s*%(\S+) = (\S+) parameter\(", line)
        if m:
            params[m.group(1)] = m.group(2)
        m = re.match(r"\s*(?:ROOT )?%\S+ = [a-z0-9]+\[([\d,]*)\]\S* "
                     r"gather\(%([\w.\-]+), .*slice_sizes=\{([\d,]*)\}"
                     r".*op_name=\"([^\"]*)\"", line)
        if m and f"sort.{step}/" in m.group(4):
            size = math.prod(int(d) for d in m.group(1).split(",") if d)
            found.append((size, m.group(3), params.get(m.group(2), "")))
    return found


def test_default_compaction_gathers_whole_blocks(default_8m):
    """Compaction moves its 2^24, 2^23 and 2^17 outputs as 128-lane
    rows; every other gather under its scope (the boundary blocks'
    pieces) has at most rows * (s_round - 1) * 128 outputs."""
    plan, text = default_8m
    nodes = _bucket_nodes(plan.root)
    assert len(nodes) == 3
    assert all(node.compact_block == 128 for node in nodes)
    repair_max = max(node.rows * (node.s_round - 1) * 128 for node in nodes)
    gathers = _gathers(text, "compact")
    rows = [size for size, sl, _ in gathers if sl.split(",")[-1] == "128"]
    for node in nodes:  # the key word and the payload
        assert rows.count(node.rows * node.lp) == 2, (node.lp, rows)
    outputs = {node.rows * node.lp for node in nodes}
    rest = [size for size, _, _ in gathers if size not in outputs]
    assert rest and max(rest) <= repair_max < 1 << 23


def test_default_relocation_gathers_read_vmem(default_8m):
    """The relocation gathers read their sources from VMEM (``S(1)``):
    from HBM they ran 1.65x slower on a v5e, which a change to the rest
    of the program can cause by moving the compiler's VMEM placement.

    The placement is the installed TPU compiler's choice, so a failure
    here says that the sort got slower, not that it is wrong: measure
    relocation on the chip (``tools/step_times.py``) before accepting
    the change, or a new compiler version, that moved it."""
    _, text = default_8m
    big = [(size, src) for size, _, src in _gathers(text, "relocate")
           if size >= 1 << 23]
    assert len(big) == 4, big  # two levels, key word and payload
    assert all("S(1)" in src for _, src in big), (
        "a relocation gather reads its source from HBM in this compile: "
        "not a wrong result, but likely ~1.65x slower relocation on a "
        "v5e; measure sort.relocate on the chip with tools/step_times.py",
        big)


@pytest.fixture(scope="module")
def mesh_25m(topo, one_chip):
    """The 4-chip benchmark cell's program (``bench/configs/
    sharded_argsort_i32_4chip.json``): ``make_sharded_sort`` at 2^25
    int32 keys over the four described chips, its plan and its compiled
    program (``one_chip`` turns the compile cache off)."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    n = 1 << 25
    _, plan = distributed_sort.make_sharded_sort(mesh, "data", n, NATIVE)
    x = jax.ShapeDtypeStruct((n,), I32,
                             sharding=NamedSharding(mesh, P("data")))
    compiled = distributed_sort._sharded_argsort.lower(x, mesh, plan).compile()
    return plan, compiled


def test_mesh_phase_plans_are_native(mesh_25m):
    """The phases run native kernels, and the bucket phase ends, below
    its two bucket levels, in the default config's widest direct sort
    (``SortConfig.direct_max`` = 16384 lanes) on its 9344-long level-1
    buckets: this compile is the tier-1 guard on that kernel."""
    plan, compiled = mesh_25m
    phases = [plan.run_plan, plan.dealt_plan, plan.sample_plan,
              plan.bucket_plan]
    assert all(p.impl == "pallas" and p.interpret is False for p in phases)
    assert [p.num_levels for p in phases] == [2, 2, 0, 2]
    deepest = plan.bucket_plan.root.bucket_plan.bucket_plan
    assert deepest.kind == "direct" and deepest.lp == 16384
    widest = [line for line in compiled.as_text().splitlines()
              if "tpu_custom_call" in line
              and re.search(r"u32\[\d+,16384\]", line)]
    assert widest, "no 16384-lane tile sort in the mesh program"


def test_mesh_relocation_gathers_read_vmem(mesh_25m):
    """As ``test_default_relocation_gathers_read_vmem``, on the 4-chip
    cell's program: the run, dealt and bucket phases' two bucket levels
    each relocate a key word and a payload into 2^23 slots or more, and
    each of those 12 gathers reads its source from VMEM (``S(1)``).
    The bucket phase's third level, gone since its level-1 buckets
    sort directly, read its source from HBM at ~22 ns an element."""
    _, compiled = mesh_25m
    big = [(size, src)
           for size, _, src in _gathers(compiled.as_text(), "relocate")
           if size >= 1 << 23]
    assert len(big) == 12, big
    assert all("S(1)" in src for _, src in big), (
        "a relocation gather reads its source from HBM in this compile: "
        "not a wrong result, but likely ~1.65x slower relocation on a "
        "v5e; measure sort.relocate on the chip with tools/step_times.py",
        big)


def test_mesh_collectives(mesh_25m):
    """Five all_to_alls (the deal's key word and payload, the bucket
    exchange's two arrays and its counts) and one collective per array
    for the sample gather, which the compiler may emit as an
    all-reduce."""
    _, compiled = mesh_25m
    ops = re.findall(r"= \S+ (all-to-all|all-gather|all-reduce|"
                     r"collective-permute|reduce-scatter)(?:-start)?\(",
                     compiled.as_text())
    assert ops.count("all-to-all") == 5, ops
    assert len(ops) == 7, ops


def test_mesh_fits_each_chip(mesh_25m):
    _, compiled = mesh_25m
    m = compiled.memory_analysis()
    per_chip = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert per_chip < 15.75 * 2**30, per_chip
