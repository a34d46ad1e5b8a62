"""tools/step_times.py: the join of trace ops with the executor's
compiled text, on synthetic events and a CPU-compiled program."""

from __future__ import annotations

import importlib.util
import pathlib
import types

import jax
import jax.numpy as jnp
import pytest

from repro.core import bucket_sort
from repro.core.sort_config import SortConfig

_PATH = pathlib.Path(__file__).resolve().parent.parent / "tools" / "step_times.py"
_SPEC = importlib.util.spec_from_file_location("step_times", _PATH)
step_times = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(step_times)

HLO = """\
ENTRY %main {
  %fusion.3 = s32[64,512]{1,0} fusion(%p), kind=kLoop, calls=%c, metadata={op_name="jit(_sort_canonical_packed)/sort.level0/sort.relocate/gather" source_file="x.py"}
  %fusion.4 = s32[64,512]{1,0} fusion(%fusion.3), metadata={op_name="jit(_sort_canonical_packed)/sort.level0/sort.level1/sort.compact/gather"}
  copy.5 = s32[64,512]{1,0} copy(%fusion.4), metadata={op_name="jit(_sort_canonical_packed)/sort.level0/sort.relocate"}
  %iota.1 = s32[512]{0} iota(), iota_dimension=0, metadata={op_name="jit(_sort_canonical_packed)/iota"}
  %copy.8 = s32[64,512]{1,0} copy(%fusion.4)
  ROOT %tuple.9 = (s32[64,512]) tuple(%fusion.4)
}
"""


def test_op_names_reads_each_instructions_metadata():
    names = step_times.op_names(HLO)
    assert set(names) == {"fusion.3", "fusion.4", "copy.5", "iota.1",
                          "copy.8", "tuple.9"}
    assert names["copy.5"].endswith("sort.level0/sort.relocate")
    assert names["copy.8"] == names["tuple.9"] == ""


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/sort.level0/sort.relocate/gather", "sort.level0/sort.relocate"),
    ("jit(f)/sort.level0/sort.level1/sort.compact/gather",
     "sort.level1/sort.compact"),
    ("jit(f)/sort.level2/sort.local_sort", "sort.level2/sort.local_sort"),
    ("jit(f)/sort.level0/iota", "unscoped"),
    ("jit(f)/sort.relocated_elsewhere/gather", "unscoped"),
    ("gather", "unscoped"),
    ("jit(_sharded_argsort)/shard_map/sort.phase_bucket/sort.level2/"
     "sort.relocate/gather", "sort.phase_bucket/sort.level2/sort.relocate"),
    ("jit(_sharded_argsort)/shard_map/sort.phase_sample/gather",
     "sort.phase_sample/unscoped"),
    ("jit(_sharded_argsort)/shard_map/sort.deal/all_to_all", "sort.deal"),
    ("jit(_sharded_argsort)/shard_map/sort.sample_exchange/all_gather",
     "sort.sample_exchange"),
    ("jit(_sharded_argsort)/shard_map/sort.partition/pallas_call",
     "sort.partition"),
    ("jit(_sharded_argsort)/shard_map/sort.pack/scatter", "sort.pack"),
    ("jit(_sharded_argsort)/shard_map/sort.exchange/all_to_all",
     "sort.exchange"),
])
def test_scope_of_takes_the_innermost_level_and_step(op_name, scope):
    assert step_times.scope_of(op_name) == scope


def test_step_times_joins_ops_inside_the_executor_module():
    names = step_times.op_names(HLO)
    modules = [("jit__sort_canonical_packed(12)", 1000, 1000),
               ("jit_iota(3)", 2500, 100),
               ("jit__sort_canonical_packed(12)", 3000, 1000)]
    ops = [
        ("%fusion.3 = s32[64,512]{1,0} fusion(%p)", 1000, 4_000_000),
        ("%fusion.4 = s32[64,512]{1,0} fusion(%fusion.3)", 1500, 2_000_000),
        ("%iota.1 = s32[512]{0} iota()", 1900, 1_000_000),
        ("%iota.1 = s32[512]{0} iota()", 2500, 9_000_000),  # eager module
        ("%fusion.3 = s32[64,512]{1,0} fusion(%p)", 3000, 4_000_000),
        ("%add.7 = s32[] add(%a, %b)", 3100, 2_000_000),
        ("%copy.8 = s32[64,512]{1,0} copy(%fusion.4)", 3200, 1_000_000),
    ]
    got = step_times.step_times(ops, modules, names, calls=2)
    assert got == {"sort.level0/sort.relocate": 4.0,
                   "sort.level1/sort.compact": 1.0,
                   "unmatched": 1.0, "unscoped": 1.0}


MESH_HLO = """\
ENTRY %main {
  %all_to_all.29 = u32[4,1,8]{2,1,0} all-to-all(%copy.1), channel_id=1, metadata={op_name="jit(_sharded_argsort)/shard_map/sort.deal/all_to_all"}
  %all-reduce.236 = u32[128]{0} all-reduce(%dynamic-update-slice), channel_id=2
  %fusion.7 = s32[1,64]{1,0} fusion(%p), metadata={op_name="jit(_sharded_argsort)/shard_map/sort.phase_bucket/sort.level0/sort.relocate/gather"}
}
"""


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=line, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in events])
        for line, events in lines.items()])


def test_mesh_program_is_read_on_every_chip():
    """Each chip's plane gives its own split; a collective whose
    op_name the compiler dropped is named by its opcode."""
    names = step_times.op_names(MESH_HLO)
    module = [(step_times.MESH_MODULE + "(3)", 0, 10_000)]
    ops = [("%all_to_all.29 = u32[4,1,8]{2,1,0} all-to-all(%copy.1)",
            100, 2_000_000),
           ("%all-reduce.236 = u32[128]{0} all-reduce(%dynamic-update-slice)",
            200, 1_000_000),
           ("%fusion.7 = s32[1,64]{1,0} fusion(%p)", 300, 4_000_000)]
    profile = types.SimpleNamespace(planes=[
        _plane("/device:TPU:0", {"XLA Modules": module, "XLA Ops": ops}),
        _plane("/device:TPU:1", {"XLA Modules": module,
                                 "XLA Ops": ops[2:]}),
        _plane("/host:CPU", {"main": [("sort.argsort", 0, 3_000_000),
                                      ("sort.launch", 10, 1_000_000)]}),
    ])
    got = step_times.read_profile(profile, names, calls=1, chips=2,
                                  module=step_times.MESH_MODULE)
    relocate = "sort.phase_bucket/sort.level0/sort.relocate"
    assert got["by_step"] == {
        "/device:TPU:0": {"collective:all-reduce": 1.0, "sort.deal": 2.0,
                          relocate: 4.0},
        "/device:TPU:1": {relocate: 4.0}}
    assert got["host_ms"] == {"sort.argsort": 3.0, "sort.launch": 1.0}
    assert got["modules"] == 1


def test_a_compiled_program_puts_its_gathers_under_a_step():
    n = 4096
    cfg = SortConfig(impl="xla", tile=256, s=16, direct_max=256)
    plan = bucket_sort.resolve_plan(n, jnp.int32, cfg)
    text = bucket_sort._sort_canonical_packed.lower(
        (jax.ShapeDtypeStruct((1, n), jnp.uint32),),
        jax.ShapeDtypeStruct((1, n), jnp.int32),
        plan=plan, pad_base0=n).compile().as_text()
    scopes = set(step_times.op_names(text).values())
    steps = {step_times.scope_of(s) for s in scopes}
    assert {"sort.level0/sort.relocate", "sort.level0/sort.compact",
            "sort.level1/sort.relocate", "sort.level1/sort.compact"} <= steps
