"""tools/step_times.py: the join of trace ops with the executor's
compiled text, on synthetic events and a CPU-compiled program."""

from __future__ import annotations

import importlib.util
import pathlib

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
