# The paper's primary contribution: deterministic sample sort (GPU BUCKET
# SORT, Dehne & Zaboli 2010) adapted to TPU — single-device Algorithm 1,
# the batched/segmented layer (many independent sorts per launch), the
# multi-chip/pod distributed variant, partial (top-k) sort, and the
# baselines the paper compares against.

# NOTE: the tuning entries themselves stay namespaced
# (repro.core.autotune.autotune, repro.core.cost_model.estimate) —
# binding the function name here would shadow the submodule (or read
# like it does).
from repro.core.autotune import (
    AutotuneResult,
    CandidateScore,
    load_plan,
    load_shard_plan,
    plan_for,
    save_plan,
    save_shard_plan,
    shard_plan_for,
)
from repro.core.cost_model import COST_MODEL_VERSION, CostBreakdown
from repro.core.bucket_sort import (
    argsort,
    argsort_batched,
    resolve_plan,
    segment_argsort,
    segment_sort,
    sort,
    sort_batched,
    sort_batched_with_stats,
    sort_kv,
    sort_kv_batched,
    sort_planned,
    sort_with_stats,
)
from repro.core.distributed_sort import DistSortSpec, make_sharded_sort, sorted_shard
from repro.core.faults import FaultInjected
from repro.core.guard import (
    CHECK_MODES,
    DegradationEvent,
    DegradationWarning,
    SortRuntimeError,
    clear_degradation_log,
    degradation_log,
)
from repro.core.key_codec import SUPPORTED_DTYPES, KeyCodec, codec_for
from repro.core.partial_sort import topk, topk_batched
from repro.core.plan import (
    LevelPlan,
    ShardPlan,
    SortPlan,
    TopkPlan,
    build_plan,
    build_shard_plan,
    build_topk_plan,
    build_words_plan,
    plan_from_dict,
    plan_to_dict,
    shard_geometry,
    shard_plan_from_dict,
    shard_plan_to_dict,
)
from repro.core.sort_config import DEFAULT_CONFIG, PAPER_CONFIG, SortConfig

__all__ = [
    "argsort",
    "argsort_batched",
    "segment_argsort",
    "segment_sort",
    "sort",
    "sort_batched",
    "sort_batched_with_stats",
    "sort_kv",
    "sort_kv_batched",
    "sort_planned",
    "sort_with_stats",
    "topk",
    "topk_batched",
    "KeyCodec",
    "codec_for",
    "SUPPORTED_DTYPES",
    "SortConfig",
    "DEFAULT_CONFIG",
    "PAPER_CONFIG",
    "SortPlan",
    "LevelPlan",
    "TopkPlan",
    "build_plan",
    "build_topk_plan",
    "build_words_plan",
    "plan_from_dict",
    "plan_to_dict",
    "resolve_plan",
    "AutotuneResult",
    "CandidateScore",
    "CostBreakdown",
    "COST_MODEL_VERSION",
    "plan_for",
    "load_plan",
    "save_plan",
    "ShardPlan",
    "build_shard_plan",
    "shard_geometry",
    "shard_plan_from_dict",
    "shard_plan_to_dict",
    "shard_plan_for",
    "load_shard_plan",
    "save_shard_plan",
    "DistSortSpec",
    "make_sharded_sort",
    "sorted_shard",
    "CHECK_MODES",
    "DegradationEvent",
    "DegradationWarning",
    "FaultInjected",
    "SortRuntimeError",
    "clear_degradation_log",
    "degradation_log",
]
