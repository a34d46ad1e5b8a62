"""Distributed deterministic sample sort across a TPU mesh (shard_map).

The paper is single-GPU; this module scales Algorithm 1 to chips/pods.
It is the cluster-level analogue of the paper's bucket phase, with one
extra "deal" round that restores the *guaranteed-capacity* property at
per-device-pair granularity — the property that makes the exchange a
single STATIC ``lax.all_to_all`` (XLA requires static shapes; a
randomized splitter choice admits no such bound — DESIGN.md §2, §9).

Per-shard pipeline (axis size D, local length n_loc, oversample c):

  1. local sort            (Algorithm 1 on the shard)
  2. DEAL: element p of the local sorted run goes to device (p mod D)
     via a static all_to_all transpose.  Afterwards every device holds a
     stride-D regular sample of *every* device's sorted data.
  3. local sort of the dealt data
  4. sampling: s_loc = c*D equidistant local samples, all_gather,
     replicated sort, D-1 equidistant global splitters  (steps 3-5)
  5. splitter ranks -> per-target chunk sizes            (steps 6-7)
  6. one static all_to_all of (D, C_pair) buckets        (step 8)
  7. local sort of received buckets                      (step 9)

Capacity guarantee: global bucket t holds B_t <= n_loc * (1 + 1/c)
elements (regular sampling, unique (key, payload) pairs).  The deal
makes every device hold (b_it/D ± 1) of source i's bucket-t elements, so

    chunk(j -> t) <= B_t/D + D  <=  n_loc*(1+1/c)/D + D  =: C_pair  (static!)

Overflow is therefore impossible; tests assert max fill <= C_pair.
The result is returned padded-ragged: (out_cap,) keys/payloads per
shard plus a valid-count — the natural output of a sample sort (global
order = concatenation of valid prefixes in device order).

PLAN-AWARE (DESIGN.md §9): the ENTIRE distributed schedule — mesh axis
and D, n_pad, oversample, deal geometry, the c_pair/out_cap
capacities, and the four per-phase local-sort :class:`SortPlan`s — is
a frozen :class:`repro.core.plan.ShardPlan` computed once by
``build_shard_plan`` (or tuned by ``autotune.shard_plan_for``).
:func:`sorted_shard` is a pure executor that derives nothing, and the
jit'd entry takes ``(mesh, plan)`` as STATIC arguments: equal
``(shape, mesh, dtype, plan)`` signatures share one compiled
executable (``trace_count`` exposes the counter; tests assert
trace-once / zero-retrace discipline exactly as the single-device path
does).  The per-phase plans inherit the strategy dispatch (DESIGN.md
§8), so shards can radix- or merge-sort their local runs.

NAMES IN A TRACE: the four local sorts run under ``sort.phase_run``,
``sort.phase_dealt``, ``sort.phase_sample`` and ``sort.phase_bucket``,
each holding the executor's own ``sort.level<d>`` and step scopes; the
deal ``all_to_all``s run under ``sort.deal``, the sample
``all_gather``s under ``sort.sample_exchange``, the splitter ranks and
chunk geometry under ``sort.partition``, the scatter into the
``(D, C_pair)`` buffer under ``sort.pack`` and the bucket
``all_to_all``s under ``sort.exchange``.  On the host,
``make_sharded_sort``'s function opens ``sort.sharded_argsort`` and one
``sort.launch`` per attempt, and counts ``sort.keys`` (n_global) and
``sort.exchange_slots`` (``d * d * c_pair``: the slots per array that
the bucket exchange carries, padding included) and
``sort.mesh_moved_elements`` (``d`` times the elements per array that
relocation and compaction write on one device, over the four phase
plans) once per call.

Keys dispatch on the ``core/key_codec`` codecs like the single-device
pipeline: ``make_sharded_sort`` accepts any codec dtype (64-bit keys
travel as two uint32 words per element through every collective; x64
mode required) and honors ``cfg.descending``.  ``sorted_shard`` itself
operates on canonical words — a bare uint32 array or a tuple of word
arrays, returned in the same structure.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import faults, guard, telemetry
from repro.core.bucket_sort import _run_node
from repro.core.key_codec import codec_for
from repro.core.plan import ShardPlan, SortPlan, build_shard_plan, shard_geometry
from repro.core.sort_config import DEFAULT_CONFIG, SortConfig
from repro.kernels import ops
from repro.kernels.bitonic import as_words, like_words

_MAXU = jnp.uint32(0xFFFFFFFF)


def trace_count() -> int:
    """Number of times the distributed entry has been TRACED in this
    process (the ``sort.sharded_traces`` counter) — the distributed
    analogue of ``bucket_sort.trace_count``; tests assert same-(mesh,
    n, dtype, plan) => one trace and plan-cache hit => zero retraces
    with it."""
    return telemetry.counts().get("sort.sharded_traces", 0)


@dataclasses.dataclass(frozen=True)
class DistSortSpec:
    """Static geometry of a distributed sort (all trace-time ints).

    Retained as the minimal arithmetic view of the schedule (the
    hypothesis property tests exercise it directly); every derived
    quantity delegates to :func:`repro.core.plan.shard_geometry`, the
    single source of truth the :class:`~repro.core.plan.ShardPlan`
    builder also reads.

    Attributes:
        axis: mesh axis name (or tuple of names) the sort spans.
        d: devices along the sort axis.
        n_local: local shard length (pre-padding).
        oversample: regular-sampling oversample factor c (bound above).
        pair_align: lane alignment of the per-pair exchange capacity.
    """

    axis: str | tuple[str, ...]
    d: int  # devices along the sort axis
    n_local: int  # local shard length (pre-padding)
    oversample: int = 8
    pair_align: int = 8

    @property
    def axis_tuple(self):
        return (self.axis,) if isinstance(self.axis, str) else tuple(self.axis)

    @property
    def _geometry(self):
        return shard_geometry(
            self.n_local, self.d, self.oversample, self.pair_align
        )

    @property
    def s_loc(self) -> int:
        return self._geometry.s_loc

    @property
    def n_pad(self) -> int:
        # Padded so the deal (multiple of d) and the equidistant sampling
        # (multiple of s_loc = oversample*d) are both exact — exact spacing
        # is what the capacity-bound proof relies on.
        return self._geometry.n_pad

    @property
    def b_t(self) -> int:
        """Max global bucket size: B_t <= n_pad * (1 + 1/oversample)."""
        return self._geometry.b_t

    @property
    def c_pair(self) -> int:
        """Static per-pair all_to_all capacity: B_t/D + D (deal bound)."""
        return self._geometry.c_pair

    @property
    def out_cap(self) -> int:
        """Static per-shard output capacity >= any bucket total B_t."""
        return self._geometry.out_cap


def _local_sort(kw, v, sub: SortPlan, pad_base):
    """Pure plan-driven local sort: hand one per-phase ``SortPlan`` off
    the :class:`ShardPlan` to the plan executor — nothing is derived
    here (shapes must match the sub-plan exactly; ``_run_node``
    asserts it)."""
    skw, sv, _ = _run_node(
        tuple(w[None, :] for w in kw), v[None, :], sub.root, sub.impl,
        sub.interpret, pad_base, None,
    )
    return tuple(w[0] for w in skw), sv[0]


def _deal_all_to_all(x, ax, d, n_pad):
    """Deal: position p -> device p mod D (static transpose all_to_all)."""
    x = jnp.swapaxes(x.reshape(n_pad // d, d), 0, 1)  # (D, n_pad/D) strided
    return jax.lax.all_to_all(x, ax, split_axis=0, concat_axis=0, tiled=False)


def sorted_shard(keys_local, vals_local: jax.Array, plan: ShardPlan):
    """Distributed sort body — call INSIDE shard_map over ``plan.axis``.

    A pure EXECUTOR of the :class:`~repro.core.plan.ShardPlan`: every
    static quantity (D, n_pad, s_loc, c_pair, out_cap, the four
    per-phase local-sort schedules, impl/interpret) is read off the
    plan; nothing is recomputed here (DESIGN.md §9).

    Args:
        keys_local: (n_local,) canonical uint32 key words — bare array
            or tuple of word arrays (msw first, see ``core/key_codec``)
            with ``plan.num_words`` words.
        vals_local: (n_local,) int32 payloads, globally unique (use
            global indices).
        plan: the static distributed schedule
            (:func:`repro.core.plan.build_shard_plan`).
    Returns:
        (keys (out_cap,) in the input structure, vals (out_cap,),
        count (), max_within ()) — valid prefix of each shard; shards
        concatenated in device order form the globally sorted sequence.
    """
    kw = as_words(keys_local)
    ax = plan.axis if len(plan.axis) > 1 else plan.axis[0]
    d, n_pad, s_loc, c_pair = plan.d, plan.n_pad, plan.s_loc, plan.c_pair
    n_glob = plan.n_glob
    pad_base = n_glob  # payloads are global indices < n_glob

    me = jax.lax.axis_index(ax)
    # Pad shard to a multiple of D with unique (all-ones, >= n_glob) pads.
    n0 = kw[0].shape[0]
    pad_n = n_pad - n0
    if pad_n:
        pk = jnp.full((pad_n,), _MAXU, jnp.uint32)
        pv = n_glob + me * pad_n + jnp.arange(pad_n, dtype=jnp.int32)
        kw = tuple(jnp.concatenate([w, pk]) for w in kw)
        vals_local = jnp.concatenate([vals_local, pv])
    v = vals_local
    pad_base += d * n_pad

    # 1. local sort
    with jax.named_scope("sort.phase_run"):
        kw, v = _local_sort(kw, v, plan.run_plan, pad_base)
    pad_base += 4 * n_glob  # disjoint pad range headroom per phase

    # 2. deal: one static all_to_all transpose per word + payload
    with jax.named_scope("sort.deal"):
        kw = tuple(
            _deal_all_to_all(w, ax, d, n_pad).reshape(n_pad) for w in kw
        )
        v = _deal_all_to_all(v, ax, d, n_pad).reshape(n_pad)

    # 3. local sort of dealt data
    with jax.named_scope("sort.phase_dealt"):
        kw, v = _local_sort(kw, v, plan.dealt_plan, pad_base)
    pad_base += 4 * n_glob

    # 4. sampling -> replicated splitters (steps 3-5 of Algorithm 1)
    samp_idx = (jnp.arange(1, s_loc + 1, dtype=jnp.int32) * (n_pad // s_loc)) - 1
    with jax.named_scope("sort.sample_exchange"):
        skw_all = tuple(
            jax.lax.all_gather(w[samp_idx], ax).reshape(d * s_loc)
            for w in kw
        )
        sv_all = jax.lax.all_gather(v[samp_idx], ax).reshape(d * s_loc)
    with jax.named_scope("sort.phase_sample"):
        sskw, ssv = _local_sort(skw_all, sv_all, plan.sample_plan, pad_base)
        sp_idx = (jnp.arange(1, d, dtype=jnp.int32) * (d * s_loc)) // d
        spkw = tuple(w[sp_idx] for w in sskw)  # (D-1,) same on every device
        spv = ssv[sp_idx]
    pad_base += 4 * d * s_loc

    # 5. splitter ranks -> chunk geometry (steps 6-7)
    with jax.named_scope("sort.partition"):
        ranks = ops.splitter_ranks(
            tuple(w[None, :] for w in kw), v[None, :],
            tuple(w[None, :] for w in spkw), spv[None, :],
            impl=plan.impl, interpret=plan.interpret,
        )[0]  # (D-1,) in [0, n_pad]
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ranks])
        ends = jnp.concatenate([ranks, jnp.full((1,), n_pad, jnp.int32)])
        counts = ends - starts  # (D,) elements per target device

    # 6. scatter into the padded (D, C_pair) buffer, one static all_to_all
    with jax.named_scope("sort.pack"):
        pos = jnp.arange(n_pad, dtype=jnp.int32)
        ind = jnp.zeros((n_pad + 1,), jnp.int32).at[ranks].add(1)
        chunk_id = jnp.cumsum(ind, dtype=jnp.int32)[:n_pad]
        within = pos - jnp.take(starts, chunk_id)
        max_within = jnp.max(within)  # bound check: < C_pair (tested)
        dest = chunk_id * c_pair + within
        dest = jnp.where(within < c_pair, dest, d * c_pair)
        bkw = tuple(
            jnp.full((d * c_pair,), _MAXU, jnp.uint32)
            .at[dest].set(w, mode="drop")
            for w in kw
        )
        bv = (
            jnp.int32(pad_base) + jnp.arange(d * c_pair, dtype=jnp.int32)
        ).at[dest].set(v, mode="drop")
    pad_base += d * d * c_pair

    faults.check("collective.exchange")  # trace-time chaos site (§11)
    with jax.named_scope("sort.exchange"):
        bkw = tuple(
            jax.lax.all_to_all(
                w.reshape(d, c_pair), ax, split_axis=0, concat_axis=0,
                tiled=False,
            )
            for w in bkw
        )
        bv = jax.lax.all_to_all(
            bv.reshape(d, c_pair), ax, split_axis=0, concat_axis=0,
            tiled=False,
        )
        recv_counts = jax.lax.all_to_all(
            counts.reshape(d, 1), ax, split_axis=0, concat_axis=0,
            tiled=False,
        ).reshape(d)

    # 7. local sort of the received buckets (step 9); reals sort before pads
    with jax.named_scope("sort.phase_bucket"):
        fkw, fv = _local_sort(
            tuple(w.reshape(d * c_pair) for w in bkw), bv.reshape(d * c_pair),
            plan.bucket_plan, pad_base,
        )
    out_cap = plan.out_cap
    count = jnp.sum(recv_counts, dtype=jnp.int32)
    # Padded shard elements (payload in [n_glob, n_glob + d*n_pad)) are real
    # inputs' pads: they sort after all true elements; exclude them.
    count = count - jnp.sum(
        (fv[:out_cap] >= n_glob) & (fv[:out_cap] < n_glob + d * n_pad),
        dtype=jnp.int32,
    )
    return (
        like_words(tuple(w[:out_cap] for w in fkw), keys_local),
        fv[:out_cap],
        count,
        max_within,
    )


@functools.partial(jax.jit, static_argnames=("mesh", "plan"))
def _sharded_argsort(keys, mesh, plan: ShardPlan):
    """The jit'd distributed entry.  ``mesh`` and ``plan`` are STATIC
    arguments: two ``make_sharded_sort`` calls with equal
    ``(shape, mesh, dtype, plan)`` signatures hit one compiled
    executable (trace-once / zero-retrace, tested)."""
    telemetry.count("sort.sharded_traces")  # python side effect: per TRACE
    codec = codec_for(plan.dtype_name, plan.descending)
    axt = plan.axis
    n_loc = plan.n_local

    def body(keys_local):
        me = jax.lax.axis_index(axt if len(axt) > 1 else axt[0])
        kw = codec.encode(keys_local)
        gid = me * n_loc + jnp.arange(n_loc, dtype=jnp.int32)
        fkw, fv, count, max_within = sorted_shard(kw, gid, plan)
        # Stack words into one (nw, out_cap) array so the shard_map
        # out_specs stay structure-independent of the codec word count.
        return (
            jnp.stack(as_words(fkw))[None],
            fv[None],
            count[None],
            max_within[None],
        )

    pspec = P(axt)
    fkw, fv, counts, mw = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspec,),
        out_specs=(P(axt, None, None), P(axt, None), pspec, pspec),
        # The local sorts are pallas_calls, whose out_shapes carry no
        # varying-axes annotation: skip the per-value vma check.
        check_vma=False,
    )(keys)
    # fkw: (D, nw, out_cap) -> per-word (D*out_cap,) flats -> decode
    words = tuple(fkw[:, i, :].reshape(-1) for i in range(codec.num_words))
    return codec.decode(words), fv.reshape(-1), counts, mw


def _degraded_host_sort(keys, plan: ShardPlan):
    """Last link of the distributed degradation chain (DESIGN.md §11):
    gather the whole array to the host, sort it on one device with a
    single stable ``lax.sort`` over the canonical words + global-index
    payload, and re-emit the distributed output contract — per-shard
    ``out_cap`` chunks whose valid prefixes (``counts[i] == n_local``)
    concatenate to the globally sorted sequence.

    Deterministic and bitwise-equal to the mesh path on the valid
    prefixes; slower (no parallelism) and returns unsharded arrays.
    ``max_within`` is reported as 0 (no exchange ran)."""
    import numpy as np

    codec = codec_for(plan.dtype_name, plan.descending)
    n = plan.d * plan.n_local
    x = jnp.asarray(np.asarray(jax.device_get(keys)))
    kw = as_words(codec.encode(x))
    gid = jnp.arange(n, dtype=jnp.int32)
    out = jax.lax.sort(tuple(kw) + (gid,), num_keys=len(kw) + 1)
    sk = codec.decode(tuple(out[:-1]))
    sv = np.asarray(out[-1])
    skn = np.asarray(sk)
    d, oc, n_loc = plan.d, plan.out_cap, plan.n_local
    out_k = np.zeros((d, oc), dtype=skn.dtype)
    out_v = np.full((d, oc), np.int32(2**31 - 1), np.int32)
    for i in range(d):
        chunk = skn[i * n_loc:(i + 1) * n_loc]
        out_k[i, :n_loc] = chunk
        if n_loc and oc > n_loc:
            out_k[i, n_loc:] = chunk[-1]  # inert pad content
        out_v[i, :n_loc] = sv[i * n_loc:(i + 1) * n_loc]
    counts = np.full((d,), n_loc, np.int32)
    mw = np.zeros((d,), np.int32)
    return (
        jnp.asarray(out_k.reshape(-1)),
        jnp.asarray(out_v.reshape(-1)),
        jnp.asarray(counts),
        jnp.asarray(mw),
    )


def _axis_degree(mesh, axis) -> tuple[tuple[str, ...], int]:
    axt = (axis,) if isinstance(axis, str) else tuple(axis)
    d = 1
    for a in axt:
        d *= mesh.shape[a]
    return axt, d


def _resolve_shard_plan(
    mesh, axt, d, n_global: int, dtype, cfg: SortConfig,
    oversample: int, pair_align: int,
) -> ShardPlan:
    """Obtain the distributed plan per ``cfg.plan`` ("default" builds it
    from the config; "autotune" goes through the persistent shard-plan
    cache, tuning on the first miss; any other string loads a shard-plan
    file saved by ``autotune.save_shard_plan``)."""
    if cfg.plan == "default":
        return build_shard_plan(
            axt, d, n_global // d, dtype, cfg,
            oversample=oversample, pair_align=pair_align,
        )
    from repro.core import autotune  # deferred: autotune imports core.plan

    if cfg.plan == "autotune":
        return autotune.shard_plan_for(
            mesh, axt, n_global, dtype, cfg,
            oversample=oversample, pair_align=pair_align,
        )
    return autotune.load_shard_plan(
        cfg.plan, axis=axt, d=d, n_local=n_global // d, dtype=dtype, cfg=cfg,
    )


def make_sharded_sort(
    mesh, axis, n_global: int, cfg: SortConfig = DEFAULT_CONFIG,
    oversample: int = 8, *, dtype=jnp.int32, pair_align: int = 8,
):
    """Build a jit'd distributed argsort over ``axis`` of ``mesh``.

    Args:
        mesh: jax device mesh.
        axis: mesh axis name (or tuple) to sort across; D = its size.
        n_global: total key count (must divide by D).
        cfg: pipeline knobs (``descending`` supported; ``cfg.plan``
            selects the schedule: "default" builds it from this config,
            "autotune" uses the measured-best distributed plan from the
            persistent cache, any other string loads a shard-plan
            file).
        oversample: regular-sampling oversample factor (power of two).
        dtype: key dtype the returned fn accepts (any codec dtype —
            64-bit needs x64 mode).  Part of the plan signature.
        pair_align: lane alignment of the per-pair exchange capacity.
    Returns:
        (fn, plan) where fn: (keys (n_global,) sharded over axis) ->
          (sorted_keys (D*out_cap,), payload_idx (D*out_cap,),
           counts (D,), max_within (D,))
        and the valid prefix of each shard (counts[i] elements)
        concatenated in shard order is the globally sorted sequence;
        payloads are original global indices (an argsort).  ``plan`` is
        the frozen :class:`~repro.core.plan.ShardPlan` (capacities:
        ``plan.c_pair``, ``plan.out_cap``, ``plan.d``).
    Raises:
        ValueError: naming the offending argument — ``axis`` spanning
            fewer than 2 devices, ``n_global`` not divisible by D or
            exceeding the int32 payload budget, or (at plan-build time)
            a bad ``oversample``/``pair_align``.
    """
    axt, d = _axis_degree(mesh, axis)
    if d < 2:
        raise ValueError(
            f"make_sharded_sort axis {axis!r} spans d={d} device(s); need "
            "d >= 2 (use bucket_sort.sort on a single device)"
        )
    if n_global % d != 0:
        raise ValueError(
            f"make_sharded_sort n_global ({n_global}) must be divisible by "
            f"the axis device count d={d}"
        )
    if n_global * 16 > 2**31:
        raise ValueError(
            f"make_sharded_sort n_global ({n_global}) exceeds the int32 "
            f"payload budget (n_global * 16 <= 2**31, i.e. n_global <= "
            f"{2**27}): per-phase pad ranges are drawn from the int32 "
            "payload space"
        )
    plan = _resolve_shard_plan(
        mesh, axt, d, n_global, dtype, cfg, oversample, pair_align
    )

    def run(keys):
        with telemetry.span("sort.sharded_argsort"):
            return _run(keys)

    def _run(keys):
        if jnp.dtype(keys.dtype).name != plan.dtype_name:
            raise ValueError(
                f"keys dtype {jnp.dtype(keys.dtype).name} does not match "
                f"the shard plan's dtype {plan.dtype_name} (pass dtype= to "
                "make_sharded_sort)"
            )
        telemetry.count("sort.keys", n_global)
        telemetry.count("sort.exchange_slots", plan.d * plan.exchange_elements)
        telemetry.count("sort.mesh_moved_elements",
                        plan.d * plan.moved_elements)
        # Degradation chain (DESIGN.md §11): mesh execution -> ONE retry
        # (a failed trace is never cached, so the retry re-traces from
        # scratch) -> deterministic gather-to-host degraded sort.  The
        # outcome is recorded on ``run.last_stats``.
        site = f"collective.exchange[D={plan.d}]"
        try:
            with telemetry.span("sort.launch"):
                out = _sharded_argsort(keys, mesh, plan)
            run.last_stats = {"degraded": False, "retries": 0}
            return out
        except guard.RECOVERABLE as e1:
            guard.record_degradation(
                site, "retry", "mesh execution", "mesh execution (retry)", e1)
        try:
            with telemetry.span("sort.launch"):
                out = _sharded_argsort(keys, mesh, plan)
            run.last_stats = {"degraded": False, "retries": 1}
            return out
        except guard.RECOVERABLE as e2:
            guard.record_degradation(
                site, "fallback", "mesh execution",
                "gather-to-host degraded sort", e2)
        out = _degraded_host_sort(keys, plan)
        run.last_stats = {"degraded": True, "retries": 1}
        return out

    run.last_stats = {"degraded": False, "retries": 0}
    return run, plan
