"""Distributed tests run in SUBPROCESSES with forced host device counts
(the main pytest process must keep the real 1-CPU topology)."""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


def test_distributed_sort_8dev():
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed_sort import make_sharded_sort
        from repro.core.sort_config import SortConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = SortConfig(tile=256, s=16, direct_max=512, impl="xla")
        rng = np.random.default_rng(3)
        for n, axis in [(8192, "data"), (8192, ("data", "model"))]:
            run, spec = make_sharded_sort(mesh, axis, n, cfg, oversample=8)
            for dist in ["uniform", "equal", "skew"]:
                if dist == "uniform": x = rng.integers(-2**31, 2**31-1, n).astype(np.int32)
                elif dist == "equal": x = np.full(n, -3, np.int32)
                else: x = (rng.zipf(1.5, n) % 100000).astype(np.int32)
                sk, sv, counts, mw = map(np.asarray, run(jnp.asarray(x)))
                oc = spec.out_cap
                got = np.concatenate([sk[i*oc:i*oc+counts[i]] for i in range(spec.d)])
                assert (got == np.sort(x)).all(), (n, axis, dist)
                assert (mw < spec.c_pair).all()
                pv = np.concatenate([sv[i*oc:i*oc+counts[i]] for i in range(spec.d)])
                assert (x[pv] == got).all()
        print("OK")
    """)


# ----------------------------------------------------------------------
# Differential-conformance slice (ISSUE 8): mesh D in {2, 4, 8} (incl.
# a 2-axis sort) x 5 dtypes x asc/desc x 4 distributions, every cell
# checked against the np.sort / argsort-permutation oracles and the
# max_within < c_pair capacity invariant.  Zero xfails.
# ----------------------------------------------------------------------

_CONFORMANCE = """
    import jax
    jax.config.update("jax_enable_x64", True)  # int64/float64 codecs
    import numpy as np, jax.numpy as jnp
    from repro.core.distributed_sort import make_sharded_sort
    from repro.core.sort_config import SortConfig
    from repro.launch.mesh import make_mesh

    mesh = make_mesh({shape}, {names})
    axis = {axis}
    n = 4096
    rng = np.random.default_rng(42)

    def gen(dtype, dist):
        if np.issubdtype(np.dtype(dtype), np.floating):
            base = (rng.standard_normal(n) * 1e6).astype(dtype)
        else:
            info = np.iinfo(dtype)
            base = rng.integers(
                info.min, info.max, n, dtype=np.int64).astype(dtype)
        if dist == "uniform":
            return base
        if dist == "equal":
            return np.full(n, base[0], dtype)
        if dist == "zipf":
            return (rng.zipf(1.5, n) % 100000).astype(dtype)
        if dist == "nearly-sorted":
            x = np.sort(base)
            idx = rng.integers(0, n - 1, n // 100)
            x[idx], x[idx + 1] = x[idx + 1].copy(), x[idx].copy()
            return x
        raise KeyError(dist)

    cells = 0
    for dtype in ["int32", "uint32", "int64", "float32", "float64"]:
        for desc in [False, True]:
            cfg = SortConfig(tile=256, s=16, direct_max=512, impl="xla",
                             descending=desc)
            run, plan = make_sharded_sort(
                mesh, axis, n, cfg, dtype=jnp.dtype(dtype))
            for dist in ["uniform", "equal", "zipf", "nearly-sorted"]:
                x = gen(dtype, dist)
                sk, sv, counts, mw = map(np.asarray, run(jnp.asarray(x)))
                oc = plan.out_cap
                got = np.concatenate(
                    [sk[i*oc:i*oc+counts[i]] for i in range(plan.d)])
                ref = np.sort(x)[::-1] if desc else np.sort(x)
                cell = (dtype, desc, dist)
                assert counts.sum() == n, (cell, counts)
                np.testing.assert_array_equal(got, ref, err_msg=str(cell))
                pv = np.concatenate(
                    [sv[i*oc:i*oc+counts[i]] for i in range(plan.d)])
                assert sorted(pv) == list(range(n)), (cell, "not a perm")
                np.testing.assert_array_equal(x[pv], got, err_msg=str(cell))
                assert (mw < plan.c_pair).all(), (cell, mw, plan.c_pair)
                cells += 1
    print("OK", cells, "cells")
"""


@pytest.mark.parametrize("devices,shape,names,axis", [
    (2, (2,), ("data",), "data"),
    (4, (4,), ("data",), "data"),
    (8, (4, 2), ("data", "model"), ("data", "model")),
], ids=["d2", "d4", "d8-2axis"])
def test_distributed_conformance_matrix(devices, shape, names, axis):
    out = run_sub(
        _CONFORMANCE.format(shape=shape, names=names, axis=repr(axis)),
        devices=devices, timeout=600,
    )
    assert "OK 40 cells" in out


def test_shard_trace_discipline_4dev():
    """Same (mesh, n, dtype, cfg) -> ONE trace shared across fresh
    make_sharded_sort calls; distinct oversample -> distinct
    executable."""
    run_sub("""
        import numpy as np, jax.numpy as jnp
        from repro.core.distributed_sort import make_sharded_sort, trace_count
        from repro.core.sort_config import SortConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        cfg = SortConfig(tile=256, s=16, direct_max=512, impl="xla")
        x = jnp.asarray(np.random.default_rng(0).integers(
            0, 1000, 4096).astype(np.int32))
        run1, p1 = make_sharded_sort(mesh, "data", 4096, cfg)
        t0 = trace_count()
        run1(x)
        assert trace_count() == t0 + 1, "first call must trace exactly once"
        run1(x)
        assert trace_count() == t0 + 1, "same-signature call retraced"
        run2, p2 = make_sharded_sort(mesh, "data", 4096, cfg)
        assert p2 is p1, "equal signature must return the memoized plan"
        run2(x)
        assert trace_count() == t0 + 1, "fresh equal-signature fn retraced"
        run3, p3 = make_sharded_sort(mesh, "data", 4096, cfg, oversample=4)
        assert p3 != p1 and p3.signature() != p1.signature()
        run3(x)
        assert trace_count() == t0 + 2, "distinct oversample must retrace"
        print("OK")
    """, devices=4)


def test_shard_plan_cache_hit_zero_retrace_2dev(tmp_path):
    """plan='autotune': first resolve tunes and persists; after
    clear_memo() the disk record reloads an EQUAL plan, so the jit
    static-arg cache hits -> zero retraces."""
    run_sub(f"""
        import os
        os.environ["REPRO_SORT_PLAN_CACHE"] = {str(tmp_path / "p.json")!r}
        import numpy as np, jax.numpy as jnp
        from repro.core import autotune
        from repro.core.distributed_sort import make_sharded_sort, trace_count
        from repro.core.sort_config import SortConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("data",))
        cfg = SortConfig(tile=256, s=16, direct_max=512, impl="xla",
                         plan="autotune")
        x = jnp.asarray(np.random.default_rng(1).integers(
            0, 10**6, 2048).astype(np.int32))
        run1, p1 = make_sharded_sort(mesh, "data", 2048, cfg)
        run1(x)
        autotune.clear_memo()  # force the on-disk path
        t0 = trace_count()
        run2, p2 = make_sharded_sort(mesh, "data", 2048, cfg)
        assert p2 == p1, "reloaded shard plan differs from the tuned one"
        run2(x)
        assert trace_count() == t0, "shard-plan-cache hit retraced"
        # persisted under the BASE signature's key (the lookup identity;
        # the tuned winner itself may carry a different cfg/knobs)
        import json
        store = json.load(open(os.environ["REPRO_SORT_PLAN_CACHE"]))
        keys = [k for k in store["plans"] if k.startswith("shard|")]
        assert keys, "tuned shard plan not persisted"
        print("OK")
    """, devices=2)


def test_make_sharded_sort_validation_messages_2dev():
    """The bare asserts became field-naming ValueErrors (ISSUE 8):
    n_global divisibility, the int32 payload budget, plan-build-time
    oversample validation, and the runtime dtype check."""
    run_sub("""
        import numpy as np, jax.numpy as jnp
        from repro.core.distributed_sort import make_sharded_sort
        from repro.core.sort_config import SortConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("data",))
        cfg = SortConfig(tile=256, s=16, direct_max=512, impl="xla")
        def expect(msg, fn):
            try:
                fn()
            except ValueError as e:
                assert msg in str(e), (msg, str(e))
            else:
                raise AssertionError(f"no ValueError: {msg}")
        expect("must be divisible by the axis device count",
               lambda: make_sharded_sort(mesh, "data", 1001, cfg))
        expect("exceeds the int32 payload budget",
               lambda: make_sharded_sort(mesh, "data", 2**27 + 2, cfg))
        expect("oversample must be a power of two",
               lambda: make_sharded_sort(mesh, "data", 2048, cfg, 5))
        expect("pair_align must be a power of two >= 8",
               lambda: make_sharded_sort(mesh, "data", 2048, cfg,
                                         pair_align=4))
        run, plan = make_sharded_sort(mesh, "data", 2048, cfg)
        expect("does not match the shard plan's dtype",
               lambda: run(jnp.zeros(2048, jnp.float32)))
        print("OK")
    """, devices=2)


def test_make_sharded_sort_rejects_single_device_axis():
    """d < 2 raises in-process (no forced-host mesh needed)."""
    from repro.core.distributed_sort import make_sharded_sort
    from repro.core.sort_config import SortConfig
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match=r"need d >= 2"):
        make_sharded_sort(
            mesh, "data", 1024,
            SortConfig(tile=256, s=16, direct_max=512, impl="xla"),
        )


def test_sharded_train_step_8dev():
    """GSPMD train step on a 4x2 mesh: loss decreases, params sharded."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import configs, sharding as shd
        from repro.config import OptimizerConfig, ParallelConfig, ShapeConfig
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import build_train_step, make_plan, param_shardings
        from repro.models import api, meta
        from repro.optim import adamw_init
        import dataclasses

        model = configs.get_smoke("qwen3-moe-30b-a3b")
        model = dataclasses.replace(model, vocab=512)
        arch = dataclasses.replace(configs.get_config("qwen3-moe-30b-a3b"), model=model)
        par = ParallelConfig(mesh_shape=(4, 2), mesh_axes=("data", "model"))
        mesh = make_mesh((4, 2), ("data", "model"))
        shp = ShapeConfig("t", 64, 8, "train")
        plan = make_plan(arch, shp, mesh, par)
        opt = OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        tpl = api.template(model)
        with shd.sharding_ctx(mesh, plan.rules):
            params = meta.init_params(tpl, jax.random.PRNGKey(0))
            params = jax.tree.map(jax.device_put, params, param_shardings(plan))
            state = adamw_init(params, opt)
            step = jax.jit(build_train_step(plan, opt), donate_argnums=(0, 1))
            rng = np.random.default_rng(0)
            toks = rng.integers(0, 512, (8, 65)).astype(np.int32)
            batch = {"tokens": jnp.asarray(toks[:, :-1]), "targets": jnp.asarray(toks[:, 1:])}
            losses = []
            for i in range(12):  # overfit one fixed batch -> must decrease
                params, state, m = step(params, state, batch)
                losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0] - 0.1, losses
        print("OK", losses[0], "->", losses[-1])
    """)


def test_multipod_mini_dryrun():
    """Mini multi-pod proof: (2,2,2) pod/data/model mesh lowers+compiles
    a train step AND a decode step for a reduced hybrid config."""
    run_sub("""
        import dataclasses, jax
        from repro import configs
        from repro.config import ParallelConfig, ShapeConfig
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import lower_cell, make_plan

        model = configs.get_smoke("jamba-1.5-large-398b")
        arch = dataclasses.replace(
            configs.get_config("jamba-1.5-large-398b"), model=model, fsdp=True)
        par = ParallelConfig(mesh_shape=(2, 2, 2),
                             mesh_axes=("pod", "data", "model"), fsdp=True)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        for shp in [ShapeConfig("t", 64, 8, "train"), ShapeConfig("d", 64, 8, "decode")]:
            plan = make_plan(arch, shp, mesh, par)
            lowered, kind = lower_cell(plan)
            compiled = lowered.compile()
            assert compiled is not None
            print(kind, "compiled OK")
    """)


def test_compressed_allreduce_8dev():
    """int8 gradient all-reduce with error feedback ~ fp32 psum mean."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import allreduce_compressed
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        def body(g):
            mean, res = allreduce_compressed({"w": g}, "data")
            exact = jax.lax.pmean(g, "data")
            return mean["w"][None], res["w"][None], exact[None]
        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                    out_specs=(P("data"), P("data"), P("data"))))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(8, 1024)).astype(np.float32))
        mean, res, exact = f(g.reshape(8*1024))
        err = np.abs(np.asarray(mean) - np.asarray(exact)).max()
        scale = np.abs(np.asarray(exact)).max()
        assert err < 0.05 * scale + 0.05, (err, scale)
        # error feedback residual bounded by one quantization step
        assert np.abs(np.asarray(res)).max() <= np.abs(np.asarray(g)).max() / 127 + 1e-6
        print("OK", err)
    """)
