"""--suite distributed: host-mesh strong scaling of the sharded sort.

On the CPU each D in {1, 2, 4, 8} runs in its OWN subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=D`` (the flag must
be set before jax import, and the parent bench process must keep its
real 1-device topology).  On a TPU every D up to the chip count runs in
this process on the first D chips.  D=1 is the single-device ``sort_kv``
baseline at the same n_global; D>=2 builds a ``("data",)`` host mesh
and times the plan-aware ``make_sharded_sort`` runner end to end.

Host "devices" here share one CPU, so this measures the *overhead*
curve of the deal-round schedule (padding, s_loc sample, fixed-shape
all_to_all at c_pair, out_cap compaction) rather than real speedup —
the derived column records Mkeys/s and the efficiency vs the D=1
baseline so successive PRs can track schedule cost at fixed n_global.
"""

from __future__ import annotations

import json
import os
import sys

_SELF = os.path.abspath(__file__)

DS = (1, 2, 4, 8)


def _measure(d: int, n_global: int, repeats: int) -> dict:
    """Time one D on the first d devices of this process."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import timeit
    from repro.core.sort_config import SortConfig

    cfg = SortConfig(tile=4096, s=64, direct_max=8192)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.integers(-(2**31), 2**31 - 1, n_global).astype(np.int32))
    out: dict = dict(d=d, n_global=n_global)
    if d == 1:
        from repro.core import bucket_sort

        t = timeit(lambda a: bucket_sort.sort_kv(
            a, jnp.arange(n_global, dtype=jnp.int32), cfg), x,
            repeats=repeats)
        out["schedule"] = "single-device sort_kv baseline"
    else:
        from repro.core.distributed_sort import make_sharded_sort
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((d,), ("data",))
        run_fn, plan = make_sharded_sort(mesh, "data", n_global, cfg)
        t = timeit(run_fn, x, repeats=repeats)
        out["schedule"] = (
            f"oversample={plan.oversample} c_pair={plan.c_pair} "
            f"out_cap={plan.out_cap}"
        )
    out["us_per_call"] = t * 1e6
    return out


def run(n_global: int = 262144, repeats: int = 3, ds=DS):
    import jax

    from benchmarks.common import forced_host_child

    # On a TPU the chips belong to this process: every D runs here on
    # jax.devices()[:D] (D above the chip count is skipped).  A child
    # process would find the chips held and fail or hang.
    tpu = jax.default_backend() == "tpu"
    rows = []
    base_us = None
    for d in ds:
        if tpu:
            if d > len(jax.devices()):
                continue
            res = _measure(d, n_global, repeats)
        else:
            res = forced_host_child(_SELF, "--child", d, n_global,
                                    repeats)
        us = res["us_per_call"]
        if d == 1:
            base_us = us
        eff = (base_us / us) if base_us else float("nan")
        mesh_kind = "tpu-mesh" if tpu else "host-mesh"
        rows.append(dict(
            name=f"distributed/d{d}",
            us_per_call=us,
            derived=(
                f"n_global={n_global} rate={n_global / us:.2f}Mkeys/s "
                f"vs_d1={eff:.2f}x {mesh_kind} {res['schedule']}"
            ),
        ))
    return rows


if __name__ == "__main__":
    if len(sys.argv) == 5 and sys.argv[1] == "--child":
        # Runs under --xla_force_host_platform_device_count=d.
        print("RESULT " + json.dumps(_measure(*map(int, sys.argv[2:5]))),
              flush=True)
    else:
        for row in run():
            print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
