# NOTE: no XLA_FLAGS here on purpose — unit tests and benches must see
# the real single CPU device; only launch/dryrun.py forces 512 host
# devices (and distributed tests spawn subprocesses with their own env).
import jax
import numpy as np
import pytest


def _map_counts() -> tuple[int, int] | None:
    """(memory mappings this process holds, the kernel's per-process
    limit), or None where /proc does not say."""
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            limit = int(f.read())
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f), limit
    except OSError:
        return None


@pytest.fixture(autouse=True)
def _bounded_compiled_programs():
    """Every program the CPU backend compiles holds memory mappings until
    JAX drops it from its caches.  A worker that runs a whole large test
    file compiles thousands (each interpret-mode kernel is several), and
    at the kernel's map limit the compiler aborts the process.  Clear the
    caches between tests once half the limit is in use."""
    yield
    counts = _map_counts()
    if counts is not None and counts[0] > counts[1] // 2:
        jax.clear_caches()


@pytest.fixture
def rng():
    return np.random.default_rng(0)
