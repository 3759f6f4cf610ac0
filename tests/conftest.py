import os
import sys

# Tests run with the real single CPU device (the dry-run sets its own 512
# fake devices in a subprocess); keep XLA quiet and deterministic.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import contextlib  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

# Persistent XLA compilation cache: tier-1 is compile-dominated (~100+
# distinct jitted programs at a few seconds each), and the cache works on
# the CPU backend — warm re-runs skip XLA entirely (tracing still runs).
# CI restores .jax_cache via actions/cache; locally it just accumulates.
use_compile_cache()


@contextlib.contextmanager
def assert_no_retrace(fn, *, compiles=0):
    """Pin the jit trace-cache growth of `fn` across the with-block.

    Exactly `compiles` new cache entries may appear while the block
    runs; the default 0 means every call inside must be served from an
    already-traced program (the one-trace-per-shape discipline reprolint's
    jit-cache-key rule guards statically, asserted dynamically). Pass
    `compiles=1` around the first run through a freshly built jitted fn
    to pin "this whole run is ONE program". No-op on jax builds without
    `_cache_size` introspection — the behavioral asserts around the pin
    still run there.
    """
    if not hasattr(fn, "_cache_size"):
        yield
        return
    n0 = fn._cache_size()
    yield
    n1 = fn._cache_size()
    assert n1 == n0 + compiles, (
        f"retrace: expected {compiles} new compile(s), got {n1 - n0} "
        f"(cache {n0} -> {n1})")


def mark_slow_unless(values, quick):
    """Parametrize a compile-heavy matrix for the two-lane test split:
    each entry of `values` (a scalar or a tuple of argvalues) stays in
    the quick lane iff it is in `quick`; everything else gets the
    `slow` mark (weekly CI / -m slow runs the full matrix). One shared
    definition so the quick-representative sets live next to their
    parametrize calls but the mechanism cannot drift between files."""
    return [pytest.param(*(v if isinstance(v, tuple) else (v,)),
                         marks=() if v in quick else (pytest.mark.slow,))
            for v in values]


@pytest.fixture(scope="session")
def single_mesh():
    return jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
