"""Test configuration: force a virtual 8-device CPU mesh before jax init.

Multi-chip sharding paths are exercised on CPU via
``--xla_force_host_platform_device_count``. The suite runs on the CPU
everywhere, a machine with a TPU included: several xdist workers cannot
share one chip, and ``chip_smoke.py`` is the chip run. ``JAX_PLATFORMS`` is
set here before jax is imported; JAX reads it itself.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")
# The designers' auto-mesh would route EVERY GP test through 8-pool sharded
# sweeps; on virtual CPU devices that multiplies work ~8x with no
# parallelism gain. Dedicated mesh tests opt back in with use_mesh=True.
os.environ.setdefault("VIZIER_DISABLE_MESH", "1")

import gc  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _gc_relief():
    """Keeps full-suite GC pauses bounded (observed failure mode: ~950
    tests of jit compilations accumulate millions of live Python objects
    (~5 GB RSS), after which any full collection stalls the main thread for
    minutes — surfacing as spurious gRPC channel-ready timeouts or apparent
    hangs in whatever test the pause lands on).

    At each module boundary: drop jax's compilation caches (their jaxprs
    dominate the object graph; cross-module cache reuse is minimal anyway),
    unfreeze the previous boundary's survivors so cycles that died since
    then are reclaimable (a freeze-only policy would make suite RSS
    monotone), collect once, then ``gc.freeze()`` the survivors into the
    permanent generation so collections between boundaries scan only new
    objects.
    """
    yield
    jax.clear_caches()
    gc.unfreeze()
    gc.collect()
    gc.freeze()
