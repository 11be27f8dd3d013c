"""Shared test fixtures.

Multi-device meshes on a CPU host need
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set BEFORE jax
initializes its backends (the `repro.launch.mesh.make_debug_mesh`
contract: the flag lives in the test process, never globally).  conftest
imports before any test module, so setting it here covers every
collected test; an externally provided device-count flag (e.g. a CI leg
exporting its own) is respected.

The 512-device production-mesh flag stays confined to the
`test_dryrun.py` SUBPROCESS — 8 host devices is the ceiling for
in-process tests.
"""

import os

_DEVICE_FLAG = "--xla_force_host_platform_device_count=8"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " " + _DEVICE_FLAG).strip()

import pytest  # noqa: E402

#: Each interpret-mode Pallas kernel compiles to an XLA:CPU executable
#: that holds several hundred memory maps for as long as a jit cache keeps
#: it.  A module that sweeps many shapes would pass Linux's per-process
#: map limit (``vm.max_map_count``, 65530 by default) and crash its
#: worker, so JAX's caches are cleared between tests once the process
#: holds this many maps.
MAP_BUDGET = 24000


def _map_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:          # not Linux: no map limit to guard
        return 0


@pytest.fixture(autouse=True)
def _release_compiled_kernels():
    yield
    if _map_count() > MAP_BUDGET:
        import gc

        import jax
        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def make_model_mesh():
    """Factory fixture: ``make_model_mesh(k)`` returns a 1-D mesh with a
    k-device ``model`` axis (skipping if the host exposes fewer devices
    — e.g. when an external XLA_FLAGS pinned a smaller count)."""
    import jax

    from repro.launch.mesh import make_debug_mesh

    def make(k: int):
        if len(jax.devices()) < k:
            pytest.skip(f"needs {k} host devices, have "
                        f"{len(jax.devices())}")
        return make_debug_mesh((k,), ("model",))

    return make
