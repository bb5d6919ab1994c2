"""Test harness config: force a virtual 8-device CPU mesh.

Distributed tests run on 8 virtual CPU devices
(xla_force_host_platform_device_count) per SURVEY.md §4. Both settings
are environment variables read when jax initialises its backend, so
they are set here before jax is imported.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu"
assert jax.device_count() == 8, jax.devices()


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    """Isolate tests from global-mesh leakage: a mesh set by one test
    (shard_model/set_mesh) must not change another test's sharding
    constraints or pipeline routing."""
    from paddle_tpu.distributed import mesh as mesh_mod
    mesh_mod._global_mesh = None
    yield
    mesh_mod._global_mesh = None


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy variant with a cheaper sibling in the default run; "
        "included when PADDLE_TPU_RUN_SLOW=1")
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection resilience suite "
        "(standalone: pytest -m chaos)")


def pytest_collection_modifyitems(config, items):
    if os.environ.get("PADDLE_TPU_RUN_SLOW") == "1":
        return
    skip = pytest.mark.skip(reason="slow variant (set PADDLE_TPU_RUN_SLOW=1)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


def jit_forward(m, *xs):
    """Shared helper: run a Layer's forward as ONE jitted functional call
    (the production Engine/jit path) and return plain arrays."""
    from paddle_tpu.nn.layer import functional_call
    from paddle_tpu.tensor import Tensor
    params, buffers = m.raw_state()

    @jax.jit
    def fwd(p, b, *a):
        out = functional_call(m, p, b, *[Tensor(x) for x in a])
        if isinstance(out, (tuple, list)):
            return tuple(t._value for t in out)
        return out._value
    return fwd(params, buffers, *xs)


# The tracing-heavy tests allocate millions of short-lived containers;
# CPython's default gen-0 threshold (700) makes the collector run
# constantly inside jax tracing on this 1-core box. Collections still
# happen — at module boundaries below — so memory stays bounded.
import gc  # noqa: E402

gc.set_threshold(200_000, 100, 100)


@pytest.fixture(autouse=True, scope="module")
def _gc_per_module():
    """Module-boundary housekeeping: compiled executables stay alive
    across modules (recompiling every model per module costs far more
    than the memory), but the tracing-heavy modules' garbage is
    collected here since the gen-0 collector above is throttled."""
    yield
    gc.collect()
