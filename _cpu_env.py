"""Dev helper: `python -c "import _cpu_env; ..."` for CPU-only runs.

Must be imported BEFORE jax: it pins the platform to the CPU and asks
for 8 virtual host devices (the mesh the tests shard over). Same setup
as tests/conftest.py, without the 8-device assertion so it also serves
quick single-device experiments.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
