"""`import paddle_tpu` must not touch any device: a backend that hangs at
start-up must not be able to hang the import, array-free users shouldn't
pay backend init — and a chip belongs to one process at a time, so a
parent that had initialised the backend could not start chip-owning
children (ProcReplica parents)."""
import os
import subprocess
import sys

import pytest


def test_import_performs_no_device_ops():
    code = (
        "import jax\n"
        "import jax._src.xla_bridge as xb\n"
        "def boom(*a, **k):\n"
        "    raise RuntimeError('DEVICE TOUCHED AT IMPORT')\n"
        "xb.backends = boom\n"
        "import paddle_tpu\n"
        "import paddle_tpu.serving_fleet\n"
        "assert not xb._backends, sorted(xb._backends)\n"
        "print('CLEAN')\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=240, cwd=".")
    assert "CLEAN" in r.stdout, r.stderr[-2000:]


TOOLS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


@pytest.mark.parametrize("tool,obs", [
    ("fleet_top", "history"), ("mem_diff", "memledger"),
    ("metrics_diff", "metrics"), ("profile_diff", "contprof")])
def test_stdlib_tool_imports_neither_jax_nor_the_package(tool, obs):
    """The operator's gates read the observability formats through
    tools/_obs.py, which file-loads the module: neither importing the
    tool nor loading what it reads may pull in jax or paddle_tpu."""
    code = (
        "import sys\n"
        f"sys.path.insert(0, {TOOLS_DIR!r})\n"
        f"import {tool}\n"
        f"mod = {tool}.obs_mod({obs!r})\n"
        f"assert mod.__name__ == '_bench_obs_{obs}', mod.__name__\n"
        "heavy = [m for m in ('jax', 'numpy', 'paddle_tpu') "
        "if m in sys.modules]\n"
        "assert not heavy, heavy\n"
        "print('LEAN')\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert "LEAN" in r.stdout, r.stderr[-2000:]
