"""`import paddle_tpu` must not touch any device: a backend that hangs at
start-up must not be able to hang the import, array-free users shouldn't
pay backend init — and a chip belongs to one process at a time, so a
parent that had initialised the backend could not start chip-owning
children (bench.py's orchestrator, ProcReplica parents)."""
import subprocess
import sys


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
