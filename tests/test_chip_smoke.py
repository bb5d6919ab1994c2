"""chip_smoke.py, pinned on the CPU.

The smoke itself only runs on a TPU (PERF.md records its runs); without
one it must exit non-zero, say why, and report no result — a CPU run is
never mistaken for a chip run. (That imports leave the backend
uninitialised, which one-process-per-chip rests on, is pinned in
test_import_device_free.py.)
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr, r.stderr[-2000:]
    assert '"ok"' not in r.stdout, "a CPU run printed a result line"
    assert "phase" not in r.stdout, "work was started without a TPU"


def test_verdict_line_has_the_contract_keys_only():
    """The driver's chip check parses the last stdout line and refuses
    any key besides ok / device{platform, kind, count}."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    device = chip_smoke.device_facts()
    line = chip_smoke.verdict_line(True, device)
    assert "\n" not in line
    got = json.loads(line)
    assert set(got) == {"ok", "device"} and got["ok"] is True
    assert set(got["device"]) == {"platform", "kind", "count"}
    assert isinstance(got["device"]["platform"], str)
    assert isinstance(got["device"]["kind"], str)
    assert type(got["device"]["count"]) is int
    assert got["device"]["count"] == 8      # conftest's virtual devices
