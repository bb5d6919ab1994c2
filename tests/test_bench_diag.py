"""bench.py's process shape and its one trusted artifact.

- The driver reads bench.py's FINAL stdout line: on probe failure it is
  compact (bounded size), parses as JSON and carries value:null — a run
  that measured nothing forwards nothing.
- The orchestrator never imports jax and runs one child per workload: a
  chip belongs to one process at a time, so a parent that had
  initialised the backend could not start chip-owning children.
- Without --smoke a worker that finds no TPU exits non-zero instead of
  timing a toy on the CPU under a device metric's name.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _env(tmp_path, **extra):
    env = dict(os.environ)
    # CAMPAIGN_CHILD skips the chip-ownership preemption: a test must
    # never SIGKILL a real in-flight campaign stage. The campaign dir
    # keeps bench_partial_* litter out of the real campaign_out/.
    env["CAMPAIGN_CHILD"] = "1"
    env["BENCH_CAMPAIGN_DIR"] = str(tmp_path)
    env.update(extra)
    return env


@pytest.fixture(scope="module")
def probe_fail_run(tmp_path_factory):
    # An unloadable backend makes the probe worker die fast and
    # deterministically.
    env = _env(tmp_path_factory.mktemp("campaign"),
               JAX_PLATFORMS="no_such_backend", BENCH_PROBE_TIMEOUT="60",
               BENCH_WORK_TIMEOUT="60")
    return subprocess.run([sys.executable, BENCH], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=180)


def _last_json_line(stdout):
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    assert lines, "bench.py printed nothing to stdout"
    return lines[-1]


def test_final_line_parses_and_is_compact(probe_fail_run):
    line = _last_json_line(probe_fail_run.stdout)
    assert len(line) <= 6000, f"final line is {len(line)} bytes"
    diag = json.loads(line)
    assert diag["value"] is None
    assert diag["metric"] == "gpt_pretrain_tokens_per_sec_per_chip"
    assert "error" in diag
    # numbers from another run are never forwarded
    assert "earlier_session_measurements" not in diag
    assert probe_fail_run.returncode == 2


def test_every_stdout_json_line_parses(probe_fail_run):
    # incremental-flush contract: anything bench.py prints to stdout
    # that looks like JSON must BE JSON (the driver tails stdout)
    for ln in probe_fail_run.stdout.strip().splitlines():
        ln = ln.strip()
        if ln.startswith("{"):
            json.loads(ln)


def test_orchestrator_is_jax_free_with_one_child_per_workload(tmp_path):
    code = (
        "import argparse, json, sys\n"
        "sys.argv = ['bench.py']\n"
        "import bench\n"
        "calls = []\n"
        "def spawn(extra, timeout_s, tag):\n"
        "    calls.append(extra)\n"
        "    if extra[1] == 'probe':\n"
        "        return 0, {'probe': 'ok', 'backend': 'tpu'}, None, 0.1\n"
        "    return 0, {'metric': extra[1], 'value': 1.0}, None, 0.1\n"
        "bench._spawn = spawn\n"
        "rc = bench._orchestrate_impl(['gpt', 'ernie', 'resnet50'],\n"
        "    argparse.Namespace(smoke=False), [])\n"
        "print(json.dumps({'rc': rc, 'calls': calls,\n"
        "    'jax': [m for m in sys.modules if m.split('.')[0] in\n"
        "            ('jax', 'jaxlib', 'paddle_tpu')]}))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=_env(tmp_path), capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(_last_json_line(proc.stdout))
    assert out["rc"] == 0
    assert out["jax"] == [], "the orchestrator process imported jax"
    assert out["calls"] == [["--worker", "probe"], ["--worker", "gpt"],
                            ["--worker", "ernie"],
                            ["--worker", "resnet50"]]


def test_worker_without_smoke_refuses_cpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, BENCH, "--worker", "gpt"], cwd=REPO,
        env=_env(tmp_path, JAX_PLATFORMS="cpu",
                 BENCH_TELEMETRY_DIR=str(tmp_path / "telemetry")),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines()
                if ln.strip().startswith("{")], "a CPU run reported a result"
