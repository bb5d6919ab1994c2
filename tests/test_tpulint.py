"""tpu-lint suite (ISSUE 13) — per-rule positive/negative fixtures,
suppression honoring, baseline stability under line drift, the
CLI gate in both directions, and the tier-1 contract itself:
the shipping tree lints clean against the committed baseline.

Pure host-side: tpulint is stdlib-ast only, none of these tests
import jax.
"""
import ast
import json
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.tpulint import rules as R                      # noqa: E402
from tools.tpulint.core import (Baseline, FileCtx,        # noqa: E402
                                load_baseline, run_lint)

FIXTURES = REPO / "tests" / "fixtures" / "tpulint"


def _ctx(source, relpath="pkg/mod.py"):
    source = textwrap.dedent(source)
    return FileCtx("/x/" + relpath, relpath, source,
                   ast.parse(source))


def _rule(rule_id, source, relpath="pkg/mod.py"):
    return R.RULES[rule_id].check(_ctx(source, relpath))


def _tree(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return tmp_path


def _lint(tmp_path, targets, baseline=None):
    return run_lint(paths=targets, root=str(tmp_path),
                    baseline=baseline or Baseline([]))


# ---------------------------------------------------------------- TRC01

class TestTRC01:
    def test_fires_on_call(self):
        fs = _rule("TRC01", """
            import jax
            f = jax.jit(lambda x: x)
        """)
        assert [f.rule for f in fs] == ["TRC01"]
        assert fs[0].symbol == "jax.jit"

    def test_fires_on_decorator_and_partial(self):
        fs = _rule("TRC01", """
            from functools import partial
            import jax

            @jax.jit
            def f(x):
                return x

            @partial(jax.jit, static_argnums=0)
            def g(n, x):
                return x
        """)
        assert len(fs) == 2

    def test_fires_on_from_import_and_pjit(self):
        fs = _rule("TRC01", """
            from jax import jit
            from jax.experimental.pjit import pjit
            a = jit(lambda x: x)
            b = pjit(lambda x: x)
        """)
        assert len(fs) == 2

    def test_tracer_jit_is_clean(self):
        fs = _rule("TRC01", """
            def build(tracer, fn):
                return tracer.jit("decode", fn, donate_argnums=(0,))
        """)
        assert fs == []

    def test_trace_py_is_exempt(self):
        fs = _rule("TRC01", """
            import jax
            jfn = jax.jit(lambda x: x)
        """, relpath="paddle_tpu/observability/trace.py")
        assert fs == []


# ---------------------------------------------------------------- TRC02

class TestTRC02:
    def test_fires_on_wall_clock_in_jitted_body(self):
        fs = _rule("TRC02", """
            import jax
            import time

            @jax.jit
            def step(x):
                return x + time.time()
        """)
        assert [f.symbol for f in fs] == ["time.time"]

    def test_fires_on_comparison_branch_in_scan_body(self):
        fs = _rule("TRC02", """
            import jax

            def outer(xs):
                def body(carry, x):
                    if carry > 0:
                        return carry, x
                    return carry + x, x
                return jax.lax.scan(body, 0, xs)
        """)
        assert [f.symbol for f in fs] == ["if-on-traced"]

    def test_module_level_scan_body_resolves(self):
        fs = _rule("TRC02", """
            import jax
            import time

            def body(carry, x):
                return carry + time.time(), x

            def run(xs):
                return jax.lax.scan(body, 0.0, xs)
        """)
        assert [f.symbol for f in fs] == ["time.time"]

    def test_truthiness_and_is_none_are_clean(self):
        # `if labels:` / `if eos is not None:` are static pytree
        # structure tests — legal under trace (the engine.py shape)
        fs = _rule("TRC02", """
            import jax

            @jax.jit
            def step(x, labels):
                eos = None
                if labels:
                    x = x + 1
                if eos is not None:
                    x = x + 2
                return x
        """)
        assert fs == []

    def test_static_shape_checks_are_clean(self):
        # `x.ndim == 3`, `len(xs) > 1`, `if not labels:` are
        # trace-time Python ints / pytree-structure tests — the
        # idiomatic static branches every jitted body in the repo
        # uses; flagging them would force suppressions on correct
        # code. A comparison on the traced VALUE itself still fires.
        fs = _rule("TRC02", """
            import jax

            @jax.jit
            def step(x, xs, labels):
                if x.ndim == 3:
                    x = x + 1
                if len(xs) > 1:
                    x = x + 2
                if not labels:
                    x = x + 3
                if x.shape[0] % 2 == 0:
                    x = x + 4
                return x
        """)
        assert fs == []
        fs2 = _rule("TRC02", """
            import jax

            @jax.jit
            def step(x):
                if x > 0:
                    x = x - 1
                return x
        """)
        assert [f.symbol for f in fs2] == ["if-on-traced"]

    def test_nested_traced_body_reported_once(self):
        # a scan body nested INSIDE a jitted body is reachable both
        # via the outer body's recursion and the traced set — one
        # violation must yield exactly one finding, not an inflated
        # non_baselined count and duplicate report rows
        fs = _rule("TRC02", """
            import jax
            import time

            @jax.jit
            def step(x, ts):
                def body(c, t):
                    return c + time.time(), t
                return jax.lax.scan(body, x, ts)
        """)
        assert [f.symbol for f in fs] == ["time.time"]

    def test_untraced_function_is_clean(self):
        fs = _rule("TRC02", """
            import time

            def host_side(x):
                return x + time.time()
        """)
        assert fs == []

    def test_method_name_cannot_alias_scan_body(self):
        # the serving.py regression: a scan body named `step` in one
        # scope must not drag an unrelated `step` METHOD into the
        # traced set
        fs = _rule("TRC02", """
            import jax
            import time

            def build(xs):
                def step(c, x):
                    return c, x
                return jax.lax.scan(step, 0, xs)

            class Engine:
                def step(self):
                    return time.time()
        """)
        assert fs == []


# ---------------------------------------------------------------- DUR01

class TestDUR01:
    def test_fires_in_durable_module(self):
        fs = _rule("DUR01", """
            def save(path, data):
                with open(path, "w") as f:
                    f.write(data)
        """, relpath="paddle_tpu/serving_fleet/journal.py")
        assert len(fs) == 1 and "open" in fs[0].symbol

    def test_fires_on_golden_token_anywhere(self):
        fs = _rule("DUR01", """
            import json
            import os

            def write(GOLDEN, doc, tmp):
                with open(GOLDEN, "w") as f:
                    json.dump(doc, f)
                os.replace(tmp, GOLDEN)
        """, relpath="tools/somesmoke.py")
        assert sorted(f.symbol for f in fs) == ['open(mode="w")',
                                                "os.replace"]

    def test_reads_and_appends_are_clean(self):
        fs = _rule("DUR01", """
            def tail(path):
                with open(path, "rb") as f:
                    return f.read()

            def append(path):
                return open(path, "ab")
        """, relpath="paddle_tpu/serving_fleet/journal.py")
        assert fs == []

    def test_atomic_py_is_exempt(self):
        fs = _rule("DUR01", """
            import os

            def atomic_replace(path, data):
                with open(path + ".tmp", "wb") as f:
                    f.write(data)
                os.replace(path + ".tmp", path)
        """, relpath="paddle_tpu/io/atomic.py")
        assert fs == []

    def test_plain_write_without_token_is_clean(self):
        fs = _rule("DUR01", """
            def note(path, text):
                with open(path, "w") as f:
                    f.write(text)
        """, relpath="tools/scratch.py")
        assert fs == []


# ---------------------------------------------------------------- CON01

_CON01_SRC = """
    import threading

    class Store:
        def __init__(self):
            self._lock = threading.Lock()
            self._data = {{}}
            self._hint = None

        def put(self, k, v):
            with self._lock:
                self._data[k] = v

        def get(self, k):
            {get_body}
"""


class TestCON01:
    def test_fires_on_unlocked_read(self):
        src = _CON01_SRC.format(get_body="return self._data.get(k)")
        fs = _rule("CON01", src,
                   relpath="paddle_tpu/observability/metrics.py")
        assert len(fs) == 1
        assert fs[0].symbol == "self._data"
        assert "Store.get" in fs[0].message

    def test_locked_read_is_clean(self):
        src = _CON01_SRC.format(
            get_body="with self._lock:\n"
                     "                return self._data.get(k)")
        fs = _rule("CON01", src,
                   relpath="paddle_tpu/observability/metrics.py")
        assert fs == []

    def test_foreign_lock_does_not_count_as_held(self):
        # `with global_lock:` (or another object's `_lock`) must not
        # satisfy the OWNING lock by substring accident — this is
        # exactly the torn-scrape race the rule exists to catch
        fs = _rule("CON01", """
            import threading

            global_lock = threading.Lock()

            class Store:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._data = {}

                def put(self, k, v):
                    with self._lock:
                        self._data[k] = v

                def leak(self, k, v):
                    with global_lock:
                        self._data[k] = v
        """, relpath="paddle_tpu/observability/metrics.py")
        assert [f.symbol for f in fs] == ["self._data"]
        assert "Store.leak" in fs[0].message

    def test_non_container_state_is_not_guarded(self):
        # self._hint (a scalar) is never lock-guarded — CON01 only
        # polices attrs the class itself treats as lock-owned
        src = _CON01_SRC.format(get_body="return self._hint")
        fs = _rule("CON01", src,
                   relpath="paddle_tpu/observability/metrics.py")
        assert fs == []

    def test_out_of_scope_file_is_clean(self):
        src = _CON01_SRC.format(get_body="return self._data.get(k)")
        assert _rule("CON01", src, relpath="pkg/other.py") == []


# ---------------------------------------------------------------- OBS01

class TestOBS01:
    def test_fires_without_allow_nan(self):
        fs = _rule("OBS01", """
            import json

            def export(doc, f):
                json.dump(doc, f)
        """, relpath="paddle_tpu/observability/export2.py")
        assert [f.symbol for f in fs] == ["json.dump"]

    def test_allow_nan_false_is_clean(self):
        fs = _rule("OBS01", """
            import json

            def export(doc, f):
                json.dump(doc, f, allow_nan=False)
        """, relpath="paddle_tpu/serving_fleet/export2.py")
        assert fs == []

    def test_out_of_scope_path_is_clean(self):
        fs = _rule("OBS01", """
            import json

            def export(doc, f):
                json.dump(doc, f)
        """, relpath="tools/whatever.py")
        assert fs == []


# ---------------------------------------------------------------- DOC01

_DOC_CATALOGUE = """
# Observability

`PADDLE_TPU_GHOST_KNOB` is mentioned here only.

## Metric catalogue

| name | type |
|---|---|
| `fleet_good_total` | counter |
| `fleet_j_{a,b}_total` | counter |
| `fleet_ghost_total` | counter |

## Next section
"""

_DOC_CODE = """
import os


def publish(reg):
    reg.counter("fleet_good_total", help="x")
    reg.counter("fleet_undoc_total", help="y")
    for name, h in (("a", "ha"), ("b", "hb")):
        reg.counter(f"fleet_j_{name}_total", help=h)
    return os.environ.get("PADDLE_TPU_UNDOC_KNOB")
"""


class TestDOC01:
    def _run(self, tmp_path, code=_DOC_CODE, doc=_DOC_CATALOGUE):
        _tree(tmp_path, {"docs/observability.md": doc,
                         "pkg/mod.py": code})
        ctxs = [_ctx(code, "pkg/mod.py")]
        return R.RULES["DOC01"].check_project(ctxs, str(tmp_path))

    def test_both_directions_fire(self, tmp_path):
        syms = {f.symbol for f in self._run(tmp_path)}
        assert syms == {"fleet_undoc_total",      # code -> docs
                        "fleet_ghost_total",      # docs -> code
                        "PADDLE_TPU_UNDOC_KNOB",  # code -> docs
                        "PADDLE_TPU_GHOST_KNOB"}  # docs -> code

    def test_fstring_loop_resolution_and_braces(self, tmp_path):
        # fleet_j_{a,b}_total rows are satisfied by the resolved
        # f-string loop emissions — no finding in either direction
        syms = {f.symbol for f in self._run(tmp_path)}
        assert not any(s.startswith("fleet_j_") for s in syms)

    def test_clean_when_reconciled(self, tmp_path):
        doc = _DOC_CATALOGUE.replace(
            "| `fleet_ghost_total` | counter |",
            "| `fleet_undoc_total` | counter |").replace(
            "`PADDLE_TPU_GHOST_KNOB` is mentioned here only.",
            "`PADDLE_TPU_UNDOC_KNOB` is the only knob.")
        assert self._run(tmp_path, doc=doc) == []


# ------------------------------------------------------- driver contracts

class TestSuppressions:
    """Every rule must honor its inline suppression (the acceptance
    bar: one fixture proving it fires is above; one proving the
    suppression works is here)."""

    CASES = {
        "TRC01": ("pkg/mod.py", """
            import jax
            f = jax.jit(lambda x: x)  # tpulint: disable=TRC01
        """),
        "TRC02": ("pkg/mod.py", """
            import jax
            import time

            @jax.jit  # tpulint: disable=TRC01
            def step(x):
                # tpulint: disable-next-line=TRC02
                return x + time.time()
        """),
        "DUR01": ("pkg/mod.py", """
            def write(GOLDEN, doc):
                # tpulint: disable-next-line=DUR01
                with open(GOLDEN, "w") as f:
                    f.write(doc)
        """),
        "CON01": ("paddle_tpu/observability/metrics.py", """
            import threading

            class Store:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._data = {}

                def put(self, k, v):
                    with self._lock:
                        self._data[k] = v

                def get(self, k):
                    # tpulint: disable-next-line=CON01
                    return self._data.get(k)
        """),
        "OBS01": ("paddle_tpu/observability/x.py", """
            import json

            def export(doc, f):
                json.dump(doc, f)  # tpulint: disable=OBS01
        """),
        "DOC01": ("pkg/mod.py", """
            import os
            # tpulint: disable-next-line=DOC01
            K = os.environ.get("PADDLE_TPU_SUPPRESSED_KNOB")
        """),
    }

    def test_each_rule_suppressible(self, tmp_path):
        for rule, (rel, src) in self.CASES.items():
            root = tmp_path / rule
            _tree(root, {rel: src})
            rep = _lint(root, [rel.split("/")[0]]
                        if "/" in rel else [rel])
            leaks = [f for f in rep["findings"] if f["rule"] == rule]
            assert leaks == [], (rule, leaks)
            assert rep["suppressed"] >= 1, rule

    def test_suppression_is_rule_scoped(self, tmp_path):
        # disabling OBS01 must not hide an unrelated rule on the line
        _tree(tmp_path, {"pkg/mod.py": """
            import jax
            f = jax.jit(lambda x: x)  # tpulint: disable=OBS01
        """})
        rep = _lint(tmp_path, ["pkg"])
        assert [f["rule"] for f in rep["findings"]] == ["TRC01"]


class TestBaseline:
    VIOLATION = """
        import jax


        def build(fn):
            return jax.jit(fn)
    """

    def _baseline(self):
        return Baseline([{"rule": "TRC01", "path": "pkg/mod.py",
                          "qualname": "build", "symbol": "jax.jit",
                          "justification": "fixture"}])

    def test_matches_on_rule_and_qualname_not_line(self, tmp_path):
        _tree(tmp_path, {"pkg/mod.py": self.VIOLATION})
        rep = _lint(tmp_path, ["pkg"], baseline=self._baseline())
        assert rep["non_baselined"] == 0 and rep["baselined"] == 1

        # drift the finding 6 lines down: the baseline must still hold
        drifted = "# pad\n" * 6 + textwrap.dedent(self.VIOLATION)
        (tmp_path / "pkg" / "mod.py").write_text(drifted)
        rep2 = _lint(tmp_path, ["pkg"], baseline=self._baseline())
        assert rep2["non_baselined"] == 0 and rep2["baselined"] == 1
        assert rep2["findings"][0]["line"] \
            == rep["findings"][0]["line"] + 6

    def test_unused_entries_are_reported(self, tmp_path):
        _tree(tmp_path, {"pkg/mod.py": "x = 1\n"})
        rep = _lint(tmp_path, ["pkg"], baseline=self._baseline())
        assert len(rep["unused_baseline"]) == 1

    def test_syntax_error_is_a_gate_failure(self, tmp_path):
        _tree(tmp_path, {"pkg/mod.py": "def broken(:\n"})
        rep = _lint(tmp_path, ["pkg"])
        assert rep["non_baselined"] == 1
        assert rep["findings"][0]["rule"] == "PARSE"

    def test_missing_target_is_a_gate_failure(self, tmp_path):
        # a typo'd CI path must trip the gate loudly, not scan zero
        # files and read as green (or bury itself under a DOC01 storm)
        _tree(tmp_path, {"pkg/mod.py": "x = 1\n"})
        rep = _lint(tmp_path, ["pgk"])   # typo
        assert rep["non_baselined"] == 1
        f = rep["findings"][0]
        assert (f["rule"], f["symbol"]) == ("PARSE", "missing-target")
        assert "pgk" in f["message"]

    def test_zero_py_target_is_a_gate_failure(self, tmp_path):
        # existing-but-barren targets are the same vacuous-green
        # class: a non-.py file and a dir that lost its sources must
        # both trip, a dir with sources must not
        _tree(tmp_path, {"script": "x = 1\n",
                         "hollow/README.md": "no code here\n",
                         "pkg/mod.py": "x = 1\n"})
        rep = _lint(tmp_path, ["script", "hollow", "pkg"])
        assert rep["files_scanned"] == 1
        assert sorted(f["path"] for f in rep["findings"]) \
            == ["hollow", "script"]
        assert all(f["symbol"] == "missing-target"
                   for f in rep["findings"])


# ------------------------------------------------------------ tier-1 bar

class TestRepoIsClean:
    def test_full_repo_zero_non_baselined(self):
        """THE contract: paddle_tpu/ + tools/ + bench.py lint clean
        against the committed baseline — a new violation fails tier-1
        before it can fail a chaos drill."""
        rep = run_lint(root=str(REPO), baseline=load_baseline())
        fresh = [f for f in rep["findings"] if not f["baselined"]]
        assert fresh == [], "\n".join(
            f"{f['path']}:{f['line']}: {f['rule']} {f['message']}"
            for f in fresh)
        assert rep["files_scanned"] > 150
        assert set(rep["rules_run"]) == {"TRC01", "TRC02", "DUR01",
                                         "CON01", "OBS01", "DOC01",
                                         "MEM01"}

    def test_committed_baseline_has_no_dead_entries(self):
        rep = run_lint(root=str(REPO), baseline=load_baseline())
        assert rep["unused_baseline"] == [], (
            "baseline entries whose findings no longer exist — "
            "delete them, the debt is paid")

    def test_committed_baseline_is_justified(self):
        bl = load_baseline()
        for e in bl.entries:
            j = e.get("justification", "")
            assert j and "UNREVIEWED" not in j, e


# ------------------------------------------------------------ the CLI gate

def _cli(args, **kw):
    return subprocess.run(
        [sys.executable, "-m", "tools.tpulint", *args],
        cwd=str(REPO), capture_output=True, text=True,
        timeout=120, **kw)


class TestCampaignGate:
    """The CLI's exit status as a gate, proven in BOTH directions from
    the committed fixtures (tests/fixtures/tpulint): the seeded
    violation tree MUST trip (exit 1), its clean twin MUST pass."""

    def test_seeded_violations_trip_the_gate(self, tmp_path):
        p = _cli(["--root", str(FIXTURES), "bad",
                  "--report", str(tmp_path / "lint_report.json")])
        assert p.returncode == 1, p.stdout + p.stderr
        verdict = json.loads(p.stdout.strip().splitlines()[-1])
        assert verdict["ok"] is False
        assert verdict["non_baselined"] >= 4
        report = json.loads((tmp_path / "lint_report.json")
                            .read_text())
        assert {"TRC01", "TRC02", "DUR01", "DOC01"} \
            <= set(report["counts"])

    def test_clean_fixture_passes_the_gate(self, tmp_path):
        p = _cli(["--root", str(FIXTURES), "good",
                  "--report", str(tmp_path / "lint_report.json")])
        assert p.returncode == 0, p.stdout + p.stderr
        verdict = json.loads(p.stdout.strip().splitlines()[-1])
        assert verdict["ok"] is True and verdict["non_baselined"] == 0

    def test_update_baseline_refuses_filtered_run(self, tmp_path):
        # --update-baseline from a --rule/paths-filtered run would
        # rewrite baseline.json from a SLICE of the findings, silently
        # deleting every other rule's entries and their justifications
        for extra in (["--rule", "DUR01"], ["paddle_tpu"]):
            p = _cli([*extra, "--update-baseline",
                      "--baseline", str(tmp_path / "bl.json")])
            assert p.returncode == 2, (extra, p.stdout, p.stderr)
            assert "FULL run" in p.stderr
            assert not (tmp_path / "bl.json").exists()

    def test_update_baseline_refuses_foreign_root(self, tmp_path):
        # --root without an explicit --baseline would rewrite the
        # COMMITTED baseline from a tree where DEFAULT_TARGETS don't
        # even exist (3 missing-target rows over 10 justifications)
        p = _cli(["--root", str(tmp_path), "--update-baseline"])
        assert p.returncode == 2, (p.stdout, p.stderr)
        assert "foreign" in p.stderr

    def test_update_baseline_never_grandfathers_parse(self, tmp_path):
        # a baselined syntax error's key carries no content, so it
        # would match EVERY future syntax error in that file — the
        # gate must stay red until the file parses again
        from tools.tpulint.core import write_baseline, Finding
        fs = [Finding("PARSE", "pkg/mod.py", 1, 0, "<module>",
                      "syntax", "SyntaxError: x"),
              Finding("TRC01", "pkg/mod.py", 3, 0, "f", "jax.jit",
                      "raw jit"),
              Finding("CON01", "pkg/mod.py", 1, 0, "<module>",
                      "checker-error", "checker crashed: Boom")]
        path = tmp_path / "bl.json"
        _, n, skipped = write_baseline(fs, path=str(path))
        assert (n, skipped) == (1, 2)   # the honest CLI verdict
        doc = json.loads(path.read_text())
        assert [e["rule"] for e in doc["entries"]] == ["TRC01"]

    def test_unused_reporting_is_scope_aware(self, tmp_path):
        # a --rule/path-filtered run never sees the other rules' or
        # paths' findings — calling their live entries "unused debt"
        # invites deleting justifications the full gate still needs
        _tree(tmp_path, {"pkg/mod.py": """
            import jax


            def build(fn):
                return jax.jit(fn)
        """, "other/mod.py": "x = 1\n"})
        bl = Baseline([
            {"rule": "TRC01", "path": "pkg/mod.py",
             "qualname": "build", "symbol": "jax.jit",
             "justification": "live"},
            {"rule": "OBS01", "path": "pkg/mod.py",
             "qualname": "emit", "symbol": "json.dumps",
             "justification": "other rule"},
            {"rule": "TRC01", "path": "elsewhere/mod.py",
             "qualname": "f", "symbol": "jax.jit",
             "justification": "other path"}])
        rep = run_lint(paths=["pkg"], rules=["TRC01"],
                       root=str(tmp_path), baseline=bl)
        assert rep["baselined"] == 1
        assert rep["unused_baseline"] == []   # out-of-scope ≠ dead
        # a genuinely dead in-scope entry still reports
        bl2 = Baseline([
            {"rule": "TRC01", "path": "pkg/gone.py",
             "qualname": "f", "symbol": "jax.jit",
             "justification": "dead"}])
        rep2 = run_lint(paths=["pkg"], rules=["TRC01"],
                        root=str(tmp_path), baseline=bl2)
        assert len(rep2["unused_baseline"]) == 1
