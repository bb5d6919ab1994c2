"""tpulint core — findings, suppressions, baseline, per-file driver.

The analyzer half of the suite: rules live in ``rules.py``, the CLI in
``__main__.py``. Everything here is stdlib-only (``ast``) so
the linter runs in CI shells and the tier-1 test process alike, and
never pays an accelerator import.

Design contracts (docs/static_analysis.md is the operator page):

- **Findings are line-drift-stable.** A finding's identity is
  ``(rule, path, qualname, symbol)`` — the enclosing function/class
  qualname plus a stable symbol (the offending call/name), NEVER the
  line number. Reformatting a file cannot invalidate the baseline.
- **Suppressions are inline and rule-scoped.** ``# tpulint:
  disable=RULE[,RULE]`` on the finding's first line, or
  ``# tpulint: disable-next-line=RULE`` on the line above. A
  suppression silences exactly the named rules, nothing else.
- **The baseline grandfathers, never hides.** ``baseline.json``
  entries carry a one-line justification; matched findings are still
  reported (``baselined: true``) and counted, they just don't fail
  the gate. Unused baseline entries are reported so the file can only
  shrink as debt is paid down.
"""
from __future__ import annotations

import ast
import json
import os
import re

__all__ = ["Finding", "FileCtx", "Baseline", "run_lint",
           "load_baseline", "write_baseline", "write_report",
           "DEFAULT_TARGETS", "repo_root"]

# scan scope when the CLI is given no paths: the shipping source
# (tests/ is deliberately out — fixtures there seed violations)
DEFAULT_TARGETS = ("paddle_tpu", "tools", "bench.py")

_SUPPRESS_RE = re.compile(
    r"#\s*tpulint:\s*(disable|disable-next-line)="
    r"([A-Z][A-Z0-9]*(?:\s*,\s*[A-Z][A-Z0-9]*)*)")


def repo_root():
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


class Finding:
    """One rule violation. Identity (``key``) is line-drift-stable:
    rule + file + enclosing qualname + symbol — never the line."""

    __slots__ = ("rule", "path", "line", "col", "qualname", "symbol",
                 "message", "baselined")

    def __init__(self, rule, path, line, col, qualname, symbol,
                 message):
        self.rule = rule
        self.path = path          # repo-relative, posix separators
        self.line = int(line)
        self.col = int(col)
        self.qualname = qualname or "<module>"
        self.symbol = symbol
        self.message = message
        self.baselined = False

    def key(self):
        return (self.rule, self.path, self.qualname, self.symbol)

    def to_json(self):
        return {"rule": self.rule, "path": self.path,
                "line": self.line, "col": self.col,
                "qualname": self.qualname, "symbol": self.symbol,
                "message": self.message, "baselined": self.baselined}

    def __repr__(self):
        return (f"{self.path}:{self.line}: {self.rule} "
                f"[{self.qualname}] {self.message}")


class FileCtx:
    """Parsed view of one source file handed to every checker."""

    def __init__(self, abspath, relpath, source, tree):
        self.abspath = abspath
        self.path = relpath
        self.source = source
        self.tree = tree
        self._qualnames = _qualname_map(tree)
        # per-file memo shared across rules (one thread per file, so
        # no lock needed): import facts, parent maps, … — rebuilding
        # these per rule (or per emit call) is O(file²) on a long file
        self.cache = {}

    def parents(self):
        """id(child) -> parent node, built once per file."""
        p = self.cache.get("parents")
        if p is None:
            p = {}
            for node in ast.walk(self.tree):
                for child in ast.iter_child_nodes(node):
                    p[id(child)] = node
            self.cache["parents"] = p
        return p

    def qualname_of(self, node):
        return self._qualnames.get(id(node), "<module>")

    def segment(self, node):
        try:
            return ast.get_source_segment(self.source, node) or ""
        except Exception:  # noqa: BLE001 — cosmetic helper only
            return ""

    def finding(self, rule, node, symbol, message):
        return Finding(rule, self.path, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0),
                       self.qualname_of(node), symbol, message)


def _qualname_map(tree):
    """id(node) -> dotted qualname of the innermost enclosing
    function/class (module-level nodes map to '<module>')."""
    out = {}

    def walk(node, stack):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            stack = stack + [node.name]
        qn = ".".join(stack) if stack else "<module>"
        out[id(node)] = qn
        for child in ast.iter_child_nodes(node):
            walk(child, stack)

    walk(tree, [])
    return out


def _suppressions(source):
    """{line_no: set(rules)} honoring both inline forms."""
    out = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(line)
        if not m:
            continue
        rules = {r.strip() for r in m.group(2).split(",") if r.strip()}
        target = i + 1 if m.group(1) == "disable-next-line" else i
        out.setdefault(target, set()).update(rules)
    return out


# -- baseline ---------------------------------------------------------------

class Baseline:
    def __init__(self, entries):
        self.entries = list(entries)
        self._by_key = {}
        for e in self.entries:
            k = (e["rule"], e["path"], e.get("qualname", "<module>"),
                 e.get("symbol", ""))
            self._by_key[k] = e
        self._used = set()

    def matches(self, finding):
        k = finding.key()
        if k in self._by_key:
            self._used.add(k)
            return True
        return False

    def unused(self):
        return [e for e in self.entries
                if (e["rule"], e["path"], e.get("qualname", "<module>"),
                    e.get("symbol", "")) not in self._used]


def default_baseline_path():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def load_baseline(path=None):
    path = path or default_baseline_path()
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError:
        return Baseline([])
    return Baseline(doc.get("entries", []))


def write_baseline(findings, path=None, previous=None):
    """Regenerate the baseline from current findings, preserving the
    justification of every entry whose key survives; new entries get
    an UNREVIEWED marker that a reviewer must replace or fix.
    Returns (path, n_written, n_skipped) — skipped are PARSE/
    checker-error findings that must be FIXED, never grandfathered
    (the gate stays red until they are)."""
    path = path or default_baseline_path()
    prev = {}
    if previous is not None:
        for e in previous.entries:
            prev[(e["rule"], e["path"], e.get("qualname", "<module>"),
                  e.get("symbol", ""))] = e.get("justification", "")
    entries, seen, skipped = [], set(), 0
    for f in sorted(findings, key=lambda f: f.key()):
        k = f.key()
        if k in seen:
            continue
        seen.add(k)
        if f.rule == "PARSE" or f.symbol == "checker-error":
            # never grandfather an infrastructure failure: its key
            # carries no error content, so one baselined syntax error
            # would mask EVERY future syntax error in that file —
            # fix the file (or the checker), don't baseline it
            skipped += 1
            continue
        entries.append({
            "rule": f.rule, "path": f.path, "qualname": f.qualname,
            "symbol": f.symbol,
            "justification": prev.get(
                k, "UNREVIEWED — justify this grandfathering or fix "
                   "the finding"),
        })
    doc = {"version": 1,
           "comment": "Grandfathered tpulint findings. Match is on "
                      "(rule, path, qualname, symbol) — stable under "
                      "line drift. Every entry needs a one-line "
                      "justification; delete entries as debt is paid.",
           "entries": entries}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    return path, len(entries), skipped


# -- driver -----------------------------------------------------------------

def _collect_files(root, targets):
    """(files, barren): `barren` are targets that contributed zero
    .py files — nonexistent, not-a-.py, or a dir with nothing to
    scan. Each must be a loud gate failure: a typo'd or hollowed-out
    CI path scanning nothing would otherwise read as green (or, for
    DOC01, as a stale-row storm over an empty scan set)."""
    files, barren = [], []
    for t in targets:
        p = os.path.join(root, t)
        n_before = len(files)
        if os.path.isfile(p) and p.endswith(".py"):
            files.append(p)
        elif os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in sorted(dirnames)
                               if d not in ("__pycache__", ".git",
                                            "fixtures")]
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        files.append(os.path.join(dirpath, fn))
        if len(files) == n_before:
            barren.append(t)
    return files, barren


def _parse_one(root, abspath):
    rel = os.path.relpath(abspath, root).replace(os.sep, "/")
    try:
        with open(abspath, encoding="utf-8") as f:
            source = f.read()
        tree = ast.parse(source, filename=rel)
    except (OSError, SyntaxError, ValueError) as e:
        return rel, None, f"{type(e).__name__}: {e}"
    return rel, FileCtx(abspath, rel, source, tree), None


def run_lint(paths=None, rules=None, root=None, baseline=None):
    """Lint `paths` (files/dirs relative to `root`); returns the
    report dict (see write_report). `rules` filters to a subset of
    rule ids; `baseline` a Baseline (default: the committed one)."""
    from . import rules as rules_mod  # late: registry import order
    root = root or repo_root()
    targets = list(paths) if paths else list(DEFAULT_TARGETS)
    baseline = baseline if baseline is not None else load_baseline()
    active = rules_mod.active_rules(rules)
    per_file = [r for r in active if not r.project_level]
    project = [r for r in active if r.project_level]

    files, findings = [], []
    parsed = []
    collected, barren = _collect_files(root, targets)
    for t in barren:
        findings.append(Finding(
            "PARSE", t, 1, 0, "<module>", "missing-target",
            f"lint target {t!r} contributed zero .py files under "
            f"{root} — typo'd or hollowed-out path? (a vacuous scan "
            f"must not pass the gate)"))
    for abspath in collected:
        rel, ctx, err = _parse_one(root, abspath)
        files.append(rel)
        if err is not None:
            findings.append(Finding("PARSE", rel, 1, 0, "<module>",
                                    "syntax", err))
        else:
            parsed.append(ctx)

    def lint_file(ctx):
        out = []
        for r in per_file:
            try:
                out.extend(r.check(ctx) or ())
            except Exception as e:  # noqa: BLE001 — one broken rule
                #                     must not silently pass the file
                out.append(Finding(r.id, ctx.path, 1, 0, "<module>",
                                   "checker-error",
                                   f"checker crashed: "
                                   f"{type(e).__name__}: {e}"))
        return out

    # serial on purpose: the checkers are pure-Python AST walks, so a
    # thread pool is GIL-bound (no speedup, real overhead) — the whole
    # default sweep is single-digit seconds
    for ctx in parsed:
        findings.extend(lint_file(ctx))
    for r in project:
        try:
            findings.extend(r.check_project(parsed, root) or ())
        except Exception as e:  # noqa: BLE001
            findings.append(Finding(r.id, "<project>", 1, 0,
                                    "<module>", "checker-error",
                                    f"checker crashed: "
                                    f"{type(e).__name__}: {e}"))

    # suppression pass (per finding line, against its own file)
    supp_by_path = {c.path: _suppressions(c.source) for c in parsed}
    kept, suppressed = [], 0
    for f in findings:
        rules_at = supp_by_path.get(f.path, {}).get(f.line, ())
        if f.rule in rules_at:
            suppressed += 1
            continue
        kept.append(f)

    non_baselined = 0
    for f in kept:
        f.baselined = baseline.matches(f)
        if not f.baselined:
            non_baselined += 1
    kept.sort(key=lambda f: (f.path, f.line, f.rule))

    by_rule = {}
    for f in kept:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    # only entries this run COULD have matched may be called unused:
    # a --rule/path-filtered run never sees the other rules'/paths'
    # findings, and reporting their entries as dead debt invites
    # deleting live justifications the full gate still needs
    active_ids = {r.id for r in active}
    tnorm = [t.rstrip("/") for t in targets]
    unused = [e for e in baseline.unused()
              if e["rule"] in active_ids
              and any(e["path"] == t or e["path"].startswith(t + "/")
                      for t in tnorm)]
    return {
        "version": 1,
        "tool": "tpulint",
        "targets": targets,
        "files_scanned": len(files),
        "rules_run": [r.id for r in active],
        "findings": [f.to_json() for f in kept],
        "counts": by_rule,
        "suppressed": suppressed,
        "baselined": sum(1 for f in kept if f.baselined),
        "non_baselined": non_baselined,
        "unused_baseline": unused,
        "_findings_objs": kept,   # in-process callers; stripped on dump
    }


def write_report(report, path):
    doc = {k: v for k, v in report.items() if not k.startswith("_")}
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    return path
