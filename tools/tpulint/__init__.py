"""tpu-lint — AST invariant checkers for this repo's hard-won
contracts (trace-safety, durability, concurrency, telemetry validity,
doc-catalogue sync). Stdlib-only; see docs/static_analysis.md.

Entry points: ``python -m tools.tpulint`` (CLI) and ``run_lint()``
(in-process — what tests/test_tpulint.py drives).
"""
from .core import (Baseline, Finding, load_baseline, run_lint,  # noqa: F401
                   write_baseline, write_report)
from .rules import RULES, active_rules  # noqa: F401
