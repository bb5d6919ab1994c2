"""Seeded tpulint violations — the gate-trip fixture.

tests/test_tpulint.py runs ``python -m tools.tpulint --root
tests/fixtures/tpulint bad`` and asserts exit 1 with exactly this
finding mix; the ``good/`` twin must exit 0. Together they prove the
CLI's exit-status gate in BOTH directions without touching the
shipping tree. (tests/ is outside the default scan targets, so these
seeds can never leak into the real repo sweep.)
"""
import os

import jax


def untraced(fn):
    return jax.jit(fn)                                    # TRC01


def clock_in_trace():
    import time

    def body(x):
        return x + time.time()                            # TRC02

    return jax.jit(body)                                  # TRC01


def clobber_golden(doc):
    golden = os.path.join("tools", "golden", "wave.json")
    with open(golden, "w") as f:                          # DUR01
        f.write(doc)


def undocumented_knob():
    return os.environ.get("PADDLE_TPU_SEEDED_BOGUS")      # DOC01
