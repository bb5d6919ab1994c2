"""Seconds the set-up spent building and first-running its programs: each
program's staged record from its call to its first outputs (trace, lowering,
backend compile or cache load, first run) plus its introspection replay."""
from benchmarks import setup_read


def read(run, trace):
    return setup_read.total(
        lambda e: e["t1"] - e["t0"] + setup_read.replay(e, "wall_s"))
