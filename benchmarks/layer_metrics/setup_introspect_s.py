"""Seconds of the set-up's introspection replays (`introspect.capture_site`:
the program lowered and compiled again ahead of time for its cost and memory
analysis)."""
from benchmarks import setup_read


def read(run, trace):
    return setup_read.total(lambda e: setup_read.replay(e, "wall_s"))
