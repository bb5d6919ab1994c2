"""Seconds of the set-up's programs from the end of their last build stage to
their first outputs: the first run on the device and what the host does
between."""
from benchmarks import setup_read


def read(run, trace):
    return setup_read.total(lambda e: e["stages"]["first_run_s"])
