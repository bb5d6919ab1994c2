"""Median duration of the engine's `prefill_*` spans in the window."""
import statistics


def read(run, trace):
    d = [(t1 - t0) * 1e3 for name, t0, t1 in run["program_spans"]
         if name.startswith(("prefill_", "tail_prefill_"))]
    return statistics.median(d) if d else None
