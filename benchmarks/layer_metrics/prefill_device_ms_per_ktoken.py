"""Device time of the full prefill programs per thousand tokens of their
buckets: summed seconds of the modules `jit_prefill_<bucket>` over summed
executions x bucket / 1000."""
from benchmarks import named


def read(run, trace):
    found = named.modules(trace, r"jit_prefill_(\d+)")
    tokens = sum(count * int(m.group(1)) for m, _, count in found)
    if not tokens:
        return None
    return sum(seconds for _, seconds, _ in found) / tokens * 1e6
