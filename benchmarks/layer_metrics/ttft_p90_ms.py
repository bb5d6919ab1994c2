"""90th percentile, over every request sent in the window, of submit to
first token (the end of the request's `prefill_*` span). At full slots it
swings with the order of the prompts (16% between seeds), so it is recorded
here and not judged: each admission's prefill stalls every slot's decoding,
which is how it moves `tpot_p90_ms`."""
from benchmarks.harness import percentile


def read(run, trace):
    return percentile(run["ttft_ms"], 90) if run["ttft_ms"] else None
