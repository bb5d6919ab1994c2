"""Share of its roofline that one Olmo-Hybrid decode step reaches: the
least time to read every stored matrix once (the embedding a row a slot),
the live slots' Gated DeltaNet state in and out and the live K/V rows, all
at the width the loop reads them, or to do the step's operations
(benchmarks/kernels/olmo_hybrid_step.py), over `jit_decode`'s device time
per step."""
from benchmarks import axk1_read as r
from benchmarks import olmo_hybrid_read
from benchmarks.kernels import olmo_hybrid_step as k


def read(run, trace):
    cfg = olmo_hybrid_read.config_of(run)
    steps = r.traced_steps(run, trace) if cfg else None
    if steps is None:
        return None
    least = r.least_ms(
        k.decode_step_bytes(cfg, r.BYTES[cfg["serve"]["weight_dtype"]],
                            r.BYTES[run["engine"]["cache_dtype"]],
                            run["mean_live_tokens"], run["mean_live_slots"]),
        k.decode_step_ops(cfg, run["mean_live_slots"],
                          run["mean_live_tokens"]), run["peak"])
    return 100.0 * least / (steps[0] / steps[1] * 1e3)
