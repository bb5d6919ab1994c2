"""Share of its roofline that the held experts' part of a decode step
reaches: the bytes of the experts that got a row and of the rows in and
out, against the assignments' operations (benchmarks/kernels/moe_experts.py),
over the `moe_experts` scope's device time per step plus the grouped
products' kernels'."""
from benchmarks import axk1_read as r
from benchmarks.kernels import moe_experts as k


def read(run, trace):
    per = r.routing_per_step(run)
    took = r.scope_ms_per_step(run, trace, k.SCOPE, k.PATTERN)
    if per is None or took is None:
        return None
    cfg = run["config"]
    sh = k.shapes(cfg, r.BYTES[cfg["serve"]["weight_dtype"]],
                  per["moe_experts_hit"], per["moe_local_assignments"])
    return 100.0 * r.least_ms(k.bytes(sh), k.ops(sh), run["peak"]) / took
