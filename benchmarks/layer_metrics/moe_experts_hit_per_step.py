"""Held experts that got at least one row, per decode step and expert
layer, as the decode program counted them (`ServingEngine.aux_counts`)."""
from benchmarks import axk1_read as r


def read(run, trace):
    per = r.routing_per_step(run)
    if per is None or not run.get("expert_layers"):
        return None
    return per["moe_experts_hit"] / run["expert_layers"]
