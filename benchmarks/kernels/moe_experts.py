"""The held experts' grouped products in one decode step, all expert
layers: the bytes of each expert that got a row (its three matrices), the
rows in (the stored width) and out (float32), and the operations of the
assignments. The products are `jax.lax.ragged_dot`, which the TPU compiler
turns into kernels it names itself (`ragged-dot-...`), outside every scope:
PATTERN finds them in the trace beside the `moe_experts` scope's
operations (sort, gather, activation, combine)."""

PATTERN = "ragged-dot"
SCOPE = "moe_experts"


def shapes(cfg, weight_bytes, experts_hit, assignments):
    return {"h": cfg["hidden_size"], "m": cfg["moe_intermediate_size"],
            "weight_bytes": weight_bytes, "experts_hit": experts_hit,
            "assignments": assignments}


def bytes(sh):
    h, m = sh["h"], sh["m"]
    return (sh["experts_hit"] * 3 * h * m * sh["weight_bytes"]
            + sh["assignments"] * h * (sh["weight_bytes"] + 4))


def ops(sh):
    return 2 * 3 * sh["h"] * sh["m"] * sh["assignments"]
