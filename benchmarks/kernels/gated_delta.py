"""The Gated DeltaNet layers' own part of one decode step, all of them: what
the `gated_delta` scope of `jit_decode` has to move and compute
(nlp/olmo_hybrid.py: the convolution's step, the l2 norms, the decay, the
delta update, the readout and the gated norm; the layers' matrices are not
under it). Bytes: each live slot's state read once and written once at
float32, its convolution rows read and written at the cache's width, the
per-token vectors in (the projections' q, k, v, z, a, b) and out (the gated
output) at float32, and the taps once a layer. Operations: the update and
readout's multiplies and adds over the state, the taps over the channels."""

SCOPE = "gated_delta"


def shapes(cfg, cache_bytes, live_slots):
    heads = cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return {"layers": sum(t == "linear_attention"
                          for t in cfg["layer_types"]),
            "heads": heads, "dk": dk, "dv": dv,
            "channels": heads * (2 * dk + dv),
            "taps": cfg["linear_conv_kernel_dim"],
            "cache_bytes": cache_bytes, "live_slots": live_slots}


def state_bytes(sh):
    """One slot's rows of one layer: the float32 state and the
    convolution's last `taps - 1` inputs."""
    return (4 * sh["heads"] * sh["dk"] * sh["dv"]
            + (sh["taps"] - 1) * sh["channels"] * sh["cache_bytes"])


def bytes(sh):
    vectors = 4 * (sh["channels"] + 2 * sh["heads"]
                   + 2 * sh["heads"] * sh["dv"])
    per_slot = 2 * state_bytes(sh) + vectors
    taps = 2 * sh["taps"] * sh["channels"]
    return sh["layers"] * (sh["live_slots"] * per_slot + taps)


def ops(sh):
    """Per slot and layer: the decay of the state (1), S^T k and S^T q
    (2 each), the rank-one update (2), over every number of the state;
    the taps (2 a tap and channel)."""
    state = 7 * sh["heads"] * sh["dk"] * sh["dv"]
    return sh["layers"] * sh["live_slots"] * (
        state + 2 * sh["taps"] * sh["channels"])
