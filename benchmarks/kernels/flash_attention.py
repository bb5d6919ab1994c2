"""Causal flash attention, forward and backward, of one train step:
operations and bytes from the shapes, and how its kernels are found in the
device trace. The kernels of `ops/pallas/flash_attention.py` (`_fwd_kernel`,
`_dq_kernel`, `_dkv_kernel`) carry no name there: each is a `custom-call` to
`tpu_custom_call`, and in the bf16 train step no other Pallas kernel runs
(72 calls a step, three per layer)."""

PATTERN = r"\[tpu_custom_call\]"


def shapes(cfg, batch, seq):
    return {"batch": batch, "seq": seq, "heads": cfg["num_attention_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "layers": cfg["num_hidden_layers"], "dtype_bytes": 2}


def ops(sh):
    """Seven products of [s, d] by [d, s] size per head: two forward
    (scores, values), five backward (scores again, dP, dQ, dK, dV), each
    2 s^2 d, halved once for the causal mask."""
    per_head = 7 * 2 * sh["seq"] ** 2 * sh["head_dim"] / 2
    return sh["layers"] * sh["batch"] * sh["heads"] * per_head


def bytes(sh):
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv: twelve [b, s, H, d] arrays in bfloat16."""
    one = sh["batch"] * sh["seq"] * sh["heads"] * sh["head_dim"] \
        * sh["dtype_bytes"]
    return sh["layers"] * 12 * one
