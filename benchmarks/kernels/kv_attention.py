"""Grouped-query attention over the paged K/V cache in one decode step,
all attention layers: each live token's key and value rows read once at
the cache's width, against every query head's score and sum over them.
In the trace it is the `paged_attention` scope of `jit_decode`, which
holds the Pallas kernel `flash_decode` (ops/pallas/flash_decode.py) when
the engine is built with `use_flash=True`."""

SCOPE = "paged_attention"
KERNEL = "flash_decode"


def shapes(cfg, kv_bytes, live_tokens):
    layers = sum(t == "full_attention" for t in cfg["layer_types"])
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "layers": layers, "kv_bytes": kv_bytes,
            "live_tokens": live_tokens}


def bytes(sh):
    return (sh["live_tokens"] * 2 * sh["kv_heads"] * sh["head_dim"]
            * sh["kv_bytes"] * sh["layers"])


def ops(sh):
    return 4 * sh["heads"] * sh["head_dim"] * sh["live_tokens"] * sh["layers"]
