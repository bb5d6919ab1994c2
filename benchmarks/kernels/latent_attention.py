"""Absorbed-form latent attention over the paged cache in one decode step,
all layers: each live token's cached row read once (W = kv_lora_rank +
qk_rope_head_dim numbers at the cache's width) against every head's query
over the W numbers and its probabilities over the first kv_lora_rank."""

SCOPE = "latent_attention"


def shapes(cfg, kv_bytes, live_tokens):
    return {"heads": cfg["num_attention_heads"], "rank": cfg["kv_lora_rank"],
            "width": cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
            "layers": cfg["num_hidden_layers"], "kv_bytes": kv_bytes,
            "live_tokens": live_tokens}


def bytes(sh):
    return sh["live_tokens"] * sh["width"] * sh["kv_bytes"] * sh["layers"]


def ops(sh):
    return (2 * sh["heads"] * (sh["width"] + sh["rank"]) * sh["live_tokens"]
            * sh["layers"])
