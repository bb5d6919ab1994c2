"""Operations and bytes of A.X-K1's served work, from the configuration
file alone (`configs/axk1-ep16.json`: the sizes as run, the share under
`published` / `deployment`). Matrices a token multiplies outside the routed
experts, an expert's three matrices per counted assignment, attention by
context, the head once a prompt."""

WEIGHT_BYTES = {"float32": 4, "bfloat16": 2}


def expert_layers(cfg):
    return sum(1 for i in range(cfg["num_hidden_layers"])
               if i >= cfg["first_k_dense_replace"]
               and i % cfg["moe_layer_freq"] == 0)


def attention_params(cfg):
    """One layer's attention matrices: W_qa, W_qb, W_kva, W_kvb, W_o. The
    absorbed form multiplies with W_kvb's two halves, the same count."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    qr, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (h * qr + qr * heads * (dn + dr) + h * (r + dr)
            + r * heads * (dn + dv) + heads * dv * h)


def expert_params(cfg):
    """One expert, shared or routed: three h x m matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_params(cfg):
    """What every token multiplies with outside the routed experts:
    attention in every layer, the dense FFN of the leading layers, router
    and shared expert of the expert layers, the output head."""
    h, L, E = cfg["hidden_size"], cfg["num_hidden_layers"], \
        expert_layers(cfg)
    router = h * cfg["published"]["n_routed_experts"]
    return (L * attention_params(cfg)
            + (L - E) * 3 * h * cfg["intermediate_size"]
            + E * (router + cfg["n_shared_experts"] * expert_params(cfg))
            + h * cfg["vocab_size"])


def latent_width(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def decode_attention_ops(cfg, live_tokens):
    """Absorbed form, all layers: every head's query against the W cached
    numbers of each live token, and the probabilities against its c_kv."""
    return (2 * cfg["num_attention_heads"]
            * (latent_width(cfg) + cfg["kv_lora_rank"])
            * live_tokens * cfg["num_hidden_layers"])


def serve_flops(cfg, prompt_lens, decode_contexts, assignments):
    """Operations the served work needs. `decode_contexts` is (the sum of
    the contexts of all decoded tokens, their count); `assignments` the
    (token, expert) pairs that fell on held experts, prefill and decode.
    Prefill attention is the expanded form's causal half."""
    L, heads = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    body = dense_params(cfg) - cfg["hidden_size"] * cfg["vocab_size"]
    head = cfg["hidden_size"] * cfg["vocab_size"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    ctx_sum, n_dec = decode_contexts
    prefill = sum(2 * body * p + 2 * head
                  + L * heads * (qk + cfg["v_head_dim"]) * p * p
                  for p in prompt_lens)
    decode = 2 * (body + head) * n_dec + decode_attention_ops(cfg, ctx_sum)
    return prefill + decode + 2 * expert_params(cfg) * assignments


def decode_step_bytes(cfg, weight_bytes, kv_bytes, live_tokens,
                      experts_hit):
    """What one decode step has to read: the weights outside the routed
    experts once (the embedding is looked up, not read), each routed
    expert that got a row, over all expert layers (`experts_hit`), and
    the live latent cache."""
    return ((dense_params(cfg) + experts_hit * expert_params(cfg))
            * weight_bytes
            + live_tokens * latent_width(cfg) * cfg["num_hidden_layers"]
            * kv_bytes)


def decode_step_ops(cfg, live_slots, live_tokens, assignments):
    return (2 * dense_params(cfg) * live_slots
            + 2 * expert_params(cfg) * assignments
            + decode_attention_ops(cfg, live_tokens))
