"""Operations and bytes of LFM2-8B-A1B's served work, from the
configuration file alone (`configs/lfm2-8b-a1b-l16.json`). Every stored
matrix outside the experts once a step (the norms' gains, a few thousand
numbers a layer, are left out), an expert's three matrices per expert that
got a row (bytes) and per counted assignment (operations), the attention
layers' live K/V rows, the convolution layers' state read and written,
the tied head once a prompt."""


def kinds(cfg):
    """(convolution layers, attention layers)."""
    n_attn = sum(t == "full_attention" for t in cfg["layer_types"])
    return cfg["num_hidden_layers"] - n_attn, n_attn


def expert_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def conv_params(cfg):
    """One short convolution: W_in (h x 3h), the taps, W_out."""
    h = cfg["hidden_size"]
    return h * 3 * h + cfg["conv_L_cache"] * h + h * h


def attention_params(cfg):
    """One attention layer: W_q, W_o (h x h), W_k, W_v (h x K/V heads x d)."""
    h = cfg["hidden_size"]
    return 2 * h * h + 2 * h * cfg["num_key_value_heads"] * head_dim(cfg)


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_params(cfg):
    """What every token multiplies with outside the experts: each layer's
    operator, the dense FFN of the leading layers, the routers, and the
    head (the embedding's matrix: looked up going in, read coming out)."""
    h = cfg["hidden_size"]
    n_conv, n_attn = kinds(cfg)
    return (n_conv * conv_params(cfg) + n_attn * attention_params(cfg)
            + cfg["num_dense_layers"] * 3 * h * cfg["intermediate_size"]
            + expert_layers(cfg) * h * cfg["num_experts"]
            + h * cfg["vocab_size"])


def kv_row_numbers(cfg):
    """Numbers cached per token over all attention layers: keys and values."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * kinds(cfg)[1]


def state_numbers(cfg):
    """Numbers of state per slot over all convolution layers."""
    return cfg["conv_L_cache"] * cfg["hidden_size"] * kinds(cfg)[0]


def decode_attention_ops(cfg, live_tokens):
    """Every query head against each live token's key, and the
    probabilities against its value, all attention layers."""
    return (4 * cfg["num_attention_heads"] * head_dim(cfg) * live_tokens
            * kinds(cfg)[1])


def decode_step_bytes(cfg, weight_bytes, cache_bytes, live_tokens,
                      live_slots, experts_hit):
    """What one decode step has to move: the weights outside the experts
    once, each expert that got a row, over all expert layers
    (`experts_hit`), the live K/V rows, and the live slots' state in and
    out."""
    return ((dense_params(cfg) + experts_hit * expert_params(cfg))
            * weight_bytes
            + (live_tokens * kv_row_numbers(cfg)
               + 2 * live_slots * state_numbers(cfg)) * cache_bytes)


def decode_step_ops(cfg, live_slots, live_tokens, assignments):
    return (2 * dense_params(cfg) * live_slots
            + 2 * expert_params(cfg) * assignments
            + decode_attention_ops(cfg, live_tokens))


def serve_flops(cfg, prompt_lens, decode_contexts, assignments):
    """Operations the served work needs. `decode_contexts` is (the sum of
    the contexts of all decoded tokens, their count); `assignments` the
    (token, expert) pairs the program counted, prefill and decode. Prefill
    attention is the causal half."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    body = dense_params(cfg) - head
    ctx_sum, n_dec = decode_contexts
    attn = 2 * cfg["num_attention_heads"] * head_dim(cfg) * kinds(cfg)[1]
    prefill = sum(2 * body * p + 2 * head + attn * p * p
                  for p in prompt_lens)
    decode = 2 * (body + head) * n_dec + decode_attention_ops(cfg, ctx_sum)
    return prefill + decode + 2 * expert_params(cfg) * assignments
