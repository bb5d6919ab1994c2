"""Operations and bytes of Olmo-Hybrid-7B's served work, from the
configuration file alone (`configs/olmo-hybrid-7b-l16.json`). A decode step
reads every stored matrix once except the embedding, of which it reads one
row a slot; the Gated DeltaNet layers' state of every live slot read and
written (`kernels/gated_delta.py`); the full-attention layers' live K/V
rows. A prompt's operations include the chunked scan's products
(nlp/olmo_hybrid.chunked_gated_delta: chunks of `CHUNK`)."""
from benchmarks.kernels import gated_delta

CHUNK = 64


def kinds(cfg):
    """(Gated DeltaNet layers, full-attention layers)."""
    n_attn = sum(t == "full_attention" for t in cfg["layer_types"])
    return cfg["num_hidden_layers"] - n_attn, n_attn


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def gated_delta_params(cfg):
    """One Gated DeltaNet mixer: q, k (h x heads dk), v, z (h x heads dv),
    o (heads dv x h), a, b (h x heads), the taps, A_log, dt_bias, the
    gain."""
    h, heads = cfg["hidden_size"], cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return (2 * h * heads * dk + 3 * h * heads * dv + 2 * h * heads
            + cfg["linear_conv_kernel_dim"] * heads * (2 * dk + dv)
            + 2 * heads + dv)


def attention_params(cfg):
    """One full-attention mixer: W_q, W_o, W_k, W_v and the two norms."""
    h = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * head_dim(cfg)
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    return 2 * h * q + 2 * h * kv + q + kv


def layer_params(cfg, kind):
    """A layer of `kind`: its mixer, the MLP, the two norms."""
    h = cfg["hidden_size"]
    mixer = attention_params(cfg) if kind == "full_attention" \
        else gated_delta_params(cfg)
    return mixer + 3 * h * cfg["intermediate_size"] + 2 * h


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def body_params(cfg):
    """Every layer and the final norm; not the embedding, not the head."""
    return sum(layer_params(cfg, t) for t in cfg["layer_types"]) \
        + cfg["hidden_size"]


def total_params(cfg):
    return body_params(cfg) + 2 * head_params(cfg)


def kv_row_numbers(cfg):
    """Numbers cached per token over all attention layers: keys and values."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * kinds(cfg)[1]


def decode_attention_ops(cfg, live_tokens):
    return (4 * cfg["num_attention_heads"] * head_dim(cfg) * live_tokens
            * kinds(cfg)[1])


def decode_step_bytes(cfg, weight_bytes, cache_bytes, live_tokens,
                      live_slots):
    sh = gated_delta.shapes(cfg, cache_bytes, live_slots)
    return ((body_params(cfg) + head_params(cfg)
             + live_slots * cfg["hidden_size"]) * weight_bytes
            + live_tokens * kv_row_numbers(cfg) * cache_bytes
            + 2 * live_slots * sh["layers"] * gated_delta.state_bytes(sh))


def decode_step_ops(cfg, live_slots, live_tokens):
    sh = gated_delta.shapes(cfg, 2, live_slots)
    return (2 * (body_params(cfg) + head_params(cfg)) * live_slots
            + decode_attention_ops(cfg, live_tokens) + gated_delta.ops(sh))


def scan_ops_per_token(cfg):
    """The chunked scan's products a position, all Gated DeltaNet layers:
    within its chunk k k^T and q k^T (2 C dk each), the triangular solve
    against [beta v | beta k] (C (dv + dk)), q k^T against the new values
    (2 C dv); against the state w S, q S and k^T v' (2 dk dv each)."""
    heads = cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    per_head = CHUNK * (5 * dk + 3 * dv) + 6 * dk * dv
    return heads * per_head * kinds(cfg)[0]


def serve_flops(cfg, prompt_lens, decode_contexts):
    """Operations the served work needs. `decode_contexts` is (the sum of
    the contexts of all decoded tokens, their count). Prefill attention is
    the causal half; a prompt's head is its last position's."""
    body = 2 * body_params(cfg)
    head = 2 * head_params(cfg)
    ctx_sum, n_dec = decode_contexts
    attn = 2 * cfg["num_attention_heads"] * head_dim(cfg) * kinds(cfg)[1]
    recur = gated_delta.ops(gated_delta.shapes(cfg, 2, 1))
    prefill = sum((body + scan_ops_per_token(cfg)) * p + head + attn * p * p
                  for p in prompt_lens)
    decode = (body + head + recur) * n_dec \
        + decode_attention_ops(cfg, ctx_sum)
    return prefill + decode
