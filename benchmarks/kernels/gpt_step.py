"""Operations of a whole GPT step, from the sizes alone (the copy of
`bench.gpt_flops_per_token`, with the parameter count worked out from the
configuration and not read from the program)."""


def param_count(cfg):
    """Parameters of the model; the tied output head counts once."""
    h, f, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    per_layer = 4 * (h * h + h) + (h * f + f) + (f * h + h) + 4 * h
    return (cfg["vocab_size"] * h + cfg["max_position_embeddings"] * h
            + L * per_layer + 2 * h)


def matmul_params(cfg):
    """Parameters that a token multiplies with: the blocks' matrices and the
    output head (the embedding tables are looked up, not multiplied)."""
    h, f, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    return L * (4 * h * h + 2 * h * f) + cfg["vocab_size"] * h


def train_flops_per_token(cfg, seq):
    """6 N for the dense products forward and backward plus 12 L h s for
    the attention scores and values; the causal half is not taken off, as
    is the convention, so the number compares with published MFUs."""
    return (6 * param_count(cfg)
            + 12 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq)


def serve_flops(cfg, prompt_lens, decode_contexts):
    """Operations the served work needs: every prompt token through the
    blocks, one row through the head per prompt, causal attention over each
    prompt, and for every decoded token the blocks, the head and attention
    over its context. `decode_contexts` is the sum of the context lengths
    of all decoded tokens and their count, (sum, count)."""
    h, f, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    body = L * (4 * h * h + 2 * h * f)
    head = cfg["vocab_size"] * h
    ctx_sum, n_dec = decode_contexts
    prefill = sum(2 * body * p + 2 * head + 2 * L * h * p * p
                  for p in prompt_lens)
    decode = 2 * (body + head) * n_dec + 4 * L * h * ctx_sum
    return prefill + decode
