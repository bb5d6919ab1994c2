"""One decode step of the live batch: the bytes the algorithm has to read
(every weight once, as stored, and the live keys and values of the active
slots) and its operations. Defined on the work, not on which attention path
ran. The decode scan is found in the trace by its count of executions, not
by a name: every jitted site of the program is `jit_traced` today."""

PATTERN = r"jit_traced"


def shapes(cfg, weight_bytes, kv_bytes, live_tokens, live_slots):
    return {"cfg": cfg, "weight_bytes": weight_bytes, "kv_bytes": kv_bytes,
            "live_tokens": live_tokens, "live_slots": live_slots}


def bytes(sh):
    cfg = sh["cfg"]
    h, f, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    weights = (L * (4 * (h * h + h) + 2 * h * f + f + h + 4 * h)
               + cfg["vocab_size"] * h + 2 * h) * sh["weight_bytes"]
    kv = sh["live_tokens"] * 2 * L * h * sh["kv_bytes"]
    return weights + kv


def ops(sh):
    cfg = sh["cfg"]
    h, f, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    dense = 2 * (L * (4 * h * h + 2 * h * f) + cfg["vocab_size"] * h)
    return dense * sh["live_slots"] + 4 * L * h * sh["live_tokens"]
