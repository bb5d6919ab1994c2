"""Jit-compiled autoregressive decoding — the fast path behind
GPTForCausalLM.generate's eager loop.

ref parity: paddlenlp.generation.GenerationMixin (greedy / top-k sampling
with a KV cache). The reference dispatches one CUDA graph per step;
TPU-native design compiles the ENTIRE decode into one XLA program:

- static KV cache: fixed [B, S_max, H, D] buffers per layer written in
  place with dynamic_update_slice (gpt.py's cache_index path) — shapes
  never change, so there is exactly one compile;
- the token loop is a lax.scan whose carry is (cache, position, token,
  rng): no host round-trip between steps, decode runs at HBM speed;
- prefill (the prompt) is one batched forward that fills the cache, then
  the scan emits max_new_tokens tokens.
"""
from __future__ import annotations

import functools
import os
import threading

import jax
import jax.numpy as jnp

from ..nn.layer import functional_call
from ..tensor import Tensor

__all__ = ["generate", "build_decode_fn", "build_beam_decode_fn",
           "clear_decode_cache"]

# generate() convenience-path memo: build_decode_fn returns a fresh
# jax.jit object, and jit's executable cache is keyed on function
# identity — without this memo every generate() call re-traces AND
# re-compiles, which masks the actual ~ms-scale decode.  Stored
# ON the model instance: the decode closures reference the model, so a
# module-level WeakKeyDictionary entry would never die (weakref's
# value-refs-key caveat); an instance attribute makes model<->fn a pure
# cycle the gc collects when the model is dropped.
_MEMO_ATTR = "_paddle_tpu_decode_fn_memo"
_MEMO_MAX = 8  # compiled decode programs kept per model (LRU)


def clear_decode_cache(model):
    """Drop generate()'s memoized compiled decode programs for `model`.

    Needed only after an in-place structural mutation that keeps the
    params pytree identical (e.g. toggling config.use_flash_attention is
    already part of the key, but swapping a sublayer for one with the
    same param shapes is not) — jit cannot see such a change, so the
    memo would otherwise serve the old forward."""
    with _model_lock(model):
        if getattr(model, _MEMO_ATTR, None):
            getattr(model, _MEMO_ATTR).clear()


# Per-model RLock: generate() holds it across build+call
# (functional_call swaps tracers into the shared model while tracing,
# so concurrent tracing on ONE model is unsafe by construction — same
# property as torch.func's functional_call); _memoized_decode_fn
# re-acquires it under generate(). Calls on *independent* models run
# concurrently — a single module-global lock serialized them all. The
# tiny global lock below guards only lock-attr creation.
_LOCK_ATTR = "_paddle_tpu_decode_lock"
_lock_creation_lock = threading.Lock()


def _model_lock(model):
    lock = getattr(model, _LOCK_ATTR, None)
    if lock is None:
        with _lock_creation_lock:
            lock = getattr(model, _LOCK_ATTR, None)
            if lock is None:
                lock = threading.RLock()
                object.__setattr__(model, _LOCK_ATTR, lock)
    return lock


def _memoized_decode_fn(model, key, build):
    # lock covers the whole lookup/evict/build: concurrent generate()
    # threads on one model must neither double-pay a ~30s remote compile
    # for the same key nor race the LRU pop (build for a *different* key
    # is serialized too — compiles are rare, simplicity wins)
    with _model_lock(model):
        per_model = getattr(model, _MEMO_ATTR, None)
        if per_model is None:
            per_model = {}
            object.__setattr__(model, _MEMO_ATTR, per_model)
        # trace-time inputs invisible to the params pytree: the model's
        # flash flag and the flash_decode env gate (ops/attention.py
        # reads it while tracing) — both must key the compiled program
        key = key + (bool(getattr(model.config, "use_flash_attention",
                                  False)),
                     os.environ.get("PADDLE_TPU_FLASH_DECODE"))
        fn = per_model.get(key)
        if fn is None:
            if len(per_model) >= _MEMO_MAX:  # bounded: drop least-recent
                per_model.pop(next(iter(per_model)))
            fn = per_model[key] = build()
        else:  # refresh LRU order
            per_model[key] = per_model.pop(key)
        return fn


def _apply_repetition_penalty(logits, seen, penalty):
    """CTRL-style (ref: paddlenlp.generation repetition_penalty): seen
    tokens' logits are divided by `penalty` when positive, multiplied
    when negative — always pushing them DOWN."""
    pen = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen, pen, logits)


def _mask_top_p(logits, top_p):
    """Nucleus filtering (jit-safe): keep the smallest prefix of the
    descending-softmax whose cumulative probability covers top_p; the
    rest go to -inf. ref: paddlenlp TopPProcess."""
    sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_l, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep everything before the crossing point, and always the top token
    keep_sorted = jnp.concatenate(
        [jnp.ones_like(cum[:, :1], bool), (cum < top_p)[:, :-1]], axis=-1)
    # threshold value per row: smallest kept logit
    thresh = jnp.min(jnp.where(keep_sorted, sorted_l, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where(logits >= thresh, logits, -jnp.inf)


def _alloc_cache(cfg, batch, s_max, dtype):
    # GQA models (Llama-style num_key_value_heads < heads) cache only
    # the kv heads — the whole point of grouped-query attention
    kv_heads = getattr(cfg, "num_key_value_heads", 0) \
        or cfg.num_attention_heads
    return [
        (jnp.zeros((batch, s_max, kv_heads, cfg.head_dim),
                   dtype=dtype),) * 2
        for _ in range(cfg.num_hidden_layers)]


def _logits(out):
    x = out[0] if isinstance(out, tuple) else out
    return x._value if isinstance(x, Tensor) else x


def _cache_fwd(model, params, buffers, tok, cache, idx):
    """One cached forward: the Tensor-wrap/unwrap adapter for the
    cache/cache_index contract, shared by the sampling and beam paths."""
    out = functional_call(
        model, params, buffers, Tensor(tok), cache=[
            (Tensor(k), Tensor(v)) for k, v in cache],
        cache_index=idx)
    logits_t, new_cache = out
    new_cache = [(k._value if isinstance(k, Tensor) else k,
                  v._value if isinstance(v, Tensor) else v)
                 for k, v in new_cache]
    return _logits(logits_t), new_cache


def _seen_from_prompt(ids, vocab_size, pad_token_id=None):
    """[B, V] bool presence mask — scatter, not a [B, S0, V] one-hot
    (which would be ~400MB transient at GPT-3 vocab/prompt sizes).

    Prompt occurrences of pad_token_id are excluded: left-padded prompts
    (often pad==eos in GPT configs) must not leave the pad/eos logit
    permanently repetition-penalized, which would bias against
    termination. Limitation: without an attention mask we cannot tell a
    genuine prompt token that happens to equal pad_token_id from
    padding, so those are exempt too; tokens EMITTED during decode are
    penalized regardless of id (the scan update masks on `done`, not on
    token identity)."""
    b = ids.shape[0]
    seen = jnp.zeros((b, vocab_size), jnp.bool_).at[
        jnp.arange(b)[:, None], ids].set(True)
    if pad_token_id is not None:
        seen = seen.at[:, pad_token_id].set(False)
    return seen


def build_decode_fn(model, max_new_tokens, temperature=1.0, top_k=0,
                    top_p=1.0, repetition_penalty=1.0, eos_token_id=None,
                    pad_token_id=0, do_sample=None,
                    cache_dtype="float32"):
    """Compile (params, buffers, ids, rng) -> [B, S0+max_new_tokens] ids.
    model must be a GPTForCausalLM (or any model supporting the
    cache/cache_index contract).

    ref parity: paddlenlp.generation.GenerationMixin sampling path —
    temperature / top_k / top_p (nucleus) / repetition_penalty /
    eos early-stop (finished rows emit pad_token_id; shapes stay static,
    so early stop costs nothing in compiles). do_sample=True forces
    multinomial sampling even with default top_k/top_p (pure temperature
    sampling); default None infers from the filters."""
    cfg = model.config
    if do_sample is None:
        do_sample = bool(temperature > 0 and (top_k or top_p < 1.0))
    sampling = do_sample and temperature > 0
    cache_dt = jnp.dtype(str(cache_dtype))

    def decode(params, buffers, ids, rng):
        from ..autograd import no_grad
        with no_grad():
            return _decode_impl(params, buffers, ids, rng)

    def _decode_impl(params, buffers, ids, rng):
        b, s0 = ids.shape
        s_max = s0 + max_new_tokens
        cache = _alloc_cache(cfg, b, s_max, cache_dt)

        def fwd(tok, cache, idx):
            return _cache_fwd(model, params, buffers, tok, cache, idx)

        # prefill the prompt in one shot
        logits, cache = fwd(ids, cache, 0)
        last = logits[:, -1, :].astype(jnp.float32)
        track_seen = repetition_penalty != 1.0
        seen = _seen_from_prompt(ids, cfg.vocab_size, pad_token_id) \
            if track_seen else None

        def sample(last, key, seen):
            if track_seen:
                last = _apply_repetition_penalty(last, seen,
                                                 repetition_penalty)
            if not sampling:
                return jnp.argmax(last, axis=-1)
            last = last / temperature
            if top_k:
                vals, cand = jax.lax.top_k(last, top_k)
                if top_p < 1.0:
                    vals = _mask_top_p(vals, top_p)
                pick = jax.random.categorical(key, vals)
                return jnp.take_along_axis(
                    cand, pick[:, None], axis=-1)[:, 0]
            if top_p < 1.0:
                last = _mask_top_p(last, top_p)
            return jax.random.categorical(key, last)

        def step(carry, _):
            cache, idx, last, key, done, seen = carry
            key, sub = jax.random.split(key)
            nxt = sample(last, sub, seen).astype(ids.dtype)
            if eos_token_id is not None:
                nxt = jnp.where(done, jnp.asarray(pad_token_id, ids.dtype),
                                nxt)
                done = done | (nxt == eos_token_id)
            if track_seen:
                # only live rows mark their emission: finished rows emit
                # pad filler which must not accrue repetition penalty
                # (a genuinely emitted token equal to pad_token_id on a
                # live row IS still penalized)
                seen = seen | (jax.nn.one_hot(nxt, cfg.vocab_size,
                                              dtype=jnp.bool_)
                               & ~done[:, None])
            logits, cache = fwd(nxt[:, None], cache, idx)
            return (cache, idx + 1, logits[:, -1, :].astype(jnp.float32),
                    key, done, seen), nxt

        done0 = jnp.zeros((b,), jnp.bool_)
        (_, _, _, _, _, _), toks = jax.lax.scan(
            step, (cache, jnp.int32(s0), last, rng, done0, seen),
            None, length=max_new_tokens)
        return jnp.concatenate([ids, toks.T], axis=1)

    return jax.jit(decode)


def build_beam_decode_fn(model, max_new_tokens, num_beams,
                         length_penalty=1.0, eos_token_id=None,
                         pad_token_id=0, temperature=1.0,
                         repetition_penalty=1.0, cache_dtype="float32"):
    """Beam search, one XLA program (ref: paddlenlp GenerationMixin
    decode_strategy='beam_search').

    TPU-native shape: all `B*K` beams run as one batch; each scan step
    scores [B, K*V] continuations, keeps the top K, and REORDERS the KV
    cache with a batched gather over the beam axis (the reference reorders
    per-layer cache tensors with index_select — same op, but here it
    stays inside the compiled program, so the cache never round-trips to
    host). Finished beams (emitted eos) are frozen: they may only extend
    with pad at unchanged score. Final selection = best
    score / len**length_penalty per batch row. num_beams=1 degenerates to
    greedy. temperature scales logits before scoring; repetition_penalty
    follows each beam's own emitted tokens (seen masks reorder with the
    beams).
    """
    cfg = model.config
    cache_dt = jnp.dtype(str(cache_dtype))
    k = int(num_beams)
    track_seen = repetition_penalty != 1.0

    def decode(params, buffers, ids):
        from ..autograd import no_grad
        with no_grad():
            return _impl(params, buffers, ids)

    def _impl(params, buffers, ids):
        b, s0 = ids.shape
        v = cfg.vocab_size
        s_max = s0 + max_new_tokens

        def fwd(tok, cache, idx):
            return _cache_fwd(model, params, buffers, tok, cache, idx)

        # prefill the [B] prompts ONCE, then tile the cache/logits per
        # beam — k identical prompt forwards would be pure waste
        cache = _alloc_cache(cfg, b, s_max, cache_dt)
        logits, cache = fwd(ids, cache, 0)
        cache = jax.tree_util.tree_map(
            lambda a: jnp.repeat(a, k, axis=0), cache)
        last = jnp.repeat(logits[:, -1, :].astype(jnp.float32), k,
                          axis=0)                      # [B*K, V]
        seen0 = (jnp.repeat(_seen_from_prompt(ids, v, pad_token_id), k,
                            axis=0).reshape(b, k, v)
                 if track_seen else None)

        scores0 = jnp.tile(
            jnp.asarray([0.0] + [-jnp.inf] * (k - 1), jnp.float32), (b, 1))
        seq0 = jnp.full((b, k, max_new_tokens), pad_token_id, ids.dtype)
        done0 = jnp.zeros((b, k), jnp.bool_)

        def reorder(tree, beam_idx):
            """Gather beam rows: leaf [B*K, ...] -> pick beam_idx per b."""
            def one(a):
                ak = a.reshape((b, k) + a.shape[1:])
                return jnp.take_along_axis(
                    ak, beam_idx.reshape((b, k) + (1,) * (a.ndim - 1)),
                    axis=1).reshape(a.shape)
            return jax.tree_util.tree_map(one, tree)

        def step(carry, t):
            cache, idx, last, scores, seqs, done, seen = carry
            if track_seen:
                last = _apply_repetition_penalty(
                    last, seen.reshape(b * k, v), repetition_penalty)
            if temperature not in (0.0, 1.0):
                last = last / temperature
            logp = jax.nn.log_softmax(last, axis=-1).reshape(b, k, v)
            if eos_token_id is not None:
                # frozen beams: only pad continues, at zero added score
                frozen = jnp.full((v,), -jnp.inf).at[pad_token_id].set(0.0)
                logp = jnp.where(done[:, :, None], frozen[None, None, :],
                                 logp)
            total = scores[:, :, None] + logp          # [B, K, V]
            top_val, top_idx = jax.lax.top_k(total.reshape(b, k * v), k)
            beam_idx = top_idx // v                    # [B, K]
            tok = (top_idx % v).astype(ids.dtype)      # [B, K]
            # reorder everything that is per-beam state
            cache = reorder(cache, beam_idx)
            seqs = jnp.take_along_axis(seqs, beam_idx[:, :, None], axis=1)
            done = jnp.take_along_axis(done, beam_idx, axis=1)
            seqs = jax.lax.dynamic_update_slice_in_dim(
                seqs, tok[:, :, None], t, axis=2)
            if eos_token_id is not None:
                done = done | (tok == eos_token_id)
            if track_seen:
                seen = jnp.take_along_axis(seen, beam_idx[:, :, None],
                                           axis=1)
                # frozen beams continue with pad filler — mask them out
                # of the seen update so pad/eos never accrues penalty
                seen = seen | (jax.nn.one_hot(tok, v, dtype=jnp.bool_)
                               & ~done[:, :, None])
            logits, cache = fwd(tok.reshape(b * k, 1), cache, idx)
            return (cache, idx + 1, logits[:, -1, :].astype(jnp.float32),
                    top_val, seqs, done, seen), None

        (cache, _, _, scores, seqs, done, _), _ = jax.lax.scan(
            step, (cache, jnp.int32(s0), last, scores0, seq0, done0, seen0),
            jnp.arange(max_new_tokens))
        # sequence lengths: position of eos + 1, else max_new_tokens
        if eos_token_id is not None:
            is_eos = seqs == eos_token_id
            has = is_eos.any(axis=-1)
            first = jnp.argmax(is_eos, axis=-1) + 1
            lens = jnp.where(has, first, max_new_tokens)
        else:
            lens = jnp.full((b, k), max_new_tokens)
        norm = scores / (lens.astype(jnp.float32) ** length_penalty)
        best = jnp.argmax(norm, axis=-1)               # [B]
        best_seq = jnp.take_along_axis(
            seqs, best[:, None, None], axis=1)[:, 0]   # [B, T]
        return jnp.concatenate([ids, best_seq], axis=1)

    return jax.jit(decode)


def generate(model, input_ids, max_new_tokens=20, temperature=1.0,
             top_k=0, top_p=1.0, repetition_penalty=1.0, num_beams=1,
             length_penalty=1.0, eos_token_id=None, pad_token_id=0,
             decode_strategy=None, seed=0, cache_dtype="float32"):
    """One-call jitted decode. Compiled decode programs are memoized on
    the model (LRU of 8 keyed by the generation args + flash flag), so
    repeated generate() calls reuse the compiled program; only new
    (B, S0) shapes retrace. Caveat: after an in-place model mutation
    that keeps the params pytree identical (e.g. swapping a sublayer
    with same-shape params), call clear_decode_cache(model).
    decode_strategy: None (infer from args) | 'greedy_search' |
    'sampling' | 'beam_search' — ref: paddlenlp GenerationMixin.

    Thread-safe: the whole call is serialized under a per-model lock
    (tracing swaps state into the shared model; calls on independent
    models proceed concurrently). For lock-free repeated calls, build a
    fn once with build_decode_fn and manage params yourself."""
    with _model_lock(model):
        return _generate_locked(
            model, input_ids, max_new_tokens, temperature, top_k, top_p,
            repetition_penalty, num_beams, length_penalty, eos_token_id,
            pad_token_id, decode_strategy, seed, cache_dtype)


def _generate_locked(model, input_ids, max_new_tokens, temperature,
                     top_k, top_p, repetition_penalty, num_beams,
                     length_penalty, eos_token_id, pad_token_id,
                     decode_strategy, seed, cache_dtype):
    # plain-python coercion: these land in the (hashable) memo key, and
    # numpy/jax 0-d scalars were accepted here before memoization
    max_new_tokens = int(max_new_tokens)
    temperature = float(temperature)
    top_k = int(top_k)
    top_p = float(top_p)
    repetition_penalty = float(repetition_penalty)
    num_beams = int(num_beams)
    length_penalty = float(length_penalty)
    eos_token_id = None if eos_token_id is None else int(eos_token_id)
    pad_token_id = None if pad_token_id is None else int(pad_token_id)
    was_training = model.training
    model.eval()
    try:
        params, buffers = model.raw_state()
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        if decode_strategy not in (None, "greedy_search", "sampling",
                                   "beam_search"):
            raise ValueError(f"unknown decode_strategy {decode_strategy!r}")
        if decode_strategy == "beam_search" or (decode_strategy is None
                                                and num_beams > 1):
            if top_k or top_p < 1.0:
                raise ValueError(
                    "beam_search scores exhaustively — top_k/top_p do not "
                    "apply (use decode_strategy='sampling' for filtered "
                    "sampling)")
            fn = _memoized_decode_fn(
                model,
                ("beam", max_new_tokens, max(num_beams, 1), length_penalty,
                 eos_token_id, pad_token_id, temperature,
                 repetition_penalty, str(cache_dtype)),
                lambda: build_beam_decode_fn(
                    model, max_new_tokens, max(num_beams, 1),
                    length_penalty, eos_token_id, pad_token_id, temperature,
                    repetition_penalty, cache_dtype=cache_dtype))
            out = fn(params, buffers, ids)
        else:
            do_sample = None
            if decode_strategy == "greedy_search":
                temperature, do_sample = 0.0, False
            elif decode_strategy == "sampling":
                do_sample = True
            fn = _memoized_decode_fn(
                model,
                ("sample", max_new_tokens, temperature, top_k, top_p,
                 repetition_penalty, eos_token_id, pad_token_id, do_sample,
                 str(cache_dtype)),
                lambda: build_decode_fn(
                    model, max_new_tokens, temperature, top_k, top_p,
                    repetition_penalty, eos_token_id, pad_token_id,
                    do_sample=do_sample, cache_dtype=cache_dtype))
            out = fn(params, buffers, ids, jax.random.PRNGKey(seed))
    finally:
        if was_training:
            model.train()
    return Tensor(out)
