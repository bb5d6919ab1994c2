"""Shared transformer modeling helpers (BERT/ERNIE/GPT).

ref: the mask preparation logic every PaddleNLP model repeats in
modeling.py (_prepare_decoder_attention_mask / get_extended_attention_mask).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..tensor import Tensor


def normalize_attention_mask(attention_mask):
    """Normalise a user attention mask to [b, 1, sq|1, sk] broadcastable
    form: 2D/3D 0/1 padding masks (int or float — the tokenizer
    convention) become bool keep-masks; 4D float masks pass through as
    additive biases (paddle.nn.functional sdpa semantics)."""
    if attention_mask is None:
        return None
    m = attention_mask._value if isinstance(attention_mask, Tensor) \
        else jnp.asarray(attention_mask)
    is_padding = m.ndim <= 3
    if m.ndim == 2:
        m = m[:, None, None, :]
    elif m.ndim == 3:
        m = m[:, None]
    if m.dtype != jnp.bool_ and is_padding:
        m = m != 0
    return Tensor(m)


def fused_residual_ln(residual, h, ln, want_sum=True):
    """LN(residual + h) scaled/shifted by `ln`'s params in ONE Pallas
    pass (ops/pallas/fused_ln.py) — the add->reduce boundary XLA keeps
    as separate HBM round trips. want_sum=True returns (y, s) with
    s = residual + h materialized (GPT pre-LN: s feeds the next
    residual); want_sum=False returns y alone and skips the sum's HBM
    write entirely (BERT/ERNIE post-LN discard it)."""
    from ..autograd import apply_op
    from ..ops.pallas.fused_ln import (fused_add_layer_norm,
                                       fused_add_layer_norm_y)
    eps = getattr(ln, "_epsilon", 1e-5)
    fn = fused_add_layer_norm if want_sum else fused_add_layer_norm_y
    return apply_op(
        lambda a, b, g, bb: fn(a, b, g, bb, eps),
        residual, h, ln.weight, ln.bias)


def from_pretrained_impl(cls, resolve, name_or_path, pretrained_path=None,
                         config_name=None, **overrides):
    """PaddleNLP `Model.from_pretrained` parity for an offline
    environment (ref: paddlenlp.transformers PretrainedModel
    .from_pretrained, which downloads by name).

    Accepted forms:
      from_pretrained('bert-base-uncased')                -> config only;
        weights need a local file, so this raises with the
        convert-and-load recipe.
      from_pretrained('bert-base-uncased',
                      pretrained_path='bert.pdparams')    -> build from
        the named config, then load the checkpoint (reference .pdparams
        pickles or paddle_tpu saves both load).
      from_pretrained('/path/ckpt.pdparams',
                      config_name='bert-base-uncased')    -> same, with
        the checkpoint path first.
    """
    import os
    name = name_or_path
    if os.path.exists(str(name_or_path)):
        if pretrained_path is not None:
            raise ValueError(
                f"'{name_or_path}' is a checkpoint path AND "
                f"pretrained_path='{pretrained_path}' was given — pass "
                "exactly one weights source")
        if not config_name:
            raise ValueError(
                f"'{name_or_path}' is a checkpoint path; also pass "
                "config_name='<config>' so the architecture can be "
                "built before loading the weights")
        pretrained_path, name = str(name_or_path), config_name
    model = cls(resolve(name, **overrides))
    if pretrained_path is None:
        raise NotImplementedError(
            f"from_pretrained('{name}') needs a weights download, which "
            "this offline environment cannot do. Recipe: in the "
            "reference framework run `paddle.save(model.state_dict(), "
            f"'{name}.pdparams')`, copy the file here, and call "
            f"from_pretrained('{name}', pretrained_path='"
            f"{name}.pdparams') — the .pdparams pickle loads directly "
            "(paddle_tpu.compat.load_pdparams)")
    from ..serialization import load
    state = load(str(pretrained_path))
    if isinstance(state, dict) and set(state) >= {"params"} and \
            all(k in ("params", "buffers", "specs") for k in state):
        state = {**state.get("params", {}), **state.get("buffers", {})}
    state = adapt_state_for_model(model, state)
    # strict, like serialization.load_into (which would re-read the
    # file — at 1.3B scale that is gigabytes of redundant unpickling)
    missing = [k for k in model.state_dict() if k not in state]
    if missing:
        raise ValueError(
            f"checkpoint {pretrained_path} (after layout conversion) "
            f"is missing parameters "
            f"{missing[:8]}{'...' if len(missing) > 8 else ''} — "
            "refusing a partial load")
    model.set_state_dict(state)
    return model


def adapt_state_for_model(model, state):
    """Bridge checkpoint layouts to the built model's: unrolled
    per-layer keys <-> scan-stacked [L, ...] leaves
    (config.scan_layers), and separate q/k/v projections <-> the fused
    Megatron-interleaved qkv_proj (config.fused_qkv) — both directions,
    composing (a stacked-fused model loads a plain reference
    checkpoint and vice versa). Returns `state` unchanged when the
    layouts already agree. ref: paddlenlp PretrainedModel's
    convert-from-other-layout hooks (from_pretrained does the
    equivalent bridging for torch-layout weights)."""
    cfg = getattr(model, "config", None)
    if cfg is None or not isinstance(state, dict) or not state:
        return state
    from ..nn.scan_stack import stack_layer_state, unstack_layer_state
    from .gpt import fuse_qkv_state, split_qkv_state
    L = getattr(cfg, "num_hidden_layers", None)
    heads = getattr(cfg, "num_attention_heads", None)

    def stacked_prefix(keys):
        for k in keys:
            if "__" in k:
                head = k.split("__", 1)[0]
                return head.rsplit(".", 1)[0] + "." if "." in head else ""
        return None

    model_keys = list(model.state_dict())
    m_stacked = stacked_prefix(model_keys)
    orig = state
    c_stacked = stacked_prefix(state)
    if c_stacked is not None and m_stacked is None and L:
        state = unstack_layer_state(state, L, prefix=c_stacked)
    want_fused = any(".qkv_proj." in k or "qkv_proj__" in k
                     for k in model_keys)
    have_sep = any(".q_proj." in k for k in state)
    have_fused = any(".qkv_proj." in k for k in state)
    if heads and want_fused and have_sep and not have_fused:
        state = fuse_qkv_state(state, heads)
    elif heads and not want_fused and have_fused:
        state = split_qkv_state(state, heads)
    if m_stacked is not None and stacked_prefix(state) is None and L:
        state = stack_layer_state(state, L, prefix=m_stacked)
    # if nothing changed semantically, hand back the original object so
    # the caller can fall through to the plain strict load
    return state if state is not orig else orig


class FromPretrainedMixin:
    """One from_pretrained for every model family: resolves the config
    resolver from cls._resolve (task heads) or the defining module's
    _resolve_config (backbones)."""

    @classmethod
    def from_pretrained(cls, name_or_path, pretrained_path=None,
                        config_name=None, **overrides):
        import sys
        resolve = getattr(cls, "_resolve", None)
        if resolve is None:
            resolve = getattr(sys.modules[cls.__module__],
                              "_resolve_config")
        return from_pretrained_impl(cls, resolve, name_or_path,
                                    pretrained_path, config_name,
                                    **overrides)
