"""Scan-over-layers: a stack of L identical blocks stored as stacked
[L, ...] parameters and applied with ONE lax.scan.

TPU-native rationale: XLA traces/compiles the scan body once, so the
program is O(1 block) instead of O(L) — the unrolled 24-layer step
of gpt3-345M takes over a minute to compile on a v5e. Storage is
stacked from construction
(no in-trace jnp.stack copy: ~5 GB transient at 1.3B). ref parity: the
reference unrolls CUDA blocks under fleet recompute; this is the
XLA-idiom equivalent (cf. flax nn.scan-style public decoders).

Used by GPT (`GPTConfig.scan_layers`), BERT/ERNIE
(`BertConfig.scan_layers`). The block forward contract is
`block(x, *invariants)` -> same-shaped x; blocks must be structurally
identical.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..tensor import Tensor
from .layer import Layer, Parameter, functional_call

__all__ = ["ScannedLayerStack", "flat_name", "stack_layer_state",
           "unstack_layer_state"]


def flat_name(dotted):
    """'attn.q_proj.weight' -> 'attn__q_proj__weight' (parameter-store
    keys may not contain dots: named_parameters joins scopes with '.')."""
    return dotted.replace(".", "__")


class ScannedLayerStack(Layer):
    """L structurally-identical blocks as stacked params + one lax.scan.

    `blocks`: freshly-constructed per-layer blocks (their initial values
    are stacked; the first becomes the traced template, its own arrays
    freed). `has_dropout`: draw one rng key at trace level and feed a
    per-layer split through the scan xs — the body traces ONCE, so a
    trace-time counter would reuse a single dropout mask across layers.
    `recompute`: jax.checkpoint around the body (remat-scan: O(1-block)
    activation memory AND program size).
    """

    def __init__(self, blocks, has_dropout=False, recompute=False):
        super().__init__()
        self.num_layers = len(blocks)
        self.has_dropout = has_dropout
        self.recompute = recompute
        template = blocks[0]
        buf_names = [n for n, _ in template.named_buffers()]
        if buf_names:
            # functional_call below feeds an empty buffers dict — a block
            # with registered buffers (BatchNorm-style running stats)
            # would silently run with default values instead of its own
            raise ValueError(
                "ScannedLayerStack blocks may not register buffers "
                f"(found {buf_names}); stack such state as a Parameter "
                "with trainable=False, or keep the model unrolled "
                "(scan_layers=False)")
        self._pnames = [n for n, _ in template.named_parameters()]
        for n in self._pnames:
            refs = [dict(b.named_parameters())[n] for b in blocks]
            p = Parameter(jnp.stack([r._value for r in refs]),
                          trainable=refs[0].trainable)
            spec = getattr(refs[0], "sharding_spec", None)
            if spec is not None:
                from jax.sharding import PartitionSpec
                p.sharding_spec = PartitionSpec(None, *spec)
            self.add_parameter(flat_name(n), p)
        # the template is NOT a sublayer (object.__setattr__ skips
        # registration): its params must not appear in state_dict /
        # parameters(). Values are freed to scalar placeholders — the
        # scan body swaps real slices in before any forward runs.
        for _, p in template.named_parameters():
            p._value = jnp.zeros((), p.dtype)
        object.__setattr__(self, "_template", template)

    def forward(self, x, *invariants):
        from ..autograd import in_jax_trace, is_grad_enabled
        xa = x._value if isinstance(x, Tensor) else x
        traced = in_jax_trace((xa,))
        if not traced and self.training and is_grad_enabled():
            raise RuntimeError(
                "scan_layers=True trains through the jitted Engine/"
                "Model path only (the eager tape cannot see through "
                "lax.scan). Use Engine.train_batch / Model.fit, wrap "
                "the step in paddle_tpu.jit.to_static, or build the "
                "model with scan_layers=False for eager training.")
        if self.has_dropout and self.training:
            from .. import framework
            keys = jax.random.split(framework.next_rng_key(),
                                    self.num_layers)
        else:
            keys = None
        stacked = {n: self._parameters[flat_name(n)]._value
                   for n in self._pnames}
        template = self._template

        def body(carry, per_layer):
            sliced, key = per_layer
            out = functional_call(template, sliced, {}, Tensor(carry),
                                  *invariants, rng=key)
            return (out._value if isinstance(out, Tensor) else out), None

        if self.recompute and self.training and traced:
            body = jax.checkpoint(body)
        y, _ = jax.lax.scan(body, xa, (stacked, keys))
        return Tensor(y, stop_gradient=not is_grad_enabled())


def stack_layer_state(state_dict, num_layers, prefix="h."):
    """Convert per-layer checkpoint keys ('h.3.attn.q_proj.weight') to
    the stacked layout ('h.attn__q_proj__weight' with a [L, ...] leading
    dim). Non-layer (or already-stacked) keys pass through. For loading
    unrolled .pdparams into a scan_layers=True model; inverse:
    unstack_layer_state."""
    import numpy as np
    per_layer, rest = {}, {}
    for k, v in state_dict.items():
        if k.startswith(prefix) and "." in k[len(prefix):]:
            idx, dotted = k[len(prefix):].split(".", 1)
            if idx.isdigit():
                per_layer.setdefault(dotted, {})[int(idx)] = v
                continue
        rest[k] = v
    for dotted, by_idx in per_layer.items():
        missing = set(range(num_layers)) - set(by_idx)
        if missing:
            raise ValueError(f"layer state for '{dotted}' missing "
                             f"indices {sorted(missing)}")
        arrs = [by_idx[i]._value if isinstance(by_idx[i], Tensor)
                else np.asarray(by_idx[i]) for i in range(num_layers)]
        rest[prefix + flat_name(dotted)] = np.stack(arrs)
    return rest


def unstack_layer_state(state_dict, num_layers, prefix="h."):
    """Inverse of stack_layer_state: stacked keys back to per-layer."""
    import numpy as np
    out = {}
    for k, v in state_dict.items():
        if k.startswith(prefix) and "__" in k[len(prefix):]:
            dotted = k[len(prefix):].replace("__", ".")
            arr = v._value if isinstance(v, Tensor) else np.asarray(v)
            if arr.shape[0] != num_layers:
                raise ValueError(
                    f"stacked leaf '{k}' has leading dim {arr.shape[0]}"
                    f" != num_layers {num_layers}")
            for i in range(num_layers):
                out[f"{prefix}{i}.{dotted}"] = arr[i]
        else:
            out[k] = v
    return out
