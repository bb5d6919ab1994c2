"""The GPT train engine the benchmark's train cell and the chip smoke build.

`build_engine` is imported by `benchmarks/drivers/train.py` (the cell
`train-345m-b8s1024`) and by `chip_smoke.py` (its `train` phase). It stays
under this name until a `benchmark` issue lets the train driver build its own
engine. The repository measures itself through `python3 benchmarks/run.py`
(BENCHMARK.json) and `python chip_smoke.py`; nothing else lives here.
"""
from __future__ import annotations


def build_engine(cfg_name, batch, seq, amp, use_flash=True, recompute=False):
    """A `hapi.Engine` over `GPTForCausalLM(cfg_name)` with dropout off,
    AdamW(1e-4, weight decay 0.01) and, with `amp`, bf16 autocast. `batch`
    is unused: the engine takes its batch from the arrays it is handed."""
    import jax.numpy as jnp
    from paddle_tpu.nlp.gpt import (GPTForCausalLM, GPT_CONFIGS,
                                    GPTPretrainingCriterion, _resolve_config)
    from paddle_tpu.hapi.engine import Engine
    from paddle_tpu.optimizer import AdamW

    max_pos = max(GPT_CONFIGS[cfg_name]["max_position_embeddings"], seq)
    model = GPTForCausalLM(_resolve_config(
        cfg_name, max_position_embeddings=max_pos,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        use_flash_attention=use_flash, recompute=recompute))
    model.train()
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01,
                parameters=model.parameters())
    return Engine(model, loss=GPTPretrainingCriterion(), optimizer=opt,
                  amp_dtype=jnp.bfloat16 if amp else None)
