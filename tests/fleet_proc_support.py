"""Engine builder the ProcReplica child processes use in tests.

File-loaded by ``proc_child.py`` via the spec's ``builder`` path —
NOT a test module (no ``test_`` prefix). The builder must be
deterministic per seed: the parent computes goldens on its own
identically-seeded engine, and the subprocess replica must generate
token-for-token the same streams for the chaos drills' token-exact
assertions to mean anything.
"""


def build_engine(seed=0, **kw):
    """gpt-tiny ServingEngine, seeded — the fleet chaos workhorse."""
    import paddle_tpu as paddle
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.nlp.serving import ServingEngine

    paddle.seed(seed)
    m = GPTForCausalLM(_resolve_config("gpt-tiny"))
    m.eval()
    d = dict(max_slots=2, page_size=16, max_seq_len=64,
             steps_per_dispatch=4)
    d.update(kw)
    return ServingEngine(m, **d)


def decode_jaxpr(eng):
    """The jaxpr of the engine's decode program (its raw, pre-tracer
    body), traced with the engine's own state as arguments."""
    import jax
    fn, _ = eng._aot_programs["decode"]
    sched = (eng._page_table, eng._seq_lens, eng._last_tokens, eng._active,
             eng._done, eng._emitted, eng._max_new, eng._eos, eng._key_base)
    return jax.make_jaxpr(fn)(eng._decode_params, eng._buffers,
                              eng._pages, *sched).jaxpr


def jaxpr_eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    yield from jaxpr_eqns(inner)
