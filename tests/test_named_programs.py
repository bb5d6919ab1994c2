"""One name from the program to the device trace (ISSUE 25): a compiled
program is called after its tracer site, a Pallas kernel after the `name=`
of its `pallas_call`, an operation carries the scope of the layer that built
it, and `introspect.site_scopes` reads those scopes back from the compiled
text, on request only."""
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from fleet_proc_support import (build_engine, decode_jaxpr,  # noqa: E402
                                jaxpr_eqns)
from paddle_tpu.observability import introspect  # noqa: E402
from paddle_tpu.observability.trace import (  # noqa: E402
    RecompileTracer, program_name)

# the package re-exports functions under its modules' names
(_common, conv_bn_act, flash_attention, flash_decode, fused_adamw,
 fused_ln, latent_decode, grouped_experts, gated_delta_decode) = (
    importlib.import_module("paddle_tpu.ops.pallas." + m)
    for m in ("_common", "conv_bn_act", "flash_attention", "flash_decode",
              "fused_adamw", "fused_ln", "latent_decode",
              "grouped_experts", "gated_delta_decode"))


# -- programs ----------------------------------------------------------------

@pytest.mark.parametrize("site,module", [
    ("decode", "jit_decode"),
    ("prefill_512", "jit_prefill_512"),
    ("train_step", "jit_train_step"),
    ("to_static:fn@3f train", "jit_to_static_fn_3f_train"),
    ("512", "jit__512"),
])
def test_a_site_names_its_compiled_program(site, module):
    tracer = RecompileTracer(name="naming")
    try:
        fn = tracer.jit(site, lambda x: x * 2 + 1, introspect=False)
        text = fn.lower(jnp.ones(4)).compile().as_text()
        assert text.startswith(f"HloModule {module},")
        assert program_name(site).isidentifier()
        assert "jit_" + program_name(site) == module
    finally:
        tracer.close()


# -- kernels -----------------------------------------------------------------

def _pallas_names(jaxpr):
    """`name` of every pallas_call equation, nested jaxprs included."""
    return [eqn.params["name"] for eqn in jaxpr_eqns(jaxpr)
            if eqn.primitive.name == "pallas_call"]


def _flash(grad):
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def f(q):
        return flash_attention.flash_attention(
            q, q, q, causal=True, interpret=True).sum()
    return (jax.grad(f) if grad else f), (q,)


def _dense_decode():
    q = jnp.ones((2, 1, 2, 64), jnp.float32)
    kv = jnp.ones((2, 128, 2, 64), jnp.float32)
    lens = jnp.asarray([5, 100], jnp.int32)
    return (lambda q, kv: flash_attention.flash_decode(
        q, kv, kv, lens, interpret=True)), (q, kv)


def _paged_decode():
    q = jnp.ones((2, 2, 1, 64), jnp.float32)
    pages = jnp.ones((2, 5, 16, 64), jnp.float32)
    table = jnp.ones((2, 3), jnp.int32)
    lens = jnp.asarray([5, 40], jnp.int32)
    return (lambda q, p: flash_decode.paged_flash_decode(
        q, p, p, table, lens, interpret=True)), (q, pages)


def _latent_decode():
    q = jnp.ones((2, 4, 40), jnp.float32)
    pages = jnp.ones((5, 16, 128), jnp.float32)
    table = jnp.ones((2, 3), jnp.int32)
    lens = jnp.asarray([5, 40], jnp.int32)
    return (lambda q, p: latent_decode.latent_flash_decode(
        q, p, table, lens, 32, 0.2, interpret=True)), (q, pages)


def _grouped_experts():
    x = jnp.ones((8, 128), jnp.float32)
    w_gate_up = jnp.ones((2, 128, 256), jnp.float32)
    w_down = jnp.ones((2, 128, 128), jnp.float32)
    return (lambda x, a, b: grouped_experts.grouped_experts(
        x, jnp.ones((8, 2)), jnp.ones((2,), bool), a, b,
        interpret=True)), (x, w_gate_up, w_down)


def _gated_delta():
    q = jnp.ones((3, 4, 16), jnp.float32)
    state = jnp.ones((3, 2, 16, 128), jnp.float32)
    return (lambda q, s: gated_delta_decode.gated_delta_decode(
        q, q, jnp.ones((3, 4, 64)), -jnp.ones((3, 4)), jnp.ones((3, 4)), s,
        interpret=True)), (q, state)


def _ln(entry, grad):
    x = jnp.ones((4, 32, 64), jnp.float32)
    g = jnp.ones((64,), jnp.float32)

    def f(x):
        out = entry(x, x, g, g, 1e-5, 0, True)
        return sum(o.sum() for o in jax.tree_util.tree_leaves(out))
    return (jax.grad(f) if grad else f), (x,)


def _adamw():
    p = jnp.ones((64, 512), jnp.float32)
    return (lambda p: fused_adamw.fused_adamw_update(
        p, p, p, p, 1e-3, 0.1, 0.001, beta1=0.9, beta2=0.999, eps=1e-8,
        weight_decay=0.01, decoupled=True, interpret=True)), (p,)


def _conv(with_res):
    x = jnp.ones((64, 128), jnp.float32)
    w = jnp.ones((128, 256), jnp.float32)
    s = jnp.ones((256,), jnp.float32)
    r = jnp.ones((64, 256), jnp.float32) if with_res else None
    return (lambda x, w: conv_bn_act.fused_conv1x1_bn_act(
        x, w, s, s, r, True, 0, True)), (x, w)


KERNEL_ENTRIES = [
    ("flash_attention", lambda: _flash(False), {"flash_fwd"}),
    ("flash_attention_grad", lambda: _flash(True),
     {"flash_fwd", "flash_bwd_dkv_dq"}),
    ("flash_decode_dense", _dense_decode, {"flash_fwd"}),
    ("paged_flash_decode", _paged_decode, {"flash_decode"}),
    ("latent_flash_decode", _latent_decode, {"latent_decode"}),
    ("grouped_experts", _grouped_experts, {"grouped_experts"}),
    ("gated_delta_decode", _gated_delta, {"gated_delta_decode"}),
    ("fused_add_ln", lambda: _ln(fused_ln.fused_add_layer_norm, False),
     {"fused_add_ln_fwd"}),
    ("fused_add_ln_grad", lambda: _ln(fused_ln.fused_add_layer_norm, True),
     {"fused_add_ln_fwd", "fused_add_ln_bwd"}),
    ("fused_add_ln_y", lambda: _ln(fused_ln.fused_add_layer_norm_y, False),
     {"fused_add_ln_y_fwd"}),
    ("fused_add_ln_y_grad",
     lambda: _ln(fused_ln.fused_add_layer_norm_y, True),
     {"fused_add_ln_y_fwd", "fused_add_ln_y_bwd"}),
    ("fused_adamw", _adamw, {"fused_adamw"}),
    ("conv1x1_bn_act", lambda: _conv(False), {"conv1x1_bn_act"}),
    ("conv1x1_bn_act_residual", lambda: _conv(True), {"conv1x1_bn_act"}),
]


@pytest.mark.parametrize("case,build,want", KERNEL_ENTRIES,
                         ids=[c[0] for c in KERNEL_ENTRIES])
def test_every_kernel_entry_point_carries_its_name(case, build, want):
    fn, args = build()
    assert set(_pallas_names(jax.make_jaxpr(fn)(*args).jaxpr)) == want


def test_kernel_names_are_unique_over_the_call_sites():
    root = os.path.dirname(_common.__file__)
    names, sites = [], 0
    for f in sorted(os.listdir(root)):
        if f.endswith(".py") and f != "_common.py":
            with open(os.path.join(root, f)) as src:
                text = src.read()
            sites += len(re.findall(r"\bpallas_call\(", text))
            names += re.findall(r'\bname="(\w+)"', text)
    # flash_attention.py: the tiled and the resident forward are both
    # `flash_fwd` (one reader, one head-size path a call), and the split
    # backward's one site is called under its two names
    shared = [n for n in set(names) if names.count(n) > 1]
    assert shared == ["flash_fwd"] and names.count("flash_fwd") == 2
    assert sites == 14 and len(names) == 15 and len(set(names)) == 14


def test_a_kernel_without_a_name_is_an_error():
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]
    shape = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    with pytest.raises(TypeError):
        _common.pallas_call(kernel, out_shape=shape, interpret=True)
    for bad in (None, "", "two words", "9lives"):
        with pytest.raises(ValueError, match="identifier"):
            _common.pallas_call(kernel, name=bad, out_shape=shape,
                                interpret=True)
    out = _common.pallas_call(kernel, name="copy_tile", out_shape=shape,
                              interpret=True)(jnp.ones((8, 128), jnp.float32))
    assert float(out.sum()) == 8 * 128


# -- layers and scopes -------------------------------------------------------

@pytest.fixture()
def clean_introspection():
    introspect.clear()
    yield
    introspect.clear()


def _tiny_train_engine():
    from paddle_tpu.hapi.engine import Engine
    from paddle_tpu.nlp.gpt import (GPTForCausalLM, GPTPretrainingCriterion,
                                    _resolve_config)
    from paddle_tpu.optimizer import AdamW
    cfg = _resolve_config("gpt-tiny", hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.train()
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01,
                parameters=model.parameters())
    eng = Engine(model, loss=GPTPretrainingCriterion(), optimizer=opt,
                 amp_dtype=jnp.bfloat16)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 16)).astype(np.int32)
    eng.train_batch([ids], [ids])
    return eng


def _components(scopes):
    return {c for path in scopes.values() for c in path.split("/")}


def test_train_step_scopes_and_the_lazy_map(clean_introspection,
                                            monkeypatch):
    parsed = []
    real = introspect.parse_scopes
    monkeypatch.setattr(introspect, "parse_scopes",
                        lambda text: parsed.append(len(text)) or real(text))
    eng = _tiny_train_engine()
    name = eng.tracer.name
    # the capture kept the compiled object and built nothing from it
    entry = introspect._latest("train_step", name)
    assert entry["_compiled"] is not None and "_scopes" not in entry
    assert not parsed
    cost = introspect.site_cost("train_step", tracer=name)
    assert cost["flops"] and not any(k.startswith("_") for k in cost)
    assert not parsed

    scopes = introspect.site_scopes("train_step", tracer=name)
    assert len(parsed) == 1 and "_compiled" not in entry
    assert {"GPTAttention", "GPTMLP", "GPTEmbeddings", "LayerNorm",
            "lm_head", "loss", "GPTPretrainingCriterion", "amp_cast",
            "optimizer"} <= _components(scopes)
    # fusions are what a device trace shows: some carry each scope
    fused = {s.split("/")[-1] for n, s in scopes.items() if "fusion" in n}
    assert {"GPTAttention", "GPTMLP", "optimizer"} <= fused
    # asked again, nothing is parsed again
    assert introspect.site_scopes("train_step", tracer=name) is scopes
    assert len(parsed) == 1
    # the report that goes to JSON holds no compiled object
    import json
    json.dumps(introspect.cost_report())
    assert introspect.site_scopes("no_such_site") is None


def test_decode_scopes_lie_inside_the_loop_body(clean_introspection):
    eng = build_engine()
    try:
        eng.warmup(buckets=(5,))
        name = eng.tracer.name
        entry = introspect._latest("decode", name)
        text = entry["_compiled"].as_text()
        assert text.startswith("HloModule jit_decode,")
        in_body = set()
        for op_name in re.findall(r'op_name="([^"]*)"', text):
            if op_name.startswith("jit(decode)/while/body/"):
                in_body |= set(op_name.split("/"))
        # the default pool (fully provisioned) is read in place: the
        # `page_gather` scope exists in the gathered form only
        assert eng.health()["decode_attention"] == "in_place"
        assert {"paged_attention", "kv_write", "sample",
                "GPTAttention", "lm_head"} <= in_body
        assert "page_gather" not in in_body
        scopes = introspect.site_scopes("decode", tracer=name)
        assert {"paged_attention", "kv_write", "sample"} \
            <= _components(scopes)
        prefill = introspect.site_scopes("prefill_16", tracer=name)
        assert {"kv_write", "sample", "GPTMLP"} <= _components(prefill)
    finally:
        eng.close()


@pytest.mark.parametrize("kw,want", [
    (dict(use_flash=None), None),
    (dict(use_flash=False), "in_place"),
    (dict(use_flash=False, num_pages=32), "gathered"),
    (dict(use_flash=True), "paged_kernel"),
], ids=["default", "in_place", "gathered", "paged_kernel"])
def test_health_names_the_attention_the_decode_program_holds(kw, want):
    """`health()["decode_attention"]` against what was traced: the
    `flash_decode` kernel is in the decode step exactly when it says
    "paged_kernel", the `page_gather` scope exactly when it says
    "gathered"."""
    import paddle_tpu as paddle
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.nlp.serving import ServingEngine
    paddle.seed(3)
    m = GPTForCausalLM(_resolve_config("gpt-tiny", num_attention_heads=1))
    m.eval()                                    # one head of 64
    eng = ServingEngine(m, max_slots=2, page_size=16, max_seq_len=48,
                        steps_per_dispatch=2, prefix_cache=False, **kw)
    try:
        said = eng.health()["decode_attention"]
        assert said == (want or said)
        assert said in ("paged_kernel", "in_place", "gathered")
        assert (said == "paged_kernel") == eng.use_flash
        jaxpr = decode_jaxpr(eng)
        assert set(_pallas_names(jaxpr)) == (
            {"flash_decode"} if said == "paged_kernel" else set())
        scopes = set()
        for eqn in jaxpr_eqns(jaxpr):
            scopes |= set(str(eqn.source_info.name_stack).split("/"))
        assert "paged_attention" in scopes
        assert ("page_gather" in scopes) == (said == "gathered")
    finally:
        eng.close()


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/jvp(GPTForCausalLM)/GPTModel/GPTMLP/dot_general",
     "GPTForCausalLM/GPTModel/GPTMLP"),
    ("jit(train_step)/transpose(jvp(GPTForCausalLM))/GPTModel/GPTMLP/mul",
     "GPTForCausalLM/GPTModel/GPTMLP"),
    ("jit(decode)/while/body/closed_call/GPTForCausalLM/GPTModel/"
     "GPTAttention/dot_general", "GPTForCausalLM/GPTModel/GPTAttention"),
    ("jit(train_step)/jvp(GPTModel)/checkpoint/GPTDecoderLayer/add",
     "GPTModel/GPTDecoderLayer"),
    ("jit(train_step)/transpose(jvp(GPTModel))/checkpoint/"
     "rematted_computation/GPTDecoderLayer/add", "GPTModel/GPTDecoderLayer"),
    ("jit(f)/GPTAttention/bhqd,bhkd->bhqk/dot_general", "GPTAttention"),
    ("jit(f)/LayerNorm/jit(_var)/jit(_where)/select_n", "LayerNorm"),
    ("jit(train_step)/jvp(loss)/GPTPretrainingCriterion/reduce_sum",
     "loss/GPTPretrainingCriterion"),
    ("jit(train_step)/optimizer/mul", "optimizer"),
    ("jit(train_step)/mul", None),
    ("reduce_sum", None),
    ("args[2]['v']['gpt.ln_f.weight']", None),
])
def test_op_names_normalise_to_one_scope_path(op_name, scope):
    assert introspect.scope_of(op_name) == scope


def test_parse_scopes_reads_instruction_names():
    text = """HloModule jit_step, is_scheduled=true
%fused_computation (p: f32[4]) -> f32[4] {
  ROOT %multiply.3 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(Net)/Block/mul" stack_frame_id=3}
}
ENTRY %main {
  %fusion.7 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(Net))/Block/mul" source_file="x.py" source_line=3}
  %flash_fwd.2 = f32[4]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(Net)/Attn/pallas_call"}
  %copy.1 = f32[4]{0} copy(%a)
  ROOT %add.9 = f32[4]{0} add(%a, %a), metadata={op_name="jit(step)/add"}
}
"""
    assert introspect.parse_scopes(text) == {
        "multiply.3": "Net/Block", "fusion.7": "Net/Block",
        "flash_fwd.2": "Net/Attn"}
