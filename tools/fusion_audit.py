"""CINN-parity fusion audit (SURVEY §7 R3).

The reference's CINN pass fuses elementwise chains (LN -> residual ->
GELU) into generated kernels so activations make one HBM round trip.
On TPU the same job belongs to XLA; this tool checks XLA actually did
it by compiling the REAL train steps (GPT decoder block / ResNet-50)
and reporting, from the backend-optimized HLO:

  - kernel count (top-level instructions of the entry computation —
    each is roughly one dispatched kernel)
  - fusion count + the largest fusions' op mixes
  - standalone (unfused) elementwise/reduce ops — each one is an extra
    full HBM round trip of an activation tensor
  - cost_analysis bytes-accessed / FLOPs -> arithmetic intensity

Usage (results are backend-specific — run on the TPU terminal):
  python tools/fusion_audit.py [--model gpt|resnet] [--out report.md]
CPU runs exercise the tooling but say nothing about TPU fusion.
"""
from __future__ import annotations

import argparse
import re
import sys
from collections import Counter

ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "exponential", "tanh", "rsqrt", "sqrt", "negate", "abs", "power",
    "select", "compare", "convert", "and", "or", "not", "xor",
    "log", "logistic", "sign", "floor", "ceil", "clamp",
}
HEAVY = {"dot", "convolution", "custom-call", "fusion", "all-reduce",
         "reduce-scatter", "all-gather", "scatter", "gather", "sort",
         "rng", "while", "conditional", "call"}


# opcode after "= <type> ": the type is either a tuple "(...)" or a
# single token; TPU-optimized HLO annotates layouts inside the type
# (e.g. bf16[8,128]{1,0:T(8,128)(2,1)S(1)}), so the type is matched as
# "anything without spaces" / a parenthesized tuple, never enumerated
_INSTR_RE = re.compile(
    r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?:\([^=\n]*?\)|\S+)\s+"
    r"([a-z][\w\-]*)\(")


def _block_after(header_re, hlo_text):
    """Yield (name, body) for each computation whose header matches.
    Headers end with '{' at end of line; the body runs to the first
    line that is exactly '}' — signatures may contain braces (TPU
    layout annotations), so never scan for 'first { after name'."""
    for fm in re.finditer(header_re + r"[^\n]*\{[ ]*$\n(.*?)^\}",
                          hlo_text, re.MULTILINE | re.DOTALL):
        yield fm.group(1), fm.group(2)


def parse_entry_computation(hlo_text):
    """Return the instruction opcodes of the ENTRY computation plus the
    full per-fusion bodies keyed by fusion name."""
    ops = []
    for _, entry in _block_after(r"^(ENTRY)\s", hlo_text):
        for line in entry.splitlines():
            mm = _INSTR_RE.match(line.strip())
            if mm:
                ops.append(mm.group(1))
        break
    bodies = {}
    for name, body in _block_after(
            r"^%?((?:fused_|wrapped_)[\w.\-]*)", hlo_text):
        bodies[name] = Counter(
            m.group(1) for m in (
                _INSTR_RE.match(l.strip()) for l in body.splitlines())
            if m)
    return ops, bodies


def audit(fn_or_layer, args, label):
    from paddle_tpu import jit as pjit
    import jax

    txt = pjit.get_hlo(fn_or_layer, *args, optimized=True)
    ops, bodies = parse_entry_computation(txt)
    if not ops and "ENTRY" in txt:
        # loud failure beats a vacuous all-zeros report that burns a
        # scarce TPU window looking like a measurement (the r4 campaign
        # shipped exactly that when TPU layout annotations broke the
        # old regexes)
        raise RuntimeError(
            f"HLO parser matched 0 entry instructions for '{label}' but "
            f"the dump contains an ENTRY computation ({len(txt)} chars) "
            "— the HLO text dialect has drifted; fix "
            "parse_entry_computation (see tests/test_fusion_audit_parser"
            ".py)")
    counts = Counter(ops)
    n_fusion = counts.get("fusion", 0)
    unfused_ew = {o: c for o, c in counts.items()
                  if o in ELEMENTWISE and o not in ("convert",)}
    report = [f"## {label}", ""]
    report.append(f"- entry instructions (~kernels): **{len(ops)}**")
    report.append(f"- fusions: **{n_fusion}**; "
                  f"dots/convs: {counts.get('dot', 0)}/"
                  f"{counts.get('convolution', 0)}; "
                  f"custom-calls: {counts.get('custom-call', 0)}")
    if unfused_ew:
        report.append(f"- **standalone elementwise ops (extra HBM "
                      f"round trips): {sum(unfused_ew.values())}** "
                      f"{dict(unfused_ew)}")
    else:
        report.append("- standalone elementwise ops: **0** — every "
                      "elementwise chain is inside a fusion")
    other = {o: c for o, c in counts.items()
             if o not in ELEMENTWISE and o not in HEAVY
             and o not in ("parameter", "constant", "tuple",
                           "get-tuple-element", "bitcast", "copy",
                           "reshape", "transpose", "broadcast", "iota",
                           "slice", "concatenate", "pad",
                           "dynamic-slice", "dynamic-update-slice",
                           "reduce")}
    if other:
        report.append(f"- other standalone ops: {dict(other)}")
    if counts.get("reduce", 0):
        report.append(f"- standalone reduces: {counts['reduce']}")
    # biggest fusions: what XLA chose to glue together
    big = sorted(bodies.items(), key=lambda kv: -sum(kv[1].values()))[:5]
    if big:
        report.append("- largest fusions:")
        for name, body in big:
            mix = ", ".join(f"{o}x{c}" for o, c in body.most_common(6))
            report.append(f"    - `{name}` ({sum(body.values())} ops): "
                          f"{mix}")
    return "\n".join(report), txt


def gpt_step(tiny=False):
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, ".")
    from bench import build_engine
    cfg = "gpt-tiny" if tiny else "gpt3-345M"
    seq = 128 if tiny else 1024
    batch = 2 if tiny else 8
    eng = build_engine(cfg, batch, seq, amp=not tiny)
    rng = np.random.default_rng(0)
    vocab = eng.network.config.vocab_size
    ids = jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32)
    # materialize opt state + the jitted fn exactly as train_batch would
    eng.train_batch([ids], [labels])
    fn = eng._train_fn
    return (lambda p, b, o, lr, st, key: fn(p, b, o, lr, st, st, key,
                                            [ids], [labels]),
            (eng._params, eng._buffers, eng._opt_state,
             np.float32(1e-4), np.int32(2), eng._rng_key))


def resnet_step(tiny=False, s2d=False, layout=None,
                fused_bottleneck=False):
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, ".")
    from bench import _resnet_layout, build_resnet_engine
    eng = build_resnet_engine(amp=not tiny, s2d=s2d,
                              layout=_resnet_layout(layout,
                                                    fused_bottleneck),
                              fused_bottleneck=fused_bottleneck)
    hw = 64 if tiny else 224
    batch = 2 if tiny else 256
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, 3, hw, hw)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 1000, (batch,)))
    eng.train_batch([x], [y])
    fn = eng._train_fn
    return (lambda p, b, o, lr, st, key: fn(p, b, o, lr, st, st, key,
                                            [x], [y]),
            (eng._params, eng._buffers, eng._opt_state,
             np.float32(0.1), np.int32(2), eng._rng_key))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("gpt", "resnet", "both"),
                    default="both")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU-sized configs (tooling smoke only)")
    ap.add_argument("--s2d", action="store_true")
    ap.add_argument("--layout", choices=("auto", "nhwc", "nchw"),
                    default=None,
                    help="resnet: channels-last A/B (see bench.py "
                         "--layout)")
    ap.add_argument("--fused-bottleneck", action="store_true",
                    help="resnet: Pallas fused bottleneck 1x1 chains "
                         "(implies nhwc while --layout is auto)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--dump-hlo", default=None,
                    help="also write the raw optimized HLO here (prefix)")
    args = ap.parse_args()
    sys.path.insert(0, ".")
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    sections = [f"# Fusion audit (backend: {jax.default_backend()})", ""]
    todo = []
    if args.model in ("gpt", "both"):
        todo.append(("gpt train step", lambda: gpt_step(args.tiny)))
    if args.model in ("resnet", "both"):
        todo.append((f"resnet50 train step (s2d={args.s2d}, "
                     f"layout={args.layout or 'auto'}, "
                     f"fused_bottleneck={args.fused_bottleneck})",
                     lambda: resnet_step(args.tiny, args.s2d,
                                         args.layout,
                                         args.fused_bottleneck)))
    for label, build in todo:
        fn, a = build()
        rep, txt = audit(fn, a, label)
        sections.append(rep)
        sections.append("")
        if args.dump_hlo:
            path = f"{args.dump_hlo}_{label.split()[0]}.hlo.txt"
            with open(path, "w") as f:
                f.write(txt)
            print(f"raw HLO -> {path}", file=sys.stderr)
    out = "\n".join(sections)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out)
    print(out)


if __name__ == "__main__":
    main()
