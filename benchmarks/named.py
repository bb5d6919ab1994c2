"""What the readers that go by name share: a compiled program is found in
the trace by its site (`RecompileTracer.jit("decode", ...)` is the module
`jit_decode(<id>)`), a Pallas kernel by the `name=` of its `pallas_call`, and
an operation's layer by the program's own map from instruction to scope
(`paddle_tpu.observability.introspect.site_scopes`, parsed from the compiled
text on the first query). Nothing here guesses by count or by size: a name
that is not there gives None, as it does on a program that names nothing."""
from __future__ import annotations

import re
import sys


def modules(trace, pattern):
    """[(match, seconds, count)] of the modules whose name `pattern`
    matches from its start; the name ends at the `(` of the program id."""
    if trace is None:
        return []
    rx = re.compile(pattern)
    out = []
    for key, (seconds, count) in trace["modules"].items():
        m = rx.fullmatch(key.split("(", 1)[0])
        if m:
            out.append((m, seconds, count))
    return out


def module(trace, site):
    """(seconds, count) of the one program of `site`, or None."""
    found = modules(trace, re.escape("jit_" + site))
    if not found:
        return None
    return sum(f[1] for f in found), sum(f[2] for f in found)


def instruction(op_key):
    """`%fusion.12 [target]` -> `fusion.12`: the name the compiled text
    gives the instruction."""
    return op_key.split(" ", 1)[0].lstrip("%")


def kernel_seconds(trace, *names):
    """Summed device seconds of the operations whose instruction name holds
    one of `names` (a kernel's `name=`), or None where there is none."""
    if trace is None:
        return None
    hit = [v[0] for k, v in trace["ops"].items()
           if any(n in instruction(k) for n in names)]
    return sum(hit) if hit else None


def introspect():
    """The program's introspection module, or None on a program without
    `site_scopes`."""
    from paddle_tpu.observability import introspect as mod
    return mod if hasattr(mod, "site_scopes") else None


def site_cost(site):
    """The compiler's numbers for `site` as the program captured them, or
    None with the reason on standard error (the capture is skipped when the
    compile outlasted PADDLE_TPU_INTROSPECT_MAX_S)."""
    from paddle_tpu.observability import introspect as mod
    cost = mod.site_cost(site)
    if cost is None:
        why = (mod.cost_report().get("skipped") or {})
        why = {k: v for k, v in why.items() if k.endswith("/" + site)}
        print(f"introspect holds no capture of {site!r}: "
              f"{why or 'never compiled through the tracer'}",
              file=sys.stderr, flush=True)
    return cost


def scope_seconds(trace, site):
    """(scoped, unscoped): device seconds of the traced operations by
    the scope path the program's map gives their instruction, {path:
    seconds}, and the seconds of those it gives none. None where the
    program has no map for `site` or the trace no operations."""
    mod = introspect()
    if mod is None or trace is None or not trace["ops"]:
        return None
    scopes = mod.site_scopes(site)
    if not scopes:
        return None
    scoped, unscoped = {}, 0.0
    for key, (seconds, _) in trace["ops"].items():
        path = scopes.get(instruction(key))
        if path is None:
            unscoped += seconds
        else:
            scoped[path] = scoped.get(path, 0.0) + seconds
    return scoped, unscoped


def seconds_under(scoped, wanted):
    """Seconds of the paths one of whose components `wanted` accepts."""
    return sum(s for path, s in scoped.items()
               if any(wanted(c) for c in path.split("/")))


def per_step_ms(seconds, trace, site):
    """`seconds` of the window over the executions of `site`'s program."""
    mod = module(trace, site)
    if seconds is None or mod is None or not mod[1]:
        return None
    return seconds / mod[1] * 1e3
