"""What the set-up readers under layer_metrics/ share: the staged records that
the program's tracers keep of each program they built
(`paddle_tpu.observability.trace.RecompileTracer`, one event a call that
traced), summed over the programs of the set-up.

A program traced inside another's trace names that site as its `parent` and
is part of it, so only the outermost records are added up; a phase record
(`ServingEngine.warmup`, no `signature`) holds programs and is not one. None
where a program record has no `stages` (a program that keeps none), and where
a record missed the persistent compile cache: a cold set-up is
`first_setup_s`'s to judge."""


def records(tracers=None):
    """The outermost program records of every live tracer, or None."""
    if tracers is None:
        from paddle_tpu.observability import trace
        tracers = trace.all_tracers()
    out = []
    for t in tracers:
        programs = [e for e in t.events() if "signature" in e]
        if any("stages" not in e for e in programs):
            return None
        sites = {e["site"] for e in programs}
        out += [e for e in programs if e["parent"] not in sites]
    for e in out:
        for st in (e["stages"], e["introspect"] or {}):
            if st.get("cache_hit") is False:
                return None
    return out or None


def total(part, tracers=None):
    """Σ part(record) over `records()`, or None."""
    recs = records(tracers)
    if recs is None:
        return None
    return float(sum(part(e) for e in recs))


def replay(e, key):
    """`key` of the record's introspection replay, 0 where it had none."""
    return (e["introspect"] or {}).get(key, 0.0)
