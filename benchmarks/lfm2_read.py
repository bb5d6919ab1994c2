"""What the LFM2 readers under layer_metrics/ share: whether a run is the
LFM2 driver's (its configuration names `layer_types` and the run holds the
routing counters), else None for every one of them."""


def config_of(run):
    cfg = run.get("config") or {}
    return cfg if "layer_types" in cfg and run.get("routing") else None
