"""What the Olmo-Hybrid readers under layer_metrics/ share: whether a run is
that driver's (its configuration's `model_type`), else None for every one
of them."""


def config_of(run):
    cfg = run.get("config") or {}
    return cfg if cfg.get("model_type") == "olmo_hybrid" else None
