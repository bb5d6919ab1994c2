"""Seconds of the set-up's programs in jax's backend compile, which on a
persistent-cache hit is the cache key, the read and the deserialisation; in
the call and in its introspection replay."""
from benchmarks import setup_read


def read(run, trace):
    return setup_read.total(
        lambda e: e["stages"]["backend_s"] + setup_read.replay(e, "backend_s"))
