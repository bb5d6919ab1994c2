"""Device time per train step of the vocabulary end: the operations that
the program's scope map puts under `lm_head` (the output projection), `loss`
(the criterion) or an embedding layer (its gradient is the large part),
forward and backward alike."""
import re

from benchmarks import named

EMBEDDING = re.compile(r"Embeddings?$")


def wanted(component):
    return component in ("lm_head", "loss") or bool(EMBEDDING.search(component))


def read(run, trace):
    found = named.scope_seconds(trace, "train_step")
    if found is None:
        return None
    return named.per_step_ms(named.seconds_under(found[0], wanted), trace,
                             "train_step")
