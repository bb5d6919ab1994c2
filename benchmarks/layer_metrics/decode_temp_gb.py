"""Scratch memory the compiler gives the decode program, in GB of 1e9
bytes: `memory_analysis().temp_size_in_bytes` as the program's introspection
captured it for the site `decode`."""
from benchmarks import named


def read(run, trace):
    cost = named.site_cost("decode")
    temp = ((cost or {}).get("memory") or {}).get("temp_bytes")
    return temp / 1e9 if temp is not None else None
