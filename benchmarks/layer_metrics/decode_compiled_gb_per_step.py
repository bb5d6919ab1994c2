"""Bytes the compiled decode program accesses per step of its scan, in GB
of 1e9 bytes: `cost_analysis()["bytes accessed"]` as the program's
introspection captured it for the site `decode`. The compiler counts a
loop's body once whatever its trip count, so this is one step of the scan
plus what lies outside the loop."""
from benchmarks import named


def read(run, trace):
    accessed = (named.site_cost("decode") or {}).get("bytes_accessed")
    return accessed / 1e9 if accessed is not None else None
