"""Load `paddle_tpu/observability/<name>.py` for a stdlib-only tool.

The observability modules are stdlib-only by contract, so an operator's tool
(`fleet_top`, `mem_diff`, `metrics_diff`, `profile_diff`) reads their formats
without paying for `import paddle_tpu` (and jax). When the package is already
imported the real module is returned, with the registry and tracer singletons
the engine publishes into; otherwise the module is loaded straight from its
file under the private key `_bench_obs_<name>` (`contprof` looks a
standalone-loaded `introspect` up under that key).
"""
from __future__ import annotations

import importlib
import importlib.util
import os
import sys

_OBS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu", "observability")


def obs_mod(name):
    if "paddle_tpu" in sys.modules:
        return importlib.import_module(f"paddle_tpu.observability.{name}")
    key = f"_bench_obs_{name}"
    mod = sys.modules.get(key)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            key, os.path.join(_OBS_DIR, f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return mod
