"""The six set-up readers on hand-made tracer records: the sums where every
program has a staged record that hit the compile cache, None where a record
has no `stages` (a program that keeps none) or missed the cache."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from paddle_tpu.observability import trace  # noqa: E402


def stages(trace_s, lower_s, backend_s, first_run_s=None, hit=True):
    st = {"trace_s": trace_s, "lower_s": lower_s, "backend_s": backend_s,
          "cache_retrieval_s": backend_s / 2, "cache_hit": hit}
    if first_run_s is not None:
        st["first_run_s"] = first_run_s
    return st


def program(site, t0, t1, st, replay, places, parent="warmup"):
    return {"site": site, "signature": "f32[4]", "ts": 0.0,
            "compile_s": t1 - t0, "unexpected": False, "kind": "program",
            "t0": t0, "t1": t1, "stages": st, "introspect": replay,
            "kernel_places": places, "parent": parent}


def serving_records():
    """Two programs primed under warmup, a nested site inside the second
    (part of it, not added again), the warmup phase record."""
    return [
        program("prefill_128", 10.0, 12.0, stages(0.5, 0.75, 0.5, 0.25),
                dict(stages(0.0, 0.25, 0.125), wall_s=0.5),
                {"flash_fwd": 4, "grouped_experts": 1}),
        program("draft", 12.5, 12.75, stages(0.125, 0.0, 0.0, 0.0),
                None, {"flash_fwd": 2}, parent="decode"),
        program("decode", 12.0, 15.0, stages(1.0, 1.0, 0.5, 0.5),
                dict(stages(0.0, 0.0, 0.25), wall_s=0.25),
                {"flash_decode": 4, "flash_fwd": 2}),
        {"site": "warmup", "kind": "phase", "ts": 0.0, "t0": 9.0,
         "t1": 16.0, "wall_s": 7.0, "parent": None,
         "kernel_places": {"flash_fwd": 6, "flash_decode": 4,
                           "grouped_experts": 1}},
    ]


class FakeTracer:
    def __init__(self, events):
        self._events = events

    def events(self):
        return list(self._events)


@pytest.fixture()
def tracers(monkeypatch):
    live = []
    monkeypatch.setattr(trace, "all_tracers", lambda: list(live))
    return live


def read(name):
    return harness.load_module("layer_metrics", name + ".py").read({}, {})


EXPECTED = {
    # (2 + 0.5) + (3 + 0.25)
    "setup_programs_s": 5.75,
    # 0.5 + 0.75 + 0.25 + 1 + 1
    "setup_lower_s": 3.5,
    # 0.5 + 0.125 + 0.5 + 0.25
    "setup_backend_s": 1.375,
    "setup_introspect_s": 0.75,
    "setup_first_run_s": 0.75,
    "setup_kernel_places": 11.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_sums_the_outermost_program_records(tracers, name):
    tracers += [FakeTracer(serving_records()),
                FakeTracer([program("train_step", 1.0, 2.0,
                                    stages(0.25, 0.25, 0.25, 0.25), None,
                                    {"flash_fwd": 1}, parent=None)]),
                FakeTracer([])]
    extra = {"setup_programs_s": 1.0, "setup_lower_s": 0.5,
             "setup_backend_s": 0.25, "setup_introspect_s": 0.0,
             "setup_first_run_s": 0.25, "setup_kernel_places": 1.0}
    assert read(name) == pytest.approx(EXPECTED[name] + extra[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_is_none_on_a_cache_miss(tracers, name):
    recs = serving_records()
    recs[2]["stages"]["cache_hit"] = False
    tracers.append(FakeTracer(recs))
    assert read(name) is None
    recs = serving_records()
    recs[0]["introspect"]["cache_hit"] = False
    tracers[:] = [FakeTracer(recs)]
    assert read(name) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_is_none_without_stages(tracers, name):
    """A program whose tracer keeps no staged record (the parent commit's
    `signature`, `compile_s`, `unexpected` alone), or none at all."""
    assert read(name) is None
    old = {k: v for k, v in serving_records()[0].items()
           if k in ("site", "signature", "ts", "compile_s", "unexpected")}
    tracers += [FakeTracer(serving_records()), FakeTracer([old])]
    assert read(name) is None


def test_no_cache_lookup_is_not_a_miss(tracers):
    """A build that asked no persistent cache (cache_hit None, as where
    none is set) is read; only a lookup that missed withholds."""
    recs = copy.deepcopy(serving_records())
    for e in recs[:3]:
        e["stages"]["cache_hit"] = None
    tracers.append(FakeTracer(recs))
    assert read("setup_programs_s") == pytest.approx(5.75)


def test_the_records_of_a_live_tracer_are_read(tracers):
    """End to end on the CPU: a real tracer's record is what the readers
    sum (t1 - t0 + the replay's wall time). The CPU's builds miss the
    cache they ask, so the record is read as one that asked none."""
    import jax.numpy as jnp
    tr = trace.RecompileTracer(name="setup-readers")
    tracers.append(tr)
    tr.jit("f", lambda x: x * 2.0)(jnp.ones((4,)))
    [e] = tr.events()
    e["stages"]["cache_hit"] = e["introspect"]["cache_hit"] = None
    assert read("setup_programs_s") == pytest.approx(
        e["t1"] - e["t0"] + e["introspect"]["wall_s"])
    assert read("setup_kernel_places") == 0.0
    tr.close()
