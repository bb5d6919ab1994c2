"""From a profiler trace (.xplane.pb) to numbers: busy intervals, device time
per module and per operation, idle gaps and what the host was doing in them.

The arithmetic works on plain tuples, so it is tested on synthetic intervals;
`read_xplane` is the only part that knows the trace's layout:

- a device is a plane named `/device:TPU:<n>`; its line `XLA Modules` holds
  one event per execution of a compiled program, named
  `jit_<function>(<program id>)`, its line `XLA Ops` one per operation, named
  by its HLO text (a Pallas kernel is a `custom-call` with the target
  `tpu_custom_call`); asynchronous copies lie on a line of their own;
- host threads are lines of the plane `/host:CPU`; the harness's marks
  (`bench:mark<i>` TraceAnnotations) lie there and tie `time.perf_counter`
  to the trace's clock.

Run `python benchmarks/trace_reduce.py <file.xplane.pb>` to look at a trace
by hand: planes, lines, counts and the first events of each line."""
from __future__ import annotations

import re
import statistics
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
MARK = re.compile(r"^bench:mark(\d+)$")
TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(hlo):
    """An operation's event is named by its whole HLO text: keep the
    result's name (`%fusion.12`) and, for a custom call, its target
    (`tpu_custom_call` is a Pallas kernel; the kernel's own name is not
    in the trace)."""
    name = hlo.split(" = ", 1)[0]
    target = TARGET.search(hlo)
    return f"{name} [{target.group(1)}]" if target else name


# -- arithmetic on intervals (seconds, any clock) ----------------------------

def clip(intervals, t0, t1):
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if min(b, t1) > max(a, t0)]


def union(intervals):
    """Sorted, merged intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_seconds(intervals, t0, t1):
    return sum(b - a for a, b in union(clip(intervals, t0, t1)))


def gaps(intervals, t0, t1):
    """The idle stretches of [t0, t1]: what the union leaves uncovered."""
    out, at = [], t0
    for a, b in union(clip(intervals, t0, t1)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if t1 > at:
        out.append((at, t1))
    return out


def owner_of(t, spans):
    """Name of the innermost (shortest) host span that holds time t."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "outside_spans"


def gap_owners(gap_list, spans, top=10):
    """Idle seconds by what the host was doing at each gap's middle,
    longest first: [[name, seconds], ...]."""
    total = {}
    for a, b in gap_list:
        name = owner_of((a + b) / 2, spans)
        total[name] = total.get(name, 0.0) + (b - a)
    return [[n, s] for n, s in
            sorted(total.items(), key=lambda kv: -kv[1])[:top]]


def seconds_by_name(events, t0, t1):
    """{name: [seconds, count]} of events (name, start, end) that begin
    inside [t0, t1)."""
    out = {}
    for name, a, b in events:
        if t0 <= a < t1:
            row = out.setdefault(name, [0.0, 0])
            row[0] += b - a
            row[1] += 1
    return out


def top_by_seconds(by_name, top=10):
    return [[n, v[0]] for n, v in
            sorted(by_name.items(), key=lambda kv: -kv[1][0])[:top]]


def matching_seconds(by_name, pattern):
    """Summed seconds and count of the names that `pattern` finds."""
    rx = re.compile(pattern)
    hit = [v for n, v in by_name.items() if rx.search(n)]
    return sum(v[0] for v in hit), sum(v[1] for v in hit)


# -- the trace file ----------------------------------------------------------

def read_xplane(path):
    """{"devices": {n: {"modules": [(name, start_s, end_s)], "ops": [...]}},
    "marks": {i: start_s}} on the trace's own clock, in seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, marks = {}, {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"modules": [], "ops": []})
            for line in plane.lines:
                key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(
                    line.name)
                if key is None:
                    continue
                short = short_name if key == "ops" else str
                dev[key] = [(short(e.name), e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    mm = MARK.match(e.name)
                    if mm:
                        marks[int(mm.group(1))] = e.start_ns * 1e-9
    return {"devices": devices, "marks": marks}


def clock_offset(trace_marks, perf_marks):
    """Seconds to add to a `time.perf_counter` reading to land on the
    trace's clock: the median over the marks both sides saw."""
    diffs = [trace_marks[i] - t for i, t in enumerate(perf_marks)
             if i in trace_marks]
    if not diffs:
        raise ValueError("the trace holds none of the harness's marks")
    return statistics.median(diffs)


def summarize(path, host, t0, t1):
    """What the readers under layer_metrics/ get as `trace`. `host` is the
    harness's HostSpans; t0, t1 bound the window on perf_counter."""
    raw = read_xplane(path)
    if not raw["devices"]:
        raise ValueError("the trace holds no /device:TPU plane")
    off = clock_offset(raw["marks"], host.marks)
    a, b = t0 + off, t1 + off
    spans = [(n, s + off, e + off) for n, s, e in host.spans]
    per_dev = []
    for _, dev in sorted(raw["devices"].items()):
        iv = [(s, e) for _, s, e in (dev["ops"] or dev["modules"])]
        per_dev.append({
            "busy_s": busy_seconds(iv, a, b),
            "gaps": gaps(iv, a, b),
            "modules": seconds_by_name(dev["modules"], a, b),
            "ops": seconds_by_name(dev["ops"], a, b)})
    first = per_dev[0]
    return {
        "window_s": t1 - t0,
        "busy_s": sum(d["busy_s"] for d in per_dev) / len(per_dev),
        "modules": first["modules"],
        "ops": first["ops"],
        "device_ops": top_by_seconds(first["ops"] or first["modules"]),
        "idle_gaps": gap_owners(first["gaps"], spans),
        "devices": len(per_dev)}


def _dump(path, out=sys.stdout):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines", file=out)
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events", file=out)
            seen = {}
            for e in evs:
                seen[e.name] = seen.get(e.name, 0) + 1
            for e in evs[:5]:
                stats = {k: v for k, v in list(e.stats)[:8]}
                print(f"    {e.name!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} {stats}", file=out)
            common = sorted(seen.items(), key=lambda kv: -kv[1])[:12]
            print(f"    most frequent: {common}", file=out)


if __name__ == "__main__":
    _dump(sys.argv[1])
