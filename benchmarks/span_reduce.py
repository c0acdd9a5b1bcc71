"""Where the host was while chip 0 idled: the program's own stations,
read from the same trace as `trace_reduce.py` reads.

The program opens a `jax.profiler.TraceAnnotation` `dprf:<station>` at
each station of a unit's way through its sweep loop (`dprf_tpu/
telemetry/trace.py`, `STATIONS`: lease, submit, resolve, wait, decode,
verify, complete, each but `lease` carrying the unit's id; `targets`
is a job's, before its first unit).  In a traced run
they are events of the plane `/host:CPU`, on the line of the thread
that ran the loop, beside the harness's own `bench:` ones and on the
clock of the device's `XLA Modules`.

`load(path)` reads the `.xplane.pb` into plain lists (`trace_reduce`'s
loader, without the device's instructions); `reduce(trace)` gives, for
`trace_reduce`'s slice (first `bench:lease` start to last
`bench:complete` end) and chip 0 (the lowest-numbered device plane, as
`breakdown` has it), with `trace_reduce`'s own segments and gaps:

- seconds by span as SELF time on the loop's thread: spans nest, and
  each nanosecond belongs to the innermost span open there, so a
  `bench:oracle` inside a `dprf:decode` is counted once;
- the device's idle seconds by the innermost `dprf:` station open at
  the time, `unnamed` where none is.

`spans(obs)` is what the metric readers call: it loads and reduces once
a run and keeps the result on `obs`.  A trace of a program that has no
stations reduces to None, and so does a run without a trace.
"""

import trace_reduce
from trace_reduce import (HOST_PREFIX, PROGRAM_PREFIX,  # noqa: F401
                          find_xplane, loop_events)

#: stations that are the host's verification of what the device found
VERIFY_PATH = (PROGRAM_PREFIX + "decode", PROGRAM_PREFIX + "verify")


def load(path):
    """{"modules": [[start_ns, end_ns, name]..] of chip 0,
    "host": [{"line": name, "events": [[start_ns, end_ns, name,
    unit id or None]..]}..] for the lines that hold a `dprf:` or
    `bench:` event}"""
    trace = trace_reduce.load(path, ops=False)
    devices = {k: d for k, d in trace["devices"].items() if d["modules"]}
    first = min(devices, key=int, default=None)
    return {"modules": devices[first]["modules"] if devices else [],
            "host": trace["host"]}


def station_of(names):
    """The innermost `dprf:` station among the open spans, without its
    prefix; `unnamed` where the program has none open."""
    for name in reversed(names):
        if name.startswith(PROGRAM_PREFIX):
            return name[len(PROGRAM_PREFIX):]
    return "unnamed"


def reduce(trace):
    """The slice's numbers, or None where the trace lacks the slice's
    ends, a device's programs or any station of the program."""
    loop = loop_events(trace)
    ends = trace_reduce.slice_ends(loop)
    if ends is None or not trace["modules"] or not any(
            e[2].startswith(PROGRAM_PREFIX) for e in loop):
        return None
    t0, t1 = ends
    segs = trace_reduce.loop_segments(loop, t0, t1)
    self_ns, verify_ns = {}, 0
    for s, e, names in segs:
        key = names[-1] if names else "none"
        self_ns[key] = self_ns.get(key, 0) + e - s
        if any(n in VERIFY_PATH for n in names):
            verify_ns += e - s
    busy, gaps = trace_reduce.busy_and_gaps(trace["modules"], t0, t1)
    idle_ns = trace_reduce.idle_by(gaps, segs, station_of)
    secs = lambda d: {k: v / 1e9 for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])}
    return {"window_s": (t1 - t0) / 1e9,
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "idle_s": sum(e - s for s, e in gaps) / 1e9,
            "self_s": secs(self_ns),
            "idle_by_station_s": secs(idle_ns),
            "verify_path_s": verify_ns / 1e9}


def spans(obs):
    """The run's reduction, made once and kept on `obs`; None without
    a trace."""
    if "span_reduce" not in obs:
        obs["span_reduce"] = None
        if obs.get("trace_dir"):
            try:
                obs["span_reduce"] = reduce(
                    load(find_xplane(obs["trace_dir"])))
            except FileNotFoundError:
                pass
    return obs["span_reduce"]


def idle_pct(obs, stations):
    """Chip 0's idle seconds under the named stations, over the slice,
    in per cent (the reader of `dispatch_idle_pct`)."""
    r = spans(obs)
    if not r:
        return None
    return 100.0 * sum(r["idle_by_station_s"].get(s, 0.0)
                       for s in stations) / r["window_s"]
