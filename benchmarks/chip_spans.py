"""The host's verification on every chip: what `span_reduce.py` (chip
0, self time) does not give.

The program opens four stations inside `dprf:decode` and `dprf:verify`
(`dprf_tpu/telemetry/trace.py`, `STATIONS`): `readback` (a unit's hit
buffers to host arrays), `reprobe` (collided tiles' re-probes
enqueued), `reprobe_wait` (the loop blocked until they have run) and
`oracle` (every call into the CPU oracle).  `span_reduce` puts each
idle nanosecond of chip 0 alone under the innermost station open on
the loop's thread; on a mesh chip 0 also runs the re-probes while the
other chips wait, and its `XLA Modules` event spans gaps they show as
idle, so chip 0 under-reads the verify path's idle there.

`reduce(trace)` gives, over `trace_reduce`'s slice (first `bench:lease`
start to last `bench:complete` end):

- `self_s`: seconds by span on the loop's thread, each nanosecond to
  the innermost span open (as `span_reduce` counts it);
- `inclusive_s`: the same, each nanosecond to every span open (a
  `bench:oracle` inside a `dprf:oracle` counts for both);
- `idle_by_chip_s`: for each chip that ran a program, its idle seconds
  by the innermost `dprf:` station open on the loop's thread.

A trace of a program without those four stations reduces to None, as
does a run without a trace.  `chip_spans(obs)` is what the metric
readers call: it loads and reduces once a run, keeps the result on
`obs`, and says on standard error how long the load took.
"""

import sys
import time

import span_reduce
import trace_reduce
from trace_reduce import PROGRAM_PREFIX, find_xplane, loop_events

#: the host's verification of what the device found: `decode`,
#: `verify` and the stations the program opens inside them
VERIFY_STATIONS = ("decode", "verify", "readback", "reprobe",
                   "reprobe_wait", "oracle")
_INNER = frozenset(PROGRAM_PREFIX + s for s in VERIFY_STATIONS[2:])


def load(path):
    """{"modules": {chip: [[start_ns, end_ns, name]..]} of every chip
    that ran a program, "host": as `trace_reduce.load` gives it}"""
    trace = trace_reduce.load(path, ops=False)
    return {"modules": {k: d["modules"]
                        for k, d in trace["devices"].items() if d["modules"]},
            "host": trace["host"]}


def reduce(trace):
    """The slice's numbers, or None where the trace lacks the slice's
    ends, a chip's programs or the host's verification stations."""
    loop = loop_events(trace)
    ends = trace_reduce.slice_ends(loop)
    if ends is None or not trace["modules"] or not any(
            e[2] in _INNER for e in loop):
        return None
    t0, t1 = ends
    segs = trace_reduce.loop_segments(loop, t0, t1)
    self_ns, inclusive_ns = {}, {}
    for s, e, names in segs:
        key = names[-1] if names else "none"
        self_ns[key] = self_ns.get(key, 0) + e - s
        for name in set(names):
            inclusive_ns[name] = inclusive_ns.get(name, 0) + e - s
    secs = lambda d: {k: v / 1e9 for k, v in d.items()}
    idle = {}
    for chip in sorted(trace["modules"], key=int):
        _, gaps = trace_reduce.busy_and_gaps(trace["modules"][chip], t0, t1)
        idle[chip] = secs(trace_reduce.idle_by(gaps, segs,
                                               span_reduce.station_of))
    return {"window_s": (t1 - t0) / 1e9, "self_s": secs(self_ns),
            "inclusive_s": secs(inclusive_ns), "idle_by_chip_s": idle}


def chip_spans(obs):
    """The run's reduction, made once and kept on `obs`; None without
    a trace or without the stations."""
    if "chip_spans" not in obs:
        obs["chip_spans"] = None
        if obs.get("trace_dir"):
            try:
                path = find_xplane(obs["trace_dir"])
            except FileNotFoundError:
                return None
            t0 = time.monotonic()
            trace = load(path)
            sys.stderr.write(f"chip_spans: trace loaded in "
                             f"{time.monotonic() - t0:.3f} s\n")
            obs["chip_spans"] = reduce(trace)
    return obs["chip_spans"]


def verify_idle_s(r):
    """Each chip's idle seconds under the host's verification, in chip
    order."""
    return [sum(by.get(s, 0.0) for s in VERIFY_STATIONS)
            for by in r["idle_by_chip_s"].values()]
