"""From the profiler's trace to numbers: the benchmark's own reduction.

`load(path)` reads an `.xplane.pb` with nothing but JAX
(`jax.profiler.ProfileData`) into plain lists; `reduce(trace, ..)`
turns those into the slice's busy, idle and kernel seconds and the
breakdown.  `tests/trace_small.json` is `load()`'s output for the last
part of a real chip trace, kept so that `reduce` can be checked off the
chip (`tests/make_trace_small.py` made it).

What a TPU trace holds (looked at by hand, PR 24): one plane
`/device:TPU:<n>` per chip with the lines `XLA Modules` (one event per
executed program: the device is busy exactly inside these), `XLA Ops`
(every HLO instruction, nested: a `while` event spans the events of
its body) and `Async XLA Ops`; the Pallas kernel is an `XLA Ops` event
whose text holds ` custom-call(`.  Host threads are lines of the plane
`/host:CPU`; `jax.profiler.TraceAnnotation`s appear there under their
own names: the harness's `bench:<name>` (`entries/crack.py`, `Spans`)
and the program's stations `dprf:<station>` (`dprf_tpu/telemetry/
trace.py`, `STATIONS`), both on the line of the thread that runs the
job's loop.  All times are nanoseconds from the trace's start, the
same clock on every plane.

The slice that is reduced runs from the start of the first host
annotation named `bench:lease` to the end of the last
`bench:complete`: from the first call the trace saw to the window's
close.  What is shared with `span_reduce.py` lives here: the loader,
the slice's ends, the cut of the loop's thread into segments by the
spans open on it, and the attribution of the device's idle gaps to
those segments.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PREFIX = "bench:"
PROGRAM_PREFIX = "dprf:"
_OP = re.compile(r"^%?(\S+) = .*?\s([a-z][a-z0-9\-]*)\(")


def find_xplane(directory):
    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path, ops=True):
    """{"devices": {"0": {"modules": [[start_ns, end_ns, name]..],
    "ops": [..] (left empty without `ops`)}}, "host": [{"line": the
    thread's name, "events": [[start_ns, end_ns, name, unit id or
    None]..]}..] for the lines that hold a `dprf:` or `bench:` event}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    wanted = {"XLA Modules": "modules"}
    if ops:
        wanted["XLA Ops"] = "ops"
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(
                m.group(1), {"modules": [], "ops": []})
            for line in plane.lines:
                if line.name in wanted:
                    dev[wanted[line.name]] = [
                        [e.start_ns, e.start_ns + e.duration_ns, e.name]
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [[e.start_ns, e.start_ns + e.duration_ns, e.name,
                        dict(e.stats).get("unit")]
                       for e in line.events
                       if e.name.startswith((PROGRAM_PREFIX, HOST_PREFIX))]
                if evs:
                    out["host"].append({"line": line.name,
                                        "events": sorted(evs)})
    return out


def loop_events(trace):
    """The events of the thread that ran the job's loop: the line that
    holds the harness's `bench:lease`."""
    for line in trace["host"]:
        if any(e[2] == HOST_PREFIX + "lease" for e in line["events"]):
            return line["events"]
    return []


def slice_ends(loop):
    """(t0, t1) of the slice, or None where the loop's thread lacks
    one of its ends."""
    leases = [e for e in loop if e[2] == HOST_PREFIX + "lease"]
    closes = [e for e in loop if e[2] == HOST_PREFIX + "complete"]
    if not leases or not closes or closes[-1][1] <= leases[0][0]:
        return None
    return leases[0][0], closes[-1][1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(evs, t0, t1):
    return [(max(s, t0), min(e, t1), n) for s, e, n in evs
            if e > t0 and s < t1]


def _self_times(ops):
    """name -> seconds an instruction ran itself, its nested
    instructions taken out (a `while` spans its body's events)."""
    out, stack = {}, []          # stack: [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, name, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + self_ns / 1e9

    for s, e, name in sorted(ops, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def short_name(text):
    """`%step.11 = s32[..] custom-call(..)` -> `custom-call step.11`."""
    m = _OP.match(text)
    return f"{m.group(2)} {m.group(1)}" if m else text[:60]


def segments(events, t0, t1):
    """[t0, t1] cut wherever a span opens or closes: [(start, end,
    names of the spans open there, outermost first)]."""
    out, stack, at = [], [], t0     # stack: [end, name]

    def emit(upto):
        nonlocal at
        if upto > at:
            out.append((at, upto, tuple(n for _, n in stack)))
            at = upto

    def close(upto):
        while stack and stack[-1][0] <= upto:
            emit(stack[-1][0])
            stack.pop()

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        emit(s)
        # a child ends with its parent at the latest
        stack.append([min(e, stack[-1][0]) if stack else e, name])
    close(t1)
    emit(t1)
    return out


def loop_segments(loop, t0, t1):
    """The slice of the loop's thread, cut by the spans open on it."""
    return segments(_clip([e[:3] for e in loop], t0, t1), t0, t1)


def busy_and_gaps(modules, t0, t1):
    """One chip's slice: the union of the intervals in which a program
    ran, and the gaps between them."""
    busy = _union([(s, e) for s, e, _ in _clip(modules, t0, t1)])
    gaps, at = [], t0
    for s, e in busy + [[t1, t1]]:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    return busy, gaps


def idle_by(gaps, segs, key):
    """{key(names of the spans open): ns} over the device's gaps: each
    nanosecond of a gap goes to the segment of the loop's thread that
    holds it."""
    idle_ns, i = {}, 0
    for gs, ge in gaps:
        while i < len(segs) and segs[i][1] <= gs:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < ge:
            s, e, names = segs[j]
            k = key(names)
            idle_ns[k] = idle_ns.get(k, 0) + min(e, ge) - max(s, gs)
            j += 1
    return idle_ns


def reduce(trace, kernel_event):
    """The slice's numbers.  kernel_event: the text an `XLA Ops` event
    of the hash kernel holds (the cell's file names it)."""
    loop = loop_events(trace)
    ends = slice_ends(loop)
    devices = {k: d for k, d in trace["devices"].items() if d["modules"]}
    if ends is None or not devices:
        return None
    t0, t1 = ends
    busy_s, kernel_s, kernel_calls, kernel_whole_s = [], [], [], []
    for dev in devices.values():
        busy, _ = busy_and_gaps(dev["modules"], t0, t1)
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        kern = [ev for ev in _clip(dev["ops"], t0, t1)
                if kernel_event in ev[2]]
        kernel_s.append(sum(e - s for s, e, _ in kern) / 1e9)
        # calls wholly inside the slice, and their time: a rate of the
        # kernel divides the one by the other
        whole = [e - s for s, e, n in dev["ops"]
                 if kernel_event in n and s >= t0 and e <= t1]
        kernel_calls.append(len(whole))
        kernel_whole_s.append(sum(whole) / 1e9)
    first = devices[min(devices, key=int)]
    ops = {}
    for name, sec in _self_times(_clip(first["ops"], t0, t1)).items():
        key = short_name(name)
        ops[key] = ops.get(key, 0.0) + sec
    # chip 0's idle, each gap under the innermost span open on the
    # loop's thread at the time, the program's or the harness's
    busy, gaps = busy_and_gaps(first["modules"], t0, t1)
    idle = idle_by(gaps, loop_segments(loop, t0, t1),
                   lambda names: names[-1] if names else "host:other")
    n = len(devices)
    top = lambda d, scale: [[k, v / scale] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:10] if v > 0]
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_s) / n,
        "kernel_s": sum(kernel_s) / n,
        "kernel_calls": sum(kernel_calls) / n,
        "kernel_whole_s": sum(kernel_whole_s) / n,
        "n_devices": n,
        # on a drained pipeline the close follows chip 0's last program
        # by the host's few milliseconds; a unit's length here says the
        # trace lost the slice's last program
        "close_after_program_s": (t1 - busy[-1][1]) / 1e9 if busy else None,
        "breakdown": {"device_ops": top(ops, 1.0),
                      "idle_gaps": top(idle, 1e9)},
    }
