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
own names.  All times are nanoseconds from the trace's start, the
same clock on every plane.

The slice that is reduced runs from the start of the first host
annotation named `bench:lease` to the end of the last
`bench:complete`: from the first call the trace saw to the window's
close.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PREFIX = "bench:"
_OP = re.compile(r"^%?(\S+) = .*?\s([a-z][a-z0-9\-]*)\(")


def find_xplane(directory):
    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path):
    """{"devices": {"0": {"modules": [[start_ns, end_ns, name]..],
    "ops": [..]}}, "host": [[start_ns, end_ns, name]..]}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(
                m.group(1), {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.start_ns, e.start_ns + e.duration_ns,
                                 e.name] for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"] += [
                    [e.start_ns, e.start_ns + e.duration_ns, e.name]
                    for e in line.events if e.name.startswith(HOST_PREFIX)]
    out["host"].sort()
    return out


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


def reduce(trace, kernel_event):
    """The slice's numbers.  kernel_event: the text an `XLA Ops` event
    of the hash kernel holds (the cell's file names it)."""
    leases = [e for e in trace["host"] if e[2] == HOST_PREFIX + "lease"]
    closes = [e for e in trace["host"] if e[2] == HOST_PREFIX + "complete"]
    devices = {k: d for k, d in trace["devices"].items() if d["modules"]}
    if not leases or not closes or not devices:
        return None
    t0, t1 = leases[0][0], closes[-1][1]
    if t1 <= t0:
        return None
    busy_s, kernel_s, kernel_calls, kernel_whole_s = [], [], [], []
    for dev in devices.values():
        busy = _union([(s, e) for s, e, _ in _clip(dev["modules"], t0, t1)])
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
    first = devices[sorted(devices, key=int)[0]]
    ops = {}
    for name, sec in _self_times(_clip(first["ops"], t0, t1)).items():
        key = short_name(name)
        ops[key] = ops.get(key, 0.0) + sec
    busy = _union([(s, e) for s, e, _ in _clip(first["modules"], t0, t1)])
    gaps, at = [], t0
    for s, e in busy + [[t1, t1]]:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    host = trace["host"]
    idle = {}
    for gs, ge in gaps:
        named = 0
        for s, e, name in _clip(host, gs, ge):
            idle[name] = idle.get(name, 0) + e - s
            named += e - s
        idle["host:other"] = idle.get("host:other", 0) + max(
            0, ge - gs - named)
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
        "breakdown": {"device_ops": top(ops, 1.0),
                      "idle_gaps": top(idle, 1e9)},
    }


