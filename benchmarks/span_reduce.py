"""Where the host was while chip 0 idled: the program's own stations,
read from the same trace as `trace_reduce.py` reads.

The program opens a `jax.profiler.TraceAnnotation` `dprf:<station>` at
each station of a unit's way through its sweep loop (`dprf_tpu/
telemetry/trace.py`, `STATIONS`: lease, submit, probe, resolve, wait,
decode, verify, complete), carrying the unit's id.  In a traced run
they are events of the plane `/host:CPU`, on the line of the thread
that ran the loop, beside the harness's own `bench:` ones and on the
clock of the device's `XLA Modules`.

`load(path)` reads the `.xplane.pb` into plain lists; `reduce(trace)`
gives, for `trace_reduce`'s slice (first `bench:lease` start to last
`bench:complete` end) and chip 0 (the lowest-numbered device plane, as
`breakdown` has it):

- seconds by span as SELF time on the loop's thread: spans nest, and
  each nanosecond belongs to the innermost span open there, so a
  `bench:oracle` inside a `dprf:decode` is counted once;
- the device's idle seconds by the innermost `dprf:` station open at
  the time, `unnamed` where none is.

`spans(obs)` is what the metric readers call: it loads and reduces once
a run and keeps the result on `obs`.  A trace of a program that has no
stations reduces to None, and so does a run without a trace.
"""

from trace_reduce import DEVICE_PLANE, HOST_PREFIX, _clip, _union, find_xplane

PROGRAM_PREFIX = "dprf:"
#: stations that are the host's verification of what the device found
VERIFY_PATH = (PROGRAM_PREFIX + "decode", PROGRAM_PREFIX + "verify")
_KEPT = (PROGRAM_PREFIX, HOST_PREFIX)


def load(path):
    """{"modules": [[start_ns, end_ns, name]..] of chip 0,
    "host": [{"line": name, "events": [[start_ns, end_ns, name,
    unit id or None]..]}..] for the lines that hold a `dprf:` or
    `bench:` event}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out, first = {"modules": [], "host": []}, None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and (first is None or int(m.group(1)) < first):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    first = int(m.group(1))
                    out["modules"] = [
                        [e.start_ns, e.start_ns + e.duration_ns, e.name]
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [[e.start_ns, e.start_ns + e.duration_ns, e.name,
                        dict(e.stats).get("unit")]
                       for e in line.events if e.name.startswith(_KEPT)]
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
    leases = [e for e in loop if e[2] == HOST_PREFIX + "lease"]
    closes = [e for e in loop if e[2] == HOST_PREFIX + "complete"]
    if not leases or not closes or not trace["modules"] or not any(
            e[2].startswith(PROGRAM_PREFIX) for e in loop):
        return None
    t0, t1 = leases[0][0], closes[-1][1]
    if t1 <= t0:
        return None
    segs = segments(_clip([e[:3] for e in loop], t0, t1), t0, t1)
    self_ns, verify_ns = {}, 0
    for s, e, names in segs:
        key = names[-1] if names else "none"
        self_ns[key] = self_ns.get(key, 0) + e - s
        if any(n in VERIFY_PATH for n in names):
            verify_ns += e - s
    busy = _union([(s, e) for s, e, _ in _clip(trace["modules"], t0, t1)])
    gaps, at = [], t0
    for s, e in busy + [[t1, t1]]:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    idle_ns, i = {}, 0
    for gs, ge in gaps:
        while i < len(segs) and segs[i][1] <= gs:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < ge:
            s, e, names = segs[j]
            key = station_of(names)
            idle_ns[key] = idle_ns.get(key, 0) + min(e, ge) - max(s, gs)
            j += 1
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
    in per cent (the readers of `probe_idle_pct` and
    `dispatch_idle_pct`)."""
    r = spans(obs)
    if not r:
        return None
    return 100.0 * sum(r["idle_by_station_s"].get(s, 0.0)
                       for s in stations) / r["window_s"]
