"""How `trace_small.json` and `trace_small.expect.json` were made (PR 30,
on the chip): one traced run of a cell, with the last seconds of what
`trace_reduce.load` read kept small enough to lie beside the tests.

    python3 benchmarks/tests/make_trace_small.py <cell> <seed> <out.json> [<seconds>]

runs `run.py --workload <cell> --seed <seed> --seconds 30 --trace 1`
(its result line is printed as ever), cuts the last <seconds> of the
loaded trace, writes the cut to <out.json> with every event's text kept
once (`pack`; `unpack` gives `load`'s lists back: a second of
`md5-mask.crack` is 21,000 instructions of 180 characters), and writes
`expect(..)` of the cut, which is `trace_reduce.reduce` of it less the
device's instructions, to <out>.expect.json: looked over by hand, then
both copied here.  The files kept are of `md5-mask.crack`, seed
3000000101, its last 0.7 s: eleven units, among them the one the phase
sampler probed, which began 0.63 s before the close.  Not part of a
measuring run.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce  # noqa: E402

KEPT = ("window_s", "busy_s", "kernel_s", "kernel_calls", "kernel_whole_s")


def cut(trace, t0_ns, t1_ns):
    """The events of a loaded trace that touch [t0, t1]."""
    def keep(evs):
        return [e for e in evs if e[1] > t0_ns and e[0] < t1_ns]
    return {"devices": {k: {"modules": keep(d["modules"]),
                            "ops": keep(d["ops"])}
                        for k, d in trace["devices"].items()},
            "host": [{"line": ln["line"], "events": keep(ln["events"])}
                     for ln in trace["host"]]}


def pack(trace):
    """A loaded trace with each event's text replaced by its index in
    `names`, and whole nanoseconds as integers."""
    names = {}

    def small(evs):
        return [[int(v) if float(v).is_integer() else v for v in e[:2]]
                + [names.setdefault(e[2], len(names))] + list(e[3:])
                for e in evs]
    out = {"devices": {k: {"modules": small(d["modules"]),
                           "ops": small(d["ops"])}
                       for k, d in trace["devices"].items()},
           "host": [{"line": ln["line"], "events": small(ln["events"])}
                    for ln in trace["host"]]}
    out["names"] = list(names)
    return out


def unpack(packed):
    """`pack` undone: what `trace_reduce.load` returns."""
    names = packed["names"]

    def full(evs):
        return [e[:2] + [names[e[2]]] + e[3:] for e in evs]
    return {"devices": {k: {"modules": full(d["modules"]),
                            "ops": full(d["ops"])}
                        for k, d in packed["devices"].items()},
            "host": [{"line": ln["line"], "events": full(ln["events"])}
                     for ln in packed["host"]]}


def expect(trace, kernel_event):
    r = trace_reduce.reduce(trace, kernel_event)
    return dict({k: r[k] for k in KEPT},
                idle_gaps=r["breakdown"]["idle_gaps"])


def main(cell, seed, out, seconds=0.7):
    import importlib
    import run
    import traffic
    kept = {}
    real_load = trace_reduce.load

    def load(path, ops=True):
        trace = real_load(path, ops=ops)
        if ops:
            kept["trace"] = trace
        return trace

    trace_reduce.load = load
    rc = run.main(["--workload", cell, "--seed", str(seed),
                   "--seconds", "30", "--trace", "1"])
    trace = kept.get("trace")
    if rc or trace is None:
        return rc or 1
    entry = importlib.import_module("entries." + traffic.load_json(
        "workloads", cell + ".json")["entry"])
    t1 = trace_reduce.slice_ends(trace_reduce.loop_events(trace))[1]
    small = cut(trace, t1 - float(seconds) * 1e9, t1 + 1)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as fh:
        json.dump(pack(small), fh, separators=(",", ":"))
    with open(os.path.splitext(out)[0] + ".expect.json", "w") as fh:
        json.dump(expect(small, entry.KERNEL_EVENT), fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:5]))
