"""How `spans_small.json` was made (PR 26, on the chip), and the
attribution tables of PERF.md section 5: one traced run of a cell with
what `span_reduce.load` read kept.

    python3 benchmarks/tests/make_spans_small.py <cell> <seed> <out.json> [<seconds>]

runs `run.py --workload <cell> --seed <seed> --seconds 30 --trace 1`
(its result line is printed as ever) and writes to <out.json> the
whole slice's reduction (`span_reduce.reduce`) with what `check` found
in it; with <seconds>, also the last <seconds> of the loaded trace to
<out>.cut.json, which is how `spans_small.json` came to be (the last
6 s of the slice of an `ntlm-1k.crack` run, seed 2600000209: five
units, 1,186 host events), and `spans_small.expect.json` is the
reduction of it, looked over by hand.  Not part of a measuring run.

The program of PR 26 had one station more, `probe` (the phase
sampler's synced unit, one in 16; deleted with the sampler in PR 32),
and one of the file's five units is such a one.  The file stays a
sound input of the reducer, which gives any `dprf:` name its idle, so
`check` still knows that a `decode` may lie inside a `probe`; a trace
of today's program holds none, and every unit is a fused one.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import span_reduce  # noqa: E402

P, B = span_reduce.PROGRAM_PREFIX, span_reduce.HOST_PREFIX


def cut(trace, t0_ns, t1_ns):
    """The events of a loaded trace that touch [t0, t1]."""
    def keep(evs):
        return [e for e in evs if e[1] > t0_ns and e[0] < t1_ns]
    return {"modules": keep(trace["modules"]),
            "host": [{"line": ln["line"], "events": keep(ln["events"])}
                     for ln in trace["host"]]}


def _inside(ev, others):
    return any(o[0] <= ev[0] and ev[1] <= o[1] for o in others)


def check(trace):
    """What a trace has to show of the stations themselves (counts of
    breaches, each 0 on a sound trace, and two sums to compare)."""
    loop = span_reduce.loop_events(trace)
    by = {}
    for e in loop:
        by.setdefault(e[2], []).append(e)
    get = lambda name: by.get(name, [])
    program = [e for e in loop if e[2].startswith(P)]
    probed = {e[3] for e in get(P + "probe")}
    per_unit = {}
    for e in program:
        if e[3] is not None and e[3] not in probed:
            per_unit[e[3]] = per_unit.get(e[3], 0) + 1
    seconds = lambda evs: sum(e[1] - e[0] for e in evs) / 1e9
    return {
        "stations_seen": sorted({e[2][len(P):] for e in program}),
        "without_unit_id": sum(e[3] is None for e in program
                               if e[2] != P + "lease"),
        "wait_outside_resolve": sum(
            not _inside(e, get(P + "resolve")) for e in get(P + "wait")),
        "decode_outside_resolve_or_probe": sum(
            not _inside(e, get(P + "resolve") + get(P + "probe"))
            for e in get(P + "decode")),
        "program_lease_outside_harness_lease": sum(
            not _inside(e, get(B + "lease")) for e in get(P + "lease")),
        # the trace stops inside the last `dprf:complete`, which is
        # therefore never written: one is expected
        "harness_complete_outside_program_complete": sum(
            not _inside(e, get(P + "complete"))
            for e in get(B + "complete")),
        "events_per_unit_not_probed_max": max(per_unit.values(),
                                              default=0),
        "lease_s": {"program": seconds(get(P + "lease")),
                    "harness": seconds(get(B + "lease"))},
        "complete_s": {"program": seconds(get(P + "complete")),
                       "harness": seconds(get(B + "complete"))},
    }


def main(cell, seed, out, seconds=None):
    import run
    kept = {}
    real_load = span_reduce.load

    def load(path):
        kept["trace"] = real_load(path)
        return kept["trace"]

    span_reduce.load = load
    rc = run.main(["--workload", cell, "--seed", str(seed),
                   "--seconds", "30", "--trace", "1"])
    trace = kept.get("trace")
    if rc or trace is None:
        return rc or 1
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as fh:
        json.dump({"cell": cell, "seed": int(seed),
                   "reduce": span_reduce.reduce(trace),
                   "check": check(trace)}, fh, indent=1)
    if seconds is not None:
        closes = [e for e in span_reduce.loop_events(trace)
                  if e[2] == B + "complete"]
        t1 = closes[-1][1]
        with open(out + ".cut.json", "w") as fh:
            json.dump(cut(trace, t1 - float(seconds) * 1e9, t1 + 1), fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:5]))
