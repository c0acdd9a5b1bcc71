"""How `trace_small.json` was made (PR 24, on the chip): the last
second of the slice of a traced run's loaded trace, cut small enough to
keep beside the tests.

    python benchmarks/tests/make_trace_small.py <file.xplane.pb> <out.json>

then `trace_small.expect.json` is `trace_reduce.reduce` of it, looked
over by hand.  Not part of a measuring run.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import trace_reduce  # noqa: E402


def cut(trace, t0_ns, t1_ns):
    """The events of a loaded trace that touch [t0, t1]."""
    def keep(evs):
        return [e for e in evs if e[1] > t0_ns and e[0] < t1_ns]
    return {"devices": {k: {"modules": keep(d["modules"]),
                            "ops": keep(d["ops"])}
                        for k, d in trace["devices"].items()},
            "host": keep(trace["host"])}


def main(xplane, out, seconds=1.0):
    trace = trace_reduce.load(xplane)
    closes = [e for e in trace["host"]
              if e[2] == trace_reduce.HOST_PREFIX + "complete"]
    t1 = closes[-1][1]
    with open(out, "w") as fh:
        json.dump(cut(trace, t1 - seconds * 1e9, t1 + 1), fh)


if __name__ == "__main__":
    main(*sys.argv[1:3])
