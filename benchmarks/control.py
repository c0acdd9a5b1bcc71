"""The control and the faults on the chip, at a cell's own size:

    python benchmarks/control.py --workload <cell> \
        --fault hits_dropped,half_units --seeds 3 --seconds 12

runs the harness `--seeds` times for each fault named, in this one
process (one set-up of the chip), with the fault planted under the
timed path, and prints for each seed the numbers compared and
`correct`, which has to be false.  `none` reads the sound program's
numbers the same way: a dozen seeds of a cell in one call.
`no_exchange` stays planted once it is, so it goes last.  Not part of a
measuring run.
"""

import argparse
import json
import os
import sys

import run          # noqa: E402  (puts benchmarks/ and the root on the path)
import faults


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    names = sorted(faults.FAULTS) + ["no_exchange", "none"]
    ap.add_argument("--fault", default="hits_dropped",
                    help="one or more of " + ",".join(names))
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    wanted = args.fault.split(",")
    if set(wanted) - set(names):
        ap.error(f"--fault takes {names}")
    bench = run.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    devs = run.find_chip(cell["chips"])
    if devs is None:
        return run.EXIT_NO_CHIP
    workdir = os.path.join(run.ROOT, ".cache", "bench", "control")
    for fault in wanted:
        planted = None
        if fault == "no_exchange":
            from dprf_tpu.parallel import sharded
            sharded.lax = faults.NoExchange(cell["chips"])
        elif fault != "none":
            planted = {"patches": faults.FAULTS[fault]}
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            r = run.measure(args.workload, seed, args.seconds, False, devs,
                            workdir, faults=planted, bench=bench)
            print(json.dumps({
                "fault": fault, "seed": seed, "correct": r["correct"],
                "compared": {k: v["value"]
                             for k, v in r["compared"].items()},
                "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                "window": r["window"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
