"""`python benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`: one measuring run of one cell of `BENCHMARK.json`.

Driven by data.  The cell's name finds `workloads/<cell>.json` (the
traffic's parameters, the entry driver, the chips), that names
`configs/<config>.json` (the job's sizes and flags) and
`entries/<entry>.py` (what the window drives); the configuration's
`engine` finds `engines/<engine>.py` (hash-file lines, the potfile's
check, the operation count); every metric `BENCHMARK.json` lists for
the cell is read by `metrics/<name>.py`.  A new cell, configuration,
entry, metric or engine is a new file (a cell, a configuration and a
metric also an entry in `BENCHMARK.json`); nothing here names one.

A run: find the chip (none, or too few: exit 3, nothing printed), make
the inputs from the seed (`traffic.py`), let the entry driver warm up
and drive the window (a traced run that comes back without a trace is
an error: exit 1, nothing printed), read the device's memory peak, run
the comparison (`compare.py`, against `reference.py`), read the metrics,
print the numbers compared beside their limits on standard error and
one JSON object as the last line of standard output.
"""

import argparse
import importlib
import json
import os
import shutil
import sys
import time

T_START = time.monotonic()          # process start, as near as Python gets

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

EXIT_NO_CHIP = 3


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cell_metrics(bench, cell_name, traced):
    """The metrics this run reports: the cell's end-to-end ones, or
    with --trace 1 its per-layer ones."""
    return [m for m in bench["per_layer" if traced else "end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def find_chip(chips):
    """The devices JAX found, or None where they are not `chips` TPU
    chips or more: a measuring run never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.stderr.write(
            f"run.py: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} x {devs[0].platform}: nothing is measured\n")
        return None
    return devs


def memory_peak_bytes(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks)) if peaks else 0


def window_shape(obs):
    """How the window went, for whoever has to explain a run that reads
    far off: its length, its units, and the longest wait between two
    completions with the second at which it ended."""
    done = sorted(t for *_, t in obs["units"] if t is not None)
    times = sorted(t - t0 for _, _, t0, t in obs["units"] if t is not None)
    waits = [(b - a, b - obs["t_open"])
             for a, b in zip([obs["t_open"]] + done, done)]
    worst = max(waits, default=(0.0, 0.0))
    shape = {"seconds": obs["t_close"] - obs["t_open"], "units": len(done),
             "unit_ms_median": 1e3 * times[len(times) // 2] if times else None,
             "unit_ms_max": 1e3 * times[-1] if times else None,
             "wait_ms_max": 1e3 * worst[0], "wait_max_at_s": worst[1]}
    if obs.get("trace"):
        # the traced slice, in seconds since the window opened: it ends
        # at the window's close and is as long as the trace says
        shape["slice_s"] = [shape["seconds"] - obs["trace"]["window_s"],
                            shape["seconds"]]
        shape["close_after_program_s"] = obs["trace"]["close_after_program_s"]
    return shape


def measure(cell_name, seed, seconds, traced, devs, workdir,
            platform="tpu", interpret=False, faults=None, bench=None,
            reach_chip_s=None, data_root=None):
    """Everything of a run after the look for a chip; returns the
    result object (tests call this with a fault planted underneath)."""
    import compare
    import traffic
    bench = bench or load_benchmark()
    if cell_name not in {w["name"] for w in bench["workloads"]}:
        raise SystemExit(f"run.py: BENCHMARK.json has no workload "
                         f"{cell_name!r}")
    cell = traffic.load_json("workloads", cell_name + ".json",
                             root=data_root)
    cfg = traffic.load_json("configs", cell["config"] + ".json",
                            root=data_root)
    entry = importlib.import_module("entries." + cell["entry"])
    plan = traffic.make_plan(cfg, cell, seed, seconds, entry.WARM_UNITS,
                             root=data_root)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    ctx = {"cfg": cfg, "cell": cell, "plan": plan, "seconds": seconds,
           "trace": traced, "workdir": workdir, "faults": faults}
    obs = entry.run(ctx)
    if traced and not obs.get("trace_dir"):
        shutil.rmtree(workdir, ignore_errors=True)
        raise SystemExit(
            f"run.py: --trace 1, but the window closed "
            f"{obs['t_close'] - obs['t_open']:.3f} s after it opened and "
            f"its slice was due at {obs['slice_due_s']:.3f} s: the profiler "
            "never started, there is no per-layer metric to read, and "
            "nothing is printed")
    obs.update(cfg=cfg, cell=cell, plan=plan, t_start=T_START,
               reach_chip_s=reach_chip_s,
               n_devices=cell["chips"], device_kind=devs[0].device_kind
               if devs else None)
    peak = memory_peak_bytes(devs or [])
    # the comparison: after the window, after the memory reading
    lanes_said = entry.judge_lanes(plan, obs)
    correct, numbers = compare.compare(
        plan, cell, obs, entry.audit(obs["session"]), lanes_said,
        platform=platform, interpret=interpret)
    obs["trace"] = None
    if traced and obs.get("trace_dir"):
        import trace_reduce
        loaded = trace_reduce.load(
            trace_reduce.find_xplane(obs["trace_dir"]))
        obs["trace"] = trace_reduce.reduce(loaded, entry.KERNEL_EVENT)
    metrics = {}
    for m in cell_metrics(bench, cell_name, traced):
        reader = importlib.import_module("metrics." + m["name"])
        value = reader.read(obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform if devs else platform,
              "kind": obs["device_kind"], "count": len(devs or []),
              "memory_peak_bytes": peak}
    # a job that ended at its hit leaves the units behind it in flight:
    # those were not attempted; any other unit never completed failed
    left = sum(t is None for *_, t in obs["units"])
    at_hit = bool(plan.plants_in("tail")) and obs["rc"] == 0
    result = {"correct": bool(correct),
              "attempted": len(obs["units"]) - (left if at_hit else 0),
              "failed": 0 if at_hit else left,
              "metrics": metrics, "device": device}
    if obs["trace"]:
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
        result["breakdown"] = obs["trace"]["breakdown"]
    result["ran"] = obs["log"]["ran"]
    result["window"] = window_shape(obs)
    result["compared"] = numbers          # last: what `correct` rests on
    shutil.rmtree(workdir, ignore_errors=True)
    return result


def report(result):
    """The numbers compared beside their limits, on standard error; the
    result as the last line of standard output."""
    for name, n in result["compared"].items():
        sys.stderr.write(f"compared {name}={n['value']} limit={n['limit']}\n")
    sys.stderr.write(f"correct={result['correct']}\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        sys.stderr.write(f"run.py: no workload {args.workload!r}\n")
        return 2
    if not os.path.isdir(os.path.join(ROOT, "dprf_tpu")):
        sys.stderr.write("run.py: no dprf_tpu package beside "
                         "benchmarks/: nothing to measure\n")
        return 2
    set_vars = sorted(k for k in os.environ if k.startswith("DPRF_"))
    if set_vars:
        sys.stderr.write(f"run.py: {set_vars} set: a measuring run takes "
                         "the program's defaults\n")
        return 2
    devs = find_chip(cells[args.workload]["chips"])
    if devs is None:
        return EXIT_NO_CHIP
    reach = time.monotonic() - T_START
    workdir = os.path.join(ROOT, ".cache", "bench", args.workload)
    result = measure(args.workload, args.seed, args.seconds,
                     bool(args.trace), devs, workdir, bench=bench,
                     reach_chip_s=reach)
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
