"""The rest of a run after the look for a chip, on the CPU at a tiny
size, sound and with the timed path broken underneath: `correct` has to
come out false for each fault a cell can have."""

import importlib
import json
import os
import subprocess
import sys
import time

import pytest

import conftest
import faults
import run
import traffic

END_TO_END = [{"name": n, "unit": "x"}
              for n in ("cand_per_s", "ttfh_s", "setup_s")]
PER_LAYER = [{"name": n, "unit": "x"} for n in (
    "reach_chip_s", "lease_pct", "unit_p95_ms",
    "oracle_pct", "window_compiles", "kernel_pct", "device_idle_pct")]


def measure(cell, seed, tmp_path, seconds=1.5, traced=False, faults=None):
    import jax
    bench = {"workloads": [{"name": cell}], "end_to_end": END_TO_END,
             "per_layer": PER_LAYER}
    return run.measure(cell, seed, seconds, traced, jax.devices(),
                       str(tmp_path / "wd"), platform="cpu",
                       interpret=True, faults=faults, bench=bench,
                       data_root=conftest.DATA, reach_chip_s=0.0)


def plan_of(cell, seed, seconds=1.5):
    import entries.crack as crack
    c = traffic.load_json("workloads", cell + ".json", root=conftest.DATA)
    cfg = traffic.load_json("configs", c["config"] + ".json",
                            root=conftest.DATA)
    return cfg, c, traffic.make_plan(cfg, c, seed, seconds,
                                     crack.WARM_UNITS)


def values(result):
    return {k: v["value"] for k, v in result["compared"].items()}


# -- sound runs ------------------------------------------------------------

def test_sound_one_target_run_is_correct(tmp_path):
    r = measure("tiny-md5.crack", 3, tmp_path)
    assert r["correct"], r["compared"]
    v = values(r)
    assert v["plants_inside"] == 1 and v["lanes_judged"] == 16
    assert set(r["metrics"]) == {"cand_per_s", "setup_s"}
    assert r["attempted"] > 2 and r["failed"] == 0
    assert list(r)[-1] == "compared"


def test_sound_list_run_finds_plants_and_twins(tmp_path):
    r = measure("tiny-ntlm.crack", 2**31 + 5, tmp_path, seconds=2.5)
    assert r["correct"], r["compared"]
    assert values(r)["plants_inside"] == 3
    assert values(r)["lanes_judged"] == 4
    assert r["metrics"]["ttfh_s"]["value"] > 0


def test_traced_run_reports_layers_and_no_device_metric_off_tpu(tmp_path):
    r = measure("tiny-ntlm.crack", 11, tmp_path, seconds=2.5, traced=True)
    assert r["correct"], r["compared"]
    m = r["metrics"]
    assert m["window_compiles"]["value"] == 0
    assert 0 < m["lease_pct"]["value"] < 100
    assert 0 < m["oracle_pct"]["value"] < 100
    # every unit, unit 0 too, went through the fused, pipelined
    # dispatch: the job's own line counts no probed batch and has its
    # seconds in `submit` and `wait`
    assert "probe" not in r["ran"]["dispatch"]
    host = dict(f.split(":") for f in r["ran"]["host"].split(","))
    assert float(host["submit"]) > 0 and float(host["wait"]) > 0
    # no TPU plane in a CPU trace: the device readers find nothing to
    # read and return nothing, never 0
    assert "kernel_pct" not in m and "device_idle_pct" not in m
    assert "busy_s" not in r["device"]


def run_entry(cell, seed, tmp_path, seconds, **cell_changes):
    import entries.crack as crack
    cfg, c, _ = plan_of(cell, seed)
    c = dict(c, **cell_changes)
    plan = traffic.make_plan(cfg, c, seed, seconds, crack.WARM_UNITS)
    wd = tmp_path / "wd"
    wd.mkdir()
    return plan, crack.run({"cfg": cfg, "cell": c, "plan": plan,
                            "seconds": seconds, "trace": False,
                            "workdir": str(wd)})


def test_window_opens_on_an_empty_pipeline_and_journal_audits(tmp_path):
    import entries.crack as crack
    plan, obs = run_entry("tiny-md5.crack", 3, tmp_path, 1.0)
    assert obs["outstanding_at_open"] == 0
    assert len(obs["warm_units"]) == crack.WARM_UNITS
    assert all(t <= obs["t_open"] for *_, t in obs["warm_units"])
    assert all(t0 >= obs["t_open"] for _, _, t0, _ in obs["units"])
    assert obs["t_close"] == max(t for *_, t in obs["units"])
    doc = crack.audit(obs["session"])
    assert doc["verdict"] in ("incomplete", "clean") and not doc["problems"]
    assert doc["jobs"][0]["digest_match"] is True
    units = obs["warm_units"] + obs["units"] + obs["tail_units"]
    assert doc["jobs"][0]["covered"] == plan.skip + sum(
        n for _, n, _, t in units if t is not None)


def test_the_tail_lies_outside_the_window_and_ends_at_the_plant(tmp_path):
    plan, obs = run_entry("tiny-md5.crack", 4, tmp_path, 1.0,
                          tail_plant={"units_per_s": 1000.0}, lane_units=2)
    (plant,) = plan.plants_in("tail")
    tail = obs["tail_units"]
    assert tail and obs["rc"] == 0
    # the clock closed the window on a drained pipeline; the same job
    # went on to the plant's unit and ended at its hit
    assert all(t is not None and t <= obs["t_close"]
               for *_, t in obs["units"])
    assert all(t0 >= obs["t_close"] for _, _, t0, _ in tail)
    assert obs["t_close"] - obs["t_open"] >= 1.0
    done = [(s, n) for s, n, _, t in tail if t is not None]
    assert done[-1][0] <= plant.index < sum(done[-1])
    assert obs["worker"] is not None


def test_a_faster_program_closes_its_window_at_the_hit(tmp_path):
    """The plant where a program a tenth as fast would need its tail:
    the hit comes inside the window and closes it, the unit in flight
    behind it is not a failed one, and the rate is over that window."""
    bench = {"workloads": [{"name": "tiny-md5.crack"}],
             "end_to_end": END_TO_END, "per_layer": PER_LAYER}
    import jax
    root = tmp_path / "data"
    import shutil
    shutil.copytree(conftest.DATA, root)
    f = root / "workloads" / "tiny-md5.crack.json"
    cell = json.loads(f.read_text())
    cell["tail_plant"]["units_per_s"] = 20.0
    f.write_text(json.dumps(cell))
    r = run.measure("tiny-md5.crack", 3, 1.5, False, jax.devices(),
                    str(tmp_path / "wd"), platform="cpu", interpret=True,
                    bench=bench, data_root=str(root), reach_chip_s=0.0)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and 30 <= r["attempted"]
    assert r["window"]["seconds"] < 1.5
    assert r["metrics"]["cand_per_s"]["value"] > 0


# -- where a traced run takes its slice ---------------------------------------
# `--seconds` 4: a traced window of 2 s with its slice due at 1 s (the
# tiny cell's `trace_slice_s`), the window's units paced at 50 a second
# (the tiny job sweeps 140 on this CPU), so `tail_plant.units_per_s`
# times 4 units behind the window's start is where the hit comes.

def paced(period):
    """A `stall` that hands the window's k-th unit on no sooner than k
    periods after its first."""
    state = {"k": 0, "first": None}

    def stall(unit):
        if state["first"] is None:
            state["first"] = time.monotonic()
        due = state["first"] + state["k"] * period
        state["k"] += 1
        time.sleep(max(0.0, due - time.monotonic()))

    return stall


def traced_entry(tmp_path, units_per_s, seconds=4.0):
    """`crack.run` traced and paced; -> (plan, obs, the slice's length
    in seconds as the trace's own host events give it)."""
    import entries.crack as crack
    import trace_reduce
    cfg, c, _ = plan_of("tiny-md5.crack", 5)
    c = dict(c, tail_plant={"units_per_s": units_per_s})
    plan = traffic.make_plan(cfg, c, 5, seconds, crack.WARM_UNITS)
    wd = tmp_path / "wd"
    wd.mkdir()
    obs = crack.run({"cfg": cfg, "cell": c, "plan": plan, "seconds": seconds,
                     "trace": True, "workdir": str(wd),
                     "faults": {"stall": paced(0.02)}})
    slice_s = None
    if obs["trace_dir"]:
        loaded = trace_reduce.load(
            trace_reduce.find_xplane(obs["trace_dir"]), ops=False)
        t0, t1 = trace_reduce.slice_ends(trace_reduce.loop_events(loaded))
        slice_s = (t1 - t0) / 1e9
    return plan, obs, slice_s


def test_a_traced_run_keeps_its_whole_slice_when_the_hit_is_in_its_tail(
        tmp_path):
    """The hit at 0.6 of `--seconds` (an untraced window would end
    there): the traced window is closed by the clock at 0.5, its slice
    is whole, and the job finds the plant in its tail."""
    plan, obs, slice_s = traced_entry(tmp_path, 30.0)
    assert obs["trace_dir"] and obs["slice_due_s"] == 1.0
    window = obs["t_close"] - obs["t_open"]
    assert 2.0 <= window < 2.3
    assert 0.85 < slice_s <= window - 1.0 + 0.05
    assert obs["tail_units"] and obs["rc"] == 0
    assert all(t is not None for *_, t in obs["units"])
    (plant,) = plan.plants_in("tail")
    assert plant.index == plan.window_start + 120 * plan.unit_size \
        + plant.index % plan.unit_size


def test_a_traced_slice_ends_at_a_hit_inside_the_traced_window(tmp_path):
    """The hit at 0.45 of `--seconds`: the job ends there, the window
    closes at its last completed unit and the slice ends with it."""
    _, obs, slice_s = traced_entry(tmp_path, 22.5)
    assert obs["trace_dir"] and obs["rc"] == 0
    window = obs["t_close"] - obs["t_open"]
    assert 1.7 < window < 2.0 and not obs["tail_units"]
    assert 0.6 < slice_s <= window - 1.0 + 0.05


def test_a_traced_run_without_a_slice_is_an_error(tmp_path, capsys):
    """The hit at 0.2 of `--seconds`, before the slice is due: the
    profiler never started, and the run says so and prints no result."""
    import shutil
    root = tmp_path / "data"
    shutil.copytree(conftest.DATA, root)
    f = root / "workloads" / "tiny-md5.crack.json"
    cell = json.loads(f.read_text())
    cell["tail_plant"]["units_per_s"] = 10.0
    f.write_text(json.dumps(cell))
    import jax
    bench = {"workloads": [{"name": "tiny-md5.crack"}],
             "end_to_end": END_TO_END, "per_layer": PER_LAYER}
    with pytest.raises(SystemExit) as e:
        run.measure("tiny-md5.crack", 5, 4.0, True, jax.devices(),
                    str(tmp_path / "wd"), platform="cpu", interpret=True,
                    faults={"stall": paced(0.02)}, bench=bench,
                    data_root=str(root), reach_chip_s=0.0)
    # a message is a non-zero exit code, written to standard error
    assert isinstance(e.value.code, str)
    assert "the profiler never started" in e.value.code
    assert "slice was due at 1.000 s" in e.value.code
    assert capsys.readouterr().out == ""
    # the same job untraced is a sound run that ends at its hit
    r = run.measure("tiny-md5.crack", 5, 4.0, False, jax.devices(),
                    str(tmp_path / "wd"), platform="cpu", interpret=True,
                    faults={"stall": paced(0.02)}, bench=bench,
                    data_root=str(root), reach_chip_s=0.0)
    assert r["correct"] and 0.7 < r["window"]["seconds"] < 1.0


def test_a_traced_and_an_untraced_run_of_one_seed_get_one_plan(
        tmp_path, monkeypatch):
    import entries.crack as crack
    seen = []

    class Stop(Exception):
        pass

    def no_run(ctx):
        seen.append(ctx)
        raise Stop

    monkeypatch.setattr(crack, "run", no_run)
    for traced in (False, True):
        with pytest.raises(Stop):
            measure("tiny-md5.crack", 2**31 + 9, tmp_path, seconds=3.0,
                    traced=traced)
    assert seen[0]["plan"] == seen[1]["plan"]
    assert seen[0]["seconds"] == seen[1]["seconds"] == 3.0
    assert [c["trace"] for c in seen] == [False, True]


def test_cand_per_s_falls_when_a_unit_stalls(tmp_path):
    sound = measure("tiny-md5.crack", 3, tmp_path, seconds=1.0)
    stalled = []

    def stall(unit):
        if not stalled:
            stalled.append(unit.unit_id)
            time.sleep(1.0)

    slow = measure("tiny-md5.crack", 3, tmp_path, seconds=1.0,
                   faults={"stall": stall})
    assert stalled and slow["correct"]
    # the same window, a second of it spent stalled: the rate is over
    # all the window's time, so it falls
    assert slow["attempted"] < sound["attempted"]
    assert (slow["metrics"]["cand_per_s"]["value"]
            < 0.8 * sound["metrics"]["cand_per_s"]["value"])


# -- the timed path broken underneath ---------------------------------------
# No test chooses its seed by where the plants fall.

@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_half_of_each_unit_left_out_one_target(tmp_path, seed):
    r = measure("tiny-md5.crack", seed, tmp_path,
                faults={"patches": faults.half_units})
    assert not r["correct"]
    # 16 lanes drawn from the seed: all in a unit's first half once in
    # 65,536 seeds
    assert values(r)["lanes_missed"] >= 1


def test_half_of_each_unit_left_out_of_a_list(tmp_path):
    """The tiny list has three plants (the cell's own has eight): a
    seed puts all three in their units' first halves one time in eight,
    so most of any four seeds have to come out not correct."""
    caught = 0
    for seed in (1, 2, 3, 4):
        r = measure("tiny-ntlm.crack", seed, tmp_path / str(seed),
                    seconds=2.5, faults={"patches": faults.half_units})
        v = values(r)
        caught += not r["correct"] and \
            v["plants_missed"] + v["lanes_missed"] >= 1
    assert caught >= 3


def test_an_altered_answer_is_not_correct(tmp_path):
    r = measure("tiny-md5.crack", 3, tmp_path,
                faults={"patches": faults.altered_answer})
    assert not r["correct"]
    assert values(r)["potfile_wrong"] == 1
    assert values(r)["plants_missed"] == 1


@pytest.mark.parametrize("cell,seconds", [("tiny-ntlm.crack", 2.5),
                                          ("tiny-md5.crack", 1.5)])
def test_the_control_is_not_correct(tmp_path, cell, seconds):
    r = measure(cell, 4, tmp_path, seconds=seconds,
                faults={"patches": faults.hits_dropped})
    assert not r["correct"]
    assert values(r)["plants_missed"] >= 1
    assert values(r)["lanes_missed"] == values(r)["lanes_judged"]


def test_a_range_swept_twice_is_not_correct(tmp_path):
    r = measure("tiny-md5.crack", 3, tmp_path,
                faults={"patches": faults.range_swept_twice})
    assert not r["correct"]
    v = values(r)
    assert v["audit_problems"] >= 1 and v["coverage_off"] >= 1


def test_another_worker_than_the_cells_is_not_correct(tmp_path,
                                                      monkeypatch):
    from dprf_tpu import cli
    real = cli._select_worker

    def oracle_worker(engine_name, device, *a, **kw):
        return real(engine_name, "cpu", *a, **kw)

    monkeypatch.setattr(cli, "_select_worker", oracle_worker)
    r = measure("tiny-md5.crack", 3, tmp_path, seconds=0.5)
    assert not r["correct"] and values(r)["path_off"] >= 1


# -- the mesh ----------------------------------------------------------------

def test_mesh_cell_is_data_alone_and_correct(tmp_path):
    r = measure("tiny-md5.mesh4", 3, tmp_path)
    assert r["correct"], r["compared"]
    assert r["ran"]["worker"] == "ShardedMaskWorker"
    assert r["ran"]["out_devices"] == "0/1/2/3"


def test_mesh_list_cell_is_data_alone_and_correct(tmp_path):
    """`tiny-ntlm.crack`'s traffic with `chips` 4: the sharded worker
    with a probe bitmap, its per-shard hit buffers gathered across the
    four devices, the maybes verified on the one host."""
    r = measure("tiny-ntlm.mesh4", 2**31 + 35, tmp_path, seconds=2.5)
    assert r["correct"], r["compared"]
    assert values(r)["plants_inside"] == 3
    assert values(r)["lanes_judged"] == 4
    assert r["ran"]["worker"] == "ShardedMaskWorker"
    assert r["ran"]["out_devices"] == "0/1/2/3"
    assert r["ran"]["verify"].startswith("lanes:")
    assert r["metrics"]["ttfh_s"]["value"] > 0


def test_the_control_is_not_correct_on_the_mesh_list(tmp_path):
    r = measure("tiny-ntlm.mesh4", 4, tmp_path, seconds=2.5,
                faults={"patches": faults.hits_dropped})
    assert not r["correct"]
    assert values(r)["plants_missed"] >= 1
    assert values(r)["lanes_missed"] == values(r)["lanes_judged"]


@pytest.mark.parametrize("cell,seconds", [("tiny-md5.mesh4", 1.5),
                                          ("tiny-ntlm.mesh4", 2.5)])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_mesh_exchange_left_out_is_not_correct(tmp_path, monkeypatch, seed,
                                               cell, seconds):
    """The exchange between chips left out: every shard keeps its own
    hits, and the host reads shard 0's.  16 lanes drawn from the seed
    all lie on shard 0 once in 4 ** 16 seeds; on the list none of the
    4 lane units answers (three seeds alike, as on the chip)."""
    from dprf_tpu.parallel import sharded
    monkeypatch.setattr(sharded, "lax", faults.NoExchange(4))
    r = measure(cell, seed, tmp_path, seconds=seconds)
    assert not r["correct"]
    assert values(r)["lanes_missed"] >= 1


# -- the command itself ------------------------------------------------------

def _run_py(cwd, *args, env=None):
    e = {k: v for k, v in os.environ.items() if not k.startswith("DPRF_")}
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"), *args],
        cwd=cwd, env=e, capture_output=True, text=True, timeout=300)


def test_off_a_tpu_a_measuring_run_exits_nonzero_and_prints_nothing():
    root = os.path.dirname(conftest.BENCH)
    p = _run_py(root, "--workload", "md5-mask.crack", "--seed", "1",
                "--seconds", "1", "--trace", "0",
                env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode == run.EXIT_NO_CHIP and p.stdout == ""
    assert "nothing is measured" in p.stderr


def test_without_the_program_the_command_fails(tmp_path):
    import shutil
    root = os.path.dirname(conftest.BENCH)
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(conftest.BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(str(tmp_path), "--workload", "md5-mask.crack", "--seed",
                "1", "--seconds", "1", "--trace", "0",
                env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout == ""


def test_a_dprf_variable_is_refused():
    root = os.path.dirname(conftest.BENCH)
    p = _run_py(root, "--workload", "md5-mask.crack", "--seed", "1",
                "--seconds", "1", "--trace", "0",
                env={"JAX_PLATFORMS": "cpu", "DPRF_PALLAS": "1"})
    assert p.returncode == 2 and p.stdout == ""


def test_benchmark_json_names_only_files_that_exist():
    root = os.path.dirname(conftest.BENCH)
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(root, c["file"]))
    for w in bench["workloads"]:
        cell = traffic.load_json("workloads", w["name"] + ".json")
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["traffic"] == w["traffic"]
        assert os.path.exists(os.path.join(
            conftest.BENCH, "entries", cell["entry"] + ".py"))
        entry = importlib.import_module("entries." + cell["entry"])
        traffic.make_plan(traffic.load_json(
            "configs", cell["config"] + ".json"), cell, 2**31 + 17,
            bench["run_seconds"], entry.WARM_UNITS)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(
            conftest.BENCH, "metrics", m["name"] + ".py"))
