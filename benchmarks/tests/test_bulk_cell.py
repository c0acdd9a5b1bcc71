"""The bulk-list cell (`ntlm-1m.crack`) at a tiny size on the CPU: the
harness end to end over a 4,500-line list (past the program's 4,096
floor, so the job takes the kernel with the probe table behind it), a
planted fault counted, the three metric readers the cell brings on
made-up observations, and the entry driver's kernel name."""

import importlib
import json
import os

import pytest

import conftest
import faults
import run

END_TO_END = [{"name": n, "unit": "x"} for n in ("cand_per_s", "setup_s")]
PER_LAYER = [{"name": n, "unit": "x"} for n in (
    "window_compiles", "kernel_pct", "kernel_sweeps",
    "mask_kernel_roofline", "target_load_s", "survivors_per_mcand",
    "probe_stage_pct")]
CELL = "tiny-ntlm-bulk.crack"


def measure(tmp_path, seed, traced=False, planted=None):
    import jax
    bench = {"workloads": [{"name": CELL}], "end_to_end": END_TO_END,
             "per_layer": PER_LAYER}
    return run.measure(CELL, seed, 2.5, traced, jax.devices(),
                       str(tmp_path / "wd"), platform="cpu",
                       interpret=True, faults=planted, bench=bench,
                       data_root=conftest.DATA, reach_chip_s=0.0)


def values(result):
    return {k: v["value"] for k, v in result["compared"].items()}


def test_sound_bulk_run_is_correct_and_says_what_ran(tmp_path):
    r = measure(tmp_path, 2**31 + 29)
    assert r["correct"], r["compared"]
    v = values(r)
    assert v["plants_inside"] == 3 and v["lanes_judged"] == 4
    ran = r["ran"]
    assert ran["worker"] == "PallasMaskWorker"
    assert ran["interpret"] == "True"
    assert ran["targets"].startswith("n:4500,table_bytes:")
    assert ran["targets"].endswith("mode:device")
    assert "survivors:" in ran["verify"] and "exact:" in ran["verify"]
    assert set(r["metrics"]) == {"cand_per_s", "setup_s"}
    assert r["failed"] == 0


def test_traced_bulk_run_reads_the_program_s_counters(tmp_path):
    r = measure(tmp_path, 31, traced=True)
    assert r["correct"], r["compared"]
    m = r["metrics"]
    assert m["window_compiles"]["value"] == 0
    assert m["target_load_s"]["value"] > 0
    # 3 plants and a handful of false positives over some 10^5 lanes
    assert 0 < m["survivors_per_mcand"]["value"] < 1000
    # no TPU plane in a CPU trace: the device readers return nothing
    assert "probe_stage_pct" not in m and "kernel_pct" not in m


def test_the_control_is_not_correct_on_a_bulk_list(tmp_path):
    r = measure(tmp_path, 5, planted={"patches": faults.hits_dropped})
    assert not r["correct"]
    assert values(r)["plants_missed"] > 0
    assert values(r)["lanes_missed"] > 0


def test_half_of_each_unit_left_out_of_a_bulk_list(tmp_path):
    r = measure(tmp_path, 6, planted={"patches": faults.half_units})
    assert not r["correct"]
    v = values(r)
    assert v["plants_missed"] + v["lanes_missed"] > 0


def _obs(**kw):
    obs = {"log": {"ran": {}, "shapes": {}},
           "cfg": {"flags": {"batch": 4096, "unit_size": 32768}},
           "cell": {"chips": 1}, "trace": None}
    obs.update(kw)
    return obs


def test_target_load_s_reads_the_targets_station():
    reader = importlib.import_module("metrics.target_load_s")
    ran = {"host": "targets:9.125,lease:0.010,submit:1.500"}
    assert reader.read(_obs(log={"ran": ran})) == 9.125
    # a program without the station (the parent): nothing, no error
    assert reader.read(_obs(log={"ran": {"host": "lease:0.010"}})) is None
    assert reader.read(_obs(log={"ran": None})) is None


def test_survivors_per_mcand_divides_by_the_line_s_own_candidates():
    reader = importlib.import_module("metrics.survivors_per_mcand")
    ran = {"verify": "lanes:0,tiles:0,host_tiles:0,survivors:300,exact:8"}
    # 10 fused units of 8 batches, 16 probed batches: 96 x 4,096 lanes
    log = {"ran": ran, "shapes": {"loop": 10, "probe": 16}}
    assert reader.read(_obs(log=log)) == pytest.approx(
        1e6 * 300 / (96 * 4096))
    old = {"ran": {"verify": "lanes:3,tiles:0,host_tiles:0"},
           "shapes": {"loop": 10}}
    assert reader.read(_obs(log=old)) is None
    assert reader.read(_obs(log={"ran": None, "shapes": {}})) is None


def test_probe_stage_pct_is_busy_less_kernel():
    reader = importlib.import_module("metrics.probe_stage_pct")
    trace = {"busy_s": 7.5, "kernel_s": 0.5, "window_s": 8.0}
    assert reader.read(_obs(trace=trace)) == pytest.approx(87.5)
    assert reader.read(_obs(trace=None)) is None
    # a trace that names no kernel (the parent's XLA pipeline)
    assert reader.read(_obs(trace=dict(trace, kernel_s=0.0))) is None


def test_the_bulk_entry_is_crack_with_the_pallas_call_s_own_text():
    import entries.crack as crack
    import entries.crack_bulk as bulk
    assert bulk.run is crack.run and bulk.judge_lanes is crack.judge_lanes
    assert bulk.audit is crack.audit and bulk.WARM_UNITS == crack.WARM_UNITS
    # event texts of a chip trace of the bulk program (PR 29): a
    # gather's custom call and a fusion that reads the kernel's output
    # are not the kernel's event; the kernel's own is, once
    events = [
        '%custom-call.38 = s32[4194304]{0:T(1024)} custom-call('
        '%param_1.536), custom_call_target="AssumeGatherIndicesInBound"',
        '%custom-call.46 = s32[256]{0:T(256)S(1)} custom-call(), '
        'custom_call_target="AllocateBuffer"',
        '%fusion.80 = s32[4194304]{0:T(1024)S(1)} fusion(u32[4,32768,128]'
        '{2,1,0:T(8,128)S(1)} %mask_digest_kernel.10), kind=kLoop',
        '%mask_digest_kernel.10 = u32[4,32768,128]{2,1,0:T(8,128)S(1)} '
        'custom-call(s32[7]{0:T(128)S(1)} %get-tuple-element.605, s32[1]'
        '{0:T(128)} %bitcast.258), custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={s32[7]{0}, s32[1]{0}}']
    assert [bulk.KERNEL_EVENT in e for e in events] == [False, False,
                                                        False, True]
    assert sum(crack.KERNEL_EVENT in e for e in events) == 3


def test_the_cell_this_pr_brings_is_data_the_harness_finds():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    import traffic
    cell = traffic.load_json("workloads", "ntlm-1m.crack.json")
    cfg = traffic.load_json("configs", cell["config"] + ".json")
    assert cell["chips"] == cells["ntlm-1m.crack"]["chips"] == 1
    assert cell["traffic"] == cells["ntlm-1m.crack"]["traffic"]
    importlib.import_module("entries." + cell["entry"])
    assert cfg["targets"] == cell["fillers"] + 8 == 1_000_000
    # every traffic parameter but the list's size is ntlm-1k.crack's
    one = traffic.load_json("workloads", "ntlm-1k.crack.json")
    same = set(one) - {"name", "config", "entry", "why", "fillers"}
    assert all(cell[k] == one[k] for k in same)
    assert [m["name"] for m in run.cell_metrics(bench, "ntlm-1m.crack",
                                                True)][-3:] == [
        "target_load_s", "survivors_per_mcand", "probe_stage_pct"]
