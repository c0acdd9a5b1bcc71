"""Benchmark-mode smoke tests on the CPU backend: every mode produces
a well-formed result dict with a positive rate.  Short runs -- these
validate plumbing and output schema, not performance."""

import jax
import pytest

pytestmark = pytest.mark.smoke

from dprf_tpu.bench import run_bench, run_config, run_scaling


def test_run_bench_xla_schema():
    res = run_bench(engine="md5", device="jax", mask="?l?l?l?l?l?l",
                    batch=4096, seconds=0.3, impl="xla")
    assert res["value"] > 0
    assert res["impl"] == "xla"
    assert res["unit"] == "H/s"
    assert res["device"] == jax.devices()[0].platform
    assert res["batches"] >= 1


def test_run_bench_cpu_oracle():
    res = run_bench(engine="md5", device="cpu", mask="?l?l?l?l?l",
                    batch=2048, seconds=0.3)
    assert res["value"] > 0 and res["device"] == "cpu"


def test_run_config_1_worker_path():
    res = run_config(1, device="jax", seconds=0.3, batch=4096)
    assert res["config"] == 1 and res["engine"] == "md5"
    assert res["value"] > 0 and res["targets"] == 1


def test_run_config_names_what_ran():
    """A config record names its path, not only its rate: worker
    class, interpret flag, dispatch shapes with counts."""
    res = run_config(1, device="jax", seconds=0.2, batch=4096,
                     unit_strides=8)
    assert res["worker"] == "DeviceMaskWorker"   # no kernel off-chip
    assert res["interpret"] == "n/a"
    assert "scan:" in res["dispatch"]            # 8 strides: fused


def _root_bench():
    import importlib.util
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_root", os.path.join(repo, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_root_bench_no_tpu_no_value(capsys, monkeypatch):
    """No chip, no number: off a TPU the driver's bench.py exits
    non-zero, prints no value, names the device it found, and starts
    no child process."""
    import json
    import subprocess

    def no_children(*a, **kw):
        raise AssertionError("bench.py started a child process")

    monkeypatch.setattr(subprocess, "Popen", no_children)
    monkeypatch.setattr(subprocess, "run", no_children)
    rc = _root_bench().main()
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc != 0 and doc["ok"] is False
    assert "value" not in doc
    assert doc["device"] == "cpu" and doc["device_count"] >= 1
    assert doc["device_kind"] == jax.devices()[0].device_kind


def test_root_bench_refuses_a_record_off_the_kernel_worker(
        capsys, monkeypatch):
    """On a TPU the record must come from the compiled kernel worker:
    any other worker class, or an interpreted kernel, is an error
    without a value -- never a slower path's number."""
    import json

    import dprf_tpu.bench as dbench
    mod = _root_bench()

    class FakeTpu:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [FakeTpu()])
    for worker, interpret, ok in (
            ("DeviceMaskWorker", "n/a", False),
            ("PallasMaskWorker", True, False),
            ("PallasMaskWorker", False, True)):
        monkeypatch.setattr(
            dbench, "run_config",
            lambda *a, **kw: {"value": 1.0, "worker": worker,
                              "interpret": interpret, "device": "tpu"})
        rc = mod.main()
        doc = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert (rc == 0) is ok
        assert ("value" in doc) is ok
        assert doc["device_kind"] == "TPU v5 lite"


def test_run_scaling_plumbing():
    assert len(jax.devices()) >= 2, "conftest fakes 8 CPU devices"
    res = run_scaling(engine="md5", mask="?l?l?l?l?l?l", n_devices=2,
                      batch_per_device=2048, seconds=0.3, inner=1)
    assert res["n_devices"] == 2
    assert res["rate_1chip"] > 0 and res["rate_ndev"] > 0
    assert res["rate_independent"] > 0
    assert res["per_chip"] == pytest.approx(res["rate_ndev"] / 2)
    # the gated number compares against the embarrassingly-parallel
    # baseline (contention-fair on a virtual mesh); the classic
    # unloaded ratio rides along as efficiency_strict
    assert res["baseline"] == "independent"
    assert res["value"] == res["efficiency"] == pytest.approx(
        min(1.0, res["rate_ndev"] / res["rate_independent"]))
    assert res["efficiency_raw"] == pytest.approx(
        res["rate_ndev"] / res["rate_independent"])
    assert res["efficiency_strict"] == pytest.approx(
        res["rate_ndev"] / (2 * res["rate_1chip"]))
    assert res["superstep"] is False       # inner=1: compat program
    assert "h2d_share" in res and "phases" in res
    assert "note" in res      # CPU mesh must be labeled plumbing-only
