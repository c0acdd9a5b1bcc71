"""chip_smoke.py off the chip: what it refuses, and that its phase
functions drive the real entry points.

The script itself runs on a TPU only.  Here (JAX_PLATFORMS=cpu) it must
exit non-zero BEFORE any job with ``"ok": false`` on its last line; its
phase functions are run at tiny sizes through the same child processes
(``python -m dprf_tpu crack | serve | worker | jobs | audit``) with the
Pallas kernels in interpret mode -- the sizes and the expected platform
are passed by the test, the script has no rehearsal switch.  Also here:
the coordinator and the clients of the distributed path must never
initialise a JAX backend (on the chip that would take it from the
worker).
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TILE = 32 * 128          # conftest pins DPRF_PALLAS_SUB=32


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke(cs, tmp_path):
    return cs.Smoke(str(tmp_path), platform="cpu", interpret=True,
                    env={"DPRF_PALLAS": "1", "JAX_PLATFORMS": "cpu"},
                    timeout=600)


# ---------------------------------------------------------------------------
# the script as the driver runs it

def _run_script(cwd, script):
    return subprocess.run([sys.executable, script], cwd=cwd,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=300)


def test_script_refuses_without_a_tpu_before_any_job(tmp_path):
    proc = _run_script(str(tmp_path), os.path.join(REPO, "chip_smoke.py"))
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode != 0
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    # the device line and the verdict: no phase ran
    assert [json.loads(ln).get("phase") for ln in lines[:-1]] == ["device"]
    assert not os.listdir(tmp_path)


def test_script_alone_in_a_directory_fails_without_a_result(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_script(str(tmp_path), "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_parent_stays_off_jax(cs):
    """Importing the script, building a Smoke and reading logs pulls in
    no jax: the process that starts chip children must never be able to
    hold the chip."""
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke as c; "
            "c.Smoke('/tmp'); c.read_log(''); "
            "from dprf_tpu import get_engine; get_engine('ntlm', 'cpu'); "
            "assert 'jax' not in sys.modules, 'parent imported jax'"
            % REPO)
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# reading what a job says it ran

LOG = """\
[    0.01s] info  loaded targets count=1 duplicates=0 engine=md5
[    3.20s] info  device platform=tpu count=1 kind=TPU v5 lite
[    9.00s] info  ran worker=PallasMaskWorker interpret=False \
dispatch=batch:2,loop:2 compile_s=3.10 cache=miss
[    9.01s] info  job finished found=1/1 tested=308915776 \
elapsed=5.00s rate=1/s exhausted=True
"""


def test_read_log_and_check_ran(cs, tmp_path):
    log = cs.read_log(LOG)
    assert log["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}
    assert log["finished"]["tested"] == "308915776"
    s = cs.Smoke(str(tmp_path))        # expects tpu, interpret False
    ran = cs.check_ran(s, log, ("PallasMaskWorker",), fused="loop")
    assert ran["cache"] == "miss" and ran["compile_s"] == "3.10"


@pytest.mark.parametrize("edit,why", [
    (("platform=tpu", "platform=cpu"), "not on a tpu"),
    (("worker=PallasMaskWorker", "worker=DeviceMaskWorker"),
     "not the kernel worker"),
    (("interpret=False", "interpret=True"), "interpret=True"),
    (("dispatch=batch:2,loop:2", "dispatch=batch:66"), "fused shape"),
    (("dispatch=batch:2,loop:2", "dispatch=batch:2,wide:2"),
     "fused shape"),
    (("dispatch=batch:2,loop:2", "dispatch=batch:2,loop:1,wide:1"),
     "fused shape"),
    (("dispatch=batch:2,loop:2", "dispatch=scan:2"), "fused shape"),
    (("compile_s=", "verify=lanes:300,tiles:1,host_tiles:2 compile_s="),
     "host oracle"),
])
def test_check_ran_fails_a_hidden_fallback(cs, tmp_path, edit, why):
    """The XLA worker, an interpreted kernel, another platform, a
    dispatch that fell from `loop` to `wide`, `scan` or per-batch, or
    collided tiles hashed whole on the host: each is a failed phase,
    never a slower pass."""
    s = cs.Smoke(str(tmp_path))
    log = cs.read_log(LOG.replace(*edit))
    with pytest.raises(cs.PhaseError, match=why):
        cs.check_ran(s, log, ("PallasMaskWorker",), fused="loop")


BCRYPT_LOG = """\
[    3.20s] info  device platform=tpu count=1 kind=TPU v5 lite
[    9.00s] info  ran worker=BcryptWordlistWorker interpret=False \
dispatch=probe:1 compile_s=2.50 cache=miss advance=pallas
"""


def test_check_ran_wants_the_bcrypt_kernel(cs, tmp_path):
    """BcryptWordlistWorker is one class whether its cost loop is the
    Pallas kernel or the XLA form: the phase reads which from the log
    and fails on the XLA form, and a worker that reports no interpret
    flag at all has no kernel."""
    s = cs.Smoke(str(tmp_path))
    workers = ("BcryptWordlistWorker",)
    ran = cs.check_ran(s, cs.read_log(BCRYPT_LOG), workers,
                       advance="pallas")
    assert ran["advance"] == "pallas"
    xla = BCRYPT_LOG.replace("advance=pallas", "advance=xla")
    with pytest.raises(cs.PhaseError, match="not the pallas kernel"):
        cs.check_ran(s, cs.read_log(xla), workers, advance="pallas")
    none = xla.replace("interpret=False", "interpret=n/a")
    with pytest.raises(cs.PhaseError, match="interpret=n/a"):
        cs.check_ran(s, cs.read_log(none), workers, advance="pallas")


# ---------------------------------------------------------------------------
# the phase functions, tiny, through the real children

def test_phase_md5_mask_tiny(cs, smoke):
    rec = cs.phase_md5_mask(smoke, mask="?l?l?l?l", batch=TILE,
                            unit=16 * TILE)
    assert rec["ok"] and rec["platform"] == "cpu"
    assert rec["worker"] == "PallasMaskWorker"
    assert rec["interpret"] == "True"
    assert rec["swept"] == 26 ** 4 and rec["plant"] == "zzzz"
    assert "loop:" in rec["dispatch"] and rec["audit"] == "clean"
    assert rec["cache"] in ("hit", "miss") and rec["wall_s"] > 0


def test_phase_md5_mask_fails_on_the_xla_worker(cs, tmp_path):
    """Same job with the kernel path off: the phase must FAIL, naming
    the worker that ran."""
    s = cs.Smoke(str(tmp_path), platform="cpu", interpret=True,
                 env={"DPRF_PALLAS": "0", "JAX_PLATFORMS": "cpu"},
                 timeout=600)
    with pytest.raises(cs.PhaseError, match="DeviceMaskWorker"):
        cs.phase_md5_mask(s, mask="?l?l?l?l", batch=TILE, unit=16 * TILE)


def test_phase_ntlm_1k_tiny(cs, smoke):
    rec = cs.phase_ntlm_1k(smoke, mask="?l?l?l?l", window=40 * TILE,
                           batch=TILE, unit=16 * TILE, n_targets=1000,
                           back=7)
    assert rec["ok"] and rec["targets"] == 1000
    assert rec["swept"] == 40 * TILE
    assert len(rec["found"]) == 1 and rec["found"][0].endswith(
        ":" + rec["plant"])
    assert "loop:" in rec["dispatch"]


def test_phase_ntlm_bulk_tiny(cs, smoke):
    """The bulk phase at 4,200 targets: the kernel worker, the fused
    loop, the table in device mode."""
    rec = cs.phase_ntlm_bulk(smoke, mask="?l?l?l?l", window=40 * TILE,
                             batch=TILE, unit=16 * TILE, n_targets=4200,
                             back=7)
    assert rec["ok"] and rec["phase"] == "ntlm-bulk"
    assert rec["worker"] == "PallasMaskWorker" and rec["targets"] == 4200
    assert rec["swept"] == 40 * TILE and "loop:" in rec["dispatch"]
    assert len(rec["found"]) == 1 and rec["found"][0].endswith(
        ":" + rec["plant"])


@pytest.mark.parametrize("targets,why", [
    ("targets=n:100000,table_bytes:2883584,mode:host-verify ",
     "not in device mode"),
    ("", "not in device mode"),
])
def test_check_ran_wants_the_bulk_table_on_the_device(cs, tmp_path,
                                                      targets, why):
    s = cs.Smoke(str(tmp_path))
    good = LOG.replace("compile_s=", "targets=n:100000,table_bytes:"
                       "2883584,mode:device compile_s=")
    cs.check_ran(s, cs.read_log(good), ("PallasMaskWorker",),
                 fused="loop", table_mode="device")
    bad = LOG.replace("compile_s=", targets + "compile_s=")
    with pytest.raises(cs.PhaseError, match=why):
        cs.check_ran(s, cs.read_log(bad), ("PallasMaskWorker",),
                     fused="loop", table_mode="device")


def test_phase_serve_tiny(cs, smoke):
    rec = cs.phase_serve(smoke, mask="?l?l?l?l", batch=TILE,
                         unit=16 * TILE, small_mask="?l?l?l")
    assert rec["ok"] and rec["job"] == "j1"
    assert rec["worker"] == "PallasMaskWorker"
    assert rec["swept"] == 26 ** 4 and rec["audit"] == "clean"


def test_phase_mesh_tiny(cs, smoke):
    """The --chips 4 comparison on four virtual CPU devices."""
    smoke.env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    rec = cs.phase_mesh(smoke, chips=4, mask="?l?l?l?l",
                        window=100 * TILE, batch=TILE, unit=64 * TILE,
                        n_targets=1000, back=7)
    assert rec["same_hits"] and rec["same_digest"]
    assert rec["out_devices"] == "0/1/2/3"


# ---------------------------------------------------------------------------
# the coordinator and the clients never initialise a backend

def test_serve_and_jobs_submit_never_initialise_a_backend(cs, tmp_path):
    """With JAX_PLATFORMS naming a platform that does not exist, any
    backend initialisation raises.  `dprf serve`, `dprf jobs submit`,
    `dprf jobs list` and `dprf audit` must work regardless: on the chip
    they run beside the worker that holds it."""
    import hashlib
    import time
    s = cs.Smoke(str(tmp_path), env={"JAX_PLATFORMS": "no_such_platform"},
                 timeout=120)
    hf = s.write("h.txt", hashlib.md5(b"zzz").hexdigest() + "\n")
    port = cs._free_port()
    addr = f"127.0.0.1:{port}"
    serve = s.spawn("serve", "?l?l?l", hf, "--engine", "md5", "--bind",
                    addr, "--session", s.path("s.session"),
                    "--potfile", s.path("s.pot"), log="serve.log")
    try:
        assert cs._wait_port(port, serve), \
            open(s.path("serve.log")).read()[-2000:]
        sub = s.dprf("jobs", "submit", "?l?l?l?l", hf, "--engine", "md5",
                     "--connect", addr)
        assert sub.returncode == 0, sub.stderr
        lst = s.dprf("jobs", "list", "--connect", addr, "-q")
        assert lst.returncode == 0, lst.stderr
        assert len(json.loads(lst.stdout.strip().splitlines()[-1])) == 2
        time.sleep(0.5)
        assert serve.poll() is None, "dprf serve died"
    finally:
        serve.kill()
        serve.wait()
    log = open(s.path("serve.log")).read()
    assert "no_such_platform" not in log and "Traceback" not in log
