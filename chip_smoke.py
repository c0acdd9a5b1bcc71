#!/usr/bin/env python
"""chip_smoke.py -- the quickest proof that dprf-tpu still starts on the
chip.

Drives the system's main path once, through the entry points a user
types (``python -m dprf_tpu crack | serve | worker | jobs | audit``),
at the stated size of the recovery jobs the repo supports, with a
planted password per phase that must come back, be accepted by the CPU
oracle and be in the potfile.  Data is made from ``--seed``.

This process stays OFF JAX: a chip belongs to one process at a time, so
every phase is a child process, and no two children that need the chip
are alive at once (the ``serve`` phase's coordinator and clients never
initialise a backend; only its one worker holds the chip).  The first
child reports platform, kind and count; unless the platform is a TPU
the script runs no job, prints ``{"ok": false, ...}`` and exits 1.

Every phase prints one JSON line saying what ran: platform,
device_kind, worker class, interpret, the dispatch shapes actually used
with their counts, compile seconds and persistent-cache hit or miss,
candidates swept, wall seconds.  A phase FAILS when the worker is not
the compiled kernel worker it expects, when a fused dispatch shape
other than the expected one shows up (or the expected one does not),
when the plant is not found, or when ``dprf audit`` is not clean.  Wall
seconds are seconds, not a rate: the benchmark is ROADMAP S1.

The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

``--chips 4`` runs ONLY the mesh path and what it is compared with:
config 2's window through ``dprf crack --devices 4`` and the same
window at ``--devices 1``; same hits, same coverage digest, and the
sharded step's output buffers on four distinct devices.
"""

import argparse
import json
import os
import re
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: BASELINE.json sizes
MD5_MASK = "?l?l?l?l?l?l"            # config 1: 308,915,776 candidates
NTLM_MASK = "?a?a?a?a?a?a?a"         # config 2: 95^7, swept by window
NTLM_WINDOW = 8589934592             # 2^33
BATCH = 4194304                      # configs 1 and 2: 2^22 lanes
UNIT = 268435456                     # 64 batches: a fused dispatch
PMKID_MASK = "?l?l?l?l?l?l?l?l"      # config 5

_PROBE = ("import jax, json; d = jax.devices(); "
          "print(json.dumps({'platform': d[0].platform, "
          "'kind': d[0].device_kind, 'count': len(d)}))")


class PhaseError(Exception):
    pass


class Smoke:
    """One smoke run's shared state: where files go, what a child's
    environment is, and what the device path is expected to be."""

    def __init__(self, workdir, seed=1, platform="tpu", interpret=False,
                 env=None, timeout=900.0):
        self.workdir = workdir
        self.seed = seed
        #: what every device child must report, and whether its
        #: kernels may be interpreted (tests on the CPU pass True)
        self.platform = platform
        self.interpret = interpret
        self.timeout = timeout
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = REPO + os.pathsep + \
            self.env.get("PYTHONPATH", "")
        # tuning lookups stay inside the run's own directory
        self.env["DPRF_TUNE_DIR"] = workdir
        self.env.update(env or {})

    def path(self, name):
        return os.path.join(self.workdir, name)

    def write(self, name, text):
        with open(self.path(name), "w") as fh:
            fh.write(text)
        return self.path(name)

    # -- children ---------------------------------------------------------

    def dprf(self, *args, check=None):
        """Run ``python -m dprf_tpu ARGS`` to its end; returns the
        CompletedProcess (text).  check: the allowed exit codes."""
        proc = subprocess.run(
            [sys.executable, "-m", "dprf_tpu", *[str(a) for a in args]],
            cwd=self.workdir, env=self.env, capture_output=True,
            text=True, timeout=self.timeout)
        if check is not None and proc.returncode not in check:
            raise PhaseError(
                f"dprf {args[0]} exited {proc.returncode}:\n"
                + proc.stderr[-3000:])
        return proc

    def spawn(self, *args, log):
        """Start ``python -m dprf_tpu ARGS`` in the background with its
        output in file ``log``."""
        fh = open(self.path(log), "w")
        return subprocess.Popen(
            [sys.executable, "-m", "dprf_tpu", *[str(a) for a in args]],
            cwd=self.workdir, env=self.env, stdout=fh, stderr=fh,
            text=True)

    def candidate(self, mask, index):
        """The mask's candidate at a keyspace index, from the program
        itself (``dprf stdout``; host only, no backend)."""
        out = self.dprf("stdout", mask, "--skip", index, "--limit", 1,
                        "-q", check=(0,)).stdout
        return out.rstrip("\n").encode("latin-1")


# ---------------------------------------------------------------------------
# reading a job's own log: the program says what it ran

_DEVICE = re.compile(r"info\s+device platform=(\S+) count=(\d+) kind=(.*)$")
_RAN = re.compile(r"info\s+ran (.*)$")
_DONE = re.compile(r"info\s+job finished (.*)$")
_KEYSPACE = re.compile(r"info\s+keyspace (.*)$")


def _kv(text):
    return dict(f.split("=", 1) for f in text.split() if "=" in f)


def read_log(stderr):
    """{device, ran: [..], finished, keyspace} from a dprf child's log."""
    rec = {"device": None, "ran": [], "finished": None, "keyspace": None}
    for line in stderr.splitlines():
        m = _DEVICE.search(line)
        if m:
            rec["device"] = {"platform": m.group(1), "kind": m.group(3),
                             "count": int(m.group(2))}
        m = _RAN.search(line)
        if m:
            rec["ran"].append(_kv(m.group(1)))
        m = _DONE.search(line)
        if m:
            rec["finished"] = _kv(m.group(1))
        m = _KEYSPACE.search(line)
        if m:
            rec["keyspace"] = _kv(m.group(1))
    return rec


def _shapes(dispatch):
    return {k: int(n) for k, n in
            (f.split(":") for f in dispatch.split(",") if ":" in f)}


def check_ran(smoke, log, workers, fused=None, advance=None,
              table_mode=None):
    """The device path must be what the phase expects: the platform,
    the kernel worker class, its interpret flag (a worker that reports
    none has no kernel: a failure), where the phase names one the
    fused dispatch shape, with no other fused shape beside it (a
    degraded dispatch would show up here as `wide`, `scan` or
    per-batch only), for bcrypt the implementation of its cost
    loop (`pallas`, never the `xla` form the same worker class can
    carry), and for a target list no collided kernel tile hashed
    whole on the host (`verify=..,host_tiles:0`: the phases' engines
    all have the device re-probe), and for a bulk list its table's
    mode (`targets=..,mode:device`: the exact verify on the chip too,
    never `host-verify`, and never no table at all)."""
    dev = log["device"]
    if dev is None or dev["platform"] != smoke.platform:
        raise PhaseError(f"device path ran on {dev}, not on a "
                         f"{smoke.platform}")
    if not log["ran"]:
        raise PhaseError("the job's log has no `ran` line")
    ran = log["ran"][-1]
    if ran["worker"] not in workers:
        raise PhaseError(f"worker {ran['worker']} is not the kernel "
                         f"worker ({'/'.join(workers)})")
    if ran["interpret"] != str(smoke.interpret):
        raise PhaseError(f"interpret={ran['interpret']}, expected "
                         f"{smoke.interpret}")
    if advance is not None and ran.get("advance") != advance:
        raise PhaseError(f"advance={ran.get('advance')}: the cost loop "
                         f"is not the {advance} kernel")
    if table_mode is not None:
        said = dict(f.split(":", 1) for f in
                    ran.get("targets", "").split(",") if ":" in f)
        if said.get("mode") != table_mode:
            raise PhaseError(f"targets={ran.get('targets')}: the list's "
                             f"probe table is not in {table_mode} mode")
    if _shapes(ran.get("verify", "")).get("host_tiles"):
        raise PhaseError(f"verify={ran['verify']}: collided tiles went "
                         "to the host oracle, not to the device re-probe")
    shapes = _shapes(ran["dispatch"])
    if fused is not None:
        others = set(shapes) - {fused, "batch"}
        if not shapes.get(fused) or others:
            raise PhaseError(
                f"dispatch {ran['dispatch']!r}: expected fused shape "
                f"{fused!r} (plus per-batch remainders) and no other")
    return ran


def check_plant(smoke, engine, line, plain, stdout, potfile):
    """The plant came back, the CPU oracle accepts it, the potfile has
    it."""
    from dprf_tpu import get_engine
    from dprf_tpu.runtime.potfile import encode_plain
    oracle = get_engine(engine, device="cpu")
    if not oracle.verify(plain, oracle.parse_target(line)):
        raise PhaseError("the CPU oracle rejects the plant itself")
    want = f"{line}:{encode_plain(plain)}"
    if stdout is not None and want not in stdout.splitlines():
        raise PhaseError(f"plant not in the job's output: {want}")
    with open(potfile) as fh:
        if want not in fh.read().splitlines():
            raise PhaseError(f"plant not in the potfile: {want}")


def audit(smoke, session, jobs=1):
    """``dprf audit``: clean verdict, every job fully and exactly-once
    covered, journaled digest reproduced.  Returns the per-job docs."""
    proc = smoke.dprf("audit", session, "--json", "-q")
    try:
        doc = json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        raise PhaseError("dprf audit printed no JSON:\n"
                         + proc.stderr[-2000:])
    if proc.returncode != 0 or doc["verdict"] != "clean":
        raise PhaseError(f"dprf audit: {doc['verdict']} "
                         f"{doc.get('problems')}")
    if len(doc["jobs"]) != jobs:
        raise PhaseError(f"audit saw {len(doc['jobs'])} job(s), "
                         f"expected {jobs}")
    for j in doc["jobs"]:
        if (j["covered"] != j["keyspace"] or j["gap_total"]
                or j["trace_overlap"] or j["digest_match"] is not True):
            raise PhaseError(f"audit of job {j['job']}: not full "
                             f"exactly-once coverage: {j}")
    return doc["jobs"]


def _record(name, smoke, log, ran, t0, **extra):
    rec = {"phase": name, "ok": True, **(log["device"] or {}),
           "worker": ran["worker"], "interpret": ran["interpret"],
           "dispatch": ran["dispatch"],
           "compile_s": float(ran["compile_s"]), "cache": ran["cache"],
           # every compile of the child, observed site or not
           "cache_hits": int(ran.get("cache_hits", 0)),
           "cache_misses": int(ran.get("cache_misses", 0))}
    if log["finished"]:
        rec["swept"] = int(log["finished"]["tested"])
        # the job's own clock, first lease to last unit; wall_s below
        # adds process start, set-up, compile and the audit
        rec["job_s"] = float(log["finished"]["elapsed"].rstrip("s"))
    rec.update(extra)
    rec["wall_s"] = round(time.monotonic() - t0, 2)
    return rec


def _crack(smoke, name, *args):
    """One ``dprf crack`` with its own session journal and potfile."""
    proc = smoke.dprf(
        "crack", *args, "--session", smoke.path(f"{name}.session"),
        "--potfile", smoke.path(f"{name}.pot"), check=(0, 1))
    log = read_log(proc.stderr)
    if proc.returncode != 0:
        raise PhaseError(f"dprf crack found nothing (exit 1):\n"
                         + proc.stderr[-3000:])
    return proc, log


# ---------------------------------------------------------------------------
# phases

def phase_md5_mask(smoke, mask=MD5_MASK, batch=BATCH, unit=UNIT):
    """BASELINE config 1 at its stated size: MD5, one hash, the whole
    mask keyspace swept (the plant is its LAST candidate), audited."""
    import hashlib
    t0 = time.monotonic()
    plain = b"z" * (len(mask) // 2)
    line = hashlib.md5(plain).hexdigest()
    hf = smoke.write("md5.hash", line + "\n")
    proc, log = _crack(smoke, "md5-mask", mask, hf, "--engine", "md5",
                       "--batch", batch, "--unit-size", unit,
                       "--unit-seconds", 0)
    ran = check_ran(smoke, log, ("PallasMaskWorker",), fused="loop")
    check_plant(smoke, "md5", line, plain, proc.stdout,
                smoke.path("md5-mask.pot"))
    keyspace = 26 ** len(plain)
    if int(log["finished"]["tested"]) != keyspace:
        raise PhaseError(f"swept {log['finished']['tested']} of "
                         f"{keyspace} candidates")
    jobs = audit(smoke, smoke.path("md5-mask.session"))
    return _record("md5-mask", smoke, log, ran, t0,
                   plant=plain.decode(), audit="clean",
                   digest=jobs[0]["digest_journal"])


def _words(rng, n):
    """n seeded 8-letter lowercase words."""
    return ["".join(rng.choices("abcdefghijklmnopqrstuvwxyz", k=8))
            for _ in range(n)]


def _unmatchable(rng, n, nbytes=16):
    return ["%0*x" % (2 * nbytes, rng.getrandbits(8 * nbytes))
            for _ in range(n)]


def _ntlm_job(smoke, mask, window, n_targets, back):
    """Config 2's hash list: n_targets - 1 uniformly random (so
    unmatchable) NTLM digests and one planted, `back` candidates before
    the end of the window.  Returns (hashfile, line, plain)."""
    import random

    from dprf_tpu import get_engine
    plain = smoke.candidate(mask, window - back)
    line = get_engine("ntlm", "cpu").hash_batch([plain])[0].hex()
    lines = _unmatchable(random.Random(smoke.seed), n_targets - 1)
    lines.insert(len(lines) // 2, line)
    return smoke.write("ntlm.hash", "\n".join(lines) + "\n"), line, plain


def phase_ntlm_1k(smoke, mask=NTLM_MASK, window=NTLM_WINDOW, batch=BATCH,
                  unit=UNIT, n_targets=1000, back=12345, devices=1,
                  name="ntlm-1k", workers=("PallasMaskWorker",),
                  fused="loop", table_mode=None):
    """BASELINE config 2: NTLM, a 1,000-line list, `--limit` window of
    the ?a x 7 mask with the plant in the window's last unit -- the
    multi-target kernel, whose every maybe the oracle verifies."""
    t0 = time.monotonic()
    hf, line, plain = _ntlm_job(smoke, mask, window, n_targets, back)
    proc, log = _crack(smoke, name, mask, hf, "--engine", "ntlm",
                       "--batch", batch, "--unit-size", unit,
                       "--unit-seconds", 0, "--limit", window,
                       "--devices", devices)
    ran = check_ran(smoke, log, workers, fused=fused,
                    table_mode=table_mode)
    check_plant(smoke, "ntlm", line, plain, proc.stdout,
                smoke.path(f"{name}.pot"))
    if int(log["finished"]["tested"]) != window:
        raise PhaseError(f"swept {log['finished']['tested']} of the "
                         f"{window}-candidate window")
    jobs = audit(smoke, smoke.path(f"{name}.session"))
    extra = {"out_devices": ran["out_devices"]} \
        if "out_devices" in ran else {}
    return _record(name, smoke, log, ran, t0, targets=n_targets,
                   plant=plain.decode("latin-1"), audit="clean",
                   digest=jobs[0]["digest_journal"],
                   found=sorted(proc.stdout.splitlines()), **extra)


def phase_ntlm_bulk(smoke, window=1 << 31, n_targets=100_000, **sizes):
    """A bulk list (past DPRF_TARGETS_PROBE_MIN): the same job with
    10^5 targets over 2^31 candidates.  It has to hash on the compiled
    kernel all the same (`interpret=False`, the fused loop) with its
    probe table on the device in `device` mode: the XLA pipeline, or a
    host-verify table, in its place is a failed phase."""
    return phase_ntlm_1k(smoke, window=window, n_targets=n_targets,
                         name="ntlm-bulk", table_mode="device", **sizes)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_port(port, proc, deadline_s=60.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        if proc.poll() is not None:
            return False
        with socket.socket() as s:
            s.settimeout(0.5)
            if s.connect_ex(("127.0.0.1", port)) == 0:
                return True
        time.sleep(0.2)
    return False


def phase_serve(smoke, mask=MD5_MASK, batch=BATCH, unit=UNIT,
                small_mask="?l?l?l?l"):
    """The distributed path, loopback: ``dprf serve`` (a small default
    job), config 1's job submitted with ``dprf jobs submit``, one
    ``dprf worker --device tpu``.  The coordinator and the client must
    not take the chip: the worker, started last, must still get it."""
    import hashlib
    t0 = time.monotonic()
    small_plain = b"z" * (len(small_mask) // 2)
    small_line = hashlib.md5(small_plain).hexdigest()
    plain = b"z" * (len(mask) // 2)
    line = hashlib.md5(plain).hexdigest()
    hf_small = smoke.write("serve-small.hash", small_line + "\n")
    hf = smoke.write("serve.hash", line + "\n")
    port = _free_port()
    addr = f"127.0.0.1:{port}"
    session, pot = smoke.path("serve.session"), smoke.path("serve.pot")
    serve = smoke.spawn(
        "serve", small_mask, hf_small, "--engine", "md5", "--bind", addr,
        "--batch", batch, "--unit-size", unit, "--unit-seconds", 0,
        "--session", session, "--potfile", pot, log="serve.log")
    worker = None
    try:
        if not _wait_port(port, serve):
            raise PhaseError("dprf serve never listened:\n"
                             + open(smoke.path("serve.log")).read()[-3000:])
        sub = smoke.dprf("jobs", "submit", mask, hf, "--engine", "md5",
                         "--connect", addr, "--batch", batch,
                         "--unit-size", unit, "--unit-seconds", 0,
                         check=(0,))
        job = json.loads(sub.stdout.strip().splitlines()[-1])
        worker = smoke.spawn("worker", "--connect", addr, "--device",
                             "tpu", log="worker.log")
        rc_w = worker.wait(timeout=smoke.timeout)
        rc_s = serve.wait(timeout=120)
    finally:
        for p in (worker, serve):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()
    wlog_text = open(smoke.path("worker.log")).read()
    if rc_w != 0 or rc_s != 0:
        raise PhaseError(f"worker exited {rc_w}, serve exited {rc_s}:\n"
                         + wlog_text[-3000:] + "\n--- serve ---\n"
                         + open(smoke.path("serve.log")).read()[-2000:])
    log = read_log(wlog_text)
    by_job = {r.get("job"): r for r in log["ran"]}
    if job["job"] not in by_job:
        raise PhaseError(f"the worker's log has no `ran` line for "
                         f"{job['job']}")
    log["ran"] = [by_job[job["job"]]]
    ran = check_ran(smoke, log, ("PallasMaskWorker",), fused="loop")
    check_plant(smoke, "md5", line, plain, None, pot)
    check_plant(smoke, "md5", small_line, small_plain, None, pot)
    jobs = audit(smoke, session, jobs=2)
    swept = {j["job"]: j["covered"] for j in jobs}
    if swept[job["job"]] != 26 ** len(plain):
        raise PhaseError(f"submitted job covered {swept}")
    return _record("serve", smoke, log, ran, t0, job=job["job"],
                   swept=swept[job["job"]], plant=plain.decode(),
                   audit="clean")


def phase_wordlist_rules(smoke, n_words=1 << 20, rules="best64",
                         batch=1 << 18, window_words=1 << 16,
                         workers=("PallasWordlistWorker",)):
    """Config 3: SHA-256 over 2^20 synthetic 8-byte words x best64,
    on-device rule expansion; one short window of word x rule
    candidates ending at the planted word.  Units are kept under
    eight batches, so only the per-batch program runs: the rules
    kernel takes minutes to compile and its fused (wide) program
    would double that."""
    import hashlib
    import random
    t0 = time.monotonic()
    words = _words(random.Random(smoke.seed), n_words)
    wl = smoke.write("words.txt", "\n".join(words) + "\n")
    n_rules = int(smoke.dprf("keyspace", wl, "-a", "wordlist", "--rules",
                             rules, "-q", check=(0,)).stdout) // n_words
    # best64's first rule is ':' (the word itself): plant the window's
    # last word, rule 0
    w = n_words - 1
    plain = words[w].encode()
    line = hashlib.sha256(plain).hexdigest()
    hf = smoke.write("sha256.hash", line + "\n")
    skip = (n_words - window_words) * n_rules
    proc, log = _crack(smoke, "wordlist-rules", wl, hf, "-a", "wordlist",
                       "--engine", "sha256", "--rules", rules,
                       "--batch", batch, "--unit-size", 4 * batch,
                       "--unit-seconds", 0, "--skip", skip)
    ran = check_ran(smoke, log, workers)
    check_plant(smoke, "sha256", line, plain, proc.stdout,
                smoke.path("wordlist-rules.pot"))
    return _record("wordlist-rules", smoke, log, ran, t0,
                   words=n_words, rules=n_rules,
                   native_reader=(log["keyspace"] or {}).get(
                       "native_reader"), plant=plain.decode())


def phase_bcrypt(smoke, n_words=4096, cost=5,
                 workers=("BcryptWordlistWorker",)):
    """Config 4 with its cost CUT from 12 to 5 so that a batch ends in
    seconds: bcrypt over a 4,096-word list, the planted word last."""
    import random

    from dprf_tpu.engines.cpu.bcrypt import bcrypt_hash
    t0 = time.monotonic()
    rng = random.Random(smoke.seed + 4)
    words = _words(rng, n_words)
    wl = smoke.write("bcrypt-words.txt", "\n".join(words) + "\n")
    plain = words[-1].encode()
    line = bcrypt_hash(plain, bytes(rng.getrandbits(8) for _ in range(16)),
                       cost)
    hf = smoke.write("bcrypt.hash", line + "\n")
    proc, log = _crack(smoke, "bcrypt", wl, hf, "-a", "wordlist",
                       "--engine", "bcrypt", "--batch", n_words,
                       "--unit-seconds", 0)
    ran = check_ran(smoke, log, workers, advance="pallas")
    check_plant(smoke, "bcrypt", line, plain, proc.stdout,
                smoke.path("bcrypt.pot"))
    return _record("bcrypt", smoke, log, ran, t0, cost=cost,
                   advance=ran["advance"],
                   reduced="cost 5, config 4 states 12",
                   plant=plain.decode())


def phase_pmkid(smoke, mask=PMKID_MASK, window=1 << 20, batch=1 << 15,
                workers=("PallasPmkidWorker",)):
    """Config 5: WPA2-PMKID (PBKDF2-HMAC-SHA1 x 4096), 8 lowercase
    characters; one short window ending just after the plant."""
    from dprf_tpu import get_engine
    t0 = time.monotonic()
    oracle = get_engine("wpa2-pmkid", "cpu")
    plain = smoke.candidate(mask, window - 77)
    tail = "*0a1b2c3d4e5f*a0b1c2d3e4f5*" + b"smokenet".hex()
    params = oracle.parse_target("00" * 16 + tail).params
    line = oracle.hash_batch([plain], params=params)[0].hex() + tail
    hf = smoke.write("pmkid.hash", line + "\n")
    proc, log = _crack(smoke, "pmkid", mask, hf, "--engine", "wpa2-pmkid",
                       "--batch", batch, "--unit-size", 1 << 18,
                       "--unit-seconds", 0, "--limit", window)
    ran = check_ran(smoke, log, workers)
    # the kernel worker is pipelined: its units reach the chip as
    # counted per-batch dispatches of the programs warmup() compiled
    if set(_shapes(ran["dispatch"])) != {"batch"}:
        raise PhaseError(f"dispatch {ran['dispatch']!r}: expected the "
                         "per-batch kernel step alone")
    check_plant(smoke, "wpa2-pmkid", line, plain, proc.stdout,
                smoke.path("pmkid.pot"))
    return _record("pmkid", smoke, log, ran, t0, plant=plain.decode())


def phase_mesh(smoke, chips=4, **sizes):
    """--chips N: config 2's window on the N-device mesh and on one
    device.  Same hits, same coverage digest, and the sharded step's
    output buffers on N distinct devices."""
    mesh = phase_ntlm_1k(smoke, devices=chips, name=f"ntlm-1k-x{chips}",
                         workers=("ShardedMaskWorker",), fused="sshard",
                         **sizes)
    print(json.dumps(mesh), flush=True)
    one = phase_ntlm_1k(smoke, devices=1, name="ntlm-1k-x1", **sizes)
    print(json.dumps(one), flush=True)
    held = set(mesh.get("out_devices", "").split("/")) - {""}
    if len(held) != chips:
        raise PhaseError(f"the sharded step's outputs live on devices "
                         f"{sorted(held)}, not on {chips} distinct ones")
    if mesh["found"] != one["found"] or mesh["digest"] != one["digest"]:
        raise PhaseError(f"mesh and one-device runs differ: "
                         f"{mesh['found']} / {mesh['digest']} against "
                         f"{one['found']} / {one['digest']}")
    return {"phase": "mesh-vs-one", "ok": True, "chips": chips,
            "same_hits": True, "same_digest": True,
            "out_devices": mesh["out_devices"]}


PHASES = (phase_md5_mask, phase_ntlm_1k, phase_ntlm_bulk, phase_serve,
          phase_wordlist_rules, phase_bcrypt, phase_pmkid)


# ---------------------------------------------------------------------------

def probe_device(env=None):
    """What JAX finds, from a child: this process never touches JAX."""
    proc = subprocess.run([sys.executable, "-c", _PROBE],
                          env=env or os.environ, capture_output=True,
                          text=True, timeout=300)
    if proc.returncode != 0:
        raise PhaseError("JAX found no device:\n" + proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh path and what it is "
                    "compared with")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "dprf_tpu")):
        sys.stderr.write("chip_smoke.py: no dprf_tpu package beside me\n")
        return 2
    try:
        device = probe_device()
    except PhaseError as e:
        sys.stderr.write(f"chip_smoke.py: {e}\n")
        return 2
    print(json.dumps({"phase": "device", **device}), flush=True)
    if device["platform"] != "tpu" or device["count"] < args.chips:
        # no accelerator (or too few): no job runs, nothing is measured
        print(json.dumps({"ok": False, "device": device}))
        return 1
    ok = True
    with tempfile.TemporaryDirectory(prefix="dprf-smoke-") as workdir:
        smoke = Smoke(workdir, seed=args.seed)
        phases = ([lambda s: phase_mesh(s, chips=4)]
                  if args.chips == 4 else PHASES)
        for phase in phases:
            try:
                print(json.dumps(phase(smoke)), flush=True)
            except (PhaseError, subprocess.TimeoutExpired, OSError,
                    KeyError, ValueError) as e:
                ok = False
                print(json.dumps({
                    "phase": getattr(phase, "__name__", "mesh"),
                    "ok": False, "error": str(e)[-4000:]}), flush=True)
    if not ok:
        print(json.dumps({"ok": False, "device": device}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
