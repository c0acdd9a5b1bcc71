"""The dprf command-line interface.

Flag surface pinned to BASELINE.json's north star: ``dprf crack
--engine=<algo> --device=tpu -a mask <mask> <hashfile>`` -- jobs that
ran against the reference's CPU engines select the TPU backend with
--device and otherwise run unchanged.

Subcommands: crack (local job), serve + worker (distributed job:
coordinator RPC + remote workers, runtime/rpc.py), bench, prewarm
(ahead-of-time compile-cache population), retry-parked (admin op on a
running coordinator), top (live fleet view from the flight recorder),
health + alerts (fleet health plane: worker state machine, per-job
SLOs, alert engine -- ISSUE 10), token (mint owner-scoped tenant
tokens), trace export (session span stream -> Perfetto), engines,
keyspace.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Optional

from dprf_tpu import engine_names, get_engine
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.runtime.coordinator import Coordinator, JobSpec
from dprf_tpu.runtime.dispatcher import Dispatcher
from dprf_tpu.runtime.potfile import Potfile
from dprf_tpu.runtime.rpc import RpcError
from dprf_tpu.runtime.session import SessionJournal, job_fingerprint
from dprf_tpu.runtime.worker import CpuWorker
from dprf_tpu.utils import env as envreg
from dprf_tpu.utils.hashlist import load_hashlist
from dprf_tpu.utils.logging import Log

_DEVICE_ALIASES = {"tpu": "jax", "jax": "jax", "cpu": "cpu"}

#: the pre-tuning hard-coded device batch; "auto" falls back here when
#: neither the session journal nor the tune cache has an entry
DEFAULT_BATCH = 1 << 18


def _batch_size(s: str):
    """--batch value: an integer, or "auto" (resolve from the tuning
    subsystem: session journal > persistent cache > DEFAULT_BATCH)."""
    if s == "auto":
        return s
    return int(s)


def _add_job_args(c, with_hashfile: bool = True) -> None:
    """Attack/job flags shared by crack and serve."""
    c.add_argument("attack_arg", help="mask string (mask attack) or "
                   "wordlist path (wordlist attack)")
    if with_hashfile:
        c.add_argument("hashfile", nargs="?", default=None,
                       help="file of target hashes (or use "
                       "--targets-file)")
    c.add_argument("--targets-file", default=None, metavar="FILE",
                   help="bulk target list (hashcat-style hash[:salt] "
                   "lines; deduped, malformed lines reported; >= "
                   "DPRF_TARGETS_PROBE_MIN targets use the "
                   "device-resident probe table)")
    c.add_argument("--engine", "-m", required=True,
                   help="hash algorithm (see `dprf engines`)")
    c.add_argument("--device", default="tpu", choices=sorted(_DEVICE_ALIASES),
                   help="execution backend (tpu == the JAX device path)")
    c.add_argument("-a", "--attack", default="mask",
                   choices=["mask", "wordlist", "combinator",
                            "hybrid-wm", "hybrid-mw"],
                   help="mask, wordlist(+rules), combinator "
                   "('left.txt,right.txt'), or hybrid word+mask / "
                   "mask+word ('words.txt,?d?d' / '?d?d,words.txt')")
    c.add_argument("--rules", default=None,
                   help="rule set for wordlist attacks (e.g. best64)")
    c.add_argument("--markov", default=None, metavar="STATS",
                   help="mask attacks: visit each position's charset in "
                   "trained-frequency order (stats from `dprf markov`)")
    c.add_argument("--order", default="index",
                   choices=["index", "markov"],
                   help="candidate enumeration order: 'index' sweeps "
                   "the keyspace linearly; 'markov' (requires "
                   "--markov) dispatches probability-ranked units "
                   "first to minimize time-to-first-hit (DPRF_ORDER_* "
                   "knobs shape the rank blocks)")
    for i in range(1, 5):
        c.add_argument(f"--custom{i}", default=None,
                       help=f"custom charset ?{i}")
    c.add_argument("--session", default=None,
                   help="session journal path (enables checkpoint/resume)")
    c.add_argument("--restore", action="store_true",
                   help="resume from --session journal")
    c.add_argument("--potfile", default="dprf.potfile")
    c.add_argument("--no-potfile", action="store_true")
    c.add_argument("--unit-size", type=int, default=1 << 22)
    c.add_argument("--unit-seconds", type=float, default=20.0,
                   metavar="S",
                   help="adaptive unit sizing: grow/shrink each "
                   "worker's WorkUnits toward S seconds apiece from "
                   "its measured throughput (0 pins --unit-size)")
    c.add_argument("--batch", type=_batch_size, default="auto",
                   help="device batch size, or 'auto' (default): use "
                   "the tuning cache written by `dprf tune`, falling "
                   f"back to {DEFAULT_BATCH}")
    c.add_argument("--hit-cap", type=int, default=64)
    c.add_argument("--skip", type=int, default=0, metavar="N",
                   help="skip the first N keyspace indices")
    c.add_argument("--limit", type=int, default=None, metavar="N",
                   help="restrict the sweep to N indices after --skip")
    c.add_argument("--quiet", "-q", action="store_true")


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dprf", description="TPU-native distributed password recovery")
    sub = p.add_subparsers(dest="command", required=True)

    c = sub.add_parser("crack", help="run a recovery job locally")
    _add_job_args(c)
    c.add_argument("--devices", type=int, default=1,
                   help="shard the job over N chips via the mesh "
                   "(any engine; with --multihost, N counts GLOBAL "
                   "devices across all hosts)")
    c.add_argument("--multihost", action="store_true",
                   help="join a cross-host device mesh via "
                   "jax.distributed (run the SAME command on every "
                   "host of the slice; TPU pods auto-detect the "
                   "coordinator)")
    c.add_argument("--coordinator-address", default=None, metavar="H:P",
                   help="multihost coordinator address (auto-detected "
                   "on TPU pods)")
    c.add_argument("--num-processes", type=int, default=None)
    c.add_argument("--process-id", type=int, default=None)
    c.add_argument("--profile", default=None, metavar="DIR",
                   help="write a jax.profiler trace of the run to DIR "
                   "(view with tensorboard)")
    c.add_argument("--increment", action="store_true",
                   help="mask attacks: sweep prefix lengths from "
                   "--increment-min to --increment-max (default: the "
                   "full mask length)")
    c.add_argument("--increment-min", type=int, default=1, metavar="N")
    c.add_argument("--increment-max", type=int, default=None, metavar="N")

    s = sub.add_parser("serve", help="run the coordinator for a "
                       "distributed job (workers connect with "
                       "`dprf worker`)")
    _add_job_args(s)
    s.add_argument("--devices", type=int, default=1,
                   help="ask each worker to shard the job's units over "
                   "N of its local chips (the wire job carries the "
                   "request; a worker's own --devices overrides, and "
                   "hosts with fewer chips degrade to what they have)")
    s.add_argument("--bind", default="127.0.0.1:41715",
                   metavar="HOST:PORT",
                   help="listen address; the protocol is unauthenticated "
                   "-- bind only to trusted networks")
    s.add_argument("--lease-timeout", type=float, default=300.0,
                   help="seconds before a silent worker's unit is "
                   "reissued")
    s.add_argument("--token", default=None,
                   help="shared secret workers must prove on connect "
                   "(default: $DPRF_TOKEN; unset = unauthenticated)")
    s.add_argument("--owner-quota", action="append", default=None,
                   metavar="OWNER=N",
                   help="per-owner AGGREGATE sweep quota (repeatable): "
                   "cap the keyspace indices all of OWNER's jobs may "
                   "sweep combined, enforced on submit and on lease")

    w = sub.add_parser("worker", help="process WorkUnits for a "
                       "`dprf serve` coordinator")
    w.add_argument("--connect", required=True, metavar="HOST:PORT")
    w.add_argument("--device", default="tpu",
                   choices=sorted(_DEVICE_ALIASES))
    w.add_argument("--devices", type=int, default=None,
                   help="shard each unit over N local chips (overrides "
                   "a job's own devices request, including an explicit "
                   "1 to pin this worker to a single chip; default: "
                   "honor the job)")
    w.add_argument("--id", default=None, help="worker id for the lease "
                   "ledger (default: host:pid)")
    w.add_argument("--batch", type=int, default=None,
                   help="override the job's device batch size")
    w.add_argument("--pipeline-depth", type=int, default=None,
                   metavar="N",
                   help="units leased ahead and submitted before the "
                   "oldest one resolves (default: $DPRF_PIPELINE_DEPTH "
                   "or 2; 1 = the serial lease->process->complete "
                   "loop)")
    w.add_argument("--token", default=None,
                   help="shared secret for an authenticated coordinator "
                   "(default: $DPRF_TOKEN)")
    w.add_argument("--quiet", "-q", action="store_true")

    b = sub.add_parser("bench", help="measure engine throughput")
    b.add_argument("--engine", "-m", default="md5")
    b.add_argument("--device", default="tpu", choices=sorted(_DEVICE_ALIASES))
    b.add_argument("--mask", default="?a?a?a?a?a?a?a?a")
    b.add_argument("--batch", type=_batch_size, default="auto",
                   help="batch size, or 'auto' (default): tuned batch "
                   "from the cache when one matches, else 1<<20")
    b.add_argument("--seconds", type=float, default=5.0)
    b.add_argument("--impl", default="auto", choices=["auto", "xla", "pallas"],
                   help="force the generic XLA pipeline or the Pallas "
                   "kernel instead of automatic selection")
    b.add_argument("--config", type=int, default=None, metavar="N",
                   help="measure acceptance workload N (1-5, see "
                   "BASELINE.json) through the real worker path instead "
                   "of the raw engine loop")
    b.add_argument("--devices", type=int, default=1, metavar="N",
                   help="scaling mode: measure the sharded step at 1 "
                   "and N chips and report per-chip rate + efficiency")
    b.add_argument("--inner", type=int, default=8, metavar="K",
                   help="scaling mode: batches fused per superstep "
                   "dispatch (1 = the per-batch compat program)")
    b.add_argument("--ablate", action="store_true",
                   help="scaling mode: also time a per-batch (inner=1) "
                   "mesh window and report superstep_speedup")
    b.add_argument("--bcrypt-cost", type=int, default=12,
                   help="cost for --config 4 (lower it off-TPU)")
    b.add_argument("--targets-sweep", action="store_true",
                   help="target-set-size sweep: measure the probe-"
                   "table step's per-candidate cost across growing "
                   "target counts (--targets-sizes) and report the "
                   "flatness ratio; --gate compares against the "
                   "TARGETS_r*.json trajectory")
    b.add_argument("--targets-sizes", default="1000,10000,100000,1000000",
                   metavar="N,N,...", help="comma-separated target "
                   "counts for --targets-sweep (10^7-ready on real "
                   "silicon; the CPU backend default caps at 10^6)")
    b.add_argument("--ttfh", action="store_true",
                   help="time-to-first-hit mode: crack planted "
                   "passwords under rank-ordered (--order markov) vs "
                   "linear dispatch and report the candidates-to-"
                   "first-hit speedup plus the steady-state H/s "
                   "penalty; --gate compares against the "
                   "TTFH_r*.json trajectory")
    b.add_argument("--plants", type=int, default=4, metavar="N",
                   help="--ttfh: planted passwords per run")
    b.add_argument("--unit-strides", type=int, default=1, metavar="K",
                   help="--config mode: device batches per WorkUnit; "
                   "real Dispatcher units span many batches, and over "
                   "a high-latency link a 1-stride unit measures the "
                   "round trip, not the chip")
    b.add_argument("--profile", default=None, metavar="DIR")
    b.add_argument("--gate", action="store_true",
                   help="regression sentinel: gate this measurement "
                   "against the committed BENCH_r*.json baseline "
                   "window (median of the last K same-device "
                   "records +/- their observed spread); the result "
                   "JSON gains a 'gate' verdict and a regression "
                   "exits non-zero")
    b.add_argument("--gate-dry", action="store_true",
                   help="no measurement: gate the NEWEST committed "
                   "BENCH record against the window before it (the "
                   "CI mode -- the trajectory audits itself)")
    b.add_argument("--baseline-dir", default=None, metavar="DIR",
                   help="directory holding BENCH_r*.json (default: "
                   "this repo's root)")
    b.add_argument("--gate-window", type=int, default=5, metavar="K",
                   help="baseline records considered by --gate")
    b.add_argument("--quiet", "-q", action="store_true")

    tn = sub.add_parser("tune", help="autotune the device batch size "
                        "for an engine and record it in the tuning "
                        "cache (consumed by `--batch auto` and bench)")
    tn.add_argument("--engine", "-m", default=None,
                    help="engine to tune (required unless --all)")
    tn.add_argument("--all", action="store_true",
                    help="sweep EVERY registered device engine (mask "
                    "attack) to pre-populate the tuning cache for a "
                    "fleet image; engines whose targets need real "
                    "salts/params are reported as skipped (tune them "
                    "individually with --hashfile).  Analyzed program "
                    "costs (telemetry/programs.py) are recorded as a "
                    "side effect of every rung")
    tn.add_argument("--device", default="tpu",
                    choices=sorted(_DEVICE_ALIASES))
    tn.add_argument("--mask", default="?a?a?a?a?a?a?a?a",
                    help="mask shaping the candidates swept during "
                    "the probe")
    tn.add_argument("--hashfile", default=None,
                    help="tune against real targets (required for "
                    "salted engines; default: one synthetic "
                    "unmatchable digest)")
    tn.add_argument("--seconds", type=float, default=2.0,
                    help="steady-state probe window per ladder rung")
    tn.add_argument("--min-batch", type=int, default=1 << 14)
    tn.add_argument("--max-batch", type=int, default=1 << 22)
    tn.add_argument("--ladder-factor", type=int, default=4,
                    help="geometric step between ladder rungs")
    tn.add_argument("--compile-budget", type=float, default=120.0,
                    metavar="S", help="skip rungs whose warmup/compile "
                    "exceeds S seconds (and stop climbing)")
    tn.add_argument("--hit-cap", type=int, default=64)
    tn.add_argument("--attack", default="mask",
                    choices=("mask", "wordlist", "combinator"),
                    help="attack shape to tune; wordlist/combinator "
                    "probe over a synthetic in-memory word source "
                    "(bench config 3's trick), so the sweep measures "
                    "the device pipeline, never file I/O")
    tn.add_argument("--rungs", default="batch",
                    choices=("batch", "inner", "sub"),
                    help="quantity to sweep: the device batch ladder "
                    "(default), the multi-batch superstep `inner` "
                    "fusion window, or the Pallas kernel tile size "
                    "(sublanes per tile)")
    tn.add_argument("--rules", default="best64",
                    help="builtin rule set shaping --attack wordlist "
                    "probes")
    tn.add_argument("--words", type=int, default=1 << 14,
                    help="synthetic word-source size for "
                    "wordlist/combinator tuning probes")
    tn.add_argument("--tune-dir", default=None,
                    help="cache directory (default: $DPRF_TUNE_DIR or "
                    "~/.cache/dprf)")
    tn.add_argument("--quiet", "-q", action="store_true")

    pw = sub.add_parser("prewarm", help="populate the persistent XLA "
                        "compile cache ahead of time (fleet images: a "
                        "worker then starts hashing in seconds, not "
                        "minutes)")
    pw.add_argument("--engines", default=None, metavar="E1,E2|all",
                    help="engines to prewarm ('all' = every registered "
                    "device engine; default: the shapes recorded in "
                    "the tuning cache)")
    pw.add_argument("--attacks", default="mask", metavar="A1,A2",
                    help="attack shapes per engine (mask, wordlist, "
                    "combinator, hybrid-wm, hybrid-mw)")
    pw.add_argument("--mask", default="?a?a?a?a?a?a?a?a",
                    help="mask shaping the prewarmed mask step (and "
                    "the mask side of hybrid shapes)")
    pw.add_argument("--rules", default=None,
                    help="rule set for wordlist-shape prewarm")
    pw.add_argument("--wordlist", default=None, metavar="FILE",
                    help="wordlist/hybrid-shape prewarm: the job's "
                    "REAL wordlist (the compiled program embeds the "
                    "packed word table; a stand-in would cache a "
                    "program no job runs)")
    pw.add_argument("--combinator", default=None, metavar="LEFT,RIGHT",
                    help="combinator-shape prewarm: the job's REAL "
                    "left,right word files (both tables are embedded)")
    pw.add_argument("--devices", type=int, default=1, metavar="N",
                    help="prewarm the SHARDED (multi-chip mesh) step "
                    "shape at N devices instead of the single-device "
                    "one; skipped gracefully on hosts with fewer")
    pw.add_argument("--batch", type=_batch_size, default="auto",
                    help="step batch, or 'auto' (default): each "
                    "engine's tuned batch from the tuning cache, "
                    f"falling back to {DEFAULT_BATCH}")
    pw.add_argument("--hit-cap", type=int, default=64)
    pw.add_argument("--jobs", type=int, default=1, metavar="N",
                    help="compile specs in N parallel child processes")
    pw.add_argument("--spec-json", default=None, help=argparse.SUPPRESS)
    pw.add_argument("--quiet", "-q", action="store_true")

    jb = sub.add_parser("jobs", help="multi-tenant job admin against a "
                        "RUNNING coordinator: submit new jobs into the "
                        "fair-share scheduler, list/inspect/cancel/"
                        "pause them, pull per-job hits")
    jsub = jb.add_subparsers(dest="jobs_cmd", required=True)

    def _jobs_client_args(c) -> None:
        c.add_argument("--connect", required=True, metavar="HOST:PORT",
                       help="the coordinator's RPC address "
                       "(`dprf serve --bind`)")
        c.add_argument("--token", default=None,
                       help="shared secret for an authenticated "
                       "coordinator (default: $DPRF_TOKEN)")
        c.add_argument("--timeout", type=float, default=30.0)
        c.add_argument("--quiet", "-q", action="store_true")

    jsb = jsub.add_parser("submit", help="submit a new job to the "
                          "scheduler; target lines are shipped, "
                          "wordlist/rules paths must exist on the "
                          "COORDINATOR host (it rebuilds and "
                          "fingerprints the job before admitting it)")
    jsb.add_argument("attack_arg", help="mask string or wordlist path")
    jsb.add_argument("hashfile", nargs="?", default=None,
                     help="file of target hashes (or use "
                     "--targets-file)")
    jsb.add_argument("--targets-file", default=None, metavar="FILE",
                     help="bulk target list (hashcat-style hash[:salt] "
                     "lines); parsed and deduped locally, shipped with "
                     "a fingerprint the coordinator's rebuild must "
                     "match")
    jsb.add_argument("--engine", "-m", required=True)
    jsb.add_argument("-a", "--attack", default="mask",
                     choices=["mask", "wordlist", "combinator",
                              "hybrid-wm", "hybrid-mw"])
    jsb.add_argument("--rules", default=None)
    jsb.add_argument("--markov", default=None, metavar="STATS")
    jsb.add_argument("--order", default="index",
                     choices=["index", "markov"],
                     help="candidate dispatch order: 'markov' leases "
                     "probability-ranked spans first (needs --markov "
                     "stats; the coordinator resolves and pins the "
                     "bijection split on the wire job)")
    for i in range(1, 5):
        jsb.add_argument(f"--custom{i}", default=None)
    jsb.add_argument("--unit-size", type=int, default=1 << 22)
    jsb.add_argument("--unit-seconds", type=float, default=20.0)
    jsb.add_argument("--batch", type=int, default=None,
                     help="device batch size shipped to workers "
                     f"(default: {DEFAULT_BATCH})")
    jsb.add_argument("--hit-cap", type=int, default=64)
    jsb.add_argument("--devices", type=int, default=1,
                     help="ask workers to shard this job's units over "
                     "N of their local chips (unified sharded "
                     "runtime; a worker's own --devices overrides)")
    jsb.add_argument("--owner", default=None,
                     help="tenant name recorded on the job (default: "
                     "$USER)")
    jsb.add_argument("--priority", type=int, default=1,
                     help="fair-share weight: a priority-3 job "
                     "receives ~3x the leases of a priority-1 job")
    jsb.add_argument("--quota", type=int, default=None, metavar="N",
                     help="cap on keyspace indices this job may sweep")
    jsb.add_argument("--rate", type=float, default=None, metavar="U/S",
                     help="lease-rate cap in units/second (token "
                     "bucket)")
    _jobs_client_args(jsb)

    jls = jsub.add_parser("list", help="list every job with state, "
                          "coverage, and fair-share accounting")
    _jobs_client_args(jls)
    for name, helptext in (
            ("status", "one job's summary (adds its keyspace and "
             "fingerprint)"),
            ("cancel", "cancel a job: no more leases, in-flight "
             "completes dropped"),
            ("pause", "pause a job (outstanding units still land; "
             "resume with `dprf jobs resume`)"),
            ("resume", "resume a paused job")):
        c = jsub.add_parser(name, help=helptext)
        c.add_argument("job", help="job id (from submit/list)")
        _jobs_client_args(c)
    jh = jsub.add_parser("hits", help="pull a job's hits (cursor-"
                         "based): each tenant streams its OWN cracks, "
                         "not the global found set")
    jh.add_argument("job", help="job id")
    jh.add_argument("--cursor", type=int, default=0,
                    help="resume from this hit sequence number")
    jh.add_argument("--follow", action="store_true",
                    help="keep polling until the job reaches a "
                    "terminal state")
    jh.add_argument("--interval", type=float, default=2.0)
    _jobs_client_args(jh)

    rp = sub.add_parser("retry-parked", help="admin op on a RUNNING "
                        "coordinator: requeue poisoned/parked units "
                        "with a fresh retry budget, without restarting "
                        "the job")
    rp.add_argument("--connect", required=True, metavar="HOST:PORT",
                    help="the coordinator's RPC address (`dprf serve "
                    "--bind`)")
    rp.add_argument("--token", default=None,
                    help="shared secret for an authenticated "
                    "coordinator (default: $DPRF_TOKEN)")
    rp.add_argument("--timeout", type=float, default=30.0)
    rp.add_argument("--quiet", "-q", action="store_true")

    for name, helptext in (("show", "print potfile-cracked targets of a "
                            "hashlist as hash:plain"),
                           ("left", "print targets of a hashlist NOT yet "
                            "in the potfile")):
        v = sub.add_parser(name, help=helptext)
        v.add_argument("hashfile")
        v.add_argument("--engine", "-m", required=True)
        v.add_argument("--potfile", default="dprf.potfile")
        v.add_argument("--quiet", "-q", action="store_true")

    tp = sub.add_parser("top", help="live terminal view of a running "
                        "coordinator: per-worker state, current unit, "
                        "span in progress, lease countdown (reads the "
                        "flight recorder over the op_trace_tail RPC)")
    tp.add_argument("--connect", required=True, metavar="HOST:PORT",
                    help="the coordinator's RPC address (`dprf serve "
                    "--bind`)")
    tp.add_argument("--interval", type=float, default=2.0, metavar="S",
                    help="seconds between refreshes")
    tp.add_argument("--iterations", type=int, default=0, metavar="N",
                    help="stop after N frames (0 = until the job "
                    "finishes / Ctrl-C)")
    tp.add_argument("--spans", type=int, default=400, metavar="N",
                    help="flight-recorder spans to fetch per frame")
    tp.add_argument("--trace", default=None, metavar="TRACE_ID",
                    help="only spans of this work-unit trace id (from "
                    "a lease table row or `dprf trace export`): watch "
                    "one unit's lifecycle bounce across the fleet")
    tp.add_argument("--follow", action="store_true",
                    help="incremental span streaming: each frame "
                    "fetches only spans newer than the last frame's "
                    "cursor (cuts refresh cost on big fleets)")
    tp.add_argument("--no-clear", action="store_true",
                    help="append frames instead of redrawing the "
                    "screen")
    tp.add_argument("--token", default=None,
                    help="shared secret for an authenticated "
                    "coordinator (default: $DPRF_TOKEN)")
    tp.add_argument("--timeout", type=float, default=30.0)
    tp.add_argument("--quiet", "-q", action="store_true")

    tr = sub.add_parser("trace", help="work with session trace streams "
                        "(the per-unit lifecycle spans recorded next "
                        "to the session journal)")
    trsub = tr.add_subparsers(dest="trace_cmd", required=True)
    te = trsub.add_parser("export", help="convert a session's span "
                          "stream to Chrome-trace JSON (open in "
                          "Perfetto / chrome://tracing)")
    te.add_argument("session", help="session journal path (or the "
                    ".trace.jsonl stream itself)")
    te.add_argument("-o", "--out", default=None,
                    help="output file (default: <session>"
                    ".perfetto.json)")
    te.add_argument("--quiet", "-q", action="store_true")
    tpl = trsub.add_parser("pull", help="incident response: arm a "
                           "fleet-wide flight-recorder pull (live "
                           "workers ship their LOCAL rings on their "
                           "next lease), then dump the coordinator's "
                           "merged ring to a .trace.jsonl file that "
                           "`dprf trace export` understands")
    tpl.add_argument("--connect", required=True, metavar="HOST:PORT",
                     help="the coordinator's RPC address")
    tpl.add_argument("-o", "--out", default="pulled.trace.jsonl",
                     help="output span stream (feed to `dprf trace "
                     "export`)")
    tpl.add_argument("--wait", type=float, default=2.0, metavar="S",
                     help="seconds to wait after arming so polling "
                     "workers can push their rings (0 with --no-arm)")
    tpl.add_argument("--no-arm", action="store_true",
                     help="dump only what the coordinator already "
                     "holds; do not ask workers for their rings")
    tpl.add_argument("--spans", type=int, default=1000, metavar="N",
                     help="page size per op_trace_pull request")
    tpl.add_argument("--token", default=None,
                     help="shared secret for an authenticated "
                     "coordinator (default: $DPRF_TOKEN)")
    tpl.add_argument("--timeout", type=float, default=30.0)
    tpl.add_argument("--quiet", "-q", action="store_true")

    hl = sub.add_parser("health", help="fleet health view of a "
                        "running coordinator: per-worker state "
                        "machine (healthy/degraded/missing/dead), "
                        "straggler flags, per-job SLOs (ETA, "
                        "time-to-first-hit, stall), active alerts")
    hl.add_argument("--json", action="store_true",
                    help="machine-readable snapshot on stdout (the "
                    "CI artifact format)")
    _jobs_client_args(hl)

    al = sub.add_parser("alerts", help="alert surface of a running "
                        "coordinator: active (pending/firing) alerts "
                        "and the recent transition history (the full "
                        "log is the session's .alerts.jsonl)")
    al.add_argument("--json", action="store_true",
                    help="machine-readable alerts on stdout")
    al.add_argument("--history", type=int, default=50, metavar="N",
                    help="recent transition events to fetch")
    _jobs_client_args(al)

    tok = sub.add_parser("token", help="mint an owner-scoped tenant "
                         "token from the coordinator's admin secret: "
                         "a client authenticating with it may only "
                         "cancel/pause/resume/pull its OWN jobs, and "
                         "its submissions are forced to that owner")
    tok.add_argument("--owner", required=True,
                     help="tenant name (1-64 chars of [A-Za-z0-9_-])")
    tok.add_argument("--token", default=None,
                     help="the coordinator's ADMIN secret (default: "
                     "$DPRF_TOKEN)")
    tok.add_argument("--quiet", "-q", action="store_true")

    rpt = sub.add_parser("report", help="one-shot performance report "
                         "from session artifacts alone (trace JSONL "
                         "+ telemetry snapshots + journal): "
                         "throughput, phase breakdown p50/p95, busy "
                         "fraction, compile-cache hit rate, pipeline "
                         "depth, per-job fair share -- no live "
                         "coordinator needed")
    rpt.add_argument("session", help="session journal path")
    rpt.add_argument("--json", action="store_true",
                     help="machine-readable report on stdout instead "
                     "of the text rendering")
    rpt.add_argument("--quiet", "-q", action="store_true")

    aud = sub.add_parser("audit", help="coverage audit from session "
                         "artifacts alone (perfreport/audit.py): "
                         "rebuild per-job coverage from journal "
                         "snapshots (fraction, gaps, digest "
                         "re-check), replay trace complete spans for "
                         "double-covered candidates, prove hits were "
                         "found exactly once -- exit 0 on verdict "
                         "clean, 3 otherwise")
    aud.add_argument("session", help="session journal path")
    aud.add_argument("--json", action="store_true",
                     help="machine-readable audit on stdout instead "
                     "of the text rendering")
    aud.add_argument("--quiet", "-q", action="store_true")

    pg = sub.add_parser("programs", help="compiled-program table of a "
                        "running coordinator: XLA-derived flops, "
                        "bytes accessed, and peak device memory per "
                        "executable -- the coordinator's own compile "
                        "sites plus the records workers ship in "
                        "heartbeats (op_programs RPC)")
    pg.add_argument("--json", action="store_true",
                    help="machine-readable program records on stdout "
                    "(the CI artifact format)")
    _jobs_client_args(pg)

    pf = sub.add_parser("profile", help="kernel-level profiling "
                        "(telemetry/profiler.py): analyze a "
                        "jax.profiler capture dir dependency-free "
                        "(top device ops, compute/collective/copy "
                        "fractions, generate/hash/compare phases), "
                        "or capture a bounded window on a live fleet "
                        "worker over RPC")
    pf.add_argument("target", nargs="?", default=None,
                    help="local mode: a capture dir (the --profile / "
                    "DPRF_JAX_PROFILE output) or a "
                    "perfetto_trace.json.gz file")
    pf.add_argument("--engine", "-m", default=None,
                    help="engine whose declared PROFILE_PHASES "
                    "patterns map device ops to generate/hash/"
                    "compare")
    pf.add_argument("--connect", default=None, metavar="HOST:PORT",
                    help="capture mode: request one bounded capture "
                    "window on a worker and pull back the analyzed "
                    "summary (the raw trace stays on the worker "
                    "host; its path rides the summary)")
    pf.add_argument("--worker", default=None, metavar="W",
                    help="worker id to capture on (default: the "
                    "slowest live worker)")
    pf.add_argument("--seconds", type=float, default=None,
                    help="capture window length (default: "
                    "$DPRF_PROFILE_SECONDS)")
    pf.add_argument("--wait", type=float, default=180.0, metavar="S",
                    help="seconds to wait for the worker to push its "
                    "summary before giving up (a cold worker first "
                    "warms the profiler's import stack off its sweep "
                    "path, then sweeps through the window)")
    pf.add_argument("--fetch", action="store_true",
                    help="no new capture: print the summaries the "
                    "coordinator already holds (incl. "
                    "alert-triggered auto-captures)")
    pf.add_argument("--top", type=int, default=20, metavar="N",
                    help="top-ops table length (local mode)")
    pf.add_argument("--json", action="store_true",
                    help="machine-readable summary on stdout (the "
                    "CI artifact format)")
    pf.add_argument("--token", default=None,
                    help="shared secret for an authenticated "
                    "coordinator (default: $DPRF_TOKEN)")
    pf.add_argument("--timeout", type=float, default=30.0)
    pf.add_argument("--quiet", "-q", action="store_true")

    mt = sub.add_parser("metrics", help="scrape a running coordinator's "
                        "/metrics endpoint (Prometheus text format)")
    mt.add_argument("--connect", required=True, metavar="HOST:PORT",
                    help="the coordinator's RPC address (`dprf serve "
                    "--bind`); /metrics is served on the same port")
    mt.add_argument("--json", action="store_true",
                    help="print the registry as a JSON snapshot "
                    "instead of Prometheus text (uses the RPC "
                    "protocol, so --token applies)")
    mt.add_argument("--token", default=None,
                    help="shared secret for a token-authenticated "
                    "coordinator's --json path (default: $DPRF_TOKEN; "
                    "the plain-text scrape never needs one)")
    mt.add_argument("--timeout", type=float, default=10.0)
    mt.add_argument("--quiet", "-q", action="store_true")

    ck = sub.add_parser("check", help="run the static-analysis suite "
                        "(lock discipline, RPC protocol contract, "
                        "env-knob registry, markers, metrics, worker "
                        "contract)")
    ck.add_argument("--root", default=None, metavar="DIR",
                    help="repo root to analyze (default: the tree "
                    "this package is installed in)")
    ck.add_argument("--only", action="append", default=None,
                    metavar="CHECK", help="run only these checks "
                    "(repeatable, or comma-separated)")
    ck.add_argument("--skip", action="append", default=None,
                    metavar="CHECK", help="skip these checks")
    ck.add_argument("--json", action="store_true",
                    help="machine-readable findings on stdout")
    ck.add_argument("--list", action="store_true",
                    help="list available checks and exit")
    ck.add_argument("--explain", metavar="CHECK", default=None,
                    help="print one check's rules and its declaration "
                    "tables as found in the repo, then exit")
    ck.add_argument("--show-suppressed", action="store_true",
                    help="also print findings silenced by inline "
                    "suppressions")
    ck.add_argument("--write-env-docs", action="store_true",
                    help="regenerate the README env-knob table from "
                    "the utils/env.py registry, then run the checks")
    ck.add_argument("--fix-skeletons", action="store_true",
                    help="emit GUARDED_BY / RELEASES declaration "
                    "skeletons for the lock and resource findings the "
                    "locks/threads checks raise, ready to paste next "
                    "to the offending class")
    ck.add_argument("--quiet", "-q", action="store_true")

    e = sub.add_parser("engines", help="list available engines")
    e.add_argument("--device", default=None)
    e.add_argument("--verbose", "-v", action="store_true",
                   help="one line per engine with its description")

    for name, helptext in (
            ("keyspace", "print the keyspace size of an attack (mask, "
             "wordlist+rules, combinator, hybrid)"),
            ("stdout", "print the attack's candidates, one per line, "
             "without hashing (pipe to other tools)")):
        k = sub.add_parser(name, help=helptext)
        k.add_argument("attack_arg", metavar="mask_or_files")
        k.add_argument("-a", "--attack", default="mask",
                       choices=["mask", "wordlist", "combinator",
                                "hybrid-wm", "hybrid-mw"])
        k.add_argument("--rules", default=None)
        k.add_argument("--markov", default=None, metavar="STATS")
        k.add_argument("--max-len", type=int, default=55)
        for i in range(1, 5):
            k.add_argument(f"--custom{i}", default=None)
        if name == "stdout":
            k.add_argument("--skip", type=int, default=0, metavar="N")
            k.add_argument("--limit", type=int, default=None, metavar="N")
        k.add_argument("--quiet", "-q", action="store_true")

    from dprf_tpu.generators.markov import MAX_LEN as _MARKOV_MAX_LEN
    t = sub.add_parser("markov", help="train per-position Markov stats "
                       "from a wordlist (for crack --markov)")
    t.add_argument("wordlist")
    t.add_argument("-o", "--out", required=True, metavar="STATS",
                   help="output stats file (.dprfstat)")
    t.add_argument("--max-len", type=int, default=_MARKOV_MAX_LEN)
    t.add_argument("--quiet", "-q", action="store_true")
    return p


def _customs(args) -> dict:
    out = {}
    for i in range(1, 5):
        v = getattr(args, f"custom{i}", None)
        if v is not None:
            out[i] = v.encode("latin-1")
    return out


# ---------------------------------------------------------------------------
# job construction (shared by crack / serve / worker)

def _wordlist_max_len(engine_name: str, engine, device: str) -> int:
    """The 55-byte single-block limit binds only on device engines whose
    packer lays words out as single-block uint32 messages (the
    digest_packed fast path).  bcrypt's device path packs its own uint8
    tables with no single-block constraint, so it keeps the engine's own
    72-byte limit; CPU-oracle jobs keep the engine limit too (e.g.
    63-byte WPA passphrases)."""
    if device == "jax":
        try:
            dev = get_engine(engine_name, device="jax")
        except KeyError:
            return engine.max_candidate_len
        if (hasattr(dev, "make_wordlist_worker")
                and hasattr(dev, "digest_packed")):
            # single-block limit of the DEVICE engine (55 for 64-byte
            # blocks, 111 for the SHA-512 family's 128-byte blocks)
            return min(getattr(dev, "_block_limit", 55),
                       engine.max_candidate_len)
    return engine.max_candidate_len


def _build_gen(attack: str, attack_arg: str, customs: dict,
               rules_spec, max_len: Optional[int], engine, device: str,
               log: Log, markov: Optional[str] = None):
    """Build the candidate generator + the attack identity string.

    max_len: wordlist packing width; None = derive from engine/device
    (the coordinator derives it and ships it to workers, who must use
    the identical value or their keyspace would disagree).
    Returns (gen, attack_desc, max_len).
    """
    if attack == "mask":
        counts = None
        markov_id = ""
        if markov:
            from dprf_tpu.generators.markov import load_stats, stats_digest
            counts = load_stats(markov)
            # stats permute the index->candidate map: part of the job
            # identity, so divergent stats files fail the fingerprint
            markov_id = f":markov={stats_digest(counts)}"
            log.info("markov ordering", stats=markov)
        gen = MaskGenerator(attack_arg, custom=customs or None,
                            markov_counts=counts)
        log.info("keyspace", mask=attack_arg, size=gen.keyspace)
        # Custom charsets change which candidate an index decodes to, so
        # they are part of the job identity.
        attack_desc = f"mask:{attack_arg}" + "".join(
            f":{i}={customs[i].hex()}" for i in sorted(customs)) + markov_id
        return gen, attack_desc, None
    if markov:
        raise ValueError("--markov applies to mask attacks only")

    if attack in ("combinator", "hybrid-wm", "hybrid-mw"):
        return _build_combinator_gen(attack, attack_arg, customs,
                                     max_len, engine, device, log)

    import hashlib as _hl

    from dprf_tpu.generators.wordlist import WordlistRulesGenerator
    from dprf_tpu.rules import resolve_rules_path

    if max_len is None:
        max_len = _wordlist_max_len(engine.name, engine, device)
    rules_id = "none"
    if rules_spec:
        with open(resolve_rules_path(rules_spec), "rb") as fh:
            rules_id = _hl.sha256(fh.read()).hexdigest()[:16]
    # from_files prefers the native (C++) loader: packed tables are
    # built at memory bandwidth, never as a Python word list.
    gen = WordlistRulesGenerator.from_files(attack_arg, rules_spec,
                                            max_len=max_len)
    if gen.n_skipped_long:
        log.warn("skipped overlong words", count=gen.n_skipped_long,
                 max_len=max_len)
    log.info("keyspace", words=gen.n_words, rules=gen.n_rules,
             size=gen.keyspace, native_reader=gen.native_reader)
    # Wordlist contents decide what an index decodes to: fingerprint
    # the word content, not the file path.
    attack_desc = f"wordlist:{gen.content_id()}:rules={rules_id}"
    return gen, attack_desc, max_len


#: largest mask keyspace a hybrid attack will materialize as a word
#: table (the mask side of -a 6/7 is typically a short digit/symbol
#: suffix; a full-size mask belongs in a plain mask attack instead)
_HYBRID_MASK_CAP = 1 << 20


def _build_combinator_gen(attack: str, attack_arg: str, customs: dict,
                          max_len: Optional[int], engine, device: str,
                          log: Log):
    """Combinator (-a combinator: 'left.txt,right.txt') and hybrid
    modes (-a hybrid-wm: 'words.txt,MASK'; -a hybrid-mw:
    'MASK,words.txt').  The mask side of a hybrid is materialized as a
    word table (capped -- see _HYBRID_MASK_CAP)."""
    from dprf_tpu.generators.combinator import CombinatorGenerator
    from dprf_tpu.generators.wordlist import load_words

    parts = attack_arg.split(",")
    if len(parts) != 2:
        raise ValueError(f"{attack} needs 'LEFT,RIGHT', got {attack_arg!r}")
    if max_len is None:
        max_len = _wordlist_max_len(engine.name, engine, device)

    def side(spec: str, is_mask: bool) -> list:
        if not is_mask:
            words, skipped = load_words(spec, max_len)
            if skipped:
                log.warn("skipped overlong words", file=spec,
                         count=skipped, max_len=max_len)
            return words
        mgen = MaskGenerator(spec, custom=customs or None)
        if mgen.keyspace > _HYBRID_MASK_CAP:
            raise ValueError(
                f"hybrid mask {spec!r} expands to {mgen.keyspace} words "
                f"(cap {_HYBRID_MASK_CAP}); use a shorter mask or a "
                "plain mask attack")
        return [mgen.candidate(i) for i in range(mgen.keyspace)]

    left_mask = attack == "hybrid-mw"
    right_mask = attack == "hybrid-wm"
    gen = CombinatorGenerator(side(parts[0], left_mask),
                              side(parts[1], right_mask),
                              max_len=max_len)
    log.info("keyspace", left=gen.n_left, right=gen.n_right,
             size=gen.keyspace)
    attack_desc = f"{attack}:{gen.content_id()}"
    return gen, attack_desc, max_len


def _align_unit_size(unit_size: int, attack: str, gen) -> int:
    """Units aligned to whole words: no candidate is ever rehashed at
    unit boundaries on the device path."""
    if attack != "wordlist":
        return unit_size
    return max(gen.n_rules, (unit_size // gen.n_rules) * gen.n_rules)


def _apply_tuned_inner(worker, engine_name: str, attack: str, gen,
                       hit_cap: int, log: Log):
    """Warm-start the multi-batch superstep fusion window from a
    `dprf tune --rungs inner` record.  SUPER_CAP bounds a worker's
    _super_inner window, so the instance override takes effect without
    touching the DPRF_SUPER_CAP env knob; a cache miss (or a worker
    with no superstep) leaves the default standing."""
    from dprf_tpu import tune as tune_mod
    inner = tune_mod.lookup_tuned_value(
        engine_name, "inner", attack=attack, device="jax",
        extras=_tune_extras(attack, hit_cap=hit_cap,
                            n_rules=getattr(gen, "n_rules", None)))
    if inner and hasattr(worker, "SUPER_CAP"):
        worker.SUPER_CAP = int(inner)
        log.info("tuned superstep window", inner=int(inner))
    return worker


def _log_device(device: str, log: Log) -> None:
    """One line at job start naming what the device path runs on --
    `--device tpu` means "the JAX device path" on whatever backend JAX
    found, so the job's own log must say which that was."""
    if device != "jax":
        return
    import jax
    devs = jax.devices()
    log.info("device", platform=devs[0].platform, count=len(devs),
             kind=devs[0].device_kind)


def _log_ran(worker, log: Log, host: str = "", **kw) -> None:
    """One line at job end saying what ran: worker class, interpret
    flag, dispatch shapes, compile cost, every compile of this
    process as a persistent-cache hit or miss, (``kdf``) the key
    derivations an iterated-KDF worker dispatched, and (``host``, from
    ``trace.format_stations``) the host's self seconds by station of
    the sweep loop."""
    from dprf_tpu import compilecache
    from dprf_tpu.runtime.worker import describe_worker
    kdf = getattr(worker, "kdf_evals", None)
    if kdf is not None:
        kw["kdf"] = f"evals:{kdf}"
    log.info("ran", **kw, **describe_worker(worker),
             **compilecache.process_cache_counts(),
             **({"host": host} if host else {}))


def _select_worker(engine_name: str, device: str, attack: str, gen,
                   targets, batch: int, hit_cap: int, oracle, n_devices: int,
                   log: Log):
    """Pick the execution backend for a job's WorkUnits.

    Engine-specific device workers first (salted pipelines plug in the
    same way fast ones do); the multi-chip mesh path for fast engines
    when n_devices > 1; the CPU oracle for --device cpu only -- a
    device job whose engine has no device worker for the attack is
    refused, never quietly run on the oracle.
    """
    _MAKERS = {"mask": "make_mask_worker",
               "wordlist": "make_wordlist_worker",
               "combinator": "make_combinator_worker",
               "hybrid-wm": "make_combinator_worker",
               "hybrid-mw": "make_combinator_worker"}
    maker_name = _MAKERS[attack]
    dev_engine = None
    if device == "jax":
        try:
            dev_engine = get_engine(engine_name, device="jax")
        except KeyError:
            pass
    if dev_engine is not None and n_devices > 1:
        import jax as _jax
        have = len(_jax.devices())
        if have < n_devices:
            # a serve-plane job may request more chips than this host
            # has: degrade to the local mesh instead of refusing the
            # job's leases (coverage is keyspace-indexed, so any
            # device count sweeps the same units)
            log.warn("host has fewer devices than requested; "
                     "clamping the mesh", requested=n_devices,
                     have=have)
            n_devices = have
    if dev_engine is not None and n_devices > 1:
        smaker = maker_name.replace("make_", "make_sharded_")
        if callable(getattr(dev_engine, smaker, None)):
            from dprf_tpu.parallel.mesh import make_mesh
            mesh = make_mesh(n_devices)
            log.info("mesh", devices=n_devices)
            per_dev = (max(1, batch // gen.n_rules)
                       if attack == "wordlist" else batch)
            return _apply_tuned_inner(
                getattr(dev_engine, smaker)(
                    gen, targets, mesh, per_dev,
                    hit_capacity=hit_cap, oracle=oracle),
                engine_name, attack, gen, hit_cap, log)
        log.warn("engine has no multi-chip pipeline; using one chip",
                 engine=engine_name)
    if dev_engine is not None and callable(getattr(dev_engine, maker_name, None)):
        return _apply_tuned_inner(
            getattr(dev_engine, maker_name)(
                gen, targets, batch=batch, hit_capacity=hit_cap,
                oracle=oracle),
            engine_name, attack, gen, hit_cap, log)
    if device == "jax":
        raise ValueError(
            f"engine {engine_name!r} has no device worker for a "
            f"{attack} attack; run it on the oracle with --device cpu")
    return CpuWorker(oracle, gen, targets)


def _load_targets(engine, hashfile: str, log: Log):
    hl = load_hashlist(engine, hashfile)
    for no, text, err in hl.skipped:
        log.warn("skipping hashlist line", line=no, error=err)
    if not hl.targets:
        log.error("no valid targets in hashlist")
        return None
    log.info("loaded targets", count=len(hl.targets),
             duplicates=hl.duplicates, engine=engine.name)
    return hl


def _load_job_targets(args, engine, log: Log):
    """Resolve the job's target set from the hashfile positional or
    the bulk ``--targets-file`` ingest path; returns an object with a
    ``.targets`` list (HashlistResult or TargetStore) or None on a
    fatal, already-logged error."""
    tf = getattr(args, "targets_file", None)
    if tf is not None:
        if args.hashfile is not None:
            log.error("pass a hashfile positional OR --targets-file, "
                      "not both")
            return None
        from dprf_tpu.targets import TargetStore
        store = TargetStore.from_file(engine, tf, log=log)
        if not store.targets:
            log.error("no valid targets in targets file", path=tf)
            return None
        return store
    if args.hashfile is None:
        log.error("no target hashes: pass a hashfile or --targets-file")
        return None
    return _load_targets(engine, args.hashfile, log)


def _setup_session(args, spec, log: Log):
    """Returns (session, completed, restored_hits, tuning, jobs,
    digest) or None on conflict; ``jobs`` is the journal's
    scheduler-submitted job records (multi-tenant serve resume,
    jobs/build.restore_jobs) and ``digest`` is the journal's coverage
    digest for the default job's restored intervals (ISSUE 19)."""
    session = None
    completed: list = []
    restored_hits: list = []
    tuning: dict = {}
    jobs: dict = {}
    digest = None
    if args.session:
        session = SessionJournal(args.session)
        prior = SessionJournal.load(args.session)
        if args.restore:
            if prior is None:
                log.warn("no session to restore; starting fresh")
            elif prior.spec.get("fingerprint") != spec.fingerprint:
                log.error("session file belongs to a different job",
                          theirs=prior.spec.get("fingerprint"),
                          ours=spec.fingerprint)
                return None
            else:
                completed = prior.completed
                restored_hits = prior.hits
                tuning = prior.tuning
                jobs = prior.jobs
                digest = prior.coverage.get(prior.default_job)
                done = sum(e - s for s, e in completed)
                log.info("resuming session", covered=done,
                         hits=len(restored_hits), jobs=len(jobs))
        elif prior is not None:
            log.error("session file exists; pass --restore to resume "
                      "or remove it", path=args.session)
            return None
    return session, completed, restored_hits, tuning, jobs, digest


def _print_results(found: dict, targets) -> None:
    from dprf_tpu.runtime.potfile import encode_plain
    for ti, plain in sorted(found.items()):
        print(f"{targets[ti].raw}:{encode_plain(plain)}")


# ---------------------------------------------------------------------------
# crack (local)

class _JobSetup:
    """Everything the crack and serve front-ends share: targets,
    generator, spec/fingerprint, session state, dispatcher."""

    def __init__(self, engine, hl, gen, max_len, unit_size, spec,
                 session, completed, restored_hits, dispatcher,
                 tuning=None, restored_jobs=None, order=None):
        #: rank<->index bijection (generators/order.py) or None: the
        #: dispatcher leases rank spans, so the worker must be wrapped
        #: in an OrderedWorker before it sees a unit
        self.order = order
        self.engine = engine
        self.hl = hl
        self.gen = gen
        self.max_len = max_len
        self.unit_size = unit_size
        self.spec = spec
        self.session = session
        self.completed = completed
        self.restored_hits = restored_hits
        self.dispatcher = dispatcher
        #: tuning records restored from the session journal (resume)
        self.tuning = tuning or {}
        #: scheduler-submitted job records from the journal (resume)
        self.restored_jobs = restored_jobs or {}


def _setup_job(args, device: str, log: Log,
               lease_timeout: Optional[float] = None):
    """Build the full job state; None means a fatal setup error (already
    logged).  Single source of truth for the fingerprint and session
    wiring, so local and distributed jobs can never diverge."""
    engine = get_engine(args.engine, device="cpu")   # parser/oracle always CPU
    from dprf_tpu.telemetry.trace import get_tracer
    with get_tracer().station("targets"):
        hl = _load_job_targets(args, engine, log)
    if hl is None:
        return None

    gen, attack_desc, max_len = _build_gen(args.attack, args.attack_arg,
                                           _customs(args), args.rules, None,
                                           engine, device, log,
                                           markov=getattr(args, "markov",
                                                          None))
    unit_size = _align_unit_size(args.unit_size, args.attack, gen)

    order = None
    if (getattr(args, "order", "index") or "index") != "index":
        if not getattr(args, "markov", None):
            log.error("--order markov requires --markov stats: the "
                      "rank order ranks trained-frequency levels")
            return None
        from dprf_tpu.generators.order import build_order
        try:
            order = build_order(args.order, gen)
        except ValueError as e:
            log.error("cannot build candidate order", error=str(e))
            return None
        log.info("rank-ordered dispatch", order=order.kind,
                 split=order.split, blocks=order.blocks,
                 block=order.block)

    spec = JobSpec(engine=engine.name, device=device, attack=args.attack,
                   attack_arg=args.attack_arg, keyspace=gen.keyspace,
                   fingerprint=job_fingerprint(
                       engine.name, attack_desc, gen.keyspace,
                       [t.digest for t in hl.targets]))

    sess = _setup_session(args, spec, log)
    if sess is None:
        return None
    (session, completed, restored_hits, tuning, restored_jobs,
     restored_digest) = sess

    kw = {} if lease_timeout is None else {"lease_timeout": lease_timeout}
    unit_seconds = getattr(args, "unit_seconds", 0) or 0
    if unit_seconds > 0:
        from dprf_tpu.telemetry import devstats
        from dprf_tpu.tune import AdaptiveUnitSizer
        # wordlist units stay word-aligned even when adaptively sized,
        # so no candidate is rehashed at unit boundaries
        align = gen.n_rules if args.attack == "wordlist" else 1
        kw["sizer"] = AdaptiveUnitSizer(
            unit_size, target_seconds=unit_seconds, align=align,
            # an explicit tiny --unit-size is a floor the sizer must
            # respect, not round up away from
            min_unit=max(align, min(unit_size, 1 << 10)),
            # OOM-headroom signal at the right ALTITUDE: the local
            # crack path hashes in THIS process, so local devstats is
            # the worker's own allocator; a serve coordinator's units
            # run on REMOTE workers, whose headroom arrives per-worker
            # through heartbeats (rpc.op_heartbeat) instead
            headroom_fn=(devstats.headroom_frac
                         if lease_timeout is None else None))
    # --skip/--limit restrict THIS run's sweep by pre-marking the
    # excluded ranges done (run-scoped: not part of the job identity,
    # exactly like resuming a partially-covered session)
    skip = min(getattr(args, "skip", 0) or 0, gen.keyspace)
    limit = getattr(args, "limit", None)
    restricted = list(completed)
    # under --order, skip/limit count candidates in the order they
    # are TRIED (ranks); the exclusions are mapped to their index
    # image because the journal -- and from_completed -- speak index
    if skip:
        restricted.extend(order.index_spans(0, skip) if order
                          else [(0, skip)])
        log.info("skipping keyspace prefix", skip=skip)
    if limit is not None and skip + limit < gen.keyspace:
        restricted.extend(
            order.index_spans(skip + limit, gen.keyspace) if order
            else [(skip + limit, gen.keyspace)])
        log.info("limiting sweep", limit=limit)
    if (skip or limit is not None) and session is not None:
        log.warn("--skip/--limit ranges will be journaled as covered "
                 "in this session; resume without them will NOT sweep "
                 "the excluded ranges")
    if restricted:
        # the journal's digest describes the RESTORED intervals only:
        # --skip/--limit append synthetic covered ranges, which would
        # (correctly) rebuild to a different digest -- so the check
        # only arms on a pure resume
        expect = (restored_digest
                  if not skip and limit is None else None)
        try:
            dispatcher = Dispatcher.from_completed(
                gen.keyspace, unit_size, restricted,
                expect_digest=expect, order=order, **kw)
        except ValueError as e:
            log.error("refusing to resume", error=str(e))
            return None
    else:
        dispatcher = Dispatcher(gen.keyspace, unit_size, order=order,
                                **kw)
    return _JobSetup(engine, hl, gen, max_len, unit_size, spec,
                     session, completed, restored_hits, dispatcher,
                     tuning=tuning, restored_jobs=restored_jobs,
                     order=order)


def _tune_extras(attack: str, hit_cap=None, n_rules=None) -> dict:
    """Tuning-cache key extras beyond (engine, device, attack):
    hit_capacity scales every hit buffer (moving the HBM ceiling), and
    the rules-set cardinality changes a wordlist step's word_batch for
    the same --batch -- either can fork the optimum, so they live in
    the key and can never alias a stale one."""
    extras: dict = {}
    if hit_cap is not None:
        extras["hit_cap"] = int(hit_cap)
    if attack == "wordlist" and n_rules:
        extras["rules_n"] = int(n_rules)
    return extras


def _resolve_batch(batch_arg, engine_name: str, device: str, attack: str,
                   log: Log, session=None, session_tuning=None,
                   hit_cap=None, n_rules=None):
    """--batch resolution: an explicit integer is pinned; "auto"
    consults the tuning subsystem -- the resumed session's journaled
    decision first (the resumed ledger's unit geometry was built around
    it, and the journal survives machines whose tune cache doesn't),
    then the persistent cache.  Returns (batch, tuned); a tuned choice
    is re-journaled so the NEXT resume sees it too."""
    from dprf_tpu import tune as tune_mod

    if batch_arg != "auto":
        return int(batch_arg), False
    extras = _tune_extras(attack, hit_cap=hit_cap, n_rules=n_rules)
    key = tune_mod.make_key(engine_name, attack=attack, device=device,
                            **extras)
    rec = (session_tuning or {}).get(key)
    batch = None
    if isinstance(rec, dict):
        try:
            batch = int(rec["batch"])
        except (KeyError, TypeError, ValueError):
            batch = None
        if batch:
            log.info("tuned batch restored from session", batch=batch)
            tune_mod.publish_tuned_batch(engine_name, device, attack,
                                         batch)
    if not batch:
        batch = tune_mod.lookup_tuned_batch(engine_name, attack=attack,
                                            device=device,
                                            extras=extras)
        if batch:
            log.info("tuned batch loaded from cache", batch=batch,
                     cache=tune_mod.cache_path())
    if not batch:
        log.info("no tuning entry for this job; using the default "
                 "batch (run `dprf tune` to sweep one)",
                 batch=DEFAULT_BATCH, engine=engine_name)
        return DEFAULT_BATCH, False
    if session is not None:
        session.record_tuning(key, {"batch": batch})
    return batch, True


def cmd_crack(args, log: Log) -> int:
    device = _DEVICE_ALIASES[args.device]
    if getattr(args, "multihost", False):
        # One mesh across hosts (DCN): every host runs this same
        # command; the job is deterministic (same fingerprint, same
        # Dispatcher order), so all processes drive identical step
        # sequences -- SPMD -- and the replicated hit buffers mean every
        # host observes every hit.  Only process 0 owns the potfile and
        # session journal to avoid duplicate writes.
        from dprf_tpu.parallel.mesh import init_multihost
        import jax as _jax
        init_multihost(args.coordinator_address, args.num_processes,
                       args.process_id)
        log.info("multihost mesh", process=_jax.process_index(),
                 n_processes=_jax.process_count(),
                 global_devices=len(_jax.devices()))
        if _jax.process_index() != 0:
            args.no_potfile = True
            args.session = None
    if getattr(args, "increment", False):
        return _crack_increment(args, device, log)
    rc, _, _ = _crack_single(args, device, log)
    return rc


def _mask_positions(mask: str) -> list[str]:
    """Mask string -> per-position token list ('?l', '??', literals)."""
    toks, i = [], 0
    while i < len(mask):
        if mask[i] == "?":
            if i + 1 >= len(mask):
                raise ValueError(f"dangling '?' at end of mask {mask!r}")
            toks.append(mask[i:i + 2])
            i += 2
        else:
            toks.append(mask[i])
            i += 1
    return toks


def _crack_increment(args, device: str, log: Log) -> int:
    """--increment: sweep mask prefix lengths min..max (hashcat
    semantics).  Each length is an independent job sharing the potfile,
    so already-cracked targets are skipped at later lengths and the
    sweep stops as soon as everything is found."""
    import copy

    if args.attack != "mask":
        log.error("--increment applies to mask attacks only")
        return 2
    try:
        toks = _mask_positions(args.attack_arg)
    except ValueError as e:
        log.error(str(e))
        return 2
    lo = args.increment_min
    hi = args.increment_max or len(toks)
    if not 1 <= lo <= hi <= len(toks):
        log.error(f"increment range {lo}..{hi} outside mask's "
                  f"1..{len(toks)} positions")
        return 2
    any_found = False
    for length in range(lo, hi + 1):
        sub = copy.copy(args)
        sub.increment = False
        sub.attack_arg = "".join(toks[:length])
        if args.session:
            # per-length journals: lengths are distinct keyspaces with
            # distinct fingerprints, so they cannot share one ledger
            sub.session = f"{args.session}-len{length}"
        log.info("increment", length=length, mask=sub.attack_arg)
        rc, result, n_targets = _crack_single(sub, device, log)
        if rc == 2:
            return 2
        if result is not None:
            any_found |= bool(result.found)
            if len(result.found) >= n_targets:
                break      # everything cracked; skip longer lengths
    return 0 if any_found else 1


def _crack_single(args, device: str, log: Log):
    """One crack job; returns (rc, JobResult | None, n_targets)."""
    from dprf_tpu import compilecache
    from dprf_tpu.telemetry.trace import format_stations, get_tracer
    compilecache.enable(log=log)
    tracer = get_tracer()
    stations0 = tracer.station_table()    # `targets` opens in set-up
    job = _setup_job(args, device, log)
    if job is None:
        return 2, None, 0
    engine, hl, gen = job.engine, job.hl, job.gen
    session, restored_hits = job.session, job.restored_hits
    dispatcher, spec = job.dispatcher, job.spec
    if session is not None:
        # flight-recorder stream next to the journal (attached BEFORE
        # the worker builds, so warmup-era spans land in the file too)
        tracer.attach_file(session.trace_path)

    batch, _ = _resolve_batch(args.batch, args.engine, device,
                              args.attack, log, session=session,
                              session_tuning=job.tuning,
                              hit_cap=args.hit_cap,
                              n_rules=getattr(gen, "n_rules", None))
    _log_device(device, log)
    worker = _select_worker(args.engine, device, args.attack, gen,
                            hl.targets, batch, args.hit_cap,
                            engine, args.devices, log)
    if job.order is not None:
        # rank-ordered dispatch: unit spans are ranks; the wrapper
        # decodes each into contiguous index runs before the (device
        # or CPU) worker's unchanged index-space sweep
        from dprf_tpu.runtime.worker import OrderedWorker
        worker = OrderedWorker(worker, job.order)
    # Overlapped warmup: start the step compile now on a background
    # thread so it runs while the potfile preloads, the session
    # restores, and the coordinator takes its first leases; the
    # coordinator joins it at the first dispatch (cold start ~=
    # max(compile, setup), not their sum).  No-op for factory-warmed
    # (Pallas) workers and for the CPU oracle path.
    warmup_async = getattr(worker, "warmup_async", None)
    if warmup_async is not None:
        warmup_async()

    potfile = None if args.no_potfile else Potfile(args.potfile)

    def progress(done, total, nfound, rate):
        eta = (total - done) / rate if rate > 0 else float("inf")
        log.info("progress", pct=f"{100.0 * done / total:.2f}%",
                 found=f"{nfound}/{len(hl.targets)}",
                 rate=f"{rate:,.0f}/s",
                 eta=(f"{eta:,.0f}s" if eta != float("inf") else "?"))

    coord = Coordinator(spec, hl.targets, dispatcher, worker,
                        session=session, potfile=potfile,
                        progress_cb=None if args.quiet else progress,
                        # device jobs verify every hit against the CPU
                        # oracle before the potfile (mirrors the
                        # distributed CoordinatorState verifier); the CPU
                        # worker IS the oracle, so no double hashing there
                        oracle=engine if device != "cpu" else None)
    coord.preload_found()
    coord.restore_hits(restored_hits)
    if coord.found:
        log.info("pre-cracked targets", count=len(coord.found))

    snap = None
    devstats_poller = None
    if session is not None:
        from dprf_tpu.telemetry import (DEFAULT as _registry,
                                        TelemetrySnapshotter,
                                        snapshot_interval)
        snap = TelemetrySnapshotter(session.telemetry_path, _registry,
                                    interval=snapshot_interval()).start()
        # HBM gauges ride the same snapshots (ISSUE 13); no-op
        # ticks on backends without memory stats
        from dprf_tpu.telemetry.devstats import DevstatsPoller
        devstats_poller = DevstatsPoller(registry=_registry).start()
    try:
        if args.profile:
            # jax.profiler capture of every step the coordinator
            # drives, through the single-flight ProfileCapture (a
            # DPRF_JAX_PROFILE env trace on the same process degrades
            # to a logged no-op instead of a crash); analyze with
            # `dprf profile DIR`
            from dprf_tpu.telemetry import profiler as profiler_mod
            with profiler_mod.get_profiler().session(
                    args.profile, owner="cli", log=log):
                result = coord.run()
            log.info("profile written (analyze with `dprf profile`)",
                     dir=args.profile)
        else:
            result = coord.run()
    finally:
        if devstats_poller is not None:
            devstats_poller.stop()
        if snap is not None:
            snap.stop()
            log.info("telemetry snapshots written",
                     path=session.telemetry_path)
        if session is not None:
            tracer.detach_file()
            log.info("trace spans written (export with `dprf trace "
                     "export`)", path=session.trace_path)

    _print_results(result.found, hl.targets)
    if result.parked:
        log.warn("job finished with POISONED units parked; their "
                 "ranges were NOT swept (see "
                 "dprf_units_poisoned_total)", parked=result.parked)
    _log_ran(worker, log, host=format_stations(tracer.station_table(),
                                               since=stations0))
    log.info("job finished",
             found=f"{len(result.found)}/{len(hl.targets)}",
             tested=result.tested, elapsed=f"{result.elapsed:.2f}s",
             rate=f"{result.rate:,.0f}/s",
             exhausted=result.exhausted)
    return (0 if result.found else 1), result, len(hl.targets)


# ---------------------------------------------------------------------------
# serve / worker (distributed)

def _parse_hostport(s: str) -> tuple:
    host, _, port = s.rpartition(":")
    return host or "127.0.0.1", int(port)


def _parse_owner_quotas(specs) -> dict:
    """--owner-quota OWNER=N (repeatable) -> {owner: int} for the
    scheduler's per-owner aggregate caps."""
    out: dict = {}
    for s in specs or ():
        owner, _, n = s.partition("=")
        if not owner or not n:
            raise ValueError(f"--owner-quota wants OWNER=N, got {s!r}")
        out[owner] = max(0, int(n))
    return out


def cmd_serve(args, log: Log) -> int:
    from dprf_tpu import compilecache
    from dprf_tpu.runtime.rpc import CoordinatorServer, CoordinatorState

    compilecache.enable(log=log)
    device = _DEVICE_ALIASES[args.device]
    job_setup = _setup_job(args, device, log,
                           lease_timeout=args.lease_timeout)
    if job_setup is None:
        return 2
    engine, hl, gen = job_setup.engine, job_setup.hl, job_setup.gen
    session, restored_hits = job_setup.session, job_setup.restored_hits
    dispatcher, spec = job_setup.dispatcher, job_setup.spec
    unit_size, max_len = job_setup.unit_size, job_setup.max_len

    potfile = None if args.no_potfile else Potfile(args.potfile)

    batch, _ = _resolve_batch(args.batch, engine.name, device,
                              args.attack, log, session=session,
                              session_tuning=job_setup.tuning,
                              hit_cap=args.hit_cap,
                              n_rules=getattr(gen, "n_rules", None))

    # Everything a worker needs to rebuild the identical job.  max_len
    # is shipped so worker-side keyspace/packing can't drift from ours.
    # batch ships RESOLVED (an int): the coordinator's tuning decision
    # applies fleet-wide unless a worker overrides with --batch.
    job = {
        "engine": engine.name,
        "attack": args.attack,
        "attack_arg": args.attack_arg,
        "customs": {str(i): v.hex() for i, v in _customs(args).items()},
        "rules": args.rules,
        "markov": args.markov,
        "max_len": max_len,
        "targets": [t.raw for t in hl.targets],
        "keyspace": gen.keyspace,
        "unit_size": unit_size,
        "batch": batch,
        "hit_cap": args.hit_cap,
        # candidate order + the resolved bijection split (pinned here
        # so workers can never fork the rank<->index map on divergent
        # DPRF_ORDER_* environments)
        "order": job_setup.order.kind if job_setup.order else "index",
        "order_split": job_setup.order.split if job_setup.order else 0,
        # sharding request: workers build the job's worker over N of
        # their local chips through the unified sharded runtime (their
        # own --devices flag overrides)
        "devices": max(1, getattr(args, "devices", 1) or 1),
        "fingerprint": spec.fingerprint,
    }

    def verify_hit(ti, plain):
        # Re-hash with the coordinator's CPU oracle before accepting: a
        # worker with a divergent device path must not poison the
        # potfile or halt the search for a target it did not crack.
        if engine.verify(plain, hl.targets[ti]):
            return True
        log.warn("rejected unverifiable hit", target=hl.targets[ti].raw[:32])
        return False

    from dprf_tpu.telemetry.trace import get_tracer
    token = args.token or envreg.get_str("DPRF_TOKEN") or None
    state = CoordinatorState(job, dispatcher, len(hl.targets),
                             verifier=verify_hit, token=token,
                             owner_quotas=_parse_owner_quotas(
                                 getattr(args, "owner_quota", None)))
    tracer = get_tracer()
    if token:
        log.info("worker authentication enabled")
    if session is not None:
        # default_job in the header lets resume fold the (now always
        # tagged) default-job lines back into the flat fields
        session.open(spec.as_dict(),
                     default_job=state.default_job_id)
        # stream the fleet's lifecycle spans (incl. the ones remote
        # workers ship back) next to the journal for dprf trace export
        tracer.attach_file(session.trace_path)
        # alert transitions land beside them (<session>.alerts.jsonl)
        state.alerts.attach_file(session.alerts_path)

    def on_progress(done, total, nfound):
        # done/total/nfound aggregate over EVERY non-cancelled job
        if not args.quiet:
            log.info("progress", pct=f"{100.0 * done / total:.2f}%",
                     found=nfound)

    # -- multi-tenant hooks (jobs/scheduler.py; all fire under
    # state.lock, so the journal writes below serialize).  ONE hit
    # path for every job including the default (ISSUE 10: the
    # untagged dual-write path is gone -- new journals tag every
    # units/hit line with its job id) -------------------------------

    def on_job_hit(job, ti, cand, plain):
        if job.job_id == state.default_job_id:
            raw = hl.targets[ti].raw
        else:
            raws = job.spec.get("targets") or []
            raw = raws[ti] if 0 <= ti < len(raws) else str(ti)
        log.info("cracked", job=job.job_id, target=str(raw)[:32],
                 lane=cand)
        if potfile is not None:
            potfile.add(raw, plain)
        if session is not None:
            session.record_hit(ti, cand, plain, job=job.job_id)

    def on_job_progress(jid, intervals, digest=None):
        if session is not None:
            session.record_units(intervals, job=jid, digest=digest)

    def on_job_event(kind, job):
        if session is None:
            return
        if kind == "submit":
            session.record_job(job.job_id, job.spec, owner=job.owner,
                               priority=job.priority, quota=job.quota,
                               rate=job.rate)
        elif kind == "gc":
            # age-based reap (DPRF_JOB_TTL_S): restore must not
            # resurrect the job
            session.record_job_gc(job.job_id)
        else:
            session.record_job_state(job.job_id, job.state)

    def on_worker_health(tr):
        # fleet health transitions -> {"type": "worker_health"}
        # journal records (fired by health_tick under state.lock, so
        # these writes serialize with the hit/progress writers)
        log.info("worker health", worker=tr.get("worker"),
                 frm=tr.get("from"), to=tr.get("to"))
        if session is not None:
            session.record_worker_health(
                tr.get("worker"), tr.get("from"), tr.get("to"),
                ts=tr.get("ts"), age_s=tr.get("age_s"))

    def on_profile(worker, summary):
        # kernel-profile summaries -> {"type": "profile"} journal
        # records (fired under state.lock by op_profile_push, so the
        # writes serialize with the other journal writers); `dprf
        # report` renders them post-mortem
        if session is not None:
            session.record_profile(worker, summary)

    state.on_progress = on_progress
    state.on_job_hit = on_job_hit
    state.on_job_progress = on_job_progress
    state.on_job_event = on_job_event
    state.on_worker_health = on_worker_health
    state.on_profile = on_profile
    from dprf_tpu.runtime.coordinator import preload_potfile
    # restored hits go through the default job's hit BUFFER (not just
    # the found dict) so op_hits_pull clients see them too
    state.seed_found(restored_hits)
    # the server is not up yet, but taking the lock costs nothing and
    # keeps the guarded-by invariant unconditional (dprf check locks)
    with state.lock:
        preload_potfile(state.found, hl.targets, potfile)
        preloaded = len(state.found)
    state.refresh_found_gauge()
    if preloaded:
        log.info("pre-cracked targets", count=preloaded)
    if job_setup.restored_jobs:
        # scheduler-submitted tenants from the journal: rebuild each
        # job's ledger/hits/state so the restart loses no coverage
        from dprf_tpu.jobs.build import restore_jobs
        restore_jobs(state, job_setup.restored_jobs, log=log,
                     lease_timeout=args.lease_timeout)

    host, port = _parse_hostport(args.bind)
    server = CoordinatorServer(state, host, port)
    log.info("serving job", bind=f"{server.address[0]}:{server.address[1]}",
             fingerprint=spec.fingerprint, keyspace=gen.keyspace)
    log.info("metrics endpoint",
             url=f"http://{server.address[0]}:{server.address[1]}/metrics")
    snap = None
    if session is not None:
        from dprf_tpu.telemetry import (TelemetrySnapshotter,
                                        snapshot_interval)
        snap = TelemetrySnapshotter(session.telemetry_path,
                                    state.registry,
                                    interval=snapshot_interval()).start()
    # the fleet health plane's evaluation loop (ISSUE 10): worker
    # state machine + stragglers + per-job SLOs + alert rules, every
    # DPRF_ALERT_EVAL_S seconds
    from dprf_tpu.telemetry.health import HealthMonitor
    monitor = HealthMonitor(state.health_tick).start()
    # No device-memory polling here: the coordinator runs no device
    # work and must never initialise a JAX backend -- a chip belongs
    # to one process, and that process is the worker.  Workers ship
    # their HBM totals on the heartbeat (rpc.worker_loop).
    try:
        server.serve_until_done()
    finally:
        monitor.stop()
        if snap is not None:
            snap.stop()
            log.info("telemetry snapshots written",
                     path=session.telemetry_path)
        if session is not None:
            tracer.detach_file()
            log.info("trace spans written (export with `dprf trace "
                     "export`)", path=session.trace_path)
    # one snapshot under the lock: the server just shut down, but a
    # worker connection thread may still be unwinding its last op
    with state.lock:
        found = dict(state.found)
        summaries = state.scheduler.summaries()
        per_job = [(j.job_id, j.dispatcher.completed_intervals(),
                    j.dispatcher.parked_count(),
                    j.dispatcher.parked_indices(),
                    j.dispatcher.coverage_digest())
                   for j in state.scheduler.jobs()]
    if session is not None:
        for jid, intervals, _, _, digest in per_job:
            session.snapshot(intervals, job=jid, digest=digest)
        session.close()
    _print_results(found, hl.targets)
    for jid, _, parked, parked_idx, _ in per_job:
        if parked:
            log.warn("job finished with POISONED units parked; their "
                     "ranges were NOT swept", job=jid, parked=parked,
                     indices=parked_idx)
    if len(summaries) > 1:
        # tenants beyond the CLI-invoked default job: their hits
        # streamed via op_hits_pull, but leave a closing audit line
        for s in summaries:
            if s["id"] != state.default_job_id:
                log.info("tenant job finished", job=s["id"],
                         owner=s["owner"], state=s["state"],
                         found=f"{s['found']}/{s['targets']}",
                         covered=f"{s['done']}/{s['total']}")
    log.info("job finished",
             found=f"{len(found)}/{len(hl.targets)}")
    return 0 if found else 1


def cmd_worker(args, log: Log) -> int:
    import os
    import socket as _socket

    from dprf_tpu import compilecache
    from dprf_tpu.runtime.rpc import CoordinatorClient, worker_loop

    compilecache.enable(log=log)
    device = _DEVICE_ALIASES[args.device]
    host, port = _parse_hostport(args.connect)
    token = args.token or envreg.get_str("DPRF_TOKEN") or None
    client = CoordinatorClient(host, port, token=token)
    hello = client.hello()
    job = hello["job"]
    default_jid = hello.get("job_id")
    log.info("job received", engine=job["engine"], attack=job["attack"],
             keyspace=job["keyspace"], targets=len(job["targets"]),
             job=default_jid)
    _log_device(device, log)

    def build_worker(spec: dict, jid):
        """Rebuild one job's worker from its wire spec, fingerprint-
        checked: a wordlist or rules file that differs in CONTENT (not
        just size) on this host would silently leave coverage holes --
        the unit ledger marks ranges done that this worker decoded to
        different candidates."""
        engine = get_engine(spec["engine"], device="cpu")
        targets = [engine.parse_target(raw) for raw in spec["targets"]]
        customs = {int(i): bytes.fromhex(v)
                   for i, v in spec.get("customs", {}).items()}
        gen, attack_desc, _ = _build_gen(
            spec["attack"], spec["attack_arg"], customs,
            spec.get("rules"), spec.get("max_len"), engine, device,
            log, markov=spec.get("markov"))
        ours = job_fingerprint(engine.name, attack_desc, gen.keyspace,
                               [t.digest for t in targets])
        if ours != spec["fingerprint"]:
            raise RpcError(
                f"local job {jid} disagrees with coordinator "
                "(different wordlist/rules file content on this "
                f"host?): ours={ours} theirs={spec['fingerprint']}")
        # the worker's own --devices wins (including an explicit 1 --
        # pin to a single chip); otherwise honor the job's sharding
        # request (serve/jobs submit carry "devices")
        n_dev = (args.devices if args.devices
                 else int(spec.get("devices") or 1))
        w = _select_worker(spec["engine"], device, spec["attack"], gen,
                           targets, args.batch or spec["batch"],
                           spec["hit_cap"], engine, n_dev, log)
        if (spec.get("order") or "index") != "index":
            # rank-ordered job: rebuild the EXACT bijection from the
            # wire spec (kind + pinned split -- local DPRF_ORDER_*
            # knobs must not fork the map) and decode leased rank
            # spans before the index-space sweep
            from dprf_tpu.generators.order import build_order
            from dprf_tpu.runtime.worker import OrderedWorker
            order = build_order(spec["order"], gen,
                                split=int(spec.get("order_split") or 0)
                                or None)
            w = OrderedWorker(w, order)
        # overlapped warmup: the step compile runs while leases
        # round-trip to the coordinator; worker_loop joins it before
        # the first dispatch
        warmup_async = getattr(w, "warmup_async", None)
        if warmup_async is not None:
            warmup_async()
        return w

    try:
        worker = build_worker(job, default_jid)
    except RpcError as e:
        log.error(str(e))
        return 2

    # multi-tenant fleets (jobs/scheduler.py): lease entries name
    # their job; an unfamiliar id fetches the spec over op_job_status,
    # rebuilds + fingerprint-checks it, and caches the worker.  A job
    # this host CANNOT build (wordlist missing here, divergent file
    # content) caches as None: worker_loop releases its leases and
    # keeps serving every other tenant -- one bad submission must not
    # kill the fleet.
    workers = {default_jid: worker} if default_jid is not None else {}

    def worker_for(jid):
        if jid in workers:
            return workers[jid]
        try:
            resp = client.call("job_status", job=jid)
            spec = resp["spec"]
            log.info("job received", engine=spec["engine"],
                     attack=spec["attack"], keyspace=spec["keyspace"],
                     job=jid)
            w = build_worker(spec, jid)
        except (RpcError, OSError, ValueError, KeyError) as e:
            log.error("job cannot run on this host; refusing its "
                      "leases", job=jid, error=str(e))
            w = None
        workers[jid] = w
        return w

    worker_id = args.id or f"{_socket.gethostname()}:{os.getpid()}"
    # worker_loop exits cleanly only on an explicit stop signal; any
    # bare connection drop (coordinator crash) or quarantine raises and
    # surfaces through main()'s error handler as a nonzero exit.
    done = worker_loop(client, worker, worker_id, log=log,
                       depth=args.pipeline_depth,
                       worker_for=worker_for)
    for jid, w in workers.items():
        if w is not None:
            _log_ran(w, log, job=jid)
    log.info("worker done", units=done)
    client.close()
    return 0


# ---------------------------------------------------------------------------

def cmd_bench(args, log: Log) -> int:
    import contextlib
    import json

    from dprf_tpu import compilecache
    from dprf_tpu.bench import run_bench, run_config
    from dprf_tpu.perfreport import compare as compare_mod

    baseline_dir = args.baseline_dir or compare_mod.repo_root()
    if args.gate_dry:
        # CI mode: audit the committed trajectory, measure nothing.
        # --ttfh redirects the audit at the TTFH_r*.json records
        verdict = compare_mod.gate_dry(
            baseline_dir, window=args.gate_window,
            pattern=(compare_mod.TTFH_PATTERN if args.ttfh
                     else "BENCH_r*.json"))
        print(json.dumps({"gate": verdict}))
        if verdict["verdict"] == "regression":
            log.error("bench gate: REGRESSION in the committed "
                      "trajectory", ratio=verdict["ratio"],
                      tolerance=verdict["tolerance"])
            return 1
        log.info("bench gate", verdict=verdict["verdict"],
                 window=verdict["window"])
        return 0
    compilecache.enable(log=log)
    _log_device(_DEVICE_ALIASES[args.device], log)
    ctx = contextlib.nullcontext()
    if args.profile:
        # kernel profile of the measurement window, through the
        # single-flight capture owner; the analyzed top-ops +
        # fractions fold into the result JSON below
        from dprf_tpu.telemetry import profiler as profiler_mod
        ctx = profiler_mod.get_profiler().session(
            args.profile, owner="bench", log=log)
    with ctx:
        if args.ttfh:
            from dprf_tpu.bench import run_ttfh
            res = run_ttfh(engine=args.engine, mask=args.mask,
                           plants=args.plants, log=log)
        elif args.targets_sweep:
            from dprf_tpu.bench import run_targets_sweep
            sizes = [int(s) for s in
                     args.targets_sizes.split(",") if s.strip()]
            res = run_targets_sweep(engine=args.engine, mask=args.mask,
                                    sizes=sizes, batch=args.batch,
                                    seconds=args.seconds, log=log)
        elif args.devices > 1:
            from dprf_tpu.bench import run_scaling
            res = run_scaling(engine=args.engine, mask=args.mask,
                              n_devices=args.devices,
                              batch_per_device=args.batch,
                              seconds=args.seconds, inner=args.inner,
                              impl=args.impl, ablate=args.ablate,
                              log=log)
        elif args.config is not None:
            res = run_config(args.config,
                             device=_DEVICE_ALIASES[args.device],
                             seconds=args.seconds, batch=args.batch,
                             bcrypt_cost=args.bcrypt_cost,
                             unit_strides=args.unit_strides, log=log)
        else:
            res = run_bench(engine=args.engine,
                            device=_DEVICE_ALIASES[args.device],
                            mask=args.mask, batch=args.batch,
                            seconds=args.seconds, impl=args.impl, log=log)
    if args.profile:
        # fold the kernel view into the BENCH record: top ops,
        # class fractions, phase split, and the measured-vs-analyzed
        # cost divergence (the bench knows its candidate count).
        # --config/--devices results carry the engine + "tested"
        # count instead of the single-run batch fields
        cands = res.get("batches", 0) * res.get("batch", 0) \
            * max(1, res.get("inner", 1)) or res.get("tested", 0)
        summary = profiler_mod.analyze_trace(
            args.profile, engine=res.get("engine") or args.engine,
            candidates=cands or None)
        res["profile"] = {
            "top_ops": (summary.get("top_ops") or [])[:10],
            "fractions": summary.get("fractions"),
            "phases": summary.get("phases"),
            "device_s": summary.get("device_s"),
            "divergence": summary.get("divergence"),
            "error": summary.get("error"),
        }
    if args.gate:
        # regression sentinel: the verdict rides the result JSON (CI
        # parses it) and a regression exits non-zero.  Scaling mode
        # gates against the SCALING_r*.json efficiency trajectory, so
        # a multichip regression alarms exactly like a throughput one.
        if args.ttfh:
            pattern = compare_mod.TTFH_PATTERN
        elif args.targets_sweep:
            pattern = compare_mod.TARGETS_PATTERN
        elif args.devices > 1:
            pattern = compare_mod.SCALING_PATTERN
        else:
            pattern = "BENCH_r*.json"
        res["gate"] = compare_mod.gate_repo(res, baseline_dir,
                                            window=args.gate_window,
                                            pattern=pattern)
    print(json.dumps(res))
    if args.gate and res["gate"]["verdict"] == "regression":
        log.error("bench gate: REGRESSION vs the baseline window",
                  ratio=res["gate"]["ratio"],
                  tolerance=res["gate"]["tolerance"])
        return 1
    return 0


def _tune_generator(attack: str, args):
    """Generator shaping a tuning probe.  wordlist/combinator reuse
    bench's synthetic in-memory word source (config 3's trick) so the
    sweep measures the device pipeline, not disk I/O; the source is
    deterministic, so cache records stay comparable across runs."""
    if attack == "mask":
        return MaskGenerator(args.mask)
    from dprf_tpu.bench import _synthetic_words
    if attack == "wordlist":
        from dprf_tpu.generators.wordlist import WordlistRulesGenerator
        from dprf_tpu.rules.parser import load_rules
        return WordlistRulesGenerator(_synthetic_words(args.words),
                                      load_rules(args.rules),
                                      max_len=24)
    from dprf_tpu.generators.combinator import CombinatorGenerator
    words = _synthetic_words(args.words)
    return CombinatorGenerator(words, words, max_len=24)


#: superstep `inner` fusion-window rungs (dprf tune --rungs inner) --
#: unordered knob values, so sweep_values probes them all
_INNER_RUNGS = (4, 8, 16, 32, 64, 128, 256)
#: Pallas kernel tile-size rungs (sublanes per tile; tile = sub * 128)
_SUB_RUNGS = (8, 16, 32, 64, 128)


def _tune_one(engine_name: str, args, device: str, log: Log) -> dict:
    """Sweep one engine's rungs and record the winner; returns the
    result JSON dict.  ``--rungs batch`` climbs the geometric batch
    ladder; ``--rungs inner`` sweeps the multi-batch superstep fusion
    window (workers' SUPER_CAP); ``--rungs sub`` sweeps the Pallas
    kernel tile size.  Raises ValueError for engines this invocation
    cannot tune (salted targets without --hashfile, every rung
    failing) -- `--all` reports those as skipped."""
    from dprf_tpu import tune as tune_mod
    from dprf_tpu.tune import (geometric_ladder, record_tuned_batch,
                               record_tuned_value, sweep, sweep_values)

    attack = getattr(args, "attack", "mask")
    rungs = getattr(args, "rungs", "batch")
    oracle = get_engine(engine_name, device="cpu")
    gen = _tune_generator(attack, args)
    if args.hashfile:
        hl = _load_targets(oracle, args.hashfile, log)
        if hl is None:
            raise ValueError("no valid targets in hashfile")
        targets = hl.targets
    else:
        try:
            # unmatchable digest (bench's trick): tuning needs load,
            # not cracks
            targets = [oracle.parse_target("ff" * oracle.digest_size)]
        except Exception:
            raise ValueError(
                "targets need salts/params; pass --hashfile with real "
                "target lines to tune against") from None

    extras = _tune_extras(attack, hit_cap=args.hit_cap,
                          n_rules=getattr(gen, "n_rules", None))

    def make_worker(batch: int):
        if device == "cpu":
            return CpuWorker(oracle, gen, targets, chunk=batch)
        return _select_worker(engine_name, device, attack, gen, targets,
                              batch, args.hit_cap, oracle, 1, log)

    knob = None
    if rungs == "batch":
        ladder = geometric_ladder(args.min_batch, args.max_batch,
                                  args.ladder_factor)
        log.info("tuning", engine=engine_name, device=device,
                 attack=attack,
                 ladder=",".join(str(b) for b in ladder))
        result = sweep(make_worker, gen.keyspace, ladder,
                       probe_seconds=args.seconds,
                       compile_budget_s=args.compile_budget, log=log)
        path = record_tuned_batch(engine_name, attack, device, result,
                                  extras=extras)
        key = tune_mod.make_key(engine_name, attack=attack,
                                device=device, **extras)
    else:
        knob = rungs
        # knob sweeps run at the already-tuned (or default) batch, so
        # the winner composes with a prior `--rungs batch` record;
        # --max-batch still caps it (CI smokes keep probe units small)
        batch = min(args.max_batch,
                    tune_mod.lookup_tuned_batch(
                        engine_name, attack=attack, device=device,
                        extras=extras)
                    or DEFAULT_BATCH)
        if rungs == "inner":
            values = [v for v in _INNER_RUNGS]

            def mk_inner(v: int):
                w = make_worker(batch)
                # SUPER_CAP bounds _super_inner's window; the instance
                # override beats the class default / env knob for this
                # probe only
                w.SUPER_CAP = int(v)
                return w

            log.info("tuning", engine=engine_name, device=device,
                     attack=attack, knob="inner", batch=batch,
                     values=",".join(str(v) for v in values))
            result = sweep_values(
                mk_inner, values, gen.keyspace,
                probe_seconds=args.seconds,
                compile_budget_s=args.compile_budget,
                unit_strides=max(values), log=log, label="inner")
        else:                    # rungs == "sub"
            if attack != "mask" or device == "cpu":
                raise ValueError("--rungs sub tunes the Pallas mask "
                                 "kernel tile; use --attack mask with "
                                 "a device backend")
            from dprf_tpu.ops.pallas_mask import pallas_mode
            mode = pallas_mode()
            if mode is None:
                raise ValueError("Pallas kernels unavailable on this "
                                 "backend (see DPRF_PALLAS)")
            try:
                dev_engine = get_engine(engine_name, device="jax")
            except KeyError:
                raise ValueError(
                    f"no jax engine named {engine_name!r}") from None
            from dprf_tpu.runtime.worker import PallasMaskWorker
            values = [v for v in _SUB_RUNGS if v * 128 <= batch]

            def mk_sub(v: int):
                w = PallasMaskWorker(dev_engine, gen, targets,
                                     batch=batch,
                                     hit_capacity=args.hit_cap,
                                     oracle=oracle, sub=v, **mode)
                w.warmup()
                return w

            log.info("tuning", engine=engine_name, device=device,
                     attack=attack, knob="sub", batch=batch,
                     values=",".join(str(v) for v in values))
            result = sweep_values(
                mk_sub, values, gen.keyspace,
                probe_seconds=args.seconds,
                compile_budget_s=args.compile_budget, log=log,
                label="sub")
        path = record_tuned_value(engine_name, knob, attack, device,
                                  result, extras=extras)
        key = tune_mod.make_key(engine_name, attack=attack,
                                device=device, knob=knob, **extras)
    log.info("tuned", engine=engine_name,
             **{knob or "batch": result.batch},
             rate=f"{result.rate_hs:,.0f}/s", cache=path)
    out = {
        "engine": engine_name,
        "device": device,
        "attack": attack,
        "env": tune_mod.env_fingerprint(engine_name, device),
        "key": key,
        "batch": result.batch,
        "rate_hs": result.rate_hs,
        "compile_s": round(result.compile_s, 3),
        "swept": [p.as_dict() for p in result.swept],
        "cache": path,
    }
    if knob:
        out["knob"] = knob
        out["value"] = result.batch
    return out


def cmd_tune(args, log: Log) -> int:
    """Sweep the batch ladder through the REAL worker path and record
    the winner in the persistent tuning cache, where `--batch auto`
    jobs and bench warm-start from it.  ``--all`` sweeps every
    registered device engine (the fleet-image pre-population pass);
    analyzed program costs land in the program registry as a side
    effect of each rung (telemetry/programs.py)."""
    import json as _json

    from dprf_tpu import compilecache

    if not args.all and not args.engine:
        log.error("pass --engine NAME (or --all to sweep every "
                  "registered engine)")
        return 2
    device = _DEVICE_ALIASES[args.device]
    if args.tune_dir:
        os.environ["DPRF_TUNE_DIR"] = args.tune_dir
    compilecache.enable(log=log)
    if not args.all:
        try:
            print(_json.dumps(_tune_one(args.engine, args, device, log)))
        except ValueError as e:
            log.error(str(e), engine=args.engine)
            return 2
        return 0
    # --all: one sweep per registered engine; a skipped or failed
    # engine is a report line, never the end of the fleet bake
    results, skipped = [], []
    names = sorted(engine_names("jax" if device == "jax" else "cpu"))
    for name in names:
        try:
            results.append(_tune_one(name, args, device, log))
        except Exception as e:   # noqa: BLE001 -- per-engine isolation
            log.warn("tune skipped", engine=name, error=str(e))
            skipped.append({"engine": name, "error": str(e)})
    from dprf_tpu.telemetry import programs as programs_mod
    programs_mod.analyze_pending()
    print(_json.dumps({
        "tuned": len(results),
        "skipped": len(skipped),
        "engines": len(names),
        "programs_analyzed": len(programs_mod.get_programs().snapshot()),
        "results": results,
        "skips": skipped,
    }))
    return 0 if results else 1


def cmd_prewarm(args, log: Log) -> int:
    """Populate the persistent compile cache ahead of time: iterate
    (engine, attack, batch) specs -- tune-cache-seeded and/or an
    explicit --engines/--attacks list -- build each worker's step
    through the real factory path, and lower+compile it WITHOUT
    dispatching.  Bake the cache dir into a fleet image and every
    worker's warmup becomes a cache load."""
    import json as _json

    from dprf_tpu import compilecache, engine_names
    from dprf_tpu.compilecache.prewarm import (RESULT_MARKER,
                                               PrewarmSpec,
                                               explicit_specs,
                                               render_table,
                                               run_prewarm,
                                               tune_seeded_specs)

    d = compilecache.enable(log=log)
    if d is None:
        log.error("persistent compile cache unavailable (disabled or "
                  "unwritable dir); nothing to prewarm into")
        return 2
    if args.spec_json:
        # child-process mode (prewarm --jobs fan-out): compile exactly
        # these specs, report one marker line each
        from dprf_tpu.compilecache.prewarm import prewarm_one
        specs = [PrewarmSpec.from_dict(s)
                 for s in _json.loads(args.spec_json)]
        for spec in specs:
            res = prewarm_one(spec, log=log)
            print(RESULT_MARKER + _json.dumps(res.as_dict()), flush=True)
        return 0
    attacks = [a.strip() for a in args.attacks.split(",") if a.strip()]
    for a in attacks:
        if a not in ("mask", "wordlist", "combinator", "hybrid-wm",
                     "hybrid-mw"):
            log.error(f"unknown attack shape {a!r} (mask, wordlist, "
                      "combinator, hybrid-wm, hybrid-mw)")
            return 2
    if args.engines:
        engines = (sorted(engine_names("jax"))
                   if args.engines == "all"
                   else [e.strip() for e in args.engines.split(",")
                         if e.strip()])
        specs = explicit_specs(engines, attacks, hit_cap=args.hit_cap,
                               mask=args.mask, rules=args.rules,
                               wordlist=args.wordlist,
                               combinator=args.combinator,
                               batch=args.batch,
                               devices=args.devices)
    else:
        specs = tune_seeded_specs("jax", hit_cap=args.hit_cap,
                                  mask=args.mask, rules=args.rules,
                                  wordlist=args.wordlist,
                                  devices=args.devices, log=log)
        if not specs:
            log.error("tuning cache has no device entries to seed "
                      "from; pass --engines (e.g. --engines md5,ntlm "
                      "or --engines all)")
            return 2
    log.info("prewarming", specs=len(specs), jobs=args.jobs, cache=d)
    results = run_prewarm(specs, jobs=args.jobs, log=log)
    if not args.quiet:
        print(render_table(results), file=sys.stderr)
    skipped = [r for r in results if r.skipped]
    ok = [r for r in results if not r.error and not r.skipped]
    print(_json.dumps({
        "cache_dir": d,
        "specs": len(results),
        "compiled": len(ok),
        "hits": sum(1 for r in ok if r.cache == "hit"),
        "misses": sum(1 for r in ok if r.cache == "miss"),
        "skipped": len(skipped),
        "errors": len(results) - len(ok) - len(skipped),
        "results": [r.as_dict() for r in results],
    }))
    return 0 if ok or skipped or not results else 1


def cmd_retry_parked(args, log: Log) -> int:
    """Admin client for rpc.op_retry_parked: requeue a live job's
    poisoned/parked units with a fresh retry budget."""
    import json as _json

    from dprf_tpu.runtime.rpc import CoordinatorClient

    host, port = _parse_hostport(args.connect)
    token = args.token or envreg.get_str("DPRF_TOKEN") or None
    client = CoordinatorClient(host, port, timeout=args.timeout,
                               token=token)
    try:
        client.hello()             # answers the auth challenge if any
        resp = client.call("retry_parked")
    finally:
        client.close()
    retried = int(resp.get("retried", 0))
    log.info("parked units requeued", retried=retried)
    print(_json.dumps({"retried": retried}))
    return 0


def cmd_top(args, log: Log) -> int:
    """Live fleet view (`dprf top --connect host:port`): renders the
    coordinator's flight recorder + lease table every --interval
    seconds -- per-worker state, current unit, lease deadline
    countdown, and recent lifecycle spans."""
    import time as _time

    from dprf_tpu.runtime.rpc import CoordinatorClient
    from dprf_tpu.telemetry.trace import render_top

    host, port = _parse_hostport(args.connect)
    token = args.token or envreg.get_str("DPRF_TOKEN") or None
    client = CoordinatorClient(host, port, timeout=args.timeout,
                               token=token)
    try:
        if token:
            client.hello()     # answer the auth challenge first
        prev = None
        frames = 0
        cursor = None
        # --follow keeps a client-side span buffer and asks only for
        # spans past the cursor; a resync (cursor fell off the
        # coordinator's ring) replaces the buffer with the full tail
        from collections import deque
        buf: deque = deque(maxlen=max(args.spans, 64))
        while True:
            if args.follow:
                resp = client.call("trace_tail", n=args.spans,
                                   since=cursor, trace=args.trace)
                if resp.get("resync") or "cursor" not in resp:
                    # resync, or a pre-cursor coordinator that ignored
                    # `since` and sent the full tail: REPLACE the
                    # buffer (appending would duplicate every span)
                    buf.clear()
                buf.extend(resp.get("spans") or [])
                cursor = resp.get("cursor") or cursor
                resp = dict(resp, spans=list(buf))
            else:
                resp = client.call("trace_tail", n=args.spans,
                                   trace=args.trace)
            text = render_top(resp, prev)
            if not args.no_clear and sys.stdout.isatty():
                sys.stdout.write("\x1b[H\x1b[2J")
            print(text)
            sys.stdout.flush()
            prev = (_time.monotonic(), resp.get("status") or {})
            frames += 1
            if args.iterations and frames >= args.iterations:
                break
            if (resp.get("status") or {}).get("stop"):
                log.info("job finished")
                break
            _time.sleep(max(0.1, args.interval))
    except KeyboardInterrupt:
        pass
    finally:
        client.close()
    return 0


def _jobs_client(args, log: Log):
    """Authenticated client for the jobs/trace admin commands."""
    from dprf_tpu.runtime.rpc import CoordinatorClient

    host, port = _parse_hostport(args.connect)
    token = args.token or envreg.get_str("DPRF_TOKEN") or None
    client = CoordinatorClient(host, port, timeout=args.timeout,
                               token=token)
    if token:
        client.hello()             # answer the auth challenge first
    return client


def cmd_jobs(args, log: Log) -> int:
    """`dprf jobs submit/list/status/cancel/pause/resume/hits`: the
    multi-tenant admin surface over a running coordinator's job
    scheduler (rpc.op_job_* / op_hits_pull).  One helper per
    subcommand: each RPC op's response lives in its own scope, so the
    protocol checker's per-op key dataflow stays exact."""
    client = _jobs_client(args, log)
    try:
        if args.jobs_cmd == "submit":
            return _jobs_submit(client, args, log)
        if args.jobs_cmd == "list":
            return _jobs_list(client, args)
        if args.jobs_cmd == "hits":
            return _jobs_hits(client, args, log)
        return _jobs_admin(client, args, log)
    finally:
        client.close()


def _jobs_submit(client, args, log: Log) -> int:
    import json as _json

    tf = getattr(args, "targets_file", None)
    targets_fingerprint = None
    if tf is not None:
        if args.hashfile is not None:
            log.error("pass a hashfile positional OR --targets-file, "
                      "not both")
            return 2
        from dprf_tpu.targets import TargetStore
        store = TargetStore.from_file(
            get_engine(args.engine, device="cpu"), tf, log=log)
        if not store.targets:
            log.error("no valid targets in targets file", path=tf)
            return 2
        lines = store.lines()
        targets_fingerprint = store.fingerprint
    elif args.hashfile is None:
        log.error("no target hashes: pass a hashfile or --targets-file")
        return 2
    else:
        with open(args.hashfile, encoding="utf-8",
                  errors="replace") as fh:
            lines = [ln.strip() for ln in fh if ln.strip()]
    spec = {
        "engine": args.engine,
        "attack": args.attack,
        "attack_arg": args.attack_arg,
        "customs": {str(i): v.hex()
                    for i, v in _customs(args).items()},
        "rules": args.rules,
        "markov": args.markov,
        "order": getattr(args, "order", "index"),
        "targets": lines,
        "targets_fingerprint": targets_fingerprint,
        "unit_size": args.unit_size,
        "unit_seconds": args.unit_seconds,
        "batch": args.batch or DEFAULT_BATCH,
        "hit_cap": args.hit_cap,
        "devices": max(1, args.devices or 1),
    }
    owner = args.owner or os.environ.get("USER") or "?"
    resp = client.call("job_submit", spec=spec, owner=owner,
                       priority=args.priority,
                       quota=args.quota, rate=args.rate)
    log.info("job submitted", job=resp.get("job_id"),
             keyspace=resp.get("keyspace"),
             fingerprint=resp.get("fingerprint"))
    print(_json.dumps({"job": resp.get("job_id"),
                       "keyspace": resp.get("keyspace"),
                       "fingerprint": resp.get("fingerprint")}))
    return 0


def _jobs_list(client, args) -> int:
    import json as _json

    resp = client.call("job_list")
    jobs = resp.get("jobs") or []
    if not args.quiet:
        print(f"{'JOB':6s} {'OWNER':12s} {'PRIO':>4s} "
              f"{'STATE':10s} {'COVERED':>18s} {'FOUND':>9s} "
              f"{'LEASES':>7s}", file=sys.stderr)
        for j in jobs:
            cov = f"{j['done']}/{j['total']}"
            print(f"{j['id']:6s} {j['owner'][:12]:12s} "
                  f"{j['priority']:>4d} {j['state']:10s} "
                  f"{cov:>18s} "
                  f"{j['found']}/{j['targets']:>4d} "
                  f"{j['leases']:>7d}", file=sys.stderr)
    print(_json.dumps(jobs))
    return 0


def _jobs_admin(client, args, log: Log) -> int:
    """status / cancel / pause / resume: one job in, its summary out."""
    import json as _json

    cmd = args.jobs_cmd
    if cmd == "status":
        resp = client.call("job_status", job=args.job)
    elif cmd == "cancel":
        resp = client.call("job_cancel", job=args.job)
    else:
        resp = client.call("job_pause", job=args.job,
                           resume=cmd == "resume")
    summary = resp.get("job") or {}
    log.info(f"job {cmd}", job=summary.get("id"),
             state=summary.get("state"))
    print(_json.dumps(summary))
    return 0


def _jobs_hits(client, args, log: Log) -> int:
    """Cursor-based per-job hit pull; --follow keeps polling until the
    job reaches a terminal state."""
    import time as _time

    spec = _jobs_client_spec(client, args.job)
    raws = (spec or {}).get("targets") or []
    cursor = max(0, args.cursor)
    while True:
        resp = client.call("hits_pull", job=args.job, cursor=cursor)
        for h in resp.get("hits") or ():
            ti = h.get("target")
            raw = (raws[ti] if isinstance(ti, int)
                   and 0 <= ti < len(raws) else str(ti))
            from dprf_tpu.runtime.potfile import encode_plain
            print(f"{raw}:"
                  f"{encode_plain(bytes.fromhex(h['plaintext']))}",
                  flush=True)
        cursor = resp.get("cursor") or cursor
        state = resp.get("state")
        if not args.follow or state in ("done", "cancelled"):
            log.info("hits pulled", job=args.job, cursor=cursor,
                     found=resp.get("found"),
                     targets=resp.get("targets"), state=state)
            return 0
        _time.sleep(max(0.1, args.interval))


def _jobs_client_spec(client, job_id: str):
    """The job's wire spec via op_job_status (target raws for
    rendering pulled hits); None when the job is unknown."""
    from dprf_tpu.runtime.rpc import RpcError
    try:
        resp = client.call("job_status", job=job_id)
    except RpcError:
        return None
    return resp.get("spec")


def cmd_trace(args, log: Log) -> int:
    """`dprf trace export SESSION`: session span stream -> Chrome-trace
    JSON (Perfetto-loadable), plus a lifecycle summary -- how many unit
    traces, reissues, orphan spans (there should be none), and
    incomplete lifecycles.  `dprf trace pull --connect` is the
    incident-response path: collect the fleet's flight-recorder rings
    from a live coordinator into a file export understands."""
    import json as _json

    from dprf_tpu.telemetry import trace as trace_mod

    if args.trace_cmd == "pull":
        return _trace_pull(args, log)

    path = trace_mod.trace_path(args.session)
    spans = trace_mod.load_trace(path)
    if not spans:
        log.error("no spans found (did the job run with --session?)",
                  path=path)
        return 2
    doc = trace_mod.export_chrome_trace(spans)
    base = (args.session[:-len(trace_mod.TRACE_SUFFIX)]
            if args.session.endswith(trace_mod.TRACE_SUFFIX)
            else args.session)
    out = args.out or base + ".perfetto.json"
    with open(out, "w", encoding="utf-8") as fh:
        _json.dump(doc, fh)
    report = trace_mod.lifecycle_report(spans)
    reissued = sum(1 for d in report["details"].values()
                   if d["reissues"])
    log.info("trace exported", out=out, spans=report["spans"],
             traces=report["traces"], reissued_units=reissued,
             orphans=report["orphans"],
             incomplete=len(report["incomplete"]))
    if report["orphans"]:
        log.warn("orphan spans present: a parent link crossed the RPC "
                 "boundary without its context (bug?)")
    print(_json.dumps({
        "out": out,
        "spans": report["spans"],
        "traces": report["traces"],
        "reissued_units": reissued,
        "orphans": report["orphans"],
        "incomplete": len(report["incomplete"]),
    }))
    return 0


def _trace_pull(args, log: Log) -> int:
    """`dprf trace pull --connect`: arm a fleet-wide ring pull (each
    live worker ships its local flight recorder with its next lease
    round trip), wait, then page the coordinator's merged ring out
    through op_trace_pull and write a .trace.jsonl stream."""
    import json as _json
    import time as _time

    client = _jobs_client(args, log)
    try:
        first = client.call("trace_pull", arm=not args.no_arm,
                            since=None, n=args.spans)
        if not args.no_arm:
            log.info("pull armed; waiting for worker rings",
                     epoch=first.get("epoch"), wait_s=args.wait)
            _time.sleep(max(0.0, args.wait))
        # page the ring: span-id cursor, stop when a page comes back
        # short (tail reached)
        spans: list = []
        cursor = None
        while True:
            resp = client.call("trace_pull", arm=False, since=cursor,
                               n=args.spans)
            page = resp.get("spans") or []
            if resp.get("resync"):
                spans = []        # cursor fell off the ring: restart
            spans.extend(page)
            cursor = resp.get("cursor") or cursor
            if len(page) < args.spans:
                break
        with open(args.out, "w", encoding="utf-8") as fh:
            for s in spans:
                fh.write(_json.dumps(s, separators=(",", ":"),
                                     default=str) + "\n")
        procs = sorted({str(s.get("proc")) for s in spans})
        log.info("trace pulled", out=args.out, spans=len(spans),
                 procs=len(procs))
        print(_json.dumps({"out": args.out, "spans": len(spans),
                           "procs": procs}))
        return 0
    finally:
        client.close()


def cmd_report(args, log: Log) -> int:
    """`dprf report SESSION`: render the performance-attribution
    report from the session's artifacts (perfreport/report.py) --
    a post-mortem needs no live coordinator."""
    import json as _json

    from dprf_tpu.perfreport import build_report, render_report

    doc = build_report(args.session)
    if doc is None:
        log.error("no session artifacts found (journal, .trace.jsonl "
                  "or .telemetry.jsonl)", session=args.session)
        return 2
    if args.json:
        print(_json.dumps(doc, sort_keys=True))
    else:
        print(render_report(doc))
    return 0


def cmd_audit(args, log: Log) -> int:
    """`dprf audit SESSION`: reconstruct the coverage story from the
    session's artifacts (perfreport/audit.py) and gate on it -- exit
    0 only when the verdict is clean, so CI and the chaos harness can
    use the exit code directly."""
    import json as _json

    from dprf_tpu.perfreport import build_audit, render_audit

    doc = build_audit(args.session)
    if doc is None:
        log.error("no session artifacts found (journal or "
                  ".trace.jsonl)", session=args.session)
        return 2
    if args.json:
        print(_json.dumps(doc, sort_keys=True))
    else:
        print(render_audit(doc))
    return 0 if doc["verdict"] == "clean" else 3


def _fmt_eta(v) -> str:
    if v is None:
        return "?"
    if v >= 3600:
        return f"{v / 3600:.1f}h"
    if v >= 120:
        return f"{v / 60:.1f}m"
    return f"{v:.0f}s"


def cmd_health(args, log: Log) -> int:
    """`dprf health --connect`: the fleet health plane's live view --
    per-worker state machine + payloads, per-job SLOs, active alerts
    (rpc.op_health)."""
    import json as _json

    client = _jobs_client(args, log)
    try:
        resp = client.call("health")
    finally:
        client.close()
    workers = resp.get("workers") or {}
    jobs = resp.get("jobs") or []
    active = resp.get("alerts") or []
    if args.json:
        print(_json.dumps({"workers": workers, "jobs": jobs,
                           "alerts": active}, sort_keys=True))
        return 0
    firing = [a for a in active if a.get("state") == "firing"]
    if firing:
        print(f"FIRING: {', '.join(a['rule'] for a in firing)}")
    print(f"{'WORKER':20s} {'STATE':>9s} {'AGE':>6s} {'RATE':>12s} "
          f"{'STRAG':>5s} {'ENGINE':>8s} {'Q':>3s}")
    for w in sorted(workers):
        rec = workers[w]
        pl = rec.get("payload") or {}
        rate = rec.get("rate_hs")
        print(f"{w[:20]:20s} {str(rec.get('state'))[:9]:>9s} "
              f"{rec.get('age_s', 0):>5.0f}s "
              f"{(f'{rate:,.0f}/s' if rate else '-'):>12s} "
              f"{('yes' if rec.get('straggler') else '-'):>5s} "
              f"{str(pl.get('engine') or '-')[:8]:>8s} "
              f"{str(pl.get('queue') if pl.get('queue') is not None else '-'):>3s}")
    print()
    print(f"{'JOB':6s} {'STATE':>9s} {'COVERED':>20s} {'RATE':>12s} "
          f"{'ETA':>7s} {'TTFH':>7s} {'STALL':>5s}")
    for j in jobs:
        cov = f"{j.get('covered', 0)}/{j.get('total', 0)}"
        rate = j.get("rate_ips")
        ttfh = j.get("ttfh_s")
        print(f"{str(j.get('job'))[:6]:6s} "
              f"{str(j.get('state'))[:9]:>9s} {cov:>20s} "
              f"{(f'{rate:,.0f}/s' if rate else '-'):>12s} "
              f"{_fmt_eta(j.get('eta_s')):>7s} "
              f"{(f'{ttfh:.1f}s' if ttfh is not None else '-'):>7s} "
              f"{('YES' if j.get('stalled') else '-'):>5s}")
    log.info("fleet health", workers=len(workers), jobs=len(jobs),
             firing=len(firing))
    return 0


def cmd_alerts(args, log: Log) -> int:
    """`dprf alerts --connect`: active alerts + the recent
    pending/firing/resolved transition history (rpc.op_alerts)."""
    import json as _json

    client = _jobs_client(args, log)
    try:
        resp = client.call("alerts", n=args.history)
    finally:
        client.close()
    active = resp.get("alerts") or []
    history = resp.get("history") or []
    if args.json:
        print(_json.dumps({"alerts": active, "history": history},
                          sort_keys=True))
        return 0
    if not active:
        print("no active alerts")
    else:
        print(f"{'RULE':20s} {'STATE':>8s} {'SEV':>8s} {'FOR':>7s} "
              f"{'VALUE':>10s} {'LABELS'}")
        for a in active:
            lv = ",".join(f"{k}={v}" for k, v in
                          sorted((a.get("labels") or {}).items()))
            print(f"{str(a.get('rule'))[:20]:20s} "
                  f"{str(a.get('state')):>8s} "
                  f"{str(a.get('severity'))[:8]:>8s} "
                  f"{a.get('since_s', 0):>6.0f}s "
                  f"{a.get('value', 0):>10.3g} {lv}")
    if history:
        print()
        print("recent transitions:")
        for e in history[-args.history:]:
            lv = ",".join(str(v) for _, v in
                          sorted((e.get("labels") or {}).items()))
            print(f"  {e.get('rule')}({lv}) -> {e.get('state')} "
                  f"value={e.get('value')}")
    log.info("alerts", active=len(active), history=len(history))
    return 0


def cmd_token(args, log: Log) -> int:
    """`dprf token --owner NAME`: mint a tenant token from the admin
    secret (rpc.owner_token).  Hand the printed token to the tenant;
    the coordinator re-derives it from the admin secret at hello, so
    no token table exists anywhere."""
    from dprf_tpu.runtime.rpc import owner_token

    secret = args.token or envreg.get_str("DPRF_TOKEN") or None
    if not secret:
        log.error("minting needs the coordinator's admin secret "
                  "(--token or $DPRF_TOKEN)")
        return 2
    print(owner_token(secret, args.owner))
    return 0


def cmd_programs(args, log: Log) -> int:
    """`dprf programs --connect`: the fleet's compiled-program table
    (op_programs) -- XLA-derived cost/memory per executable, merged
    from the coordinator's compile sites and worker heartbeats."""
    import json as _json

    from dprf_tpu.telemetry import programs as programs_mod

    client = _jobs_client(args, log)
    try:
        resp = client.call("programs")
    finally:
        client.close()
    records = resp.get("programs") or []
    if args.json:
        print(_json.dumps(records, sort_keys=True))
    else:
        print(programs_mod.render_table(records))
    log.info("programs", records=len(records))
    return 0


def cmd_profile(args, log: Log) -> int:
    """`dprf profile`: kernel-level profiling (ISSUE 15).  Local mode
    analyzes an existing capture (dependency-free perfetto parse);
    --connect requests a bounded capture window on a fleet worker
    over op_profile and polls until the analyzed summary arrives."""
    import json as _json

    from dprf_tpu.telemetry import profiler as profiler_mod

    if args.connect:
        return _profile_connect(args, log, profiler_mod, _json)
    if not args.target:
        log.error("profile: give a capture dir / trace file to "
                  "analyze, or --connect for a live capture")
        return 2
    doc = profiler_mod.analyze_trace(args.target, engine=args.engine,
                                     top=args.top)
    if args.json:
        print(_json.dumps(doc, sort_keys=True))
    else:
        print(profiler_mod.render_summary(doc))
    return 1 if doc.get("error") else 0


def _profile_connect(args, log: Log, profiler_mod, _json) -> int:
    """The capture+pull flow: op_profile request -> the worker's next
    lease/heartbeat carries the window -> it sweeps through the
    window, analyzes locally, pushes the summary -> we poll the
    coordinator's summary table for our request id."""
    import time as _time

    client = _jobs_client(args, log)
    try:
        if args.fetch:
            resp = client.call("profile", worker=args.worker)
            summaries = resp.get("summaries") or {}
            if args.json:
                print(_json.dumps(summaries, sort_keys=True))
            else:
                for w in sorted(summaries):
                    for s in summaries[w]:
                        print(f"--- {w}")
                        print(profiler_mod.render_summary(s))
            log.info("profile summaries",
                     workers=len(summaries))
            return 0
        resp = client.call("profile", action="request",
                           worker=args.worker, seconds=args.seconds)
        rid = resp.get("request_id")
        worker = resp.get("worker")
        log.info("capture requested", worker=worker, request=rid)
        deadline = _time.monotonic() + max(1.0, args.wait)
        summary = None
        while _time.monotonic() < deadline:
            try:
                st = client.call("profile", worker=worker)
            except (OSError, RpcError):
                # the serve session can legitimately end mid-poll
                # (short job: the drain's read-grace covers the
                # normal push->read window, but a killed or crashed
                # coordinator shouldn't turn into a CLI traceback)
                log.warn("coordinator went away mid-poll",
                         worker=worker, request=rid)
                break
            for s in (st.get("summaries") or {}).get(worker, []):
                if s.get("request_id") == rid:
                    summary = s
                    break
            if summary is not None:
                break
            _time.sleep(0.5)
    finally:
        client.close()
    if summary is None:
        log.error("no summary arrived in time (worker still "
                  "compiling/warming the profiler deps, dead, or "
                  "never leasing?)", worker=worker,
                  waited=f"{args.wait:.0f}s")
        return 1
    if args.json:
        print(_json.dumps(summary, sort_keys=True))
    else:
        print(profiler_mod.render_summary(summary))
    return 1 if summary.get("error") else 0


def cmd_metrics(args, log: Log) -> int:
    """Scrape a running coordinator: plain HTTP GET on the RPC port
    (no client library; works for curl/Prometheus too).  --json asks
    the authenticated RPC op for the structured snapshot instead."""
    host, port = _parse_hostport(args.connect)
    if args.json:
        import json as _json

        from dprf_tpu.runtime.rpc import CoordinatorClient
        token = args.token or envreg.get_str("DPRF_TOKEN") or None
        client = CoordinatorClient(host, port, timeout=args.timeout,
                                   token=token)
        try:
            if token:
                client.hello()       # answer the auth challenge first
            resp = client.call("metrics", format="json")
        finally:
            client.close()
        print(_json.dumps(resp.get("metrics", {}), indent=2,
                          sort_keys=True))
        return 0
    from dprf_tpu.telemetry import scrape_metrics
    sys.stdout.write(scrape_metrics(host, port, timeout=args.timeout))
    return 0


def cmd_show(args, log: Log) -> int:
    """hashcat --show parity: hash:plain for every potfile-cracked
    target of the hashlist."""
    from dprf_tpu.runtime.potfile import encode_plain

    engine = get_engine(args.engine, device="cpu")
    hl = _load_targets(engine, args.hashfile, log)
    if hl is None:
        return 2
    pot = Potfile(args.potfile)
    n = 0
    for t in hl.targets:
        plain = pot.get(t.raw)
        if plain is not None:
            print(f"{t.raw}:{encode_plain(plain)}")
            n += 1
    log.info("cracked", count=f"{n}/{len(hl.targets)}")
    return 0


def cmd_left(args, log: Log) -> int:
    """hashcat --left parity: targets still missing from the potfile."""
    engine = get_engine(args.engine, device="cpu")
    hl = _load_targets(engine, args.hashfile, log)
    if hl is None:
        return 2
    pot = Potfile(args.potfile)
    n = 0
    for t in hl.targets:
        if pot.get(t.raw) is None:
            print(t.raw)
            n += 1
    log.info("uncracked", count=f"{n}/{len(hl.targets)}")
    return 0


def cmd_check(args, log: Log) -> int:
    from dprf_tpu import analysis
    argv = []
    if args.root:
        argv += ["--root", args.root]
    for v in args.only or ():
        argv += ["--only", v]
    for v in args.skip or ():
        argv += ["--skip", v]
    if args.explain:
        argv += ["--explain", args.explain]
    for flag in ("json", "list", "show_suppressed", "write_env_docs",
                 "fix_skeletons"):
        if getattr(args, flag):
            argv.append("--" + flag.replace("_", "-"))
    return analysis.main(argv)


def cmd_engines(args, log: Log) -> int:
    devices = [args.device] if args.device else ["cpu", "jax"]
    for dev in devices:
        try:
            names = engine_names(dev)
        except KeyError:
            names = []
        if not getattr(args, "verbose", False):
            print(f"{dev}: {', '.join(names)}")
            continue
        from dprf_tpu.engines import engine_class
        print(f"{dev}:")
        for n in names:
            doc = (engine_class(n, dev).__doc__ or "").strip()
            first = doc.splitlines()[0] if doc else ""
            print(f"  {n:14s} {first}")
    return 0


def _attack_gen(args, log: Log):
    """Engine-free generator from an attack spec (keyspace / stdout)."""
    customs = _customs(args)
    if args.attack == "mask":
        counts = None
        if getattr(args, "markov", None):
            from dprf_tpu.generators.markov import load_stats
            counts = load_stats(args.markov)
        return MaskGenerator(args.attack_arg, custom=customs or None,
                             markov_counts=counts)
    if getattr(args, "markov", None):
        # same contract as crack: silently unordered output would be
        # worse than the error
        raise ValueError("--markov applies to mask attacks only")
    if args.attack == "wordlist":
        from dprf_tpu.generators.wordlist import WordlistRulesGenerator
        return WordlistRulesGenerator.from_files(
            args.attack_arg, args.rules, max_len=args.max_len)
    gen, _, _ = _build_combinator_gen(
        args.attack, args.attack_arg, customs, args.max_len,
        None, "cpu", log)
    return gen


def cmd_keyspace(args, log: Log) -> int:
    print(_attack_gen(args, log).keyspace)
    return 0


def cmd_markov(args, log: Log) -> int:
    from dprf_tpu.generators.markov import (save_stats, stats_digest,
                                            train_file)
    counts = train_file(args.wordlist, max_len=args.max_len)
    save_stats(args.out, counts)
    log.info("markov stats written", out=args.out,
             words_weight=int(counts[0].sum()),
             digest=stats_digest(counts))
    return 0


def cmd_stdout(args, log: Log) -> int:
    """Stream the attack's candidate bytes, one per line, without
    hashing -- for piping into other tools and for debugging what a
    mask/rule spec actually expands to (hashcat's --stdout)."""
    gen = _attack_gen(args, log)
    start = max(0, args.skip)
    end = gen.keyspace if args.limit is None else \
        min(gen.keyspace, start + args.limit)
    out = sys.stdout.buffer
    try:
        for s in range(start, end, 8192):
            n = min(8192, end - s)
            for c in gen.candidates(s, n):
                if c is None:        # rule-rejected keyspace hole
                    continue
                out.write(c)
                out.write(b"\n")
        out.flush()
    except BrokenPipeError:          # |head is normal use, not an error
        import os
        os.dup2(os.open(os.devnull, os.O_WRONLY), out.fileno())
    return 0


_COMMANDS = {
    "crack": cmd_crack,
    "serve": cmd_serve,
    "worker": cmd_worker,
    "bench": cmd_bench,
    "tune": cmd_tune,
    "prewarm": cmd_prewarm,
    "jobs": cmd_jobs,
    "retry-parked": cmd_retry_parked,
    "top": cmd_top,
    "trace": cmd_trace,
    "health": cmd_health,
    "alerts": cmd_alerts,
    "token": cmd_token,
    "report": cmd_report,
    "audit": cmd_audit,
    "programs": cmd_programs,
    "profile": cmd_profile,
    "metrics": cmd_metrics,
    "check": cmd_check,
    "show": cmd_show,
    "left": cmd_left,
    "engines": cmd_engines,
    "keyspace": cmd_keyspace,
    "stdout": cmd_stdout,
    "markov": cmd_markov,
}


def main(argv: Optional[list] = None) -> int:
    args = _build_parser().parse_args(argv)
    log = Log(quiet=getattr(args, "quiet", False))
    # library code logs through the module-level DEFAULT; mirror -q
    from dprf_tpu.utils.logging import DEFAULT
    DEFAULT.quiet = log.quiet
    try:
        return _COMMANDS[args.command](args, log)
    except (ValueError, KeyError, OSError, RpcError) as e:
        log.error(str(e))
        return 2


if __name__ == "__main__":
    sys.exit(main())
