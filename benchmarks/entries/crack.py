"""Entry driver `crack`: the job `dprf crack` runs, in this process.

The window drives `dprf_tpu.cli.main(["crack", <mask>, <hashfile>,
"--engine", .., "--batch", .., "--unit-size", .., "--unit-seconds",
"0", "--skip", .., "--session", .., "--potfile", .., "--devices",
..])`: `cmd_crack` -> `_crack_single` -> `_setup_job`,
`_resolve_batch`, `_select_worker`, `Coordinator.run`, with the session
journal, the potfile and oracle verification on.

`dprf crack` runs a finite job and has no run-time limit, so three
names of `dprf_tpu.cli` are replaced for the length of the call, and
put back after it; no file of the program is changed:

- `_setup_job` returns the real job with `job.dispatcher` inside a
  `WindowDispatcher` (lets the warm units through as set-up, opens the
  window on an empty pipeline, closes it once `--seconds` have passed
  (in a traced run: `TRACED_WINDOW` of them) and its units are
  drained, times every `lease()` and `complete()`)
  and `job.engine`, the CPU oracle, inside an `OracleProxy` that times
  its hash calls;
- `Potfile` is a subclass that notes when each line was written;
- `_select_worker` returns the real worker and lets the harness keep
  hold of it.

The window is from its opening to the last `complete()` of the drain:
it ends in a real sync.  A traced run's window is half as long
(`TRACED_WINDOW`) and the profiler runs over its last seconds
(`SliceTracer`); everything else of the run, the plan included, is an
untraced run's.  A job with one target ends at its hit, so its
plant lies behind the window (`traffic.py`, `tail_plant`): after the
drain the same job, with the same worker, goes on leasing outside
every clock (the tail) until it has found the plant, and then the
worker is handed further units around the plants (`judge_lanes`).  A
`--runtime` option on `dprf crack` itself would remove the seam
(PERF.md, Open questions).
"""

import contextlib
import io
import json
import os
import re
import sys
import time

#: units let through as set-up before the window opens: the fused
#: program is compiled lazily at its first call, which is unit 0's;
#: unit 1 is a warm one, so the window opens on a program that has run
WARM_UNITS = 2
#: a traced run closes its window at this share of `--seconds`.  The
#: plan is an untraced run's (same seed, same `--seconds`, same plant),
#: so a one-target job's plant lies twice as far behind a traced window
#: as it must: a program up to twice as fast as the cell's
#: `tail_plant.units_per_s` still sweeps its whole slice before its hit
#: ends the job (the slice used to be the last seconds of the full
#: window, and a program a quarter faster lost it, in silence)
TRACED_WINDOW = 0.5
#: the trace is stopped this long after the window's close: stopped at
#: the close itself, one traced run in three lost the slice's last
#: program from `XLA Modules` (70 ms of false idle; PERF.md, 3)
STOP_AFTER_S = 0.25
#: the text an `XLA Ops` event of the hash kernel holds in the trace:
#: the Pallas kernel is the programs' one custom call
KERNEL_EVENT = " custom-call("

_RAN = re.compile(r"info\s+ran (.*)$")
_DEVICE = re.compile(r"info\s+device platform=(\S+) count=(\d+) kind=(.*)$")


def _kv(text):
    return dict(f.split("=", 1) for f in text.split() if "=" in f)


class _Tee(io.TextIOBase):
    """Pass the job's own log through to stderr and keep a copy."""

    def __init__(self, stream):
        self.stream, self.kept = stream, []

    def write(self, s):
        self.kept.append(s)
        return self.stream.write(s)

    def flush(self):
        self.stream.flush()

    def text(self):
        return "".join(self.kept)


def read_log(text):
    """{device, ran, shapes} from a job's log: the program says what it
    ran; shapes is its `dispatch=probe:64,loop:15` as a dict."""
    rec = {"device": None, "ran": None, "shapes": {}}
    for line in text.splitlines():
        m = _DEVICE.search(line)
        if m:
            rec["device"] = {"platform": m.group(1), "kind": m.group(3),
                             "count": int(m.group(2))}
        m = _RAN.search(line)
        if m:
            rec["ran"] = _kv(m.group(1))
            rec["shapes"] = {
                k: int(n) for k, n in
                (f.split(":") for f in rec["ran"].get("dispatch", "")
                 .split(",") if ":" in f)}
    return rec


class Spans:
    """Host intervals of the window, by name: seconds inside them, and
    (while a trace is taken) the same intervals as profiler
    annotations, so that the trace can say what the host was doing in
    a gap of the device."""

    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self.seconds = {}
        self.counts = {}
        self.open = False           # the window is open
        self.annotate = False       # a trace is running

    @contextlib.contextmanager
    def span(self, name):
        if self.annotate:
            import jax
            ctx = jax.profiler.TraceAnnotation("bench:" + name)
        else:
            ctx = contextlib.nullcontext()
        t0 = self.clock()
        try:
            with ctx:
                yield
        finally:
            if self.open:
                self.seconds[name] = (self.seconds.get(name, 0.0)
                                      + self.clock() - t0)
                self.counts[name] = self.counts.get(name, 0) + 1


class WindowDispatcher:
    """The job's dispatcher, with a window ended by the clock.

    Everything not named here is the real dispatcher's.  Three phases,
    each entered on a drained pipeline: the warm units (set-up), the
    window (`--seconds` by the clock, then `lease()` gives nothing
    until every unit leased in it is completed), and where `tail_to`
    is given the tail: the job goes on, outside the window, until it
    ends at its hit or has completed the unit that holds index
    `tail_to`.  Without a tail the dispatcher reports `done()` at the
    clock.  `stall` (tests): called with each unit leased inside the
    window, before it is handed on.  `on_close`: called once, when the
    window has closed."""

    def __init__(self, inner, warm_units, seconds, spans,
                 clock=time.monotonic, tracer=None, stall=None,
                 counter=None, tail_to=None, on_close=None):
        self._inner = inner
        self._warm, self._seconds = int(warm_units), float(seconds)
        self._spans, self._clock = spans, clock
        self._tracer, self._stall = tracer, stall
        self._tail_to, self._on_close = tail_to, on_close
        #: counter(): a number read at the window's opening and at
        #: its close (the process's compile count)
        self._counter = counter or (lambda: 0)
        self._leased = 0
        self._in_flight = 0          # window units not yet completed
        self.closed = False          # the window has closed
        self.tail_over = False       # the plant's unit is completed
        self.t_open = self.t_close = None
        self.outstanding_at_open = None
        self.count_at_open = self.count_at_close = None
        #: unit id -> [start, length, t_leased, t_completed, phase]
        self.units = {}

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _late(self):
        return (self.t_open is not None
                and self._clock() - self.t_open >= self._seconds)

    def _close(self):
        self.closed = True
        self._spans.open = False
        if self._on_close is not None:
            self._on_close()

    def lease(self, worker_id="local"):
        if self.t_open is None and self._leased >= self._warm:
            if self._inner.outstanding_count():
                return None          # the warm units are still in flight
            self.outstanding_at_open = self._inner.outstanding_count()
            self.count_at_open = self.count_at_close = self._counter()
            self.t_open = self.t_close = self._clock()
            self._spans.open = True
        if not self.closed and self._late():
            if self._in_flight:
                return None          # the window's units drain first
            self._close()
        if self.closed and (self._tail_to is None or self.tail_over):
            return None
        if self._tracer is not None and self.t_open is not None \
                and not self.closed:
            self._tracer.tick(self._clock() - self.t_open)
        with self._spans.span("lease"):
            unit = self._inner.lease(worker_id)
        if unit is not None:
            self._leased += 1
            phase = ("warm" if self.t_open is None
                     else "tail" if self.closed else "window")
            self.units[unit.unit_id] = [unit.start, unit.length,
                                        self._clock(), None, phase]
            if phase == "window":
                self._in_flight += 1
                if self._stall is not None:
                    self._stall(unit)
        return unit

    def done(self):
        if self._tail_to is None:
            return self._late() or self._inner.done()
        return self.tail_over or self._inner.done()

    def complete(self, unit_id, elapsed=None, worker_id=None):
        with self._spans.span("complete"):
            ok = self._inner.complete(unit_id, elapsed=elapsed,
                                      worker_id=worker_id)
        rec = self.units.get(unit_id)
        if rec is not None:
            rec[3] = self._clock()
            if rec[4] == "window":
                self.t_close = rec[3]
                self.count_at_close = self._counter()
                self._in_flight -= 1
                if not self._in_flight and self._late():
                    self._close()
            if self._tail_to is not None and \
                    rec[0] <= self._tail_to < rec[0] + rec[1]:
                self.tail_over = True    # found there, or missed
        return ok

    def units_of(self, phase):
        """[(start, length, t_leased, t_completed)] leased in that
        phase, in lease order."""
        return [tuple(r[:4]) for r in self.units.values()
                if r[4] == phase]


class OracleProxy:
    """The CPU oracle with its hash calls timed."""

    def __init__(self, inner, spans):
        self._inner, self._spans = inner, spans

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def hash_batch(self, *a, **kw):
        with self._spans.span("oracle"):
            return self._inner.hash_batch(*a, **kw)

    def verify(self, *a, **kw):
        with self._spans.span("oracle"):
            return self._inner.verify(*a, **kw)


class SliceTracer:
    """Takes the profiler's trace of the window's last `slice_s`
    seconds; `finish()` stops it.  `started` stays False where the
    window closed (by the clock or at the job's hit) before
    `start_after_s`: such a run has no slice, which `run.py` reports as
    an error."""

    def __init__(self, directory, start_after_s, spans):
        self.directory, self.start_after = directory, start_after_s
        self._spans = spans
        self.started = False

    def tick(self, since_open):
        if not self.started and since_open >= self.start_after:
            import jax
            self.started = True
            # the Python tracer hooks every call of the interpreter and
            # slows the pure-Python oracle threefold: off
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.directory, profiler_options=opts)
            self._spans.annotate = True

    def finish(self):
        """Stop the trace: `STOP_AFTER_S` after the window's close, on
        the loop's thread and outside every clock (and again, to no
        effect, once the job has returned)."""
        if self.started and self._spans.annotate:
            import jax
            self._spans.annotate = False
            time.sleep(STOP_AFTER_S)
            jax.profiler.stop_trace()


def _crack_argv(cfg, cell, hashfile, skip, session, potfile):
    f = cfg["flags"]
    argv = ["crack", cfg["mask"], hashfile, "--engine", cfg["engine"],
            "--batch", str(f["batch"]), "--unit-size", str(f["unit_size"]),
            "--unit-seconds", str(f["unit_seconds"]), "--skip", str(skip),
            "--session", session, "--potfile", potfile,
            "--devices", str(cell["chips"])]
    return argv


def _call_cli(argv, patches):
    """`cli.main(argv)` with the named `cli` attributes replaced for
    the call; returns (exit code, the job's log, its stdout)."""
    from dprf_tpu import cli
    saved = {k: getattr(cli, k) for k in patches}
    tee, out = _Tee(sys.stderr), io.StringIO()
    real_stderr = sys.stderr
    try:
        for k, v in patches.items():
            setattr(cli, k, v)
        sys.stderr = tee
        with contextlib.redirect_stdout(out):
            rc = cli.main(argv)
    finally:
        sys.stderr = real_stderr
        for k, v in saved.items():
            setattr(cli, k, v)
    return rc, tee.text(), out.getvalue()


def _timed_potfile(base, stamps):
    class TimedPotfile(base):
        def add(self, target_key, plain):
            super().add(target_key, plain)
            stamps.append((time.monotonic(), target_key))

    return TimedPotfile


def audit(session):
    """`dprf audit SESSION --json`, in this process: the program's own
    coverage verdict on the journal it left."""
    rc, log, out = _call_cli(["audit", session, "--json", "-q"], {})
    try:
        doc = json.loads(out.strip().splitlines()[-1])
    except (ValueError, IndexError):
        doc = {"verdict": "unreadable", "problems": [log[-500:]],
               "jobs": []}
    doc["rc"] = rc
    return doc


def run(ctx):
    """One measuring run.  ctx: cfg, cell, plan, seconds (what the plan
    was made for: the window's length, and in a traced run twice it,
    `TRACED_WINDOW`), trace (bool), workdir, faults (tests and
    control.py: {"stall": fn(unit), "patches": fn(cli) -> names of
    `cli` to replace: the harness's own replacements go around them}).  Returns the observations the
    comparison and the metric readers take."""
    from dprf_tpu import cli, compilecache
    cfg, cell, plan = ctx["cfg"], ctx["cell"], ctx["plan"]
    faults = ctx.get("faults") or {}
    wd = ctx["workdir"]
    hashfile = os.path.join(wd, "targets.hash")
    with open(hashfile, "w") as fh:
        fh.write("\n".join(plan.lines) + "\n")
    session = os.path.join(wd, "window.session")
    potfile = os.path.join(wd, "window.potfile")
    spans = Spans()
    tracer, window_s = None, float(ctx["seconds"])
    if ctx["trace"]:
        window_s *= TRACED_WINDOW
        slice_s = min(float(cell.get("trace_slice_s", 4.0)), 0.5 * window_s)
        tracer = SliceTracer(os.path.join(wd, "trace"),
                             window_s - slice_s, spans)
    holder, stamps = {}, []
    under = faults["patches"](cli) if faults.get("patches") else {}
    real_setup = under.get("_setup_job", cli._setup_job)
    real_select = under.get("_select_worker", cli._select_worker)
    tail = plan.plants_in("tail")

    def setup_job(args, device, log, lease_timeout=None):
        job = real_setup(args, device, log, lease_timeout=lease_timeout)
        if job is None:
            return None
        job.dispatcher = holder["dispatcher"] = WindowDispatcher(
            job.dispatcher, WARM_UNITS, window_s, spans,
            tracer=tracer, stall=faults.get("stall"),
            counter=lambda: sum(
                compilecache.process_cache_counts().values()),
            tail_to=tail[0].index if tail else None,
            on_close=tracer.finish if tracer else None)
        job.engine = OracleProxy(job.engine, spans)
        return job

    def select_worker(*a, **kw):
        holder["worker"] = real_select(*a, **kw)
        return holder["worker"]

    patches = dict(under, _setup_job=setup_job, _select_worker=select_worker,
                   Potfile=_timed_potfile(under.get("Potfile", cli.Potfile),
                                          stamps))
    rc, log, out = None, "", ""
    try:
        rc, log, out = _call_cli(
            _crack_argv(cfg, cell, hashfile, plan.skip, session, potfile),
            patches)
    finally:
        if tracer is not None:
            tracer.finish()
    disp = holder.get("dispatcher")
    if disp is None or disp.t_open is None:
        raise RuntimeError(
            f"the job never opened its window (exit {rc}):\n{log[-2000:]}")
    obs = {
        "rc": rc, "log": read_log(log), "stdout": out,
        "t_open": disp.t_open, "t_close": disp.t_close,
        "outstanding_at_open": disp.outstanding_at_open,
        "units": disp.units_of("window"),
        "warm_units": disp.units_of("warm"),
        "tail_units": disp.units_of("tail"),
        "host_seconds": dict(spans.seconds),
        "host_counts": dict(spans.counts),
        "potfile": potfile, "session": session,
        "potfile_stamps": stamps, "worker": holder.get("worker"),
        # None in a traced run too, where the window closed before its
        # slice was due: the profiler never started
        "trace_dir": tracer.directory if tracer and tracer.started else None,
        "slice_due_s": tracer.start_after if tracer else None,
        "window_compiles": disp.count_at_close - disp.count_at_open,
    }
    return obs


def judge_lanes(plan, obs):
    """The worker that swept the window, handed the units of
    `plan.lane_units` one by one, each of the job's unit length and
    each holding a plant on another of its lanes: what it reports for
    each, as [(candidate index, plaintext)], or None where it raised.
    Outside every clock; the worker is let go after."""
    from dprf_tpu.runtime.workunit import WorkUnit
    worker, said = obs.pop("worker", None), []
    for i, start in enumerate(plan.lane_units):
        try:
            hits = worker.process(WorkUnit(1_000_000 + i, start,
                                           plan.unit_size))
            said.append([(int(h.cand_index), bytes(h.plaintext))
                         for h in hits])
        except Exception as e:      # noqa: BLE001 -- a lane not judged
            sys.stderr.write(f"judge_lanes: unit at {start}: {e!r}\n")
            said.append(None)
    return said
