"""Dispatcher, session journal, potfile unit tests."""

import json

import pytest

pytestmark = pytest.mark.smoke

from dprf_tpu.runtime.dispatcher import Dispatcher, IntervalSet
from dprf_tpu.runtime.potfile import Potfile, encode_plain, decode_plain
from dprf_tpu.runtime.session import SessionJournal, job_fingerprint


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_interval_set_merge():
    s = IntervalSet()
    s.add(10, 20)
    s.add(0, 5)
    s.add(5, 10)          # bridges
    assert s.intervals() == [(0, 20)]
    s.add(30, 40)
    assert s.gaps(50) == [(20, 30), (40, 50)]
    assert s.covered() == 30
    assert s.contains_range(3, 18)
    assert not s.contains_range(18, 25)


def test_dispatcher_full_sweep():
    d = Dispatcher(keyspace=1000, unit_size=128)
    seen = []
    while True:
        u = d.lease("w0")
        if u is None:
            break
        seen.append((u.start, u.end))
        d.complete(u.unit_id)
    assert seen[0] == (0, 128)
    assert seen[-1] == (896, 1000)       # tail unit is short
    assert d.done()
    assert d.progress() == (1000, 1000)


def test_dispatcher_lease_expiry_reissues():
    clk = FakeClock()
    d = Dispatcher(keyspace=256, unit_size=128, lease_timeout=10.0, clock=clk)
    u1 = d.lease("w0")
    u2 = d.lease("w1")
    assert d.lease("w2") is None          # everything outstanding
    clk.t = 11.0                          # w0 and w1 die
    u3 = d.lease("w2")                    # reissued unit
    assert (u3.start, u3.end) in {(u1.start, u1.end), (u2.start, u2.end)}
    # late completion by the dead worker is idempotent
    d.complete(u1.unit_id)
    d.complete(u3.unit_id)
    u4 = d.lease("w2")
    d.complete(u4.unit_id)
    assert d.done()


def test_dispatcher_resume_from_completed():
    # covered: [0,100) and [200,300); frontier 300 -> gap [100,200) pending
    d = Dispatcher.from_completed(keyspace=1000, unit_size=64,
                                  completed=[(0, 100), (200, 300)])
    first = d.lease()
    second = d.lease()
    assert (first.start, first.end) == (100, 164)
    assert (second.start, second.end) == (164, 200)
    third = d.lease()
    assert third.start == 300             # continues at frontier
    done, total = d.progress()
    assert (done, total) == (200, 1000)


def test_session_journal_roundtrip(tmp_path):
    p = str(tmp_path / "job.session")
    j = SessionJournal(p, snapshot_every=1)
    j.open({"engine": "md5", "fingerprint": "abc"})
    j.record_units([(0, 100)])
    j.record_hit(0, 42, b"pass")
    j.record_units([(0, 250)])
    j.close()
    st = SessionJournal.load(p)
    assert st.spec["fingerprint"] == "abc"
    assert st.completed == [(0, 250)]     # last snapshot wins
    assert st.hits[0]["index"] == 42
    assert bytes.fromhex(st.hits[0]["plaintext"]) == b"pass"


def test_session_journal_torn_tail(tmp_path):
    p = str(tmp_path / "job.session")
    j = SessionJournal(p, snapshot_every=1)
    j.open({"engine": "md5"})
    j.record_units([(0, 64)])
    j.close()
    with open(p, "a") as fh:
        fh.write('{"type": "units", "intervals": [[0, 9')   # torn write
    st = SessionJournal.load(p)
    assert st.completed == [(0, 64)]


def test_fingerprint_sensitivity():
    a = job_fingerprint("md5", "mask:?l?l", 676, [b"x" * 16])
    assert a == job_fingerprint("md5", "mask:?l?l", 676, [b"x" * 16])
    assert a != job_fingerprint("md5", "mask:?l?d", 676, [b"x" * 16])
    assert a != job_fingerprint("md5", "mask:?l?l", 676, [b"y" * 16])


def test_potfile_roundtrip(tmp_path):
    p = str(tmp_path / "t.pot")
    pot = Potfile(p)
    pot.add("deadbeef", b"hello")
    pot.add("cafebabe", b"\x01\xffbin:")
    # reload from disk
    pot2 = Potfile(p)
    assert pot2.get("deadbeef") == b"hello"
    assert pot2.get("cafebabe") == b"\x01\xffbin:"
    assert "deadbeef" in pot2 and len(pot2) == 2


@pytest.mark.parametrize("plain", [b"simple", b"", b"with:colon",
                                   b"\x00\x01", "pässword".encode(),
                                   b"$HEX[41]"])
def test_plain_encoding_roundtrip(plain):
    assert decode_plain(encode_plain(plain)) == plain


def test_dispatcher_chaos_full_coverage():
    """Elastic-recovery stress (SURVEY.md section 5): workers randomly
    crash (fail), stall (lease expiry), or double-report completions;
    the ledger must still converge to exactly-full coverage."""
    import random
    rng = random.Random(7)
    clk = FakeClock()
    # retry cap disabled: this chaos model fails units at random (not
    # because the unit itself is poisoned), so parking would be wrong
    # -- full convergence is the invariant under test
    d = Dispatcher(keyspace=10_000, unit_size=37, lease_timeout=50.0,
                   clock=clk, max_unit_retries=None)
    held = []                      # units currently "running"
    completed_ids = []
    for _ in range(200_000):
        if d.done():
            break
        clk.t += rng.uniform(0, 5)
        action = rng.random()
        if action < 0.45 or not held:
            u = d.lease(f"w{rng.randrange(8)}")
            if u is not None:
                held.append(u)
        elif action < 0.75:
            u = held.pop(rng.randrange(len(held)))
            d.complete(u.unit_id)
            completed_ids.append(u.unit_id)
        elif action < 0.85:
            u = held.pop(rng.randrange(len(held)))
            d.fail(u.unit_id)
        elif action < 0.95:
            # stalled worker: just sit on the unit past its lease;
            # dispatcher reaps it and someone else finishes it
            clk.t += 60.0
            if held and rng.random() < 0.5:
                held.pop(rng.randrange(len(held)))   # worker died silently
        else:
            # late/duplicate completion of an already-finished unit
            if completed_ids:
                d.complete(rng.choice(completed_ids))
    assert d.done()
    assert d.completed_intervals() == [(0, 10_000)]


def test_dispatcher_poison_guard_parks_after_retry_cap():
    """A unit that fails every worker that touches it must be PARKED
    after the retry cap, not reissued forever: before the guard,
    Dispatcher.fail()/reap_expired() livelocked the whole job on one
    poisoned unit."""
    from dprf_tpu.telemetry import MetricsRegistry

    m = MetricsRegistry()
    d = Dispatcher(keyspace=256, unit_size=128, registry=m,
                   max_unit_retries=5)
    poisoned = d.lease("w0")
    for i in range(5):
        assert d.parked_count() == 0
        d.fail(poisoned.unit_id)
        if i < 4:                       # reissued, not yet parked
            again = d.lease("w0")
            assert (again.start, again.end) == (poisoned.start,
                                                poisoned.end)
    # 5th failure parks it: the range becomes unreachable this run
    assert d.parked_count() == 1
    assert d.parked_indices() == poisoned.length
    assert m.counter("dprf_units_poisoned_total",
                     labelnames=("job",)).value(job="j0") == 1
    # the rest of the keyspace still sweeps, and the job terminates
    u = d.lease("w1")
    assert (u.start, u.end) == (128, 256)
    d.complete(u.unit_id)
    assert d.lease("w1") is None
    assert d.done()                     # reachable keyspace covered
    assert not d.exhausted()            # ...but honestly NOT exhausted
    assert d.progress() == (128, 256)


def test_dispatcher_poison_guard_counts_lease_expiry():
    """Lease expiry (dead worker) burns the same retry budget as an
    explicit fail -- a unit that kills every worker that leases it
    never reports fail() at all."""
    clk = FakeClock()
    d = Dispatcher(keyspace=128, unit_size=128, lease_timeout=10.0,
                   clock=clk, max_unit_retries=3)
    for _ in range(3):
        u = d.lease("w0")
        assert u is not None
        clk.t += 11.0                   # worker dies holding the lease
        d.reap_expired()
    assert d.parked_count() == 1
    assert d.done() and not d.exhausted()


def test_dispatcher_retry_parked_requeues_with_fresh_budget():
    """Satellite (ISSUE 3): the retry-parked admin op un-parks
    poisoned units WITHOUT restarting the job -- attempt counts reset
    (a requeued unit gets the full retry budget again), the parked
    gauge drops to 0, and `done()` stops treating the ranges as
    unreachable."""
    from dprf_tpu.telemetry import MetricsRegistry

    m = MetricsRegistry()
    d = Dispatcher(keyspace=256, unit_size=128, registry=m,
                   max_unit_retries=2)
    poisoned = d.lease("w0")
    d.fail(poisoned.unit_id)
    d.fail(d.lease("w0").unit_id)       # 2nd failure parks it
    u = d.lease("w1")                   # rest of the keyspace done
    d.complete(u.unit_id)
    assert d.parked_count() == 1 and d.done() and not d.exhausted()
    assert m.gauge("dprf_units_parked",
                   labelnames=("job",)).value(job="j0") == 1

    assert d.retry_parked() == 1
    assert d.parked_count() == 0 and d.parked_indices() == 0
    assert m.gauge("dprf_units_parked",
                   labelnames=("job",)).value(job="j0") == 0
    assert not d.done()                 # the range is reachable again
    # fresh budget: the requeued unit survives max_unit_retries - 1
    # NEW failures before parking again (attempt count was reset)
    again = d.lease("w2")
    assert (again.start, again.end) == (poisoned.start, poisoned.end)
    d.fail(again.unit_id)
    assert d.parked_count() == 0        # 1 of 2: reissued, not parked
    d.complete(d.lease("w2").unit_id)
    assert d.exhausted()                # full honest coverage now
    assert d.retry_parked() == 0        # idempotent when nothing parked
    # the parking EVENT counter keeps history; reissue reason is logged
    assert m.counter("dprf_units_poisoned_total",
                     labelnames=("job",)).value(job="j0") == 1
    assert m.counter("dprf_units_reissued_total",
                     labelnames=("reason", "job")).value(
        reason="retry_parked", job="j0") == 1


def test_rpc_retry_parked_admin_op():
    """The op reaches the dispatcher through CoordinatorState (what
    `dprf retry-parked --connect` invokes server-side)."""
    from dprf_tpu.runtime.rpc import CoordinatorState
    from dprf_tpu.telemetry import MetricsRegistry

    m = MetricsRegistry()
    d = Dispatcher(keyspace=128, unit_size=128, registry=m,
                   max_unit_retries=1)
    state = CoordinatorState({"engine": "md5"}, d, n_targets=1,
                             registry=m)
    resp = state.op_lease({"worker_id": "w0"})
    state.op_fail({"unit_id": resp["unit"]["id"]})   # parks (cap 1)
    assert state.op_status({})["parked"] == 1
    assert state.op_retry_parked({}) == {"ok": True, "retried": 1}
    assert state.op_status({})["parked"] == 0
    assert state.op_lease({"worker_id": "w1"})["unit"] is not None


def test_dispatcher_retry_count_resets_nothing_on_success():
    """Retries are per-unit: one unit's failures must not park a
    DIFFERENT unit, and a unit that eventually completes clears its
    tally."""
    d = Dispatcher(keyspace=512, unit_size=128, max_unit_retries=5)
    u1 = d.lease("w0")
    for _ in range(4):
        d.fail(u1.unit_id)
        u1 = d.lease("w0")
        assert u1 is not None
    d.complete(u1.unit_id)              # 4 failures then success
    assert d.parked_count() == 0
    while True:
        u = d.lease("w0")
        if u is None:
            break
        d.complete(u.unit_id)
    assert d.exhausted()


def test_resume_resplit_with_different_unit_size_exact_coverage():
    """Satellite regression (ISSUE 2): a session journaled under one
    unit size resumes under ANOTHER (adaptive sizing makes that the
    normal case) -- gap re-splitting with the new size must yield
    exact coverage: every uncovered index issued exactly once, no
    overlap with the journaled intervals."""
    keyspace = 10_000
    # intervals a previous run with odd adaptive sizes might journal
    completed = [(0, 37), (1000, 1771), (4096, 9001)]
    for new_size in (64, 300, 8192):
        d = Dispatcher.from_completed(keyspace, new_size, completed)
        issued = []
        while True:
            u = d.lease("w")
            if u is None:
                break
            issued.append((u.start, u.end))
            d.complete(u.unit_id)
        # disjoint among themselves and with the journaled coverage
        spans = sorted(issued + list(completed))
        for (s1, e1), (s2, e2) in zip(spans, spans[1:]):
            assert e1 <= s2, f"overlap: {(s1, e1)} vs {(s2, e2)}"
        assert sum(e - s for s, e in issued) == keyspace - sum(
            e - s for s, e in completed)
        assert d.exhausted()
        assert d.completed_intervals() == [(0, keyspace)]


def test_coordinator_rejects_unverifiable_hit_and_rescans(tmp_path):
    """A buggy device worker reporting a wrong plaintext must not poison
    the potfile: the local Coordinator re-hashes hits with the CPU
    oracle, rejects the fake, and exactly rescans the unit -- finding
    the true crack the buggy worker missed."""
    from dprf_tpu.engines import get_engine
    from dprf_tpu.generators.mask import MaskGenerator
    from dprf_tpu.runtime.coordinator import Coordinator, JobSpec
    from dprf_tpu.runtime.worker import Hit
    from dprf_tpu.runtime.workunit import WorkUnit

    oracle = get_engine("md5", device="cpu")
    gen = MaskGenerator("?l?l?l")
    secret = b"fox"
    target = oracle.parse_target(
        __import__("hashlib").md5(secret).hexdigest())

    class BuggyWorker:
        """Claims a wrong plaintext for the target, never the real one."""
        def __init__(self):
            self.gen = gen
            self.targets = [target]

        def process(self, unit: WorkUnit):
            if unit.start <= gen.index_of(secret) < unit.end:
                return [Hit(0, unit.start, b"zzz")]   # fake plaintext
            return []

    pot = Potfile(str(tmp_path / "pot"))
    spec = JobSpec(engine="md5", device="jax", attack="mask",
                   attack_arg="?l?l?l", keyspace=gen.keyspace,
                   fingerprint="t")
    disp = Dispatcher(gen.keyspace, 26 * 26)
    coord = Coordinator(spec, [target], disp, BuggyWorker(),
                        potfile=pot, oracle=oracle)
    result = coord.run()
    assert coord.rejected >= 1
    assert result.found == {0: secret}          # rescan found the truth
    assert pot.get(target.raw) == secret        # potfile never poisoned


def test_coordinator_cpu_path_trusts_worker(tmp_path):
    """oracle=None (the CPU path) records hits directly -- no double
    hashing of every CpuWorker hit."""
    from dprf_tpu.engines import get_engine
    from dprf_tpu.generators.mask import MaskGenerator
    from dprf_tpu.runtime.coordinator import Coordinator, JobSpec
    from dprf_tpu.runtime.worker import CpuWorker

    oracle = get_engine("md5", device="cpu")
    gen = MaskGenerator("?l?l")
    secret = b"ok"
    target = oracle.parse_target(
        __import__("hashlib").md5(secret).hexdigest())
    spec = JobSpec(engine="md5", device="cpu", attack="mask",
                   attack_arg="?l?l", keyspace=gen.keyspace,
                   fingerprint="t")
    disp = Dispatcher(gen.keyspace, 64)
    coord = Coordinator(spec, [target], disp,
                        CpuWorker(oracle, gen, [target]))
    result = coord.run()
    assert result.found == {0: secret} and coord.rejected == 0
