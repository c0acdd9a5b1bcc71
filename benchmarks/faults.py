"""Faults planted under the timed path, and the control.

Each is a function of `dprf_tpu.cli` that returns the names to replace
in it for the length of the job (`entries/crack.py` puts its own
replacements around them).  `tests/test_harness.py` runs
each on the CPU at a tiny size and sees `correct` come out false;
`control.py` runs them on the chip at a cell's own size.  A measuring
run never loads this file.

The system states no precision, so the control breaks a guarantee the
configurations state: `hits_dropped` (the device's answers thrown away
at readback) breaks "every planted password inside a covered interval
is found".
"""


def hits_dropped(cli):
    """The control: what the device found is thrown away at readback."""
    real = cli._select_worker

    def select(*a, **kw):
        worker = real(*a, **kw)
        worker._decode_queued = lambda *a, **kw: []
        return worker

    return {"_select_worker": select}


def half_units(cli):
    """Half of every unit left out: the worker sweeps a unit's first
    half and the unit is completed as a whole."""
    real = cli._select_worker

    def select(*a, **kw):
        from dprf_tpu.runtime.workunit import WorkUnit
        worker = real(*a, **kw)
        submit = worker.submit
        worker.submit = lambda u: submit(
            WorkUnit(u.unit_id, u.start, u.length // 2))
        return worker

    return {"_select_worker": select}


def altered_answer(cli):
    """An answer altered where it is written: the potfile gets another
    plaintext than the one found."""
    from dprf_tpu.runtime.potfile import Potfile

    class Altered(Potfile):
        def add(self, key, plain):
            super().add(key, plain[:-1] + bytes([plain[-1] ^ 1]))

    return {"Potfile": Altered}


def range_swept_twice(cli):
    """A covered interval swept twice: after its fourth lease the
    dispatcher hands the third unit's range out again."""
    real = cli._setup_job

    def setup(*a, **kw):
        job = real(*a, **kw)
        inner = job.dispatcher
        lease, seen = inner.lease, []

        def again(worker_id="local"):
            unit = lease(worker_id)
            if unit is not None:
                if len(seen) == 3:
                    inner._next_start = seen[2]     # rewind the frontier
                seen.append(unit.start)
            return unit

        inner.lease = again
        return job

    return {"_setup_job": setup}


class NoExchange:
    """`parallel/sharded.py`'s `lax` with the exchange between chips
    left out: every shard keeps its own hits, the host reads shard
    0's.  Set it as `dprf_tpu.parallel.sharded.lax`."""

    def __init__(self, n_devices):
        self._n = n_devices

    def __getattr__(self, name):
        from jax import lax
        return getattr(lax, name)

    def psum(self, x, axis):
        return x

    def all_gather(self, x, axis):
        import jax.numpy as jnp
        return jnp.stack([x] * self._n)


FAULTS = {"hits_dropped": hits_dropped, "half_units": half_units,
          "altered_answer": altered_answer,
          "range_swept_twice": range_swept_twice}
