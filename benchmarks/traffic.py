"""The one traffic generator: a workload file's parameters and a seed
-> the inputs of one run (a `Plan`).

A workload file (`workloads/<cell>.json`) holds only numbers; what they
mean is written here, once, so that a later PR can bring a new cell as
a data file alone:

`sweep_start_units`  `[lo, hi)`: the job starts its sweep at a
    unit-aligned index drawn from the seed in that range of whole
    units.
`fillers`  that many lines no candidate matches (the engine's
    `filler_line`: uniformly random digests).
`window_plants`  a list of {"at_units": [lo, hi), "twin": bool}: one
    planted password each, at `window start + u * unit_size` with `u`
    drawn from the seed in `[lo, hi)`; a twin is a second plant inside
    the same aligned block of `twin_block` candidates (the kernel's
    tile today: two hits in one tile take the collided-tile rescan).
    The window starts `warm_units` units (the entry driver's) after
    the sweep's start.
`tail_plant`  {"units_per_s"}, for a job that ends at its first hit
    (one target): the plant lies `units_per_s x --seconds` whole units
    behind the window's start, at a lane of that unit drawn from the
    seed: a little further than the cell sweeps in its window today,
    so the job reaches it in a short tail after the clock has closed
    the window (a program that has become that much faster reaches it
    sooner, and its hit closes the window).
`lane_units`  so many further units of the job's unit length, each
    around one of the plants (in turn) with its start drawn from the
    run's seed, so that the plant falls on another lane of the unit
    each time: the worker that swept the window is handed them after
    the job, and has to report just the plants that lie in each.
`list_seed`  a number draws the start, the plants and the fillers from
    it instead of the run's seed: the same list and range for every
    run, and the run's seed then orders the lines of the hash file and
    draws the lane units.  A list's prefilter passes other candidates
    for other digests, and two of them in one tile cost the host a
    rescan: with only the 8 plants of 1,000 lines drawn from the run's
    seed, `ntlm-1k.crack`'s rate moved by a tenth from seed to seed
    and not at all between two runs of one seed (PERF.md, 6).  A cell
    that is to do the same work on every seed keeps its whole list.

The same seed gives the same plan.  Everything else of a run -- mask,
engine, batch and unit flags -- is the configuration's.
"""

import dataclasses
import json
import math
import os
import random

import engines
import reference

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts, root=None):
    """A data file of the benchmark (tests keep tiny ones of their own
    under another root)."""
    with open(os.path.join(root or HERE, *parts)) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Plant:
    index: int
    plain: bytes
    line: str            # the target's hash line
    where: str           # "window" | "tail"


@dataclasses.dataclass
class Plan:
    seed: int
    mask: str
    engine: str
    keyspace: int
    unit_size: int
    skip: int                       # the window job's --skip
    window_start: int               # skip + warm units
    lines: list                     # the hash file's lines
    plants: list                    # [Plant]
    lane_units: list = ()           # starts of the units of judge_lanes

    def plants_in(self, where):
        return [p for p in self.plants if p.where == where]


def make_plan(cfg, cell, seed, seconds, warm_units, root=None):
    """Configuration + workload parameters + seed (+ the window's
    length and the entry driver's warm units) -> Plan.  The
    configuration's engine (`engines/<engine>.py`, searched under
    `root` too) writes the hash file's lines; one that has no module is
    an error here, naming the missing file."""
    engine = engines.load(cfg["engine"], root)
    order = random.Random(int(seed))
    fixed = cell.get("list_seed")
    rng = order if fixed is None else random.Random(int(fixed))
    frng = rng if fixed is None else random.Random(int(fixed))
    unit = int(cfg["flags"]["unit_size"])
    keyspace = reference.keyspace(cfg["mask"])
    lo, hi = cell["sweep_start_units"]
    skip = rng.randrange(lo, hi) * unit
    start = skip + int(warm_units) * unit
    plants = []

    def plant(index, where):
        plain = reference.candidate(cfg["mask"], index)
        plants.append(Plant(index, plain,
                            engine.target_line(plain, rng, cfg), where))

    for spec in cell.get("window_plants", []):
        a, b = spec["at_units"]
        index = start + int(rng.uniform(a, b) * unit)
        plant(index, "window")
        if spec.get("twin"):
            block = int(cell["twin_block"])
            base = index - index % block
            twin = base + rng.randrange(block - 1)
            twin += twin >= index           # any lane but the plant's
            plant(twin, "window")
    if cell.get("tail_plant"):
        behind = math.ceil(float(cell["tail_plant"]["units_per_s"])
                           * float(seconds))
        plant(start + behind * unit + rng.randrange(unit), "tail")
    lines = [engine.filler_line(frng, cfg)
             for _ in range(int(cell.get("fillers", 0)))]
    lines += [p.line for p in plants]
    order.shuffle(lines)
    lane_units = [plants[i % len(plants)].index - order.randrange(unit)
                  for i in range(int(cell.get("lane_units", 0)))]
    if len(set(lines)) != len(lines) or max(
            p.index for p in plants) + unit >= keyspace:
        raise ValueError("workload parameters do not fit the keyspace")
    return Plan(int(seed), cfg["mask"], cfg["engine"], keyspace, unit,
                skip, start, lines, plants, lane_units)
