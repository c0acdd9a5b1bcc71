"""The comparison that decides `correct`.

What a run has to show, from what the timed job left behind (its
potfile, its session journal, its own log) against the plain reference
(`reference.py`), once the window has closed:

`plants_missed`   planted passwords whose index lies inside an interval
    the job covered and that are not in its potfile with the reference's
    plaintext.  The plants are the reference's candidates at seeded
    indices, hashed by the reference: finding one takes the program's
    index decode, its hash kernel, its compare (or probe bitmap and the
    host verification behind it), the hit readback, the oracle and the
    potfile; twins in one tile take the collided-tile rescan.  A job
    with one target has its plant behind the window and finds it in
    its tail: a job that never covered it has missed it.
`lanes_missed`    of the further units the timed job's worker was handed
    after the job, each holding a plant on another lane drawn from the
    run's seed, those for which it did not report exactly the plants
    that lie in it, each with its index and the reference's plaintext.
    One target gives a job one lane that answers, and a fixed list the
    same lanes in every run; this puts them where the seed says.
`potfile_wrong`   potfile lines that are not a target of the job or
    whose plaintext the reference does not hash to that target (the
    engine's `matches`, `engines/<engine>.py`).
`audit_problems`  what `dprf audit` holds against the journal: an
    unreadable or dirty verdict, a coverage digest it cannot reproduce,
    a candidate covered twice, a hit recorded twice.
`coverage_off`    candidates by which the journal's covered set differs
    from what the harness saw leased and completed (the skipped prefix
    plus every unit, warm ones too, exactly once and without a hole).
`path_off`        statements of the job's own log that are not the
    cell's: the platform, as many devices as the cell has chips (in
    the device line, and under the outputs of a job on a mesh), and
    `interpret=False`: a compiled kernel, no plain XLA step
    (`interpret=n/a`) and no oracle worker in its place.

Every one is a count with the limit 0: the guarantees are exact.
"""

import engines
import reference

LIMITS = {"plants_missed": 0, "lanes_missed": 0, "potfile_wrong": 0,
          "audit_problems": 0, "coverage_off": 0, "path_off": 0}


def _covered(intervals, index):
    return any(s <= index < e for s, e in intervals)


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def plants_missed(plants, swept, potfile_lines):
    """(missed, inside): plants inside `swept` intervals not in the
    potfile with their plaintext; and how many were inside."""
    have = {(h, p) for h, p in potfile_lines}
    inside = [p for p in plants if _covered(swept, p.index)]
    return sum((p.line, p.plain) not in have for p in inside), len(inside)


def potfile_wrong(engine, target_lines, potfile_lines):
    targets, matches = set(target_lines), engines.load(engine).matches
    return sum(h not in targets or not matches(h, plain)
               for h, plain in potfile_lines)


def audit_problems(doc):
    n = len(doc.get("problems") or [])
    n += doc.get("verdict") not in ("clean", "incomplete")
    for j in doc.get("jobs") or []:
        n += j.get("digest_match") is not True
        n += bool(j.get("trace_overlap")) + bool(j.get("hit_dupes"))
    n += len(doc.get("jobs") or []) != 1
    return n


def coverage_off(doc, skip, units, keyspace):
    """Journal against the harness's own ledger of units."""
    tiles = _merge([(s, s + n) for s, n in units])
    swept = sum(n for _, n in units)
    off = 0
    if units and (len(tiles) != 1 or tiles[0][0] != skip
                  or tiles[0][1] - tiles[0][0] != swept):
        off += 1                  # a unit leased twice, or a hole
    jobs = doc.get("jobs") or [{}]
    want = skip + swept
    off += abs((jobs[0].get("covered") or 0) - want)
    gaps = [list(g) for g in jobs[0].get("gaps") or []]
    off += gaps != ([[want, keyspace]] if want < keyspace else [])
    return off


def lanes_missed(plan, said):
    """Units of `judge_lanes` for which the worker did not say exactly
    the plants that lie in them."""
    missed = 0
    for start, hits in zip(plan.lane_units, said):
        want = sorted((p.index, p.plain) for p in plan.plants
                      if start <= p.index < start + plan.unit_size)
        missed += hits is None or sorted(hits) != want
    return missed


def path_off(log, cell, platform, interpret):
    ran, dev = log.get("ran") or {}, log.get("device") or {}
    off = dev.get("platform") != platform
    off += ran.get("interpret") != str(interpret)
    if platform == "tpu":         # a CPU run has the devices it has
        off += dev.get("count") != cell["chips"]
    if cell["chips"] > 1:
        off += len(ran.get("out_devices", "").split("/")) != cell["chips"]
    return int(off)


def compare(plan, cell, obs, audit, lanes_said, platform="tpu",
            interpret=False):
    """-> (correct, numbers): numbers is {name: {"value", "limit"}} in
    the order of LIMITS, plus `plants_inside` and `lanes_judged` (no
    limit: how many the run could be judged on)."""
    units = obs["warm_units"] + obs["units"] + obs["tail_units"]
    done = [(s, n) for s, n, _, t in units if t is not None]
    swept = _merge([(s, s + n) for s, n in done])
    pot = reference.read_potfile(obs["potfile"])
    missed, inside = plants_missed(plan.plants_in("window"), swept, pot)
    tail = plan.plants_in("tail")
    found_tail = False
    if tail:
        m, i = plants_missed(tail, swept, pot)
        found_tail = (m, i) == (0, 1)
        missed += 0 if found_tail else 1
        inside += i
    lanes = lanes_missed(plan, lanes_said)
    wrong = potfile_wrong(plan.engine, plan.lines, pot)
    audit_n = audit_problems(audit)
    cov = coverage_off(audit, plan.skip, done, plan.keyspace)
    # a unit leased and never completed was not drained; only a job
    # that ended at its hit leaves the units behind it in flight
    if not found_tail:
        cov += sum(t is None for *_, t in units)
    path = path_off(obs["log"], cell, platform, interpret)
    values = {"plants_missed": missed, "lanes_missed": lanes,
              "potfile_wrong": wrong, "audit_problems": audit_n,
              "coverage_off": cov, "path_off": path}
    numbers = {k: {"value": int(values[k]), "limit": LIMITS[k]}
               for k in LIMITS}
    correct = all(v["value"] <= v["limit"] for v in numbers.values())
    numbers["plants_inside"] = {"value": inside, "limit": None}
    numbers["lanes_judged"] = {"value": len(lanes_said), "limit": None}
    return correct, numbers
