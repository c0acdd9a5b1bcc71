"""An engine is a file (`engines/<engine>.py`): the plans MD5 and NTLM
make through the seam are the parent's to the byte, the counts are the
parent's, a third engine comes as new files alone, and `wpa2_pmkid`
holds hashcat's published example."""

import hashlib
import json
import os
import random

import pytest

import conftest
import engines
import faults
import reference
import run
import traffic

WARM = 2
with open(os.path.join(conftest.HERE, "golden_plans.json")) as _fh:
    GOLDEN = json.load(_fh)["cells"]
TINY = {f[:-5] for f in os.listdir(os.path.join(conftest.DATA, "workloads"))}


def plan_digest(plan):
    return hashlib.sha256(repr((
        plan.skip, plan.window_start, plan.lines,
        [(p.index, p.plain, p.line, p.where) for p in plan.plants],
        list(plan.lane_units))).encode()).hexdigest()


# -- MD5 and NTLM behind the seam ---------------------------------------------

@pytest.mark.parametrize("seed", [1, 2**31 + 17, 3500000607])
@pytest.mark.parametrize("cell", sorted(GOLDEN))
def test_the_same_seed_gives_the_parent_s_plan_to_the_byte(cell, seed):
    """The five real cells and the tiny ones: the order of the draws
    from the generators, the fillers' hex and the plants' lines are
    what they were before an engine was a file."""
    root = conftest.DATA if cell in TINY else None
    c = traffic.load_json("workloads", cell + ".json", root=root)
    cfg = traffic.load_json("configs", c["config"] + ".json", root=root)
    plan = traffic.make_plan(cfg, c, seed, GOLDEN[cell]["seconds"], WARM)
    assert plan_digest(plan) == GOLDEN[cell]["digests"][str(seed)]


@pytest.mark.parametrize("engine,length,targets,ops", [
    ("md5", 9, 1, 437), ("md5", 6, 1, 384), ("ntlm", 7, 1000, 285)])
def test_the_counts_through_the_seam(engine, length, targets, ops):
    import work
    cfg = {"engine": engine, "targets": targets}
    assert engines.load(engine).ops_per_candidate(length, cfg) == ops
    plant = traffic.Plant(0, b"x" * length, "", "tail")
    plan = traffic.Plan(0, "", engine, 0, 0, 0, 0, [], [plant])
    assert work.ops_of({"cfg": cfg, "plan": plan}) == ops


def test_an_engine_with_no_module_fails_when_the_plan_is_made():
    cell = traffic.load_json("workloads", "tiny-md5.crack.json",
                             root=conftest.DATA)
    cfg = dict(traffic.load_json("configs", "tiny-md5.json",
                                 root=conftest.DATA), engine="no-such")
    with pytest.raises(LookupError) as e:
        traffic.make_plan(cfg, cell, 1, 1.5, WARM)
    assert os.path.join("benchmarks", "engines", "no_such.py") in str(e.value)


def test_the_old_names_still_hash():
    assert reference.digest_hex("md5", b"password") == \
        "5f4dcc3b5aa765d61d8327deb882cf99"
    assert reference.digest_hex("ntlm", b"password") == \
        reference.ntlm(b"password").hex() == reference.md4(
            "password".encode("utf-16-le")).hex()


# -- a third engine as new files only ------------------------------------------

def measure_sha1(tmp_path, planted=None):
    import jax
    bench = {"workloads": [{"name": "tiny-sha1.crack"}], "per_layer": [],
             "end_to_end": [{"name": n, "unit": "x"}
                            for n in ("cand_per_s", "setup_s")]}
    return run.measure("tiny-sha1.crack", 2**31 + 36, 1.5, False,
                       jax.devices(), str(tmp_path / "wd"), platform="cpu",
                       interpret=True, faults=planted, bench=bench,
                       data_root=conftest.DATA, reach_chip_s=0.0)


def test_a_third_engine_is_new_files_under_the_data_root(tmp_path):
    """`sha1`: its module, configuration and workload all lie under
    `tests/data/`; the benchmark's own `engines/` does not hold it."""
    assert not os.path.exists(os.path.join(engines.HERE, "sha1.py"))
    r = measure_sha1(tmp_path)
    assert r["correct"], r["compared"]
    assert r["compared"]["potfile_wrong"]["value"] == 0
    assert r["compared"]["plants_inside"]["value"] == 1
    assert r["ran"]["worker"] == "PallasMaskWorker"


def test_its_altered_potfile_is_wrong_by_its_own_matches(tmp_path):
    r = measure_sha1(tmp_path, {"patches": faults.altered_answer})
    assert not r["correct"]
    assert r["compared"]["potfile_wrong"]["value"] == 1


# -- wpa2_pmkid -------------------------------------------------------------------

#: hashcat's published example for mode 16800, password `hashcat!`
HASHCAT_16800 = ("2582a8281bf9d4308d6f5731d0e61c61*4604ba734d4e*"
                 "89acf0e761f4*ed487162465a774bfba60eb603a39f3a")


def test_pmkid_holds_hashcat_s_example():
    e = engines.load("wpa2-pmkid")
    assert e.matches(HASHCAT_16800, b"hashcat!")
    assert not e.matches(HASHCAT_16800, b"hashcat?")
    ap = HASHCAT_16800.replace("*4604ba734d4e*", "*4604ba734d4f*")
    sta = HASHCAT_16800.replace("*89acf0e761f4*", "*89acf0e761f5*")
    assert not e.matches(ap, b"hashcat!")
    assert not e.matches(sta, b"hashcat!")
    assert not e.matches("no*such*line", b"hashcat!")


def test_pmkid_lines_from_the_seed():
    e = engines.load("wpa2-pmkid")
    a = e.target_line(b"12345678", random.Random(7), {})
    assert a == e.target_line(b"12345678", random.Random(7), {})
    assert a != e.target_line(b"12345678", random.Random(8), {})
    assert e.matches(a, b"12345678") and not e.matches(a, b"12345679")
    pmkid, ap, sta, essid = a.split("*")
    assert [len(x) for x in (pmkid, ap, sta)] == [32, 12, 12]
    assert 1 <= len(bytes.fromhex(essid)) <= 32
    # the program's parser takes the line as it is written
    from dprf_tpu import get_engine
    t = get_engine("wpa2-pmkid", "cpu").parse_target(a)
    assert t.raw == a and t.params["essid"] == bytes.fromhex(essid)


def test_a_pmkid_filler_matches_no_candidate_of_the_mask():
    e = engines.load("wpa2-pmkid")
    rng = random.Random(2**31 + 5)
    lines = [e.filler_line(rng, {}) for _ in range(3)]
    assert len(set(lines)) == 3
    for line in lines:
        assert [len(x) for x in line.split("*")[:3]] == [32, 12, 12]
        for i in (0, 1, 12345678, 99999999):
            assert not e.matches(line, reference.candidate("?d" * 8, i))


def test_pmkid_count_and_the_compressions_under_it():
    e = engines.load("wpa2-pmkid")
    blocks = e.compressions(8)
    assert sum(n for _, n in blocks) == 2 + 2 * 2 * 4096 + 4 == 16390
    ops = e.ops_per_candidate(8, {"targets": 1})
    assert 1.4e7 < ops < 1.7e7
    # a compression with nothing folded: 80 steps, 64 schedule words of
    # three xors and a rotate, five adds (FIPS 180-4, 6.1.2)
    full = 20 * (6 + 3) + 40 * (6 + 2) + 20 * (6 + 4) + 64 * 4 + 5
    assert e.compression_ops([None] * 16) == full == 961
    # a block that is the same for every candidate: no schedule, and
    # `K + W[t]` one constant
    assert e.compression_ops([0] * 16) == full - 64 * 4 - 80
    assert all(625 <= e.compression_ops(b) <= full for b, _ in blocks)
    # the rate `engines/device/pmkid.py`'s docstring states cannot pass
    # the measured peak
    import work
    assert 156.5e3 * ops < work.peak_int32("TPU v5 lite")
