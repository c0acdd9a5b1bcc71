"""The generator: the same seed gives the same plan, large seeds work,
and the plants lie where the workload file says."""

import conftest
import reference
import traffic


WARM = 2


def _plan(cell, seed, seconds=1.5):
    c = traffic.load_json("workloads", cell + ".json", root=conftest.DATA)
    cfg = traffic.load_json("configs", c["config"] + ".json",
                            root=conftest.DATA)
    return cfg, c, traffic.make_plan(cfg, c, seed, seconds, WARM)


def test_same_seed_same_plan_and_large_seeds():
    a = _plan("tiny-ntlm.crack", 2**31 + 12345)[2]
    b = _plan("tiny-ntlm.crack", 2**31 + 12345)[2]
    c = _plan("tiny-ntlm.crack", 7)[2]
    assert a == b and a.lines != c.lines
    assert len(a.lines) == 40 == len(set(a.lines))
    assert a.lane_units == b.lane_units != c.lane_units


def test_a_list_seed_keeps_the_list_and_the_run_seed_orders_it():
    cfg, cell, _ = _plan("tiny-ntlm.crack", 1)
    cell = dict(cell, list_seed=5)
    a = traffic.make_plan(cfg, cell, 1, 1.5, WARM)
    b = traffic.make_plan(cfg, cell, 2**31 + 9, 1.5, WARM)
    assert a.plants == b.plants and a.skip == b.skip
    assert sorted(a.lines) == sorted(b.lines) and a.lines != b.lines
    # the lanes judged after the window still come from the run's seed
    assert a.lane_units != b.lane_units


def test_without_it_the_whole_list_moves_with_the_seed():
    a, b = _plan("tiny-ntlm.crack", 1)[2], _plan("tiny-ntlm.crack", 2)[2]
    assert a.skip == b.skip                  # the file's range is one unit
    assert set(a.lines).isdisjoint(b.lines)


def test_plants_lie_where_the_file_says():
    cfg, cell, plan = _plan("tiny-ntlm.crack", 99)
    unit = cfg["flags"]["unit_size"]
    w = plan.plants_in("window")
    assert len(w) == 3
    assert plan.window_start <= w[0].index < plan.window_start + unit
    assert w[0].index // 4096 == w[1].index // 4096 and \
        w[0].index != w[1].index
    for p in w:
        assert reference.candidate(cfg["mask"], p.index) == p.plain
        assert reference.ntlm(p.plain).hex() == p.line


def test_tail_plant_lies_behind_the_window_by_the_files_rate():
    cfg, cell, plan = _plan("tiny-md5.crack", 5, seconds=2.0)
    (p,) = plan.plants
    unit = cfg["flags"]["unit_size"]
    assert p.where == "tail" and plan.lines == [p.line]
    assert plan.window_start == plan.skip + WARM * unit
    behind = (p.index - plan.window_start) // unit
    assert behind == cell["tail_plant"]["units_per_s"] * 2.0
    assert reference.md5(reference.candidate(cfg["mask"], p.index)).hex() \
        == p.line
    # half the window, half the way
    (q,) = _plan("tiny-md5.crack", 5, seconds=1.0)[2].plants
    assert (q.index - plan.window_start) // unit == behind // 2


def test_lane_units_hold_the_plant_on_lanes_all_over_the_unit():
    cfg, cell, plan = _plan("tiny-md5.crack", 2**31 + 77)
    (p,) = plan.plants
    unit = cfg["flags"]["unit_size"]
    lanes = [p.index - s for s in plan.lane_units]
    assert len(lanes) == cell["lane_units"] == len(set(lanes))
    assert all(0 <= a < unit for a in lanes)
    assert min(lanes) < unit // 4 and max(lanes) > 3 * unit // 4
    batch = cfg["flags"]["batch"]
    assert len({(a // batch) % 4 for a in lanes}) == 4   # every shard


def test_lane_units_of_a_list_go_round_its_plants():
    cfg, cell, plan = _plan("tiny-ntlm.crack", 12)
    unit = cfg["flags"]["unit_size"]
    assert len(plan.lane_units) == cell["lane_units"] == 4
    for i, start in enumerate(plan.lane_units):
        p = plan.plants[i % len(plan.plants)]
        assert start <= p.index < start + unit
