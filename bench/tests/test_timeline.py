"""The trace reduction: nesting and self time, busy time as a union,
idle gaps named by the host phase, and the exposed part of collective
time; on a small made-up trace and on traces recorded on the chip."""
from pathlib import Path

import pytest

import timeline

RECORDED = sorted((Path(__file__).parent / "data").glob("timeline-*.json.gz"))


def small():
    """One chip, times in ns; the host annotations bound the window
    [0, 100).  A loop (while.1, 0-30) holds two fusions; an all-reduce
    at 30-50 runs after it, fusion.4 inside its span."""
    events = [
        ["%while.1 = (s32[]) while(...)", 0, 30],
        ["%fusion.1 = f32[8] fusion(...)", 0, 20],
        ["%fusion.2 = f32[8] fusion(...)", 20, 28],
        ["%all-reduce.3 = f32[8] all-reduce(...)", 30, 50],
        ["%fusion.4 = f32[8] fusion(...)", 40, 45],
        ["%copy.5 = f32[8] copy(...)", 70, 80],
        ["%fusion.6 = f32[8] fusion(...)", 95, 120],
    ]
    red = timeline.reduce_line(events)
    red["leaves"] = timeline.near(red["leaves"], red["collectives"])
    host = [["bench.dispatch", 0, 10], ["bench.fetch", 50, 90],
            ["bench.batch", 55, 65], ["bench.dispatch", 90, 100]]
    return {"devices": {"0": red}, "host": host}


def test_nesting_and_self_time():
    dev = small()["devices"]["0"]
    assert [t[0] for t in dev["top"]] == [
        "while.1 (s32[])", "all-reduce.3 f32[8]", "copy.5 f32[8]",
        "fusion.6 f32[8]"]
    assert dev["self"]["while.1 (s32[])"] == 30 - 20 - 8
    assert dev["self"]["fusion.1 f32[8]"] == 20
    assert dev["collectives"] == [["all-reduce.3 f32[8]", 30, 50]]


def test_short_names_keep_the_result_type():
    assert timeline.short("%fusion.3 = bf16[8,128]{1,0:T(8,128)} "
                          "fusion(bf16[8,128]{1,0} %p)") == \
        "fusion.3 bf16[8,128]"
    assert timeline.short("%while.2 = (s32[]{:T(128)}, f32[4]{0}) "
                          "while((s32[], f32[4]) %t)") == \
        "while.2 (s32[], f32[4])"
    assert timeline.short("all-reduce.1") == "all-reduce.1"


def test_a_fusion_that_calls_a_collective_is_one():
    red = timeline.reduce_line([
        ["%fusion.7 = f32[8] fusion(f32[8] %p), kind=kCustom, "
         "calls=%all-reduce-scatter.2", 0, 10],
        ["%fusion.8 = f32[8] fusion(f32[8] %p), calls=%fused_add", 10, 20]])
    assert red["collectives"] == [["fusion.7 f32[8]", 0, 10]]
    assert red["leaves"] == [[10, 20]]


def test_union_and_busy():
    tl = timeline.Timeline(small())
    assert tl.window_s == pytest.approx(100e-9)
    # fusion.4 lies inside the all-reduce's span: busy 0-50, 70-80, 95-100
    assert tl.busy_ns("0") == 50 + 10 + 5
    assert tl.idle_share() == pytest.approx(1 - 65 / 100)


def test_collective_exposure():
    tl = timeline.Timeline(small())
    total, exposed = tl.collective_ns("0")
    assert total == 20
    # fusion.4 hides 40-45 of the all-reduce: bare are 30-40 and 45-50
    assert exposed == 15


def test_idle_gaps_named_by_host_phase():
    tl = timeline.Timeline(small())
    assert tl.idle_gaps("0") == [(50, 70), (80, 95)]
    bd = tl.breakdown()
    assert [g[0] for g in bd["idle_gaps"]] == ["bench.fetch", "bench.fetch"]
    assert bd["idle_gaps"][0][1] == pytest.approx(20e-9)
    assert bd["device_ops"][0] == ["fusion.6 f32[8]", pytest.approx(25e-9)]


def test_subtract_and_union():
    assert timeline.union([(5, 7), (0, 3), (2, 4)]) == [[0, 4], [5, 7]]
    assert timeline.subtract([(0, 10)], [[2, 3], [5, 12]]) == \
        [(0, 2), (3, 5)]
    assert timeline.near([[0, 5], [10, 20], [30, 40]],
                         [["all-reduce", 12, 31]]) == [[10, 20], [30, 40]]


def test_a_trace_is_recorded():
    assert RECORDED


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_trace(path):
    tl = timeline.Timeline(timeline.load(path))
    assert 0 < tl.busy_s <= tl.window_s
    for c in tl.chips:
        total, exposed = tl.collective_ns(c)
        assert 0 <= exposed <= total
        assert sum(e - s for s, e in tl.idle_gaps(c)) + tl.busy_ns(c) \
            == tl.t1 - tl.t0
    bd = tl.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    # self times of a chip add up to its busy time over the whole trace
    for c in tl.chips:
        dev = tl.plain["devices"][c]
        assert sum(dev["self"].values()) == sum(e - s for _, s, e
                                                in dev["top"])
