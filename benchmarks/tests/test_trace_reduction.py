"""The reduction from trace events to intervals, on events written by
hand; tests/test_recorded_trace.py holds it against a trace from the chip."""

import xplane as trace


def ev(s, e, name="op", where=""):
    return (s, e, name, where)


def test_union_counts_nested_and_overlapping_once():
    events = [ev(0, 10), ev(2, 5), ev(8, 14), ev(20, 25)]
    assert trace.union(events) == [[0, 14], [20, 25]]
    assert trace.busy_ns(events) == 19


def test_gaps_are_the_rest_of_the_window():
    events = [ev(2, 5), ev(8, 14)]
    assert trace.gaps(events, 0, 20) == [(0, 2), (5, 8), (14, 20)]


def test_clip_cuts_to_the_window():
    assert trace.clip([ev(0, 10), ev(12, 30), ev(40, 50)], 5, 20) == [
        (5, 10, "op", ""), (12, 20, "op", "")]


def test_leaves_only_drops_enclosing_events():
    loop = ev(0, 100, "while")
    body = [ev(0, 40, "a"), ev(40, 90, "call"), ev(45, 60, "b"),
            ev(60, 90, "c")]
    after = ev(120, 130, "d")
    names = sorted(e[2] for e in trace.leaves_only([loop, *body, after]))
    assert names == ["a", "b", "c", "d"]


def test_matching_looks_in_name_and_statistics():
    events = [ev(0, 1, "fusion.3", "jit(grow)/lgbm.split_step/dot"),
              ev(1, 2, "lgbm.histogram.kernel"), ev(2, 3, "copy")]
    assert len(trace.matching(events, ["lgbm.split_step"])) == 1
    assert len(trace.matching(events, ["lgbm."])) == 2


def test_top_ops_sums_by_name_in_seconds():
    events = [ev(0, 2e9, "a"), ev(3e9, 4e9, "a"), ev(5e9, 6e9, "b")]
    assert trace.top_ops(events, 1) == [["a", 3.0]]


def test_idle_goes_to_the_host_span_that_overlaps_it():
    idle = [(0, 10), (20, 30)]
    host = [(0, 6, "Booster.update"), (22, 30, "final sync")]
    got = dict(trace.attribute_gaps(idle, host))
    assert got == {"Booster.update": 6e-9, "final sync": 8e-9,
                   "(no span)": 6e-9}
