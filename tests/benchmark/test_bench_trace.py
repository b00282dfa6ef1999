"""The trace reduction on a synthesized two-chip trace."""
import pytest

from bench import trace as T


def test_union_merges_overlaps_and_drops_empty():
    got = T.union([(3.0, 4.0, "c"), (0.0, 1.0, "a"), (0.5, 2.0, "b"),
                   (5.0, 5.0, "empty")])
    assert got == [(0.0, 2.0), (3.0, 4.0)]
    assert T.length(got) == pytest.approx(3.0)


def test_subtract_leaves_the_uncovered_parts():
    a = [(0.0, 10.0)]
    b = [(1.0, 2.0), (4.0, 6.0), (9.0, 12.0)]
    assert T.subtract(a, b) == [(0.0, 1.0), (2.0, 4.0), (6.0, 9.0)]
    assert T.subtract([(0.0, 1.0)], []) == [(0.0, 1.0)]


def _trace():
    # chip 0: compute 0-4, all-reduce 3-6 (1 s under compute, 2 s bare),
    # compute 8-9; chip 1: compute 0-5, all-reduce 5-6 (1 s bare)
    ops = {0: [(0.0, 4.0, "fusion.1"), (3.0, 6.0, "all-reduce.7"),
               (8.0, 9.0, "dot.2")],
           1: [(0.0, 5.0, "fusion.1"), (5.0, 6.0, "all-reduce.7")],
           2: [(0.0, 10.0, "not-a-chip-of-this-run")]}
    host = [(-1.0, 10.0, "bench.window"), (6.0, 7.5, "bench.build"),
            (7.5, 8.0, "bench.dispatch")]
    return ops, host


def test_busy_idle_and_exposed_collective():
    ops, host = _trace()
    r = T.reduce_events(ops, host, device_ids=[0, 1], steps=4)
    assert r.window_s == pytest.approx(11.0)
    # chip 0 busy 0-6 and 8-9 = 7 s; chip 1 busy 0-6 = 6 s
    assert r.busy_s == pytest.approx(6.5)
    assert r.idle_share == pytest.approx(1 - 6.5 / 11.0)
    # bare collective: chip 0 two seconds (4-6), chip 1 one (5-6)
    assert r.exposed_collective_s == pytest.approx(1.5)


def test_idle_gaps_named_by_the_host_span_covering_them():
    ops, host = _trace()
    r = T.reduce_events(ops, host, device_ids=[0, 1], steps=4)
    # chip 0 is idle -1-0 (nothing), 6-8 (mostly build), 9-10 (nothing)
    assert r.idle_gaps == [("build", pytest.approx(2.0)),
                           ("other", pytest.approx(1.0)),
                           ("other", pytest.approx(1.0))]


def test_top_ops_are_averaged_over_chips_and_sorted():
    ops, host = _trace()
    r = T.reduce_events(ops, host, device_ids=[0, 1], steps=4)
    names = [n for n, _ in r.top_ops]
    assert names[0] == "fusion.1"
    assert dict(r.top_ops)["fusion.1"] == pytest.approx(4.5)
    assert dict(r.top_ops)["all-reduce.7"] == pytest.approx(2.0)
    assert "not-a-chip-of-this-run" not in names


def test_only_a_collectives_own_opcode_makes_it_collective():
    assert T.COLLECTIVE.search("%all-reduce.7 = f32[8] all-reduce(%x)")
    assert T.COLLECTIVE.search(
        "%ar = (f32[8], f32[8]) all-reduce-start(%x, %y), channel_id=1")
    assert T.COLLECTIVE.search("all-gather.2")
    assert not T.COLLECTIVE.search(
        "%fusion.3 = f32[8] fusion(%all-reduce.7, %p), kind=kLoop")


def test_no_collective_reads_nothing():
    ops = {0: [(0.0, 1.0, "fusion.1")]}
    r = T.reduce_events(ops, [], device_ids=[0], steps=1)
    assert r.exposed_collective_s is None
    assert r.window_s == pytest.approx(1.0)      # from the ops themselves
    assert r.breakdown() == {"device_ops": [["fusion.1", 1.0]],
                             "idle_gaps": []}
