"""The reduction from a profiler trace to numbers: its arithmetic on
hand-made events, and the whole of it on small traces recorded on the
chip (``fixtures/``, written by ``record_fixture.py``: the Paxos
configuration searched to depth 4 on one chip and on four)."""

import glob
import gzip
import os

import pytest

from benchmark.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_merges_overlaps_and_drops_empty_intervals():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 9), (8, 12)]) == \
        [(0, 4), (5, 12)]
    assert trace.total(trace.union([(0, 10), (2, 3), (20, 25)])) == 15


def test_self_time_does_not_charge_a_while_for_its_body():
    events = [(0, 100, "while.1"), (10, 30, "fusion.2"),
              (40, 60, "fusion.2"), (45, 50, "copy.3"),
              (100, 120, "all-to-all.4")]
    got = {}
    for (_s, _e, name), secs in trace.self_by_event(events):
        got[name] = got.get(name, 0) + round(secs * 1e9)
    assert got == {"while.1": 60, "fusion.2": 35, "copy.3": 5,
                   "all-to-all.4": 20}


def test_clip_counts_only_what_lies_inside_the_host_spans():
    busy = [(0, 10), (20, 30), (40, 50)]
    assert trace.clip(busy, [(5, 25), (45, 100)]) == 5 + 5 + 5
    assert trace.clip(busy, []) == 0


def test_names_as_the_chip_writes_them():
    hlo = ("%copy.5894 = u32[2097152,8,4]{0,2,1:T(4,128)} "
           "copy(u32[2097152,8,4]{1,0,2:T(8,128)} %bitcast.5375)")
    assert trace.short_op(hlo) == "copy.5894 u32[2097152,8,4]"
    assert trace.short_op("%all-to-all.27 = (u32[8]{0}, u32[8]{0}) "
                          "all-to-all(...)").startswith("all-to-all.27 (")
    assert trace.short_op("dot_general.1") == "dot_general.1"
    assert trace.op_kind("all-to-all.27 (u32[8]{0}") == "all-to-all"
    assert trace.op_kind("fusion.370 u32[524289,4]") == "fusion"
    assert trace.program_name("jit__lambda(34703330079278)") == \
        "jit__lambda"


def _reduce_fixture(name, tmp_path):
    path = os.path.join(HERE, "fixtures", name + ".xplane.pb.gz")
    raw = tmp_path / (name + ".xplane.pb")
    raw.write_bytes(gzip.open(path, "rb").read())
    return trace.reduce(str(raw))


def _common(r, chips):
    assert r["devices"] == chips == len(r["busy_s_by_device"])
    # busy is a union: never more than the window, on any device
    assert all(0 < b <= r["window_s"]
               for b in r["busy_s_by_device"].values())
    assert r["idle_share"] == pytest.approx(
        1 - min(r["busy_s_by_device"].values()) / r["window_s"])
    # program runs are made of the operations inside them
    assert sum(r["programs"].values()) == pytest.approx(r["busy_s"],
                                                        rel=0.05)
    # self times partition the operations' union
    assert sum(r["op_self_s"].values()) == pytest.approx(r["busy_s"],
                                                         rel=1e-6)
    assert 0 < len(r["device_ops"]) <= 10 >= len(r["idle_gaps"])
    assert all(len(n) < 120 and s > 0 for n, s in r["device_ops"])
    assert all(s > 0 for _n, s in r["idle_gaps"])


def test_a_search_traced_on_four_chips(tmp_path):
    """``record_fixture.py paxos3-deep-mesh4 4`` (my chip run, PR 24):
    the Paxos configuration searched to depth 4 on the 2x2 mesh."""
    r = _reduce_fixture("paxos-depth4-4chips", tmp_path)
    _common(r, 4)
    assert r["busy_s"] == pytest.approx(0.64586, rel=1e-4)
    assert r["window_s"] == pytest.approx(0.98415, rel=1e-4)
    # the superstep is found by the host span that dispatched it, and is
    # nearly all of the device's work in a search
    assert 0.99 * r["busy_s"] < r["busy_in_span"]["superstep"] \
        <= r["busy_s"]
    assert r["busy_in_span"]["promote"] < 0.01 * r["busy_s"]
    # the owner-hashed exchange: all_to_all on every chip, a small share
    a2a = sum(s for n, s in r["op_self_s"].items()
              if trace.op_kind(n) == "all_to_all")
    assert 0 < a2a < r["collective_s"] < 0.02 * r["busy_s"]
    assert r["collective_s"] == pytest.approx(0.0048438, rel=1e-4)
    # the four levels are tiny: a third of the slice is the host between
    # dispatches, with no benchmark span open or inside the superstep
    assert 0.3 < r["idle_share"] < 0.4
    assert {n for n, _s in r["idle_gaps"]} <= {"none", "superstep",
                                               "promote", "level",
                                               "init"}


def test_two_lab_calls_traced_on_one_chip(tmp_path):
    """The first traced run of ``lab1-entry`` (my chip run, PR 24: an
    exhaust call and a goal call): the chip is idle nearly all the time
    and every idle second falls inside one of the calls."""
    r = _reduce_fixture("lab1-two-calls-1chip", tmp_path)
    _common(r, 1)
    assert r["collective_s"] == 0
    assert r["idle_share"] == pytest.approx(0.97634, rel=1e-4)
    assert set(r["busy_in_span"]) == {"call.exhaust", "call.goal"}
    assert sum(r["busy_in_span"].values()) == pytest.approx(
        r["busy_s"], rel=1e-3)
    assert {n for n, _s in r["idle_gaps"]} == {"call.exhaust",
                                               "call.goal"}
    assert sum(s for _n, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=0.01)
