"""The data of the staged lab cells (driver ``lab_phases``), checked
without running anything: a cycle cut to fit the run budget
(``benchmark/README.md``) must still be a cycle the driver can run, must
keep the one exact count ``correct`` leans on, and must say what it cut
in the same words everywhere."""

import json
import os

import pytest

from benchmark.drivers.lab_phases import GOAL_OF
from helpers import ROOT


def _staged_cells():
    """``(cell entry, traffic file, configuration entry, its file)`` of
    every cell whose traffic file names the driver ``lab_phases``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        man = json.load(fh)
    out = []
    for entry in man["workloads"]:
        with open(os.path.join(ROOT, man["paths"][0], "workloads",
                               entry["name"] + ".json")) as fh:
            traffic = json.load(fh)
        if traffic["driver"] != "lab_phases":
            continue
        cfg_entry = next(c for c in man["configs"]
                         if c["name"] == entry["config"])
        with open(os.path.join(ROOT, cfg_entry["file"])) as fh:
            out.append((entry, traffic, cfg_entry, json.load(fh)))
    return out


def _starts_are_earlier(entry, traffic, cfg_entry, config):
    """A phase starts from ``root`` or from the goal state of a phase
    EARLIER in the cycle: ``one_cycle`` has no other state to give it."""
    seen = []
    for name in traffic["params"]["cycle"]:
        start = config["phases"][name]["start"]
        assert start == "root" or (
            start.startswith(GOAL_OF)
            and start[len(GOAL_OF):] in seen), (name, start, seen)
        seen.append(name)


def _traced_phase_is_staged(entry, traffic, cfg_entry, config):
    """The traced slice is a call of the cycle that starts from a goal
    state: ``derive_root_s.suite`` and ``root_replay_events.suite`` read
    the provenance replay only such a call makes."""
    cycle = traffic["params"]["cycle"]
    traced = traffic["params"]["traced_phases"]
    assert traced and set(traced) <= set(cycle)
    for name in traced:
        assert config["phases"][name]["start"].startswith(GOAL_OF), name


def _reduced_is_what_the_cycle_leaves_out(entry, traffic, cfg_entry,
                                          config):
    cut = set(config["phases"]) - set(traffic["params"]["cycle"])
    assert len(set(config["reduced"])) == len(config["reduced"])
    assert set(config["reduced"]) == cut
    assert sorted(cfg_entry["reduced"]) == sorted(config["reduced"])
    # every phase keeps its pinned answer, cut or not: the CPU tests
    # run them all
    assert set(config["reference"]) == set(config["phases"])


def _cycle_keeps_an_exact_count(entry, traffic, cfg_entry, config):
    """``verify`` compares discovered counts only where the space was
    exhausted, and the control fails by that count alone (PERF.md §6):
    a cycle without such a phase would pass the control."""
    assert any(config["reference"][name]["end_condition"]
               == "SPACE_EXHAUSTED" for name in traffic["params"]["cycle"])


def _both_files_say_the_same(entry, traffic, cfg_entry, config):
    for key in ("name", "config", "traffic", "chips", "why"):
        assert traffic[key] == entry[key], key
    assert config["name"] == cfg_entry["name"]
    assert config["source"] == cfg_entry["source"]
    assert len(entry["why"]) <= 200


CHECKS = [_starts_are_earlier, _traced_phase_is_staged,
          _reduced_is_what_the_cycle_leaves_out,
          _cycle_keeps_an_exact_count, _both_files_say_the_same]


@pytest.mark.parametrize("check", CHECKS,
                         ids=[c.__name__.lstrip("_") for c in CHECKS])
@pytest.mark.parametrize("cell", _staged_cells(),
                         ids=lambda c: c[0]["name"])
def test_staged_cell_data(cell, check):
    check(*cell)


def test_there_is_a_staged_cell():
    assert "paxos3-suite" in [c[0]["name"] for c in _staged_cells()]
