"""A new cell, configuration, driver and per-layer metric are found as
files plus ``BENCHMARK.json`` entries, with no edit to a file that is
there."""

import json
import os
import shutil

import pytest

from benchmark.harness import manifest

from helpers import ROOT


@pytest.fixture
def copy(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root


def test_every_cell_of_the_manifest_resolves():
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in man["workloads"]:
        cell = manifest.load_cell(ROOT, w["name"])
        assert cell.chips == w["chips"]
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for fn in ("prepare", "measure", "verify", "end_to_end"):
            assert callable(getattr(cell.driver, fn))
        # every per-layer metric's `moves` is reported in the cell
        reported = {m.name for m in cell.end_to_end}
        assert all(m.moves in reported for m in cell.per_layer)


def test_new_files_are_found_without_editing_the_harness(copy):
    before = {p: open(os.path.join(dp, p)).read()
              for dp, _d, fs in os.walk(copy / "benchmark")
              for p in fs if p.endswith((".py", ".json"))}
    bench = copy / "benchmark"
    (bench / "configs" / "toy-config.json").write_text(json.dumps(
        {"name": "toy-config", "knob": 7, "reduced": []}))
    (bench / "workloads" / "toy-cell.json").write_text(json.dumps(
        {"name": "toy-cell", "config": "toy-config", "traffic": "toy",
         "driver": "toy_driver", "chips": 1, "params": {"n": 3}}))
    (bench / "drivers" / "toy_driver.py").write_text(
        "def prepare(ctx): ctx.state['n'] = ctx.cell.params['n']\n"
        "def measure(ctx, seconds):\n"
        "    return {'attempted': ctx.state['n'], 'failed': 0}\n"
        "def verify(ctx, run): return []\n"
        "def end_to_end(ctx, run): return {'toy_rate': 1.5}\n")
    (bench / "layer_metrics" / "toy_count.new.py").write_text(
        "def compute(run): return float(run['attempted'])\n")
    man = json.loads((copy / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "toy-config", "source": "none",
                           "file": "benchmark/configs/toy-config.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "toy-cell", "config": "toy-config",
                             "traffic": "toy", "chips": 1, "why": "test"})
    man["end_to_end"].append({"name": "toy_rate", "unit": "x/s",
                              "better": "higher", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["toy-cell"]})
    man["per_layer"].append({"name": "toy_count.new", "unit": "count",
                             "better": "higher",
                             "source": "program_counter", "layer": "toy",
                             "moves": "toy_rate",
                             "workloads": ["toy-cell"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(man))

    cell = manifest.load_cell(str(copy), "toy-cell")
    assert cell.config["knob"] == 7 and cell.params == {"n": 3}
    assert [m.name for m in cell.end_to_end] == ["setup_s", "toy_rate"]
    assert [m.name for m in cell.per_layer] == ["toy_count.new"]
    assert cell.per_layer[0].compute({"attempted": 3}) == 3.0
    # ... the old cells still resolve, and no file that was there changed
    assert manifest.load_cell(str(copy), "paxos3-deep").per_layer
    after = {p: open(os.path.join(dp, p)).read()
             for dp, _d, fs in os.walk(bench)
             for p in fs if p in before}
    assert after == before

    # and the new cell runs through the same runner to a last line
    from benchmark.harness import runner
    import io, time  # noqa: E401
    out = io.StringIO()
    res = runner.run(cell, 1, 0.1, False,
                     {"platform": "cpu", "kind": "TPU v5 lite",
                      "count": 1}, time.time(), out=out)
    assert res["metrics"]["toy_rate"] == {"value": 1.5, "unit": "x/s"}
    assert res["attempted"] == 3 and res["correct"] is True


def test_a_metric_without_workloads_follows_what_it_moves(copy):
    man = json.loads((copy / "BENCHMARK.json").read_text())
    for m in man["per_layer"]:
        if m["name"] == "peak_hbm_gb":
            del m["workloads"]
    (copy / "BENCHMARK.json").write_text(json.dumps(man))
    names = {c: {m.name for m in
                 manifest.load_cell(str(copy), c).per_layer}
             for c in ("paxos3-deep", "lab1-entry")}
    assert "peak_hbm_gb" in names["paxos3-deep"]      # reports states_per_s
    assert "peak_hbm_gb" not in names["lab1-entry"]   # does not


def test_unknown_names_are_errors(copy):
    with pytest.raises(manifest.ManifestError, match="no cell"):
        manifest.load_cell(str(copy), "nope")
    os.remove(copy / "benchmark" / "layer_metrics" / "peak_hbm_gb.py")
    with pytest.raises(manifest.ManifestError, match="peak_hbm_gb"):
        manifest.load_cell(str(copy), "paxos3-deep")
