"""Shared by the rehearsals: a cell at tiny caps, and a run of it with
the look for a chip left out."""

import dataclasses
import io
import json
import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# The device block the chip check would have returned.  The platform is
# the one the rehearsal really runs on; the kind is the v5e's, so that
# the table of peaks is exercised.
DEV = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
TINY_ENGINE = dict(chunk=64, frontier_cap=1 << 12, visited_cap=1 << 15)


def tiny_cell(name: str, root: str = ROOT, **params):
    """Cell ``name`` with the engine's caps cut to what a CPU test holds
    (the protocol's shapes stay) and ``params`` over the traffic's."""
    from benchmark.harness import manifest

    cell = manifest.load_cell(root, name)
    config = dict(cell.config)
    if "engine" in config:
        config["engine"] = dict(config["engine"], **TINY_ENGINE)
        config["must_pass_depth"] = 3
    workload = dict(cell.workload,
                    params=dict(cell.params, **params))
    return dataclasses.replace(cell, config=config, workload=workload)


def run_cell(cell, seed=2**31 + 17, seconds=2.0, trace=False):
    """``(result, lines)`` of one run through ``runner.run``."""
    from benchmark.harness import runner

    out = io.StringIO()
    result = runner.run(cell, seed, seconds, trace,
                        dict(DEV, count=cell.chips), time.time(), out=out)
    lines = out.getvalue().strip().splitlines()
    assert json.loads(lines[-1]) == json.loads(json.dumps(result))
    return result, lines
