"""Seconds of one STAGED lab call under ``entry.derive_root`` — the
chunk-1 replayer built and compiled (``entry.root.build``) and the
start state's ``TensorProvenance`` history replayed through it, one host
round trip an event (``entry.root.replay``) — mean per call of the
traced slice, from the program's own phases on the trace's clock.  The
two children's seconds go to stderr beside it (a program from before
PR 27 has none)."""

import sys

from benchmark.harness.call_notes import mean_per_call
from benchmark.harness.program_spans import secs, stage_seconds

CHILDREN = ("entry.root.build", "entry.root.replay")


def compute(run: dict):
    total = stage_seconds(run, ("entry.derive_root",))
    parts = {name: mean_per_call(run, lambda notes, name=name: sum(
        secs(n) for n in notes if n["name"] == name) or None)
        for name in CHILDREN}
    if any(v is not None for v in parts.values()):
        print("info derive_root by part, mean seconds per staged call: "
              + ", ".join(f"{k} {v:.4f}" for k, v in parts.items()
                          if v is not None)
              + f"; entry.derive_root {total:.4f}", file=sys.stderr,
              flush=True)
    return total
