"""Device microseconds of the level PROMOTE per state the traced level
explored: the seconds (per chip) of the programs ``jit_promote`` and
``jit_promote_rebase`` inside the traced slice — the slice opens before
the promote INTO the traced level and closes when that level does, so
it holds exactly one — over the states the level explored.  Every
operation of those programs names the scope ``promote``; a twin with
delta lanes (``Field(delta=)``) runs ``jit_promote_rebase`` where the
level base moved, whose re-encode names ``promote.rebase`` besides
(``tpu/sharded.py`` ``_rebase_rows``).  The reader prints the re-base's
part beside the whole on stderr.  None where no whole level was traced,
or from a program whose promote is not in the slice."""

import sys

from benchmark.harness.levels import traced_level

PROMOTE, REBASE = "jit_promote", "jit_promote_rebase"


def compute(run: dict):
    got = traced_level(run)
    programs = (run.get("trace") or {}).get("programs") or {}
    mine = {k: v for k, v in programs.items() if k in (PROMOTE, REBASE)}
    if got is None or not mine or not got[1]:
        return None
    _lv, explored, _expanded = got
    per_state = 1e6 / explored
    print(f"info promote, traced level: {per_state * sum(mine.values()):.6f}"
          f" us/state over {explored} states explored, of which the "
          f"re-base ({REBASE}) {per_state * mine.get(REBASE, 0.0):.6f}; "
          f"device seconds {mine}", file=sys.stderr, flush=True)
    return per_state * sum(mine.values())
