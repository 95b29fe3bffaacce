"""History events replayed to derive one staged call's root: the
``events`` field of its ``entry.root.replay`` spans (one a ladder
attempt), mean per staged call of the traced slice.  Exact: the depth of
the goal state the call started from.  The span's other field,
``staged_ops`` (the history's drops and undrops, which no device step
replays), goes to stderr beside it."""

import sys

from benchmark.harness.call_notes import mean_per_call


def _field(field):
    def value(notes):
        replays = [n for n in notes if n["name"] == "entry.root.replay"]
        if not replays:
            return None         # not a staged call, or no such span
        return float(sum(int(n[field]) for n in replays))
    return value


def compute(run: dict):
    events = mean_per_call(run, _field("events"))
    if events is not None:
        print(f"info root replay, mean per staged call: events "
              f"{events:g}, staged_ops "
              f"{mean_per_call(run, _field('staged_ops')):g}",
              file=sys.stderr, flush=True)
    return events
