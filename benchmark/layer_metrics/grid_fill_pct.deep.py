"""Share of the expand grid that held an enabled event in the traced
level, in percent: the states the level explored (``explored`` less
``explored0`` of its ``search.level`` span: one a valid event of a valid
row) over the slots its chunk steps computed, ``chunks`` x the
configuration's ``chunk`` rows a chip x the chips x the event slots of a
row (the sum of ``engine.ev_budget``: message slots + timer slots).  The
rest is masked work: rows past the frontier's end in a level's last
chunk, and slots of the window that no event of the state fills.
Exact.  None where no whole level was traced or the span counts no
chunk steps."""

from benchmark.harness.program_spans import traced_level


def compute(run: dict):
    level = traced_level(run)
    if level is None or not level.get("chunks"):
        return None
    engine = run["config"]["engine"]
    slots = (int(level["chunks"]) * int(engine["chunk"]) * int(run["chips"])
             * sum(int(n) for n in engine["ev_budget"]))
    return 100.0 * (int(level["explored"]) - int(level["explored0"])) / slots
