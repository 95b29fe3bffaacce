"""Share of the window's closed levels whose promote RE-BASED the
frontier, in percent: of the run's level records, those with
``rebased`` = 1 — the minimum of some delta lane (``Field(delta=)``: lab
2's view numbers) over the level's successors differs from the base the
level was packed against, so the promote re-encodes every row the level
appended (``rebase_rows``) before the next level reads them.  Exact: the
level records are the program's own counters.  0 says the re-encode
never ran in the window and the promote moved counters only, as every
other twin's does.  None from a program whose level records carry no
``rebased`` (a twin without delta lanes, or a program before PR 47)."""


def compute(run: dict):
    levels = [lv for lv in run.get("levels") or [] if "rebased" in lv]
    if not levels:
        return None
    return 100.0 * sum(int(lv["rebased"]) for lv in levels) / len(levels)
