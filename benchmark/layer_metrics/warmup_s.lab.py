"""Seconds of set-up spent in the one warm pass of the cycle's calls
(each builds its engine and compiles or loads its programs)."""


def compute(run: dict):
    return run.get("warmup_s")
