"""Device time in collective operations (the owner-hashed all-to-all and
the level sync's reductions) over device busy time, traced slice, %."""


def compute(run: dict):
    tr = run.get("trace")
    if not tr or run["chips"] < 2 or not tr["busy_s"]:
        return None
    return 100.0 * tr["collective_s"] / tr["busy_s"]
