"""Of the window's walker steps — every walker takes one a step of the
fleet, advancing or not: walkers x rounds x steps a round — the share
that ended a probe (a prune, the depth bound, a dead end, or in a fleet
that is not strict a truncation: the fleet's ``restarts``; exact), in per
cent: how often a walker is sent back to the root."""


def compute(run: dict):
    sd = run.get("swarm")
    if not run.get("trace") or not sd or not sd.get("rounds"):
        return None
    steps = sd["walkers"] * sd["rounds"] * run["steps_per_round"]
    return 100.0 * sd["restarts"] / steps
