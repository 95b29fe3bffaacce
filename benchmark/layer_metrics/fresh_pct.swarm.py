"""Of the window's advanced walker steps, the share that reached a state
the fleet's table had not seen (``fresh / explored``, the fleet's own
counters at the window's end; exact), in per cent: what a random search
finds for the steps it takes."""


def compute(run: dict):
    sd = run.get("swarm")
    if not run.get("trace") or not sd or not sd.get("explored"):
        return None
    return 100.0 * sd["unique"] / sd["explored"]
