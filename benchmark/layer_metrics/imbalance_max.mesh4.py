"""Max over mean of the states each chip explored in the traced level
(``levels[*].per_device.explored``): 1.0 is an even split."""

from benchmark.harness.levels import traced_level


def compute(run: dict):
    got = traced_level(run)
    if got is None:
        return None
    per = (got[0].get("per_device") or {}).get("explored")
    if not per or len(per) < 2 or not sum(per):
        return None
    return max(per) / (sum(per) / len(per))
