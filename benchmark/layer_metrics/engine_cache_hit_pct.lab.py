"""Share of one lab call's engines that the lab entry had kept from an
earlier call (``tpu/backend.py``: twin, engine with its traced and
compiled programs, trace step, in one bounded table): of the call's
``entry.build_engine`` spans (one a ladder attempt), those whose
``cached`` field is 1, in percent, mean per call of the traced slice.
100 means the call constructed no engine; a program from before PR 30
writes no such field and gives None."""

from benchmark.harness.call_notes import mean_per_call


def _hit_pct(notes):
    builds = [n for n in notes if n["name"] == "entry.build_engine"]
    if not builds or any("cached" not in n for n in builds):
        return None
    return 100.0 * sum(int(n["cached"]) == 1 for n in builds) / len(builds)


def compute(run: dict):
    return mean_per_call(run, _hit_pct)
