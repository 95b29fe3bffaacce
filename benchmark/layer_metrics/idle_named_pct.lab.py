"""Of one lab call's idle seconds (gaps of at least 20 us between the
worst device's operations), the share whose innermost open ``dslabs:``
span is NOT a container (the call's root, ``entry.derive_root``,
``entry.search``, ``entry.warm_run``, ``search.level``): how far the
host's doing in a call has a name.  The lab cells' counterpart of
``scope_coverage_pct.deep``; mean per traced call.  Reads low on a
program from before ISSUE 38, whose spans stop at the stage."""

from benchmark.harness.idle_by_span import mean_per_call


def compute(run: dict):
    return mean_per_call(run, "named_pct")
