"""Share of the superstep's device self time, traced level, spent in
operations that name a ``dslabs.<scope>`` themselves (their own
``op_name`` in the executable's text), in percent: how far the per-scope
numbers can be trusted.  The compiler's own operations keep no
``op_name``; the reader prints on stderr, beside each scope, what their
neighbours' scopes would add to it, and the largest operations left with
no scope at all — neither counts as covered."""

from benchmark.harness.program_spans import scope_table


def compute(run: dict):
    table = scope_table(run)
    if table is None:
        return None
    named = sum(table["named"].values())
    total = named + sum(table["near"].values()) + table["unscoped"]
    return 100.0 * named / total if total else None
