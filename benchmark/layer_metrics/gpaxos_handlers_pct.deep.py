"""Share of the HANDLERS' OWN device time, traced level, that in-group
Paxos takes, in percent: of the superstep's self time in operations that
name a fragment scope themselves (``expand.handlers.<fragment>``: one
more ``dslabs.`` level, written by ``tpu/compiler.py`` for a twin built
of fragments), the part under ``expand.handlers.gpaxos`` — the
replicated log of a multi-server replica group (ballots,
P1a/P1b/P2a/P2b, heartbeats, catch-up, log GC, and the proposals the
store's handlers inject).  The rest is the store's effect switch and
wiring (``expand.handlers.spec``) and other fragments.  It says whether
a rewrite of the handlers' bodies should start at the log or at the
store.  Operations that name plain ``expand.handlers`` are left OUT:
they are the vmaps' plumbing around the handlers, and every fusion
rooted in it (a fusion takes its root's name), which belongs to no
fragment; the reader's stderr table prices them.  None from a program
whose handlers name no fragment (every twin before PR 40, and a twin
built of none)."""

from benchmark.harness.program_spans import scope_table

FRAGMENT = "expand.handlers."
GPAXOS = FRAGMENT + "gpaxos"


def compute(run: dict):
    table = scope_table(run)
    if table is None:
        return None
    fragments = {k: v for k, v in table["named"].items()
                 if k.startswith(FRAGMENT)}
    total = sum(fragments.values())
    if not total:
        return None
    return 100.0 * fragments.get(GPAXOS, 0.0) / total
