"""The object-checker side of a configuration, made from the seed: the
``SearchState`` a lab test would build, with the clients' commands (keys
and values of the APPENDs) drawn from a seeded generator.  The program
receives only these built states.

The tensor twins are value-blind (``tpu/adapters/paxos.py``
"value-collapse"), so the seed changes what the object checker and the
witness replay handle, never the shape or amount of the device's work.
"""

from __future__ import annotations

import random
import string
from typing import List


def _word(rng: random.Random, n: int = 6) -> str:
    return "".join(rng.choices(string.ascii_lowercase + string.digits,
                               k=n))


def append_workload(rng: random.Random, commands: int):
    """``commands`` APPENDs to one seeded key of the client's own
    (``%a`` is the client's address, as in the upstream workloads), each
    with a seeded value; the expected results are the growing value."""
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload

    key = _word(rng)
    values = [_word(rng, 4) for _ in range(commands)]
    return kv_workload(
        [f"APPEND:{key}-%a:{v}" for v in values],
        ["".join(values[:i + 1]) for i in range(commands)])


def build(spec: dict, seed: int):
    """A fresh ``SearchState`` for the configuration's ``object_state``.
    Equal ``seed`` gives equal commands, every time it is called."""
    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.search.search_state import SearchState
    from dslabs_tpu.testing.generator import NodeGenerator

    rng = random.Random(seed)
    kind = spec["kind"]
    clients = [LocalAddress(f"client{i}")
               for i in range(1, spec["clients"] + 1)]
    workloads = {a: append_workload(rng, spec["commands_per_client"])
                 for a in clients}
    if kind == "clientserver":
        from dslabs_tpu.labs.clientserver.clientserver import (
            SimpleClient, SimpleServer)
        from dslabs_tpu.labs.clientserver.kvstore import KVStore

        servers: List = [LocalAddress("server")]
        gen = NodeGenerator(
            server_supplier=lambda a: SimpleServer(a, KVStore()),
            client_supplier=lambda a: SimpleClient(a, servers[0]),
            workload_supplier=lambda a: None)
    elif kind == "paxos":
        from dslabs_tpu.labs.clientserver.kvstore import KVStore
        from dslabs_tpu.labs.paxos.paxos import PaxosClient, PaxosServer

        servers = [LocalAddress(f"server{i}")
                   for i in range(1, spec["servers"] + 1)]
        group = tuple(servers)
        gen = NodeGenerator(
            server_supplier=lambda a: PaxosServer(a, group, KVStore()),
            client_supplier=lambda a: PaxosClient(a, group),
            workload_supplier=lambda a: None)
    else:
        raise ValueError(f"unknown object_state kind {kind!r}")
    state = SearchState(gen)
    for a in servers:
        state.add_server(a)
    for a in clients:
        state.add_client_worker(a, workloads[a])
    return state


def settings(spec: dict):
    """``SearchSettings`` from a configuration's ``calls`` entry:
    predicate names are those of ``dslabs_tpu.testing.predicates``."""
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.testing import predicates

    s = SearchSettings()
    for name in spec.get("invariants", []):
        s.add_invariant(getattr(predicates, name))
    for name in spec.get("goals", []):
        s.add_goal(getattr(predicates, name))
    for name in spec.get("prunes", []):
        s.add_prune(getattr(predicates, name))
    if spec.get("max_time") is not None:
        s.max_time(spec["max_time"])
    if spec.get("max_depth") is not None:
        s.set_max_depth(spec["max_depth"])
    return s
