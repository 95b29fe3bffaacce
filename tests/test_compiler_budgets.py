"""The spec compiler's budget dry-run (ISSUE 48, tpu/compiler.py
``ProtocolSpec._count_budgets``): every handler of every node instance
run ONCE on zero operands, only to count its effect rows.

What is held here:

* the budgets are shapes of the device program (``max_sends`` sizes the
  send block), so for every protocol factory a benchmark configuration
  names and for the lab adapters' twins at their first ladder rung the
  counts, the largest ``ctx.fail`` code and the coverage sets the
  conformance linter reads are pinned to what the tree gave BEFORE the
  dry-run moved to the host (the parent of PR 48);
* the dry-run's operands and everything a handler computes from them
  sit on the process's first CPU device, whatever the default device
  is; its zero state is built once and no invocation sees what another
  wrote; a process without a CPU backend gets the same budgets.

The ``compile.twin`` span, the totals it feeds and the benchmark's
reader of them are held in ``tests/test_program_spans.py``.
"""

import contextlib
import importlib
import json
import os

import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu import compiler  # noqa: E402
from dslabs_tpu.tpu.compiler import (Field, MessageType, NodeKind,  # noqa: E402
                                     ProtocolSpec, TimerType)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config_factory(config):
    """The factory call ``benchmark/drivers/timeboxed_bfs.py``
    ``build_protocol`` makes for ``benchmark/configs/<config>.json``."""
    def build():
        with open(os.path.join(ROOT, "benchmark", "configs",
                               config + ".json")) as f:
            spec = json.load(f)["protocol"]
        mod, _, fn = spec["factory"].partition(":")
        return getattr(importlib.import_module(mod), fn)(**spec["kwargs"])
    return build


def _lab_factory(module, fn, **kwargs):
    def build():
        return getattr(importlib.import_module(
            "dslabs_tpu.tpu." + module), fn)(**kwargs)
    return build


# (max_sends, max_sets, _exc_hi, touched slots / quorums / sends) at
# 8ac820b, the parent of PR 48.
PINNED = {
    # the benchmark's configurations, by their own factory call
    "paxos-n3c2": (_config_factory("lab3-paxos-n3c2"),
                   (11, 1, 0, 3, 1, 10)),
    "shardstore-g2c2": (_config_factory("lab4-shardstore-g2c2"),
                        (3, 1, 0, 0, 0, 8)),
    "shardstore-multi-g2n3": (_config_factory("lab4-shardstore-g2n3"),
                              (37, 1, 0, 6, 2, 16)),
    "pb-s2c2": (_config_factory("lab2-primarybackup-s2c2"),
                (3, 1, 0, 0, 0, 9)),
    # the lab adapters' twins at rung 0 (``initial_caps``), with what
    # the benchmark's lab cells hand their bindings.  Lab 1's entry
    # binds the HAND twin (tpu/protocols/clientserver.py: no dry-run);
    # its spec is the compiled one the parity tests hold to it
    "lab1-clientserver-c2w3": (_lab_factory(
        "specs", "clientserver_spec", n_clients=2, w=3),
        (1, 1, 0, 0, 0, 2)),
    "lab3-test22-rung0": (_lab_factory(
        "specs_lab3", "make_paxos_protocol", n=3, n_clients=2, w=1,
        max_slots=2, net_cap=32, timer_cap=6), (8, 1, 0, 3, 1, 10)),
    "lab4-join-rung0": (_lab_factory(
        "specs_lab4", "make_join_protocol", n_joins=2, net_cap=12,
        timer_cap=4), (1, 1, 0, 0, 0, 2)),
    "lab4-2pc-rung0": (_lab_factory(
        "specs_lab4", "make_shardstore_tx_protocol", n_tx=1, net_cap=48,
        timer_cap=6), (4, 1, 0, 2, 0, 10)),
    # the swarm probe's five-server twin (``paxos5-random``): the one
    # benchmark twin with a ``ctx.fail`` code
    "paxos-n5-probe": (_lab_factory(
        "specs_lab3", "make_paxos_protocol", n=5, n_clients=2, w=1,
        max_slots=3, net_cap=2048, timer_cap=10, loud_refusal=True),
        (19, 1, 1, 3, 1, 10)),
}


@pytest.fixture
def dry_runs(monkeypatch):
    """The specs whose budget dry-run ran, in order."""
    seen = []
    real = ProtocolSpec._count_budgets

    def spy(self):
        seen.append(self)
        return real(self)

    monkeypatch.setattr(ProtocolSpec, "_count_budgets", spy)
    return seen


@pytest.mark.parametrize("case", sorted(PINNED))
def test_budgets_are_the_parents(case, dry_runs):
    build, want = PINNED[case]
    built = build()
    protocol = built.compile() if isinstance(built, ProtocolSpec) else built
    (spec,) = dry_runs
    assert (protocol.max_sends, protocol.max_sets, spec._exc_hi,
            len(spec._touched_slots), len(spec._touched_quorums),
            len(spec._touched_sends)) == want


# --------------------------------------------------------- the mechanism

def _tiny_spec(seen):
    """Two servers and a client; every handler notes what it was given
    (``seen``: (handler, instance devices, payload devices, value read
    BEFORE its own put, the state dict's id))."""
    spec = ProtocolSpec(
        "tiny-dry-run",
        nodes=[NodeKind("server", 2, (Field("n", hi=9),
                                      Field("log", size=3, hi=9))),
               NodeKind("client", 1, (Field("k", init=1, hi=3),))],
        messages=[MessageType("REQ", ("i",), bounds={"i": (0, 3)}),
                  MessageType("REPLY", ("i",), bounds={"i": (0, 3)})],
        timers=[TimerType("TICK", ("i",), 10, 10, bounds={"i": (0, 3)})],
        net_cap=8, timer_cap=4)

    def note(name, ctx, payload, *fields):
        got = [ctx.get(f) for f in fields]
        seen.append(dict(
            handler=name,
            devices=set().union(*(x.devices() for x in got),
                                *(v.devices() for v in payload.values())),
            read=[x.tolist() for x in got], state=id(ctx._st)))

    @spec.on("server", "REQ")
    def srv_req(ctx, m):
        note("srv_req", ctx, m, "n", "log")
        ctx.put("n", 7)
        # what a handler COMPUTES follows the default device too
        seen[-1]["devices"] |= (ctx.get("n") + m["i"]).devices()
        # past the guard: were the zero state shared and mutable, the
        # next invocation would read this
        ctx._st[("server", ctx._idx, "n")] = ctx.get("n") + 7
        ctx.send("REPLY", 2, i=m["i"])

    @spec.on("client", "REPLY")
    def cli_reply(ctx, m):
        note("cli_reply", ctx, m, "k")
        ctx.send("REQ", 0, i=m["i"])
        ctx.send("REQ", 1, i=m["i"])

    @spec.on_timer("client", "TICK")
    def cli_tick(ctx, t):
        note("cli_tick", ctx, t, "k")
        ctx.set_timer("TICK", i=t["i"])

    spec.initial_messages.append(("REQ", 2, 0, {"i": 1}))
    spec.initial_timers.append(("TICK", 2, {"i": 1}))
    spec.goals["DONE"] = lambda v: v.get("client", 0, "k") == 3
    return spec


def test_the_dry_run_sits_on_the_hosts_cpu_device():
    seen = []
    spec = _tiny_spec(seen)
    cpu0, other = jax.devices("cpu")[0], jax.devices()[3]
    assert other != cpu0
    with jax.default_device(other):
        protocol = spec.compile()
        # the default device is the caller's again afterwards
        assert jax.numpy.zeros(()).devices() == {other}
    dry = seen[:4]                  # compile() traces nothing beyond it
    assert [d["handler"] for d in dry] == [
        "srv_req", "srv_req", "cli_reply", "cli_tick"]
    for d in dry:
        assert d["devices"] == {cpu0}, d
    assert (protocol.max_sends, protocol.max_sets) == (2, 1)
    assert spec._invocations == 4


def test_no_invocation_sees_what_another_wrote():
    seen = []
    _tiny_spec(seen).compile()
    first, second = seen[0], seen[1]
    assert first["handler"] == second["handler"] == "srv_req"
    # its own dict of the one zero state: what the first invocation put
    # (and wrote past its guard) is not there for the second
    assert first["state"] != second["state"]
    assert first["read"] == second["read"] == [0, [0, 0, 0]]
    assert [d["read"] for d in seen[2:4]] == [[0], [0]]


def test_without_a_cpu_backend_the_budgets_are_the_same(monkeypatch):
    def no_cpu(backend=None):
        if backend == "cpu":
            raise RuntimeError("Unknown backend cpu")
        return real(backend)

    real = jax.devices
    with_cpu = _tiny_spec([])
    want = with_cpu._count_budgets()
    monkeypatch.setattr(jax, "devices", no_cpu)
    assert isinstance(compiler._on_host_cpu(), contextlib.nullcontext)
    seen = []
    spec = _tiny_spec(seen)
    other = real()[3]
    with jax.default_device(other):
        assert spec._count_budgets() == want == (2, 1)
    # it ran where it always ran: on the default device
    assert all(d["devices"] == {other} for d in seen)
    assert (spec._exc_hi, spec._touched_sends) == (
        with_cpu._exc_hi, with_cpu._touched_sends)
