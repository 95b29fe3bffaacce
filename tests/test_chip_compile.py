"""Compile the main path for the chip that is described, not attached.

The TPU's compiler is installed in the CPU-only sandbox and compiles for
a ``v5e:2x2`` topology that is DESCRIBED: what it refuses here (a kernel
Mosaic cannot lower, a program that does not fit 16 GB of HBM) costs no
chip time.  Nothing runs — a compile that passes is not a chip run
(``chip_smoke.py`` is) — but these guard every later PR for free.

This is the ONLY file that describes a topology, and it does so inside a
module-scoped fixture: never at import, never in a ``skipif`` or
``parametrize`` argument, never in ``conftest.py`` — only one process at
a time may load the TPU's library, and every xdist worker imports every
test file.  The compiles run in the test's own process, with the
persistent compile cache switched off around them (an entry compiled
for a described chip cannot be read back without one).

The flagship whole-program compiles take minutes and are marked
``slow``; run them with ``-m slow`` (their memory analysis is printed,
``-s`` shows it).
"""

import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, SingleDeviceSharding  # noqa: E402

import chip_smoke  # noqa: E402
from dslabs_tpu.tpu import backend, kernels, visited  # noqa: E402
from dslabs_tpu.tpu.sharded import ShardedTensorSearch  # noqa: E402

HBM_BYTES = 15.75 * (1 << 30)     # what the compiler grants on one v5e


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import \
        compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _mesh(topo, n):
    return Mesh(np.array(topo.devices[:n]), ("search",))


def _insert_args(cap, batch, sharding):
    return (jax.ShapeDtypeStruct(visited.table_shape(cap), jnp.uint32,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((batch, 4), jnp.uint32,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((batch,), jnp.bool_, sharding=sharding))


# ------------------------------------------------------------------ kernels

@pytest.mark.parametrize("cap", [1 << 15, 1 << 24],
                         ids=["cap2^15", "cap2^24-flagship"])
def test_default_visited_insert_compiles(one_chip, cap, monkeypatch):
    """``visited.insert`` as the DEFAULT path resolves it (no knob set)
    compiles for the chip at the small-table size every lab ``dfs``
    probe uses and at the flagship's 2^24 slots — and is the jnp path,
    not the Pallas kernel."""
    monkeypatch.delenv("DSLABS_VISITED_PALLAS", raising=False)
    fn = jax.jit(lambda t, k, v: visited.insert(t, k, v),
                 donate_argnums=0)
    compiled = fn.lower(*_insert_args(cap, 8192, one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < HBM_BYTES)


_PLUMBING = {"parameter", "tuple", "get-tuple-element", "while",
             "bitcast"}


def _table_sized_in_loops(text, limit=1 << 21):
    """``[(computation, instruction, opcode)]`` of an optimised HLO
    module: every instruction with ``limit`` or more elements in a
    computation that a ``while`` reaches (body, condition, and what they
    call), less the plumbing that only passes the carry along and less
    the write that updates it in place (the ``scatter`` itself and the
    fusion around it; the frontier log's ``dynamic-update-slice``)."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            cur.append(line.strip())
    called = re.compile(
        r"(?:body|condition|calls|to_apply|true_computation|"
        r"false_computation)=%?([\w.\-]+)")
    todo = [c for lines in comps.values() for ln in lines
            if " while(" in ln
            for c in re.findall(r"(?:body|condition)=%?([\w.\-]+)", ln)]
    assert todo, "no while loop in the program"
    reached = set()
    while todo:
        c = todo.pop()
        if c not in reached and c in comps:
            reached.add(c)
            for ln in comps[c]:
                todo += called.findall(ln)
                for group in re.findall(
                        r"branch_computations=\{([^}]*)\}", ln):
                    todo += [x.strip().lstrip("%")
                             for x in group.split(",")]
    big = []
    for c in sorted(reached):
        for ln in comps[c]:
            m = re.match(
                r"(?:ROOT\s+)?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", ln)
            if m is None or m.group(3) in _PLUMBING:
                continue
            sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
                     for dims in re.findall(r"\w+\[([\d,]*)\]",
                                            m.group(2))]
            in_place = m.group(3) in ("scatter", "dynamic-update-slice") or (
                m.group(3) == "fusion" and any(
                    " scatter(" in x
                    for x in comps.get(called.search(ln).group(1), ())))
            if max(sizes, default=0) >= limit and not in_place:
                big.append((c, m.group(1), m.group(3)))
    return big


def _shapes(text):
    """``{instruction: shape}`` of an optimised HLO module's text."""
    return dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", text))


def _elements(shape):
    dims = re.search(r"\[([\d,]*)\]", shape).group(1)
    return int(np.prod([int(d) for d in dims.split(",") if d]))


def _scatter_widths(text, shape):
    """Update indices of every ``scatter`` in an optimised HLO module
    whose operand has ``shape`` (``"u32[32,2097152]"``): the elements
    of its indices operand (one index a row or column written)."""
    shapes = _shapes(text)
    return [_elements(shapes[indices]) for indices in re.findall(
        r"= " + re.escape(shape) + r"\S* scatter\(%[\w.\-]+, "
        r"%([\w.\-]+),", text)]


def _gather_widths(text, shape):
    """Indices of every ``gather`` in an optimised HLO module whose
    operand has ``shape``: the elements of its indices operand (one
    index a column read)."""
    shapes = _shapes(text)
    return [_elements(shapes[indices]) for operand, indices in re.findall(
        r" gather\(%([\w.\-]+), %([\w.\-]+)\)", text)
        if shapes[operand] == shape]


@pytest.mark.parametrize("nested", [False, True],
                         ids=["alone", "in-a-16-step-loop"])
def test_flagship_insert_converts_no_table(one_chip, nested, monkeypatch):
    """The default insert at the flagship's 2^24 slots and one chunk
    step's 49,152 keys keeps the table in ONE layout: under 64 MB of
    temporaries (the ``[V + 1, 4]`` table took 4,592 MB: a slice, a
    relayout loop, a reshape and a transposing copy of the 268 MB
    table per probe iteration) and, inside the loops, no table-sized
    instruction but the scatter that updates the carry in place —
    alone and as the superstep nests it, in an outer loop.  And it
    writes narrow: no scatter on the table is handed more than one
    block of ``K`` = 6,144 columns (the chip pays per index, written or
    dropped: 49,152 of them cost 5 ms a chunk step until PR 34).  And
    it probes narrow: no gather on the table is handed more than ``K``
    indices either (the batch's 49,152 cost 2.0-2.5 ms a chunk step
    until PR 37)."""
    monkeypatch.delenv("DSLABS_VISITED_PALLAS", raising=False)

    def insert16(t, k, v):
        def body(i, c):
            t, n = c
            t, ins, _ = visited.insert(t, k + i.astype(jnp.uint32), v)
            return t, n + jnp.sum(ins)
        return jax.lax.fori_loop(0, 16, body, (t, jnp.int32(0)))

    fn = jax.jit(insert16 if nested else visited.insert,
                 donate_argnums=0)
    compiled = fn.lower(*_insert_args(1 << 24, 49152, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    assert _table_sized_in_loops(compiled.as_text()) == []
    widths = _scatter_widths(compiled.as_text(), "u32[32,2097152]")
    assert visited.block_width(49152) == 6144
    assert widths and max(widths) <= 6144, widths
    reads = _gather_widths(compiled.as_text(), "u32[32,2097152]")
    assert reads and max(reads) <= 6144, reads


def test_pallas_insert_is_refused_by_mosaic(one_chip):
    """The Pallas insert, asked for by name, is REFUSED by the TPU
    compiler (scatter has no Pallas TPU lowering) — the reason it is on
    no default path.  When a kernel lands that Mosaic accepts, this
    test is the one to turn around."""
    fn = jax.jit(lambda t, k, v: visited.pallas_insert(
        t, k, v, interpret=False))
    with pytest.raises(NotImplementedError, match="scatter"):
        fn.lower(*_insert_args(1 << 15, 4096, one_chip)).compile()


def test_fingerprint_kernel_compiles(one_chip):
    """``kernels.fingerprint_rows(mode="pallas")`` at [8192, 842] — the
    flagship protocol's lane width — lowers through Mosaic."""
    x = jax.ShapeDtypeStruct((8192, 842), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda f: kernels.fingerprint_rows(f, mode="pallas")
    ).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ----------------------------------------------------------- whole programs

def _lab1_search(mesh):
    """The lab 1 client-server twin exactly as ``backend._run_tensor``
    builds it at the ladder's first rung (runtime masks, trace
    recording, chunk 512) — on the described mesh."""
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.testing.predicates import CLIENTS_DONE, RESULTS_OK

    state = chip_smoke._lab1_state(2, 2)
    settings = (SearchSettings().add_invariant(RESULTS_OK)
                .add_prune(CLIENTS_DONE))
    binding = backend.resolve_binding(state)
    binding.check_settings(settings)
    net_cap, timer_cap = binding.initial_caps()
    protocol, marr, tarr = backend._bind_protocol(
        binding, settings, net_cap, timer_cap)
    f_cap, v_cap = backend._LADDER[0]
    search = ShardedTensorSearch(
        protocol, mesh, chunk_per_device=512, frontier_cap=f_cap,
        visited_cap=v_cap, strict=True, record_trace=True)
    search.set_runtime_masks(marr, tarr)
    return search


def _aot(search):
    """Compile superstep, promote and root init through the seam the
    engine already has (``aot_warmup`` over ``_carry_sds()``); returns
    {name: compiled}."""
    search.aot_warmup()
    exes = search._aot_exes
    init = [k for k in exes if isinstance(k, tuple) and k[0] == "init"]
    assert "superstep" in exes and "promote" in exes and len(init) == 1
    return {"superstep": exes["superstep"], "promote": exes["promote"],
            "init": exes[init[0]]}


def _fits(exes):
    for name, exe in exes.items():
        mem = exe.memory_analysis()
        # Arguments alias the outputs (donated carry): live bytes are
        # the larger of the two plus the temporaries.
        live = (max(mem.argument_size_in_bytes, mem.output_size_in_bytes)
                + mem.temp_size_in_bytes)
        print(f"{name}: live={live / 2**30:.2f} GiB {mem}")
        assert live < HBM_BYTES, (name, mem)


@pytest.mark.parametrize("n_devices", [1, 4])
def test_lab1_programs_compile(topo, n_devices):
    """The sharded superstep, promote and root init of the lab 1 twin
    at the lab ladder's first rung, on a one-device and on a
    four-device mesh of the described chip; the four-device superstep
    carries the owner-hashed all-to-all."""
    exes = _aot(_lab1_search(_mesh(topo, n_devices)))
    _fits(exes)
    text = exes["superstep"].as_text()
    assert ("all-to-all" in text) == (n_devices > 1)


def _lab3_suite_search(mesh, attempt):
    """The lab 3 twin of PaxosTest test22's state (3 servers, 2 clients,
    one APPEND each — the benchmark's ``paxos3-suite`` cell) exactly as
    ``backend._run_tensor`` builds it on rung ``attempt`` of the capacity
    ladder, under the first phase's settings."""
    from benchmark.harness import manifest

    cell = manifest.load_cell(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "paxos3-suite")
    state = cell.driver.build_state(
        cell.config["deployment"]["object_state"], 1)
    settings = cell.driver.build_settings(
        cell.config["phases"]["decide"], state)
    binding = backend.resolve_binding(state)
    net_cap, timer_cap = binding.initial_caps()
    protocol, marr, tarr = backend._bind_protocol(
        binding, settings, net_cap << attempt, timer_cap + 2 * attempt)
    f_cap, v_cap = backend._LADDER[attempt]
    search = ShardedTensorSearch(
        protocol, mesh, chunk_per_device=512, frontier_cap=f_cap,
        visited_cap=v_cap, strict=True, record_trace=True)
    search.set_runtime_masks(marr, tarr)
    return search


@pytest.mark.slow
@pytest.mark.parametrize("attempt", [0, 1])
def test_lab3_suite_programs_compile(topo, one_chip, attempt):
    """What a staged lab 3 call builds on one chip, on the ladder's
    first two rungs: superstep (trace recording on), promote, root init,
    and the chunk-1 step that ``derive_root`` replays a staged state's
    history through and ``trace.decode_trace`` a witness."""
    import dataclasses

    from dslabs_tpu.tpu.engine import TensorSearch

    search = _lab3_suite_search(_mesh(topo, 1), attempt)
    exes = _aot(search)
    replayer = TensorSearch(dataclasses.replace(
        search.p, deliver_message=None, deliver_timer=None), chunk=1)
    exes["step_one"] = jax.jit(replayer._step_one).lower(
        jax.ShapeDtypeStruct((search.lanes,), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    _fits(exes)
    assert "all-to-all" not in exes["superstep"].as_text()


def _lab4_search(mesh, phase, attempt):
    """A twin of ShardStorePart2Test test09's staged search (the
    benchmark's ``shardtx-suite`` cell) exactly as ``backend._run_tensor``
    builds it on rung ``attempt`` of the capacity ladder: ``join`` binds
    the shard-master twin from the root, ``commit`` the 2PC twin from
    the joined state (the object checker's: 0.02 s) plus the client."""
    from benchmark.harness import manifest
    from dslabs_tpu.search.search import BFS

    cell = manifest.load_cell(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "shardtx-suite")
    drv, spec = cell.driver, cell.config["deployment"]["object_state"]
    state = drv.build_state(spec, 1)
    if phase != "join":
        state = drv.add_client(BFS(drv.build_settings(
            cell.config["phases"]["join"], state)).run(
                state).goal_matching_state, spec, 1)
    settings = drv.build_settings(cell.config["phases"][phase], state)
    binding = backend.resolve_binding(state)
    binding.check_settings(settings)
    net_cap, timer_cap = binding.initial_caps()
    protocol, marr, tarr = backend._bind_protocol(
        binding, settings, net_cap << attempt, timer_cap + 2 * attempt)
    f_cap, v_cap = backend._LADDER[attempt]
    search = ShardedTensorSearch(
        protocol, mesh, chunk_per_device=512, frontier_cap=f_cap,
        visited_cap=v_cap, strict=True, record_trace=True)
    search.set_runtime_masks(marr, tarr)
    return search


@pytest.mark.parametrize("phase,attempt", [
    ("join", 0), ("commit", 0), ("commit", 1)])
def test_lab4_staged_programs_compile(topo, one_chip, phase, attempt):
    """What a staged lab 4 call builds on one chip, on the rungs it
    stands on (``join`` answers on the first; ``commit`` climbs to the
    second on every call: PERF.md, PR 33): superstep (trace recording
    on), promote, root init, and the chunk-1 trace step of the twin a
    phase binds."""
    import dataclasses

    from dslabs_tpu.tpu.engine import TensorSearch

    search = _lab4_search(_mesh(topo, 1), phase, attempt)
    assert search.p.name == ("shardmaster-join-w2" if phase == "join"
                             else "shardstore-tx-g2-w1")
    exes = _aot(search)
    replayer = TensorSearch(dataclasses.replace(
        search.p, deliver_message=None, deliver_timer=None), chunk=1)
    exes["step_one"] = jax.jit(replayer._step_one).lower(
        jax.ShapeDtypeStruct((search.lanes,), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    _fits(exes)
    assert "all-to-all" not in exes["superstep"].as_text()


def _frontier_sized_moves(text, elements):
    """``[(instruction, opcode)]`` of an optimised HLO module: every
    ``copy``, ``transpose`` or ``scatter`` (``copy-start`` too) whose
    result has ``elements`` or more elements — a whole frontier moved
    or re-laid."""
    return [(name, op) for name, shape, op in re.findall(
        r"%([\w.\-]+) = (\w+\[[\d,]*\])\S* "
        r"(copy|copy-start|transpose|scatter)\(", text)
        if _elements(shape) >= elements]


@pytest.fixture(scope="module")
def shardkv_deep(topo):
    """``(cell, search, {name: compiled})``: the benchmark's
    ``shardkv-deep`` cell as its driver builds it, compiled once for one
    described chip (under a minute)."""
    from benchmark.drivers.timeboxed_bfs import build_protocol
    from benchmark.harness import manifest

    cell = manifest.load_cell(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "shardkv-deep")
    eng = cell.config["engine"]
    search = ShardedTensorSearch(
        build_protocol(cell.config["protocol"]),
        _mesh(topo, 1), chunk_per_device=eng["chunk"],
        frontier_cap=eng["frontier_cap"], visited_cap=eng["visited_cap"],
        strict=True, ev_budget=tuple(eng["ev_budget"]))
    return cell, search, _aot(search)


def test_shardkv_deep_programs_compile(shardkv_deep):
    """Lab 4's part 1 twin (the benchmark's ``shardkv-deep`` cell) as
    its driver builds it, at the configuration's caps — a frontier of
    6,815,744 rows a buffer (3.65 GB each at 536 bytes a row) and a
    table of 2^25 slots: superstep, promote and root init compile for
    one described chip and fit it.  Since PR 45 the two frontiers are
    flat logs of packed words (``s32[(F + K) * 134]``: a 1-D array has
    ONE layout) and the append a contiguous ``dynamic-update-slice`` of
    a block, so the compiler's plan for the superstep holds the carry's
    TWO frontier-sized buffers and nothing of their size beside them:
    7.74 GiB live, 0.46 GB of it temporaries.  (Until then ``nxt`` was
    ``s32[6815745,134]``, kept rows-minor by the runtime because 134
    words are not a multiple of 128 lanes, and the append a row scatter
    whose dynamic offset the compiler wanted on the MAJOR dimension: the
    loop carried ``nxt`` lane-padded, 6.98 GB for 3.65, behind a
    frontier-sized ``copy`` in and another out every dispatch — FOUR
    frontier-sized buffers, 14.61 GiB of 15.75 with 7.74 GB of
    temporaries, 9.46 at 2^22 rows, 15.48 at 7,340,032: what bounded
    this cell's frontier, PERF.md section 4.)  The promote is a swap of
    the two logs on the host: its program moves no frontier either.
    And the dedup layer still probes and writes narrow at these shapes:
    no gather or scatter on the table is handed more than one block of
    6,144 indices.  Under a minute of compile (the program is a fifth
    of Paxos' text)."""
    cell, search, exes = shardkv_deep
    eng = cell.config["engine"]
    assert (eng["frontier_cap"], eng["visited_cap"]) == (6815744, 1 << 25)
    assert (search.lanes, search.bytes_per_state) == (
        cell.config["protocol"]["lanes"],
        cell.config["protocol"]["packed_bytes_per_state"])
    _fits(exes)
    k = visited.block_width(eng["chunk"] * search._ev_slots)
    assert k == 6144
    frontier = eng["frontier_cap"] * search.plane
    log = (eng["frontier_cap"] + k) * search.plane
    for name in ("superstep", "promote"):
        mem = exes[name].memory_analysis()
        assert (max(mem.argument_size_in_bytes, mem.output_size_in_bytes)
                + mem.temp_size_in_bytes < 9 << 30), (name, mem)
        assert mem.temp_size_in_bytes < 10 ** 9, (name, mem)
        text = exes[name].as_text()
        # both frontiers enter as 1-D logs ...
        for leaf in ("cur", "nxt"):
            assert re.search(
                rf"%c(?:arry)?__{leaf}__\S* = s32\[{log}\]\S* parameter\(",
                text), (name, leaf)
        # ... and nothing moves or re-lays a buffer of their size
        assert _frontier_sized_moves(text, frontier) == [], name
    text = exes["superstep"].as_text()
    assert "all-to-all" not in text
    # the append: a block of K rows written in place into the log
    assert re.search(
        rf"= s32\[{log}\]\S* dynamic-update-slice\(", text)
    table = _scatter_widths(text, f"u32[32,{eng['visited_cap'] // 8}]")
    reads = _gather_widths(text, f"u32[32,{eng['visited_cap'] // 8}]")
    assert table and max(table) <= k, table
    assert reads and max(reads) <= k, reads
    assert _table_sized_in_loops(text, limit=1 << 27) == []


def test_a_kind_assembles_its_rows_by_one_concatenate(shardkv_deep):
    """What the guard cells' speed rests on since PR 49, and nothing in
    the program says: behind ``_expand_chunk``'s
    ``optimization_barrier`` each event kind's branch hands out its
    successor rows ``s32[C * b, lanes]`` built by ONE ``concatenate``.
    Without the barrier — and in the tree before the branch — the
    compiler wrote the same rows in place, one
    ``dynamic-update-slice`` a lane group into ``s32[C, b, lanes]`` (42
    of them in this twin, 29 + 13), which cost ``shardkv-deep`` and
    ``pb-deep`` 7 % of their states a second (PERF.md section 6, PR
    49).  A compiler that goes back to the in-place writes fails here,
    on the sandbox, before a chip reads it as a loss."""
    cell, search, exes = shardkv_deep
    text = exes["superstep"].as_text()
    c, lanes = cell.config["engine"]["chunk"], search.lanes
    assert text.count(" conditional(") == 2
    for b in (search._ev_msg, search._ev_tmr):
        assert len(re.findall(
            rf"= s32\[{c * b},{lanes}\]\S* concatenate\(", text)) == 1, b
        assert re.findall(
            rf"= s32\[(?:{c},{b}|{c * b}),{lanes}\]\S* "
            r"dynamic-update-slice\(", text) == [], b


def test_pb_deep_programs_compile(topo):
    """Lab 2's compiled twin (the benchmark's ``pb-deep`` cell, the
    tree's one twin with delta lanes) as its driver builds it, at the
    configuration's caps, for one described chip: superstep, both
    promotes and root init compile and fit.  The carry is the two
    frontier logs, the table and nothing of their size beside them.
    The promote of a level whose base stayed moves counters only, as
    every other twin's.  The promote of a level whose base MOVED
    (``promote_rebase``) adds one word-wise add over the occupied
    prefix, a block of the append's K rows at a time, written in place:
    no temporaries to speak of, no ``[rows, 1]`` column, no
    ``[rows, lanes]`` intermediate, no frontier-sized move.  (Until PR
    47 the one promote unpacked and re-packed all ``frontier_cap`` rows
    at every level: 38 GB of temporaries at 2^22 rows, headed by 128x
    padded ``u32[4194304,1]`` columns — it did not compile.)"""
    from benchmark.drivers.timeboxed_bfs import build_protocol
    from benchmark.harness import manifest

    cell = manifest.load_cell(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "pb-deep")
    eng = cell.config["engine"]
    search = ShardedTensorSearch(
        build_protocol(cell.config["protocol"]),
        _mesh(topo, 1), chunk_per_device=eng["chunk"],
        frontier_cap=eng["frontier_cap"], visited_cap=eng["visited_cap"],
        strict=True, ev_budget=tuple(eng["ev_budget"]))
    assert (search.lanes, search.bytes_per_state) == (
        cell.config["protocol"]["lanes"],
        cell.config["protocol"]["packed_bytes_per_state"])
    assert search._mesh_delta and len(search._delta_lanes) == cell.config[
        "protocol"]["delta_lanes"]
    exes = _aot(search)
    exes["promote_rebase"] = search._aot_exes["promote_rebase"]
    _fits(exes)
    k = visited.block_width(eng["chunk"] * search._ev_slots)
    frontier = eng["frontier_cap"] * search.plane
    log = (eng["frontier_cap"] + k) * search.plane
    for name in ("superstep", "promote", "promote_rebase"):
        mem = exes[name].memory_analysis()
        assert mem.temp_size_in_bytes < 10 ** 9, (name, mem)
        text = exes[name].as_text()
        for leaf in ("cur", "nxt"):
            assert re.search(
                rf"%c(?:arry)?__{leaf}__\S* = s32\[{log}\]\S* parameter\(",
                text), (name, leaf)
        assert _frontier_sized_moves(text, frontier) == [], name
    for name in ("promote", "promote_rebase"):
        mem = exes[name].memory_analysis()
        assert mem.temp_size_in_bytes < 1 << 24, (name, mem)
        # nothing shaped [rows, 1] or [rows, lanes] for a block's rows
        # or more: the rows are never unpacked (the re-base's addend is
        # one [K, words] tile, made once)
        wide = [m.groups() for m in (
            re.fullmatch(r"\w+\[(\d+),(\d+)\]", shape)
            for shape in _shapes(exes[name].as_text()).values()) if m]
        assert [rc for rc in wide if int(rc[0]) >= k and int(rc[1]) in (
            1, search.lanes)] == [], (name, wide)
    plain, rebase = (exes[n].as_text() for n in ("promote",
                                                 "promote_rebase"))
    # a level without a re-base: no loop, no write into a log
    assert " while(" not in plain and "dynamic-update-slice" not in plain
    # a re-base: one block of K rows sliced, added to and written back
    assert re.search(rf"= s32\[{log}\]\S* dynamic-update-slice\(", rebase)
    assert re.search(rf"s32\[{k * search.plane}\]\S* dynamic-slice\(", rebase)
    assert "dslabs.promote.rebase" in rebase
    assert "all-to-all" not in exes["superstep"].as_text()


@pytest.mark.slow
def test_shardkv_n3_deep_programs_compile(topo):
    """Lab 4's multi-server twin (the benchmark's ``shardkv-n3-deep``
    cell, ``setupStates(2, 3, 1, 10)``) as its driver builds it, at the
    configuration's caps: superstep, promote and root init compile for
    one described chip.  Since PR 45 the superstep's plan is the
    carry's TWO frontier logs of 1,507,328 (+ 6,144 slack) rows of
    1,792 bytes (5.42 GB), the 2^24-slot table and 2.53 GB of chunk
    temporaries (49,152 successors of 1,272 lanes: this twin's rows are
    2.9 times ``shardkv-deep``'s): 7.66 GiB of 15.75, and no ``copy``,
    ``transpose`` or ``scatter`` of a frontier's size.  Since PR 49 each
    event kind's handlers and merge sit under a ``conditional`` whose
    outputs are buffers of their own (a kind's rows, valid mask and
    overflow counts): 2.65 GB of temporaries, 7.77 GiB.  The
    configuration's ``sizing`` still records the plan the cap was sized
    under — FOUR frontier-sized buffers (the carry's two and both entry
    copies of a row-scattered ``nxt``), 10.8 GB, with 4.1 GiB of
    temporaries, 14.10 GiB; a ``benchmark`` PR's to restate (PERF.md
    section 7).  Five minutes of compile here (28 MB of optimised
    text): ``-m slow``."""
    from benchmark.drivers.timeboxed_bfs import build_protocol
    from benchmark.harness import manifest

    cell = manifest.load_cell(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "shardkv-n3-deep")
    eng = cell.config["engine"]
    search = ShardedTensorSearch(
        build_protocol(cell.config["protocol"]),
        _mesh(topo, 1), chunk_per_device=eng["chunk"],
        frontier_cap=eng["frontier_cap"], visited_cap=eng["visited_cap"],
        strict=True, ev_budget=tuple(eng["ev_budget"]))
    assert (search.lanes, search.bytes_per_state) == (
        cell.config["protocol"]["lanes"],
        cell.config["protocol"]["packed_bytes_per_state"])
    exes = _aot(search)
    _fits(exes)
    mem = exes["superstep"].memory_analysis()
    live = (max(mem.argument_size_in_bytes, mem.output_size_in_bytes)
            + mem.temp_size_in_bytes)
    assert live == pytest.approx(8_340_699_648, abs=64 << 20)
    # ... 6 GiB and more under the plan the configuration was sized for
    assert live + (6 << 30) < cell.config["sizing"]["bytes"][
        "superstep_live_by_memory_analysis"]
    text = exes["superstep"].as_text()
    assert "all-to-all" not in text
    assert _frontier_sized_moves(
        text, eng["frontier_cap"] * search.plane) == []
    # the handlers' operations name their fragment in the chip's text
    assert "dslabs.expand.handlers.gpaxos" in text
    assert "dslabs.expand.handlers.spec" in text
    # a device branch an event kind, in the chip's text too
    assert text.count(" conditional(") >= 2


@pytest.mark.slow
@pytest.mark.parametrize("cell_name", ["paxos3-deep", "shardkv-deep",
                                       "shardkv-n3-deep"])
def test_deep_programs_from_the_store_have_the_text_compiled_in_place(
        topo, cell_name, tmp_path, request):
    """The executable store (tpu/compile_cache.py, ISSUE 41) under each
    deep configuration's engine, for the described chip: a first
    engine's warm-up compiles superstep, promote and root init in place
    and keeps each — whole, under the stamp of the runtime and the
    backend the engine's OWN devices belong to (the described TPU's,
    not the CPU this process runs on) — and a second engine, built
    anew, asks for them under the very same keys.  What the second is
    handed has the text of the first, hash for hash (less the tables
    that say where in the source an operation was traced).  A described
    chip's client compiles and serializes but cannot LOAD
    (``DeserializeLoadedExecutable`` is UNIMPLEMENTED there: a doubt,
    so a miss), so here the second is compiled in place again and the
    equality says that an entry holds what this source compiles to;
    with a chip attached the second IS the entry (three hits), and
    ``chip_smoke.py``'s warm phase holds the loaded superstep to the
    same equality.  Minutes of compile, twice: ``-m slow``."""
    import hashlib
    import pickle
    import zlib

    from benchmark.drivers.timeboxed_bfs import build_protocol
    from benchmark.harness import manifest
    from dslabs_tpu.tpu import compile_cache

    # the store under ``tmp_path`` (by JAX's own setting: a function
    # patched into ``compile_cache`` would itself be a reason for no key)
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    request.addfinalizer(lambda: jax.config.update(
        "jax_compilation_cache_dir", was))
    cell = manifest.load_cell(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        cell_name)
    eng = cell.config["engine"]

    def program(text):
        # Two traces of one function differ in WHERE each operation came
        # from — the call stack a second trace runs under is shorter
        # (FileLocations, StackFrames, every ``stack_frame_id``) — and in
        # nothing the chip executes: compared without the tables.
        head, _, rest = text.partition("\nFileNames\n")
        body = rest[rest.index("\n\n\n"):] if rest else ""
        return re.sub(r" ?stack_frame_id=\d+", "", head + body)

    def texts():
        search = ShardedTensorSearch(
            build_protocol(cell.config["protocol"]), _mesh(topo, 1),
            chunk_per_device=eng["chunk"],
            frontier_cap=eng["frontier_cap"],
            visited_cap=eng["visited_cap"], strict=True,
            ev_budget=tuple(eng["ev_budget"]))
        assert search.store_key() is not None
        return search, {
            name: hashlib.sha256(
                program(exe.as_text()).encode()).hexdigest()
            for name, exe in _aot(search).items()}

    before = compile_cache.totals()
    first, compiled = texts()
    entries = sorted(os.listdir(compile_cache.store_dir()))
    assert len(entries) == 3 and all(e.endswith(".exe") for e in entries)
    for entry in entries:
        with open(os.path.join(compile_cache.store_dir(), entry),
                  "rb") as f:
            stamp, payload, _in_tree, _out_tree = pickle.loads(
                zlib.decompress(f.read()))
        assert stamp == compile_cache._stamp(first._store_devices())
        assert stamp[2] == "tpu" and payload
    _second, again = texts()
    assert again == compiled
    assert sorted(os.listdir(compile_cache.store_dir())) == entries
    after = compile_cache.totals()
    lookups = (after["exe_store_hit_n"] + after["exe_store_miss_n"]
               - before["exe_store_hit_n"] - before["exe_store_miss_n"])
    assert lookups == 6
    print(f"{cell_name}: {after['exe_store_hit_n'] - before['exe_store_hit_n']}"
          f" of the second engine's 3 programs were loaded; entries "
          f"{[os.path.getsize(os.path.join(compile_cache.store_dir(), e)) for e in entries]} bytes")


def _flagship_search(mesh, chunk):
    caps = dict(chip_smoke.FLAGSHIP, chunk=chunk)
    return ShardedTensorSearch(
        chip_smoke.flagship_protocol(), mesh,
        chunk_per_device=caps["chunk"],
        frontier_cap=caps["frontier_cap"],
        visited_cap=caps["visited_cap"], strict=True,
        ev_budget=chip_smoke.EV_BUDGET)


@pytest.mark.slow
@pytest.mark.parametrize("n_devices", [1, 4])
def test_flagship_programs_compile(topo, n_devices):
    """The flagship protocol at chip_smoke's caps: superstep, promote,
    root init and the bare ``_expand_chunk`` — carry plus temporaries
    must fit one chip's HBM, no gather on the visited table and no
    scatter on it or on ``nxt`` takes more than one block of indices,
    and nothing in the superstep's loops but those scatters is as large
    as the table.  Minutes of compile: ``-m slow``."""
    search = _flagship_search(_mesh(topo, n_devices),
                              chip_smoke.FLAGSHIP["chunk"])
    assert search.lanes == 842
    # The frontier pair is sized in PACKED words, not 842-lane rows.
    assert search.plane < search.lanes // 3
    exes = _aot(search)
    if n_devices == 1:
        c = search.cpd
        sh = SingleDeviceSharding(topo.devices[0])
        exes["expand_chunk"] = jax.jit(search._expand_chunk).lower(
            jax.ShapeDtypeStruct((c, search.lanes), jnp.int32,
                                 sharding=sh),
            jax.ShapeDtypeStruct((c,), jnp.bool_, sharding=sh)).compile()
    _fits(exes)
    text = exes["superstep"].as_text()
    assert ("all-to-all" in text) == (n_devices > 1)
    # Write narrow: a chunk step hands the table's scatter and the
    # frontier append one block of K indices at a time, of the 49,152
    # slots one chip fills and the 98,312 four receive.
    k = visited.block_width(49152 if n_devices == 1 else 98312)
    assert k == (6144 if n_devices == 1 else 12289)
    table = _scatter_widths(text, "u32[32,2097152]")
    nxt = _scatter_widths(
        text, f"s32[{chip_smoke.FLAGSHIP['frontier_cap'] + 1},"
        f"{search.plane}]")
    reads = _gather_widths(text, "u32[32,2097152]")
    assert table and max(table) <= k, table
    assert nxt and max(nxt) <= k, nxt
    assert reads and max(reads) <= k, reads
    assert _table_sized_in_loops(text, limit=1 << 26) == []


@pytest.mark.slow
@pytest.mark.parametrize("chunk", [1024, 2048, 8192])
def test_flagship_superstep_fits_at_chunk(topo, chunk):
    """PR 22's finding (``test_flagship_chunk_8192_does_not_fit``),
    turned around by ISSUE 32.  At chunk 8192 the compiler
    used to refuse the flagship superstep — 42 GB of HBM against 15.75,
    nearly all of it the codec's [chunk*48, 1] uint32 columns padded
    128x by the (8, 128) tile.  The codec now assembles all words in one
    contraction and the superstep compiles and fits at every chunk
    asked: live bytes by ``memory_analysis()`` 5.23 GiB at 1024 (the
    cells' chunk; 2.80 of it temporaries), 6.78 at 2048, 10.82 at 8192
    (printed: ``-s``).  Whether a larger chunk is FASTER is a chip
    run's to say and a ``benchmark`` issue's to ask: the cells' chunk
    lives in ``benchmark/configs/``."""
    search = _flagship_search(_mesh(topo, 1), chunk)
    search.aot_warmup()
    _fits({f"superstep@{chunk}": search._aot_exes["superstep"]})
