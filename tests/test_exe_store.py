"""The executable store (tpu/compile_cache.py ``stored``, ISSUE 41): a
warm process LOADS its search programs instead of tracing them.

What must hold: a second process on a warm store traces no superstep and
answers exactly as one that compiled in place; the key tells apart
everything the traced program is a function of (a miss is always safe, a
stale hit is a wrong verdict); every doubt about an entry is a miss that
replaces it; two writers of one key leave one whole file; the store is
bounded; a spec the fingerprinter cannot vouch for is never stored.

Every test here points the store at a directory of its own (the suite's
shared one may be warm from an earlier run): children through
``JAX_COMPILATION_CACHE_DIR``, in-process tests through JAX's own
setting (the ``own_store`` fixture) — not by patching
``compile_cache.cache_dir``: a test's function standing in a package
module is itself a reason to build no key.
"""

import dataclasses
import json
import os
import pickle
import shutil
import subprocess
import sys
import zlib

import jax
import jax.numpy as jnp
import pytest

from dslabs_tpu.tpu import compile_cache
from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh
from dslabs_tpu.tpu.specs import pingpong_spec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One fresh interpreter: build what ``plan`` says, run it, and print one
# JSON line — the answer, ``compile_cache.totals()`` and how often JAX
# traced or lowered a function named ``superstep``.
_CHILD = r"""
import json, os, sys
sys.path.insert(0, %(root)r)
plan = json.loads(sys.argv[1])
import jax
seen = {"trace": 0, "lower": 0}
def _on(event, secs, **kw):
    if "superstep" in str(kw.get("fun_name")):
        for kind in seen:
            if kind in event.rsplit("/", 1)[-1].replace("to_mlir", "lower"):
                seen[kind] += 1
jax.monitoring.register_event_duration_secs_listener(_on)
from dslabs_tpu.tpu import compile_cache
if plan["kind"] == "deep":
    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh
    from dslabs_tpu.tpu.specs import pingpong_spec
    import dataclasses
    search = ShardedTensorSearch(
        dataclasses.replace(pingpong_spec(workload_size=3).compile(),
                            goals={}), make_mesh(plan["devices"]),
        chunk_per_device=16, frontier_cap=256, visited_cap=1 << 10,
        aot_warmup=True)
    out = search.run()
    answer = {"end": out.end_condition, "depth": out.depth,
              "unique": out.unique_states, "explored": out.states_explored,
              "levels": [[lv["depth"], lv["unique"], lv["explored"]]
                         for lv in out.levels]}
    text = search._aot_exes["superstep"].as_text()
    answer["scoped"] = "dslabs.expand" in text
    answer["memory"] = search._aot_exes[
        "superstep"].memory_analysis() is not None
else:
    from dslabs_tpu.tpu import backend
    from tests.test_search_backend import _answer, _lab1, _lab1_settings
    res = backend.tensor_bfs(_lab1(plan["seed"]), _lab1_settings("exhaust"))
    answer = _answer(res)
    answer["end"] = answer["end"].name
    answer["provenance"] = None
    answer["kept_text"] = "dslabs.expand" in next(iter(
        backend._KEPT._table[k] for k in backend._KEPT._table
        if k[0] == "engine")).as_text()
print(json.dumps({"answer": answer, "totals": compile_cache.totals(),
                  "superstep": seen, "dir": compile_cache.store_dir()}))
"""


def _child(cache_dir, **plan):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env.pop("DSLABS_AOT_WARMUP", None)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD % {"root": ROOT}, json.dumps(plan)],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture
def own_store(tmp_path):
    """The store under ``tmp_path`` for one in-process test (JAX's XLA
    cache object keeps the directory it first used; the store asks the
    setting every time)."""
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", was)


def _entries(cache_dir):
    folder = os.path.join(str(cache_dir), "executables")
    return sorted(os.path.join(folder, f) for f in os.listdir(folder)
                  if f.endswith(".exe"))


@pytest.fixture(scope="module")
def warm(tmp_path_factory):
    """A cache directory after ONE process built the small twin's engine
    in it, and what that process reported."""
    folder = tmp_path_factory.mktemp("exe-store")
    return folder, _child(folder, kind="deep", devices=2)


# ------------------------------------------------ (a) two processes in turn

def test_second_process_loads_what_the_first_compiled(warm, tmp_path):
    folder, first = warm
    assert first["dir"] == os.path.join(str(folder), "executables")
    assert (first["totals"]["exe_store_hit_n"],
            first["totals"]["exe_store_miss_n"]) == (0, 3)
    assert first["superstep"]["trace"] >= 1 <= first["superstep"]["lower"]
    assert first["totals"]["exe_store_write_s"] > 0
    assert len(_entries(folder)) == 3
    copy = tmp_path / "copy"
    shutil.copytree(folder, copy)
    second = _child(copy, kind="deep", devices=2)
    assert (second["totals"]["exe_store_hit_n"],
            second["totals"]["exe_store_miss_n"]) == (3, 0)
    assert second["superstep"] == {"trace": 0, "lower": 0}
    assert second["totals"]["exe_store_load_s"] > 0
    assert second["totals"]["exe_store_bytes"] == sum(
        os.path.getsize(p) for p in _entries(copy))
    # the same answer, level record for level record, and the readers of
    # telemetry.register_program find text, scopes and a memory plan
    assert second["answer"] == first["answer"]
    assert first["answer"]["end"] == "SPACE_EXHAUSTED"
    assert second["answer"]["scoped"] and second["answer"]["memory"]
    # nothing left half-written, nothing new
    assert sorted(os.listdir(copy / "executables")) == sorted(
        os.listdir(folder / "executables"))


def test_mesh_width_is_in_the_key(warm, tmp_path):
    copy = tmp_path / "copy"
    shutil.copytree(warm[0], copy)
    other = _child(copy, kind="deep", devices=1)
    assert (other["totals"]["exe_store_hit_n"],
            other["totals"]["exe_store_miss_n"]) == (0, 3)
    assert other["answer"]["levels"] == warm[1]["answer"]["levels"]
    assert len(_entries(copy)) == 6


# --------------------------------------------------- (c) every doubt a miss

def _truncate(path):
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(blob[:len(blob) // 2])


def _garbage(path):
    with open(path, "wb") as f:
        f.write(os.urandom(4096))


def _other_jaxlib(path):
    with open(path, "rb") as f:
        stamp, *rest = pickle.loads(zlib.decompress(f.read()))
    stamp = (stamp[0], "0.0.1") + tuple(stamp[2:])
    with open(path, "wb") as f:
        f.write(zlib.compress(pickle.dumps((stamp, *rest))))


@pytest.mark.parametrize("spoil", [_truncate, _garbage, _other_jaxlib],
                         ids=["truncated", "garbage", "other-jaxlib"])
def test_a_spoilt_entry_is_a_miss_and_is_replaced(warm, tmp_path, spoil):
    copy = tmp_path / "copy"
    shutil.copytree(warm[0], copy)
    for path in _entries(copy):
        spoil(path)
    again = _child(copy, kind="deep", devices=2)
    assert (again["totals"]["exe_store_hit_n"],
            again["totals"]["exe_store_miss_n"]) == (0, 3)
    assert again["superstep"]["trace"] >= 1
    assert again["answer"] == warm[1]["answer"]
    healed = _child(copy, kind="deep", devices=2)
    assert (healed["totals"]["exe_store_hit_n"],
            healed["totals"]["exe_store_miss_n"]) == (3, 0)
    assert healed["answer"] == warm[1]["answer"]


def test_a_store_that_cannot_be_written_keeps_nothing(warm, tmp_path):
    """The store's place is taken by a plain file (a read-only directory
    stops no writer that runs as root): nothing can be read or written
    there, and the engine traces and answers as ever."""
    (tmp_path / "executables").write_text("not a directory")
    out = _child(tmp_path, kind="deep", devices=2)
    assert (out["totals"]["exe_store_hit_n"],
            out["totals"]["exe_store_miss_n"]) == (0, 3)
    assert out["totals"]["exe_store_write_s"] == 0
    assert out["totals"]["exe_store_bytes"] == 0
    assert out["answer"] == warm[1]["answer"]
    assert (tmp_path / "executables").read_text() == "not a directory"


# ------------------------------------------- (d) two writers of one key

def test_two_processes_storing_at_once_leave_whole_files(warm, tmp_path):
    plan = json.dumps({"kind": "deep", "devices": 2})
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CHILD % {"root": ROOT}, plan], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, cwd=ROOT)
        for _ in range(2)]
    outs = [p.communicate(timeout=600) for p in procs]
    assert [p.returncode for p in procs] == [0, 0], outs
    assert sorted(os.listdir(tmp_path / "executables")) == sorted(
        os.listdir(warm[0] / "executables"))        # no .tmp, same keys
    third = _child(tmp_path, kind="deep", devices=2)
    assert (third["totals"]["exe_store_hit_n"],
            third["totals"]["exe_store_miss_n"]) == (3, 0)
    assert third["answer"] == warm[1]["answer"]


# ------------------------------------------------------- (g) the lab entry

def test_lab_entry_second_process_traces_no_superstep(tmp_path):
    first = _child(tmp_path, kind="lab", seed=5)
    assert first["totals"]["exe_store_hit_n"] == 0
    assert first["totals"]["exe_store_miss_n"] == 4     # engine 3 + step
    assert first["superstep"]["trace"] >= 1
    # another seed: the twin is value-blind, so the keys are the same
    second = _child(tmp_path, kind="lab", seed=5)
    other = _child(tmp_path, kind="lab", seed=11)
    for out in (second, other):
        assert (out["totals"]["exe_store_hit_n"],
                out["totals"]["exe_store_miss_n"]) == (4, 0)
        assert out["superstep"] == {"trace": 0, "lower": 0}
        assert out["answer"]["kept_text"]
    assert second["answer"] == first["answer"]
    assert other["answer"]["end"] == "SPACE_EXHAUSTED"
    assert other["answer"]["discovered"] == 80


# ------------------------------------------------- (b) what the key tells

def _engine(protocol=None, devices=1, **kw):
    args = dict(chunk_per_device=16, frontier_cap=256, visited_cap=1 << 10)
    args.update(kw)
    return ShardedTensorSearch(
        protocol or pingpong_spec(workload_size=3).compile(),
        make_mesh(devices), **args)


def _superstep_key(search):
    sds = search._carry_sds()
    return compile_cache.program_key(
        search.store_key(), "superstep",
        (sds, jnp.asarray(1 << 30, jnp.int32)))


def test_the_key_is_the_same_for_the_same_engine():
    assert _superstep_key(_engine()) == _superstep_key(_engine())
    assert _superstep_key(_engine()) is not None


def _handler_constant(monkeypatch):
    return _engine(pingpong_spec(workload_size=4).compile())


def _goals_stripped(monkeypatch):
    return _engine(dataclasses.replace(
        pingpong_spec(workload_size=3).compile(), goals={}))


def _knob_variable(monkeypatch):
    monkeypatch.setenv("DSLABS_SOME_KNOB", "1")
    return _engine()


def _xla_flags(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", "")
                       + " --xla_cpu_enable_fast_math=false")
    return _engine()


_VARIANTS = {
    "handler-constant": _handler_constant,
    "chunk": lambda mp: _engine(chunk_per_device=32),
    "frontier-cap": lambda mp: _engine(frontier_cap=512),
    "visited-cap": lambda mp: _engine(visited_cap=1 << 11),
    "ev-budget": lambda mp: _engine(ev_budget=(4, 2)),
    "strict": lambda mp: _engine(strict=False),
    "record-trace": lambda mp: _engine(record_trace=True),
    "goals-stripped": _goals_stripped,
    "knob-variable": _knob_variable,
    "xla-flags": _xla_flags,
    "mesh-width": lambda mp: _engine(devices=2),
}


@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_the_key_tells_apart(variant, monkeypatch):
    base = _superstep_key(_engine())
    other = _superstep_key(_VARIANTS[variant](monkeypatch))
    assert other is not None and other != base


def test_one_byte_of_the_package_is_another_key(tmp_path):
    """The digest reads every ``*.py`` under a root, path and bytes, and
    the package's is the first thing in every key."""
    (tmp_path / "sub").mkdir()
    (tmp_path / "a.py").write_bytes(b"x = 1\n")
    (tmp_path / "sub" / "b.py").write_bytes(b"y = 2\n")
    (tmp_path / "notes.txt").write_bytes(b"not source")
    first = compile_cache._digest(str(tmp_path))
    (tmp_path / "notes.txt").write_bytes(b"still not source")
    compile_cache._digest.cache_clear()
    assert compile_cache._digest(str(tmp_path)) == first
    (tmp_path / "sub" / "b.py").write_bytes(b"y = 3\n")
    compile_cache._digest.cache_clear()
    assert compile_cache._digest(str(tmp_path)) != first
    (tmp_path / "sub" / "b.py").write_bytes(b"y = 2\n")
    (tmp_path / "sub" / "b.py").rename(tmp_path / "sub" / "c.py")
    compile_cache._digest.cache_clear()
    assert compile_cache._digest(str(tmp_path)) != first
    compile_cache._digest.cache_clear()
    assert compile_cache.environment_key(jax.devices()[:1])[0] == (
        compile_cache._digest(compile_cache._PACKAGE))


def test_a_handler_constant_changes_no_shape():
    """The case the key exists for: two twins of equal lanes, caps and
    abstract arguments whose handlers close over another constant."""
    a = _engine(pingpong_spec(workload_size=5).compile())
    b = _engine(pingpong_spec(workload_size=4).compile())
    assert (a.lanes, a.plane) == (b.lanes, b.plane)
    assert str(a._carry_sds()) == str(b._carry_sds())
    assert a.store_key() != b.store_key()


def test_the_roots_owner_and_home_are_in_the_initialisers_key():
    base = _engine().store_key()
    args = (jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.uint32))
    keys = {compile_cache.program_key(base, "init_carry", args, owner, home)
            for owner, home in ((0, 8), (1, 8), (0, 16))}
    assert len(keys) == 3
    assert compile_cache.program_key(None, "init_carry", args, 0, 8) is None


# ------------------------------------ (f) what the fingerprinter cannot vouch

class _Opaque:
    """No dataclass, no array, no function: hashed by type alone."""

    limit = 3


def _spec_closing_over(value):
    spec = pingpong_spec(workload_size=3)

    @spec.on("server", "REQ")
    def srv_req(ctx, m):
        ctx.send("REPLY", 1, i=m["i"] + 0 * getattr(value, "limit", 0))

    return spec.compile()


def test_a_closure_hashed_by_type_is_never_stored(own_store):
    search = _engine(_spec_closing_over(_Opaque()))
    assert search.store_key() is None
    before = compile_cache.totals()
    search.aot_warmup()
    after = compile_cache.totals()
    assert not os.path.exists(compile_cache.store_dir())
    assert all(after[k] == before[k] for k in after
               if k.startswith("exe_store_"))
    assert search.run().end_condition == "GOAL_FOUND"


def _wrapped_mixer(flat, seed, sum_fn=None):
    from dslabs_tpu.tpu import engine

    return engine._fingerprint32.__wrapped__(flat[:, :3], seed, sum_fn)


def _goal_reading(holder):
    """The small twin with a goal that closes over an object — hashed
    by type — and reads ONE attribute of it."""
    p = pingpong_spec(workload_size=3).compile()
    return dataclasses.replace(p, goals={
        "K": lambda s: s["nodes"][0] == holder.limit})


def test_a_predicate_closing_over_an_object_is_hashed_by_its_trace():
    """A lab binding's predicates close over the binding, an object
    whose commands' values the twin never reads: such a predicate is
    vouched for by its jaxpr over the protocol's abstract state — equal
    for objects that differ in what it does not read, another for
    another constant — and a predicate that cannot be traced is weak."""
    from dslabs_tpu.service.memo import program_fingerprint

    a, b, c = _Opaque(), _Opaque(), _Opaque()
    b.commands, c.limit = ["another seed's"], 2
    fps = [program_fingerprint(_goal_reading(h)) for h in (a, b, c)]
    assert not any(fp["weak"] for fp in fps)
    assert fps[0]["fp"] == fps[1]["fp"] != fps[2]["fp"]
    assert __file__ in fps[0]["files"]
    untraceable = dataclasses.replace(
        _goal_reading(a), goals={"K": lambda s: bool(s["nodes"][0])
                                 and a.limit})
    assert program_fingerprint(untraceable)["weak"]
    assert _engine(_goal_reading(a)).store_key() is not None
    assert _engine(untraceable).store_key() is None


@pytest.mark.parametrize("seeds", [(5, 77)])
def test_a_lab_bindings_engine_has_one_key_for_every_seed(seeds):
    """Lab 3 through the lab entry's own binding (``PaxosBinding``: every
    predicate closes over it): the bound protocol's fingerprint is not
    weak, the same for another seed's commands, another for another
    phase's predicates."""
    from benchmark.drivers import lab_phases
    from dslabs_tpu.service.memo import program_fingerprint
    from dslabs_tpu.tpu import backend

    def bound(seed, goals):
        state = lab_phases.build_state(
            {"kind": "paxos", "servers": 3, "clients": 2,
             "commands_per_client": 1}, seed)
        settings = lab_phases.build_settings(
            {"max_time": 60, "invariants": ["RESULTS_OK"], "goals": goals,
             "prunes": [], "partition": [], "timers_off": [],
             "max_depth": None}, state)
        binding = backend.resolve_binding(state)
        binding.check_settings(settings)
        return program_fingerprint(backend._bind_protocol(
            binding, settings, *binding.initial_caps())[0])

    one, other = (bound(seed, ["CLIENTS_DONE"]) for seed in seeds)
    assert not one["weak"] and one["fp"] == other["fp"]
    assert bound(seeds[0], [{"negate": "NONE_DECIDED"}])["fp"] != one["fp"]


@pytest.mark.parametrize("where", ["module", "class"])
def test_a_patched_package_is_never_stored(own_store, monkeypatch, where):
    """The benchmark's control wraps ``engine._fingerprint32`` from a
    test file; the digest of the package's files cannot see that, so a
    function from outside the package standing in one of its modules
    (or classes) is a reason to build no key: the narrowed program is
    traced, never stored, and never handed the real one."""
    from dslabs_tpu.tpu import engine

    search = _engine()
    assert search.store_key() is not None
    if where == "module":
        _wrapped_mixer.__wrapped__ = engine._fingerprint32
        monkeypatch.setattr(engine, "_fingerprint32", _wrapped_mixer)
    else:
        monkeypatch.setattr(ShardedTensorSearch, "_prog",
                            lambda self, name, default: default)
    assert search.store_key() is None
    search.aot_warmup()
    assert not os.path.exists(compile_cache.store_dir())
    monkeypatch.undo()
    assert search.store_key() is not None


def test_a_handler_outside_the_package_brings_its_file_into_the_key():
    """This file's handler is hashable by value, so the engine has a
    key — with the source of this file (and of every other module that
    is neither the package's nor the interpreter's) read into it."""
    search = _engine(_spec_closing_over(7))
    assert search.store_key() is not None
    devices = jax.devices()[:1]
    env = compile_cache.environment_key(devices, [__file__])
    assert os.path.abspath(__file__) in {
        os.path.abspath(f) for f, _ in env[-1]}
    assert compile_cache.environment_key(devices)[-1] == ()
    assert compile_cache.environment_key(devices, ["<stdin>"]) is None


# ------------------------------------------------------------ (e) eviction

def test_eviction_keeps_the_store_bounded_and_the_newest(own_store,
                                                         monkeypatch):
    tmp_path = own_store
    arg = jax.ShapeDtypeStruct((8,), jnp.int32)
    devices = jax.devices()[:1]

    def store(i):
        return compile_cache.stored(
            f"{i:064x}", f"p{i}",
            lambda: jax.jit(lambda x: x * i + i).lower(arg).compile(),
            devices)

    def present():
        return {int(os.path.basename(p)[:-4], 16)
                for p in _entries(tmp_path)}

    assert int(store(1)(jnp.arange(8, dtype=jnp.int32))[2]) == 3
    size = os.path.getsize(_entries(tmp_path)[0])
    monkeypatch.setattr(compile_cache, "STORE_BOUND", 3 * size + size // 2)
    for i, path in enumerate(_entries(tmp_path)):
        os.utime(path, (1000, 1000))
    for i in (2, 3):
        store(i)
        os.utime(os.path.join(compile_cache.store_dir(), f"{i:064x}.exe"),
                 (1000 + i, 1000 + i))
    assert present() == {1, 2, 3}
    hits = compile_cache.totals()["exe_store_hit_n"]
    assert int(store(1)(jnp.arange(8, dtype=jnp.int32))[2]) == 3
    assert compile_cache.totals()["exe_store_hit_n"] == hits + 1
    # what a killed writer left an hour ago goes with the next write; a
    # writer still at work keeps its file
    stale, fresh = (os.path.join(compile_cache.store_dir(), n)
                    for n in ("killed.tmp", "writing.tmp"))
    for path in (stale, fresh):
        with open(path, "wb") as f:
            f.write(b"half")
    os.utime(stale, (1000, 1000))
    store(4)        # 2 is now the least recently used
    assert present() == {1, 3, 4}
    assert not os.path.exists(stale) and os.path.exists(fresh)
    os.unlink(fresh)
    monkeypatch.setattr(compile_cache, "STORE_BOUND", 1)
    store(5)        # a bound below one entry still keeps the one written
    assert present() == {5}
    total = sum(os.path.getsize(p) for p in _entries(tmp_path))
    assert total == os.path.getsize(_entries(tmp_path)[0])


# ----------------------------------- XLA:CPU re-serializes without kernels

def test_what_the_xla_cache_loaded_is_not_written_on_the_cpu(own_store):
    """jaxlib 0.9.0, XLA:CPU: an executable that was itself LOADED
    serializes to a payload that loads and then fails where it runs.  So
    a compile that the XLA cache answered is not stored on this
    backend."""
    tmp_path = own_store
    arg = jax.ShapeDtypeStruct((8,), jnp.int32)
    compiled = jax.jit(lambda x: x + 41).lower(arg).compile()

    def from_the_xla_cache():
        compile_cache._on_event("/jax/compilation_cache/cache_hits")
        return compiled

    compile_cache.stored("a" * 64, "p", from_the_xla_cache,
                         jax.devices()[:1])
    assert not os.path.exists(compile_cache.store_dir())
    compile_cache.stored("a" * 64, "p", lambda: compiled,
                         jax.devices()[:1])
    assert len(_entries(tmp_path)) == 1
