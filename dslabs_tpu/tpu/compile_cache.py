"""Persistent XLA compile-cache wiring (one place, one path).

The cache directory is part of the cache's key, so a directory that
moves never hits.  This module is the single seam:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
  and this module sets no directory at all;
* where it is not set, the cache lives at ``<checkout>/.jax_cache``,
  computed from this package's own location — the same path from every
  process and every working directory (``.gitignore`` lists it).

It is also where the program listens to JAX's own compile events
(``jax.monitoring``): :func:`setup` registers ONE listener for the life
of the process, which writes each event as a ``compile.event`` mark
(tpu/telemetry.py) — on the profiler's clock in a traced run, in the
flight log of a current recorder — and keeps the process totals
:func:`totals` reads.

Together with the engines' AOT warm-up (``ShardedTensorSearch
.aot_warmup``) the second construction of any config pays near-zero
compile: the warm-up's ``.lower().compile()`` hits the on-disk cache
instead of XLA.

And it is where COMPILED EXECUTABLES outlive a process (the store,
below).  JAX's cache is keyed by the lowered module, so a process must
trace and lower a program before it can ask for it — for the search
programs that Python pass is most of set-up.  The store is asked BEFORE
anything is traced, under a key built from what the traced program is a
function of (:func:`environment_key` here, the engine's and the
protocol's parts in ``TensorSearch.store_key``); :func:`stored` loads
the executable or, on any doubt, has it compiled as before and keeps it.
"""

from __future__ import annotations

import functools
import hashlib
import os
import pickle
import tempfile
import zlib
import threading
import time
from typing import Callable, Optional

__all__ = ["setup", "cache_dir", "totals", "twin_built", "DEFAULT_DIR",
           "store_dir", "environment_key", "program_key", "stored",
           "STORE_BOUND"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The persistent-compile-cache directory currently in effect."""
    import jax

    return jax.config.jax_compilation_cache_dir


# JAX's compile events, by the short name a ``compile.event`` mark
# carries as ``kind``.  ``trace`` events nest (tracing a function traces
# the jitted functions it calls), ``retrieve`` lies inside
# ``backend_compile`` (which times compile-or-load).
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieve",
}
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}
_lock = threading.Lock()
_listening = False
_totals = {k + "_s": 0.0 for k in _DURATIONS.values()}
_totals.update({k + "_n": 0 for k in _DURATIONS.values()})
_totals.update({k + "_n": 0 for k in _EVENTS.values()})
# [start, secs] of the trace events counted so far, oldest first: an
# event that began before them contains them, and takes their place.
# Held to _TRACE_KEEP entries by folding the two oldest into one, which
# miscounts only if a trace still open began between those two: each
# fold is counted (``trace_folded_n``), so a total that may be high
# says so.
_trace_counted: list = []
_TRACE_KEEP = 1 << 16
_totals["trace_folded_n"] = 0
# The executable store's part: lookups that loaded / that did not, the
# seconds spent loading and writing, the bytes of the entries loaded
# and written.
_totals.update(exe_store_hit_n=0, exe_store_miss_n=0, exe_store_load_s=0.0,
               exe_store_write_s=0.0, exe_store_bytes=0)
# The twins built (``ProtocolSpec.compile()`` calls) and their seconds.
_totals.update(twin_build_n=0, twin_build_s=0.0)


def _on_duration(event, secs, **kw) -> None:
    kind = _DURATIONS.get(event)
    if kind is None:
        return
    from dslabs_tpu.tpu import telemetry

    secs = float(secs)
    with _lock:
        _totals[kind + "_n"] += 1
        _totals[kind + "_s"] += secs
        if kind == "trace":
            start = time.monotonic() - secs
            while _trace_counted and _trace_counted[-1][0] >= start:
                _totals["trace_s"] -= _trace_counted.pop()[1]
            _trace_counted.append([start, secs])
            if len(_trace_counted) > _TRACE_KEEP:
                _trace_counted[0][1] += _trace_counted.pop(1)[1]
                _totals["trace_folded_n"] += 1
    fields = {"kind": kind, "secs": round(secs, 6)}
    if kw.get("fun_name"):
        fields["fun"] = str(kw["fun_name"])
    telemetry.mark("compile.event", **fields)


def _on_event(event, **_kw) -> None:
    kind = _EVENTS.get(event)
    if kind is None:
        return
    from dslabs_tpu.tpu import telemetry

    with _lock:
        _totals[kind + "_n"] += 1
    telemetry.mark("compile.event", kind=kind, secs=0.0)


def totals() -> dict:
    """What the process has spent in JAX's compile machinery since
    :func:`setup` first ran: seconds and counts by kind — ``trace_s``
    (jaxpr tracing, nested traces counted once), ``lower_s`` (jaxpr to
    MLIR), ``backend_compile_s`` (XLA compile or persistent-cache load;
    ``retrieve_s`` is the loading part of it) — the persistent cache's
    ``cache_hit_n`` / ``cache_miss_n``, and ``trace_folded_n``: how
    often the record of counted traces was shortened (0: ``trace_s``
    is exact).  The executable store's: ``exe_store_hit_n`` /
    ``exe_store_miss_n`` (lookups under a key; a program whose key
    could not be built is not looked up), ``exe_store_load_s``,
    ``exe_store_write_s``, ``exe_store_bytes`` (entries loaded and
    written).  The spec compiler's: ``twin_build_n`` /
    ``twin_build_s``, the twins built (:func:`twin_built`)."""
    with _lock:
        return dict(_totals)


def twin_built(secs: float) -> None:
    """One ``ProtocolSpec.compile()`` took ``secs`` (its
    ``compile.twin`` span): a lab call builds a twin a ladder rung and
    a binding, and the totals sum them, as set-up pays them."""
    _count(twin_build_n=1, twin_build_s=float(secs))


def setup() -> str:
    """Enable JAX's persistent compilation cache and return its
    directory: the one ``JAX_COMPILATION_CACHE_DIR`` names (left to
    JAX), else :data:`DEFAULT_DIR`.  Idempotent — every engine
    constructor calls it."""
    import jax

    global _listening
    if not _listening:
        # JAX offers no stable way to take a listener out again, so
        # there is one, registered once.
        import jax.monitoring

        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            and jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        from jax.experimental.compilation_cache import \
            compilation_cache as cc

        os.makedirs(DEFAULT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
        # The runtime holds a cache singleton initialised with the dir
        # at FIRST use — without a reset, a dir change after any cached
        # compile is silently ignored.
        cc.reset_cache()
    # Cache quick compiles too: the search programs are rebuilt by
    # every process that constructs an engine, and the key is
    # platform-specific anyway.  0.3 s and not 0.5: on the v5e lab 3's
    # ``_step_one``, which every staged call rebuilds, compiles in about
    # 0.5 s — under the floor a few times and over it later, so a cold
    # process cached it at a moment chance chose (PERF.md, PR 27); the
    # small programs every call rebuilds take 0.02-0.21 s there.
    if jax.config.jax_persistent_cache_min_compile_time_secs > 0.3:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.3)
    return jax.config.jax_compilation_cache_dir


# ---------------------------------------------------- the executable store
#
# One file an entry, ``<store_dir()>/<key>.exe``: a compressed pickle of
# the executable as ``jax.experimental.serialize_executable`` gives it
# (payload, in-tree, out-tree) under a stamp of the runtime that wrote
# it.  A miss is always safe and a stale hit is a wrong verdict, so
# every doubt is a miss: no key, no file, a torn or foreign file, a
# runtime that refuses to load it.

STORE_BOUND = 1 << 30       # bytes the store may hold; least recently
                            # used entries beyond it go (every commit
                            # makes new keys)
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_KEY_ENV = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")


def store_dir() -> str:
    """Where the executables are kept: beside the XLA cache, wherever
    that lives."""
    return os.path.join(cache_dir(), "executables")


@functools.lru_cache(maxsize=None)
def _digest(root: str) -> str:
    """SHA-256 over every ``*.py`` under ``root`` (path and bytes)."""
    h = hashlib.sha256()
    for folder, dirs, files in os.walk(root):
        dirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                h.update(os.path.relpath(path, root).encode() + b"\0")
                with open(path, "rb") as f:
                    h.update(f.read() + b"\0")
    return h.hexdigest()


def _stamp(devices) -> tuple:
    """The runtime an entry was written by and for, checked again when
    it is loaded: jax, jaxlib, the platform and version of the backend
    that holds ``devices``."""
    import jax
    import jaxlib

    backend = devices[0].client
    return (jax.__version__, jaxlib.__version__, backend.platform,
            backend.platform_version)


def _foreign_code(files) -> Optional[set]:
    """The source files a key must read besides the package's: those of
    ``files`` (where a protocol's functions were defined) that lie
    outside the package and, if there is one, every other module of the
    process that is neither the package's nor the interpreter's (what
    such a function's globals may come from).  None — no key can be
    vouched for — where one of ``files`` is no file, and where the
    package AS LOADED is not the package on disk: a function from such
    a file standing in a package module's or class's namespace (a
    test's wrapper around the fingerprint mixer: the digest of the
    files cannot see it)."""
    import sys
    import sysconfig
    import types

    home = (os.path.realpath(_PACKAGE) + os.sep,) + tuple({
        os.path.realpath(p) + os.sep
        for p in sysconfig.get_paths().values()})

    def foreign(path) -> bool:
        return not (path.startswith("<")
                    or os.path.realpath(path).startswith(home))

    modules = list(sys.modules.items())
    for name, mod in modules:
        if name.partition(".")[0] != "dslabs_tpu":
            continue
        spaces = [vars(mod)] + [vars(v) for v in vars(mod).values()
                                if isinstance(v, type)
                                and v.__module__ == name]
        for space in spaces:
            for v in list(space.values()):
                if isinstance(v, (staticmethod, classmethod)):
                    v = v.__func__
                if isinstance(v, types.FunctionType) and foreign(
                        v.__code__.co_filename):
                    return None
    if any(f.startswith("<") for f in files):   # no file to read
        return None
    seen = {f for f in files if foreign(f)}
    if seen:
        for _, mod in modules:
            f = getattr(mod, "__file__", None)
            if f and f.endswith(".py") and foreign(f):
                seen.add(f)
    return seen


def environment_key(devices, files=()) -> Optional[tuple]:
    """What every program an engine on ``devices`` traces is a function
    of besides the engine and its protocol: the package's source (one
    byte changed in any ``*.py`` under ``dslabs_tpu/`` is another key)
    and what :func:`_foreign_code` adds to it, the runtime
    (:func:`_stamp`), the devices' kind and how many the backend has,
    ``XLA_FLAGS``, ``LIBTPU_INIT_ARGS`` and the two switches of JAX that
    change what a trace computes (the program's own knob variables are
    read where its engines read them: ``TensorSearch.store_key``).
    None where that cannot be vouched for, or a function has no file to
    read."""
    import jax

    sources = []
    try:
        for f in sorted(_foreign_code(files)):
            with open(f, "rb") as fh:
                sources.append((f, hashlib.sha256(fh.read()).hexdigest()))
    except (TypeError, OSError):    # a patched package; an unreadable file
        return None
    return (_digest(_PACKAGE), _stamp(devices), devices[0].device_kind,
            devices[0].client.device_count(),
            tuple((k, os.environ.get(k)) for k in _KEY_ENV),
            (bool(jax.config.jax_enable_x64),
             str(jax.config.jax_default_matmul_precision)),
            tuple(sources))


def program_key(base: Optional[str], name: str, args, *extra
                ) -> Optional[str]:
    """The store's key of ONE program of an engine whose
    ``store_key()`` is ``base``: with its name, the abstract arguments
    and shardings it is lowered for, and what else is baked into it
    (``extra``: the carry initialiser's owner and home slot).  None
    stays None."""
    if base is None:
        return None
    import jax

    leaves, tree = jax.tree_util.tree_flatten(args)
    return hashlib.sha256(repr((base, name, str(tree), [
        (tuple(x.shape), str(x.dtype), repr(getattr(x, "sharding", None)))
        for x in leaves], extra)).encode()).hexdigest()


def _count(**by) -> None:
    with _lock:
        for k, v in by.items():
            _totals[k] += v


def _load(path: str, name: str, devices):
    """The executable kept at ``path``, on ``devices``; None (a miss)
    for whatever reason it cannot be had."""
    from jax.experimental import serialize_executable

    from dslabs_tpu.tpu import telemetry

    t0 = time.monotonic()
    exe = None
    try:
        with telemetry.phase("compile.store.load", program=name):
            with open(path, "rb") as f:
                blob = f.read()
            stamp, payload, in_tree, out_tree = pickle.loads(
                zlib.decompress(blob))
            if stamp != _stamp(devices):
                raise ValueError(f"written by {stamp}")
            exe = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                backend=devices[0].client, execution_devices=devices)
    except Exception:  # noqa: BLE001 — every doubt is a miss
        pass
    if exe is not None:
        try:
            os.utime(path)          # most recently used
        except OSError:             # a store that can only be read
            pass
    secs = time.monotonic() - t0
    if exe is None:
        _count(exe_store_miss_n=1)
    else:
        _count(exe_store_hit_n=1, exe_store_load_s=secs,
               exe_store_bytes=len(blob))
    telemetry.mark("compile.event", secs=round(secs, 6), fun=name,
                   kind="store_miss" if exe is None else "store_hit")
    return exe


def _write(path: str, name: str, exe, devices) -> None:
    """Keep ``exe`` at ``path``: a temporary file renamed into place
    (two writers of one key leave one whole file), then the least
    recently used entries beyond :data:`STORE_BOUND` removed, never
    this one.  A directory that cannot be written keeps nothing."""
    from jax.experimental import serialize_executable

    from dslabs_tpu.tpu import telemetry

    t0 = time.monotonic()
    try:
        with telemetry.phase("compile.store.write", program=name):
            blob = zlib.compress(pickle.dumps(
                (_stamp(devices),)
                + tuple(serialize_executable.serialize(exe))), 1)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                       suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
            except BaseException:
                os.unlink(tmp)
                raise
            _evict(path)
    except Exception:  # noqa: BLE001 — a store that cannot keep is no fault
        return
    _count(exe_store_write_s=time.monotonic() - t0,
           exe_store_bytes=len(blob))


def _evict(keep: str) -> None:
    """Hold the store to :data:`STORE_BOUND`: the least recently used
    entries go, never ``keep``; so does what a writer that was killed
    left half-written an hour ago."""
    entries = []
    for entry in os.scandir(os.path.dirname(keep)):
        try:
            st = entry.stat()
            if entry.name.endswith(".exe"):
                entries.append((st.st_mtime, st.st_size, entry.path))
            elif (entry.name.endswith(".tmp")
                  and st.st_mtime < time.time() - 3600):
                os.unlink(entry.path)
        except OSError:             # another process removed it
            continue
    total = sum(size for _, size, _ in entries)
    for _, size, path in sorted(entries):
        if total <= STORE_BOUND:
            break
        if path != keep:
            try:
                os.unlink(path)
            except OSError:
                pass
            total -= size


def stored(key: Optional[str], name: str, compile_: Callable, devices):
    """The executable of the program ``name``: loaded from the store
    under ``key`` onto ``devices`` where it is there, else what
    ``compile_()`` traces, lowers and compiles, kept for the next
    process.  ``key`` None (no key could be vouched for): ``compile_()``
    and nothing else."""
    if key is None:
        return compile_()
    devices = list(devices)
    path = os.path.join(store_dir(), key + ".exe")
    exe = _load(path, name, devices)
    if exe is None:
        xla_hits = totals()["cache_hit_n"]
        exe = compile_()
        # XLA:CPU (jaxlib 0.9.0) serializes an executable that it LOADED
        # without its kernels: the entry loads and then fails where it
        # runs ("Function … not found").  So on that backend what the
        # XLA cache handed over is not written; a TPU's is whole
        # (PERF.md section 6, PR 41).
        if not (devices[0].platform == "cpu"
                and totals()["cache_hit_n"] > xla_hits):
            _write(path, name, exe, devices)
    return exe
