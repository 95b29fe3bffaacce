"""Cross-job memoization for the checking service (ISSUE 16 tentpole).

The service workload is adversarially redundant — a class of a thousand
students submits near-identical protocols, and each student resubmits
after a one-line fix — yet before this module every accepted job
re-explored its state space from the root.  Three reuse legs, all keyed
on a STRUCTURAL spec fingerprint (never source text):

* **Verdict cache** — an exact-key hit (same structure, same predicates,
  same budget and engine-relevant knobs) returns the cached verdict with
  zero device dispatches, journaled as a ``memo_hit`` event with a
  ``cached=true`` verdict and a near-zero COSTS charge.
* **Warm start** — same structure, bigger budget: the new job's run dir
  is pre-seeded with the prior run's deepest checkpoint (device visited
  table + host spill tier + frontier all restore through the existing
  ``tpu/checkpoint.py`` path), so the search resumes at the cached
  frontier depth with EXACT counts — bit-identical to a cold run at
  equal depth, because the checkpoint stores the exact visited union.
* **Incremental re-check** — the structural diff localizes to a handler
  set H: tag-reachability over the compiled spec's event table bounds
  the first level whose expansion could fire H, and the job resumes
  from the deepest archived per-level checkpoint at or below that bound
  (``levels_skipped`` >= 1 for any handler not reachable at the root).

Invalidation is loud and conservative: the engine checkpoint
``config_fingerprint`` (protocol name/widths/caps, strictness, symmetry
perm count, checkpoint format version), the pack/symmetry env gates, and
the memo format version all ride the key; any mismatch — or any spec
whose closure the fingerprinter cannot hash by VALUE — is a cold run,
never a stale verdict.  The known boundary: a tenant module's own file
contents are hashed into the introspection cache key, but modules IT
imports are not — docs/memo.md spells out the contract.

Knobs: ``DSLABS_MEMO`` (service default ON), ``DSLABS_MEMO_DIR``
(default ``<root>/memo``), ``DSLABS_MEMO_TIER_CAP`` (largest visited
tier archived per signature, default 4M keys).

Store layout (beside the service journal, torn-tolerant):

    memo/verdicts.jsonl            append-only exact-key verdict lines
    memo/sigs/<sig>/sig.json       signature record (atomic replace)
    memo/sigs/<sig>/ckpt.npz       deepest checkpoint for the signature
    memo/sigs/<sig>/tier.npz       versioned visited tier (tpu/spill.py)
    memo/sigs/<sig>/levels/*.npz   per-level checkpoints (incremental)

Running this module as ``__main__`` is the CPU-pinned introspection
child (the same parent/child split as the admission gate): it builds
the protocol, computes the structural fingerprint + handler effect
table, and prints one JSON line.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import inspect
import json
import os
import shutil
import sys
import textwrap
import threading
from typing import Dict, List, Optional, Tuple

__all__ = ["MemoStore", "MemoPlan", "MEMO_FORMAT", "memo_enabled",
           "memo_dir", "introspect_protocol", "introspect_child",
           "program_fingerprint", "factory_source_hash",
           "env_fingerprint", "key_fields",
           "verdict_key", "sig_key", "divergence_depth",
           "witness_digest", "UNCACHEABLE_ENDS"]

MEMO_FORMAT = "dslabs-memo-v1"

# Verdicts whose end condition depends on wall time or transient
# capacity pressure are never cached — an identical resubmit could
# legitimately produce a different (better) answer.
UNCACHEABLE_ENDS = ("TIME_EXHAUSTED", "CAPACITY_EXHAUSTED")

_FALSY = ("0", "off", "false", "no")


def memo_enabled(env: Optional[dict] = None) -> bool:
    """``DSLABS_MEMO``: ON by default for the service path."""
    e = env if env is not None else os.environ
    return str(e.get("DSLABS_MEMO", "1")).strip().lower() not in _FALSY


def memo_dir(root: str, env: Optional[dict] = None) -> str:
    e = env if env is not None else os.environ
    return e.get("DSLABS_MEMO_DIR") or os.path.join(root, "memo")


def _tier_cap(env: Optional[dict] = None) -> int:
    e = env if env is not None else os.environ
    try:
        return int(e.get("DSLABS_MEMO_TIER_CAP", "") or (1 << 22))
    except ValueError:
        return 1 << 22


def _sha(obj) -> str:
    """Canonical short hash of a JSON-able object."""
    blob = json.dumps(obj, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()[:24]


# ------------------------------------------------------------ fingerprint
#
# The structural fingerprint hashes WHAT the spec is, not what it is
# called or how it is formatted: node kinds (fields, domains, init),
# message/timer types (fields, bounds), caps, symmetry groups, initial
# events, handler ASTs (docstrings/decorators/function names stripped),
# and predicate ASTs.  The spec's display name, the factory module
# name, whitespace, and comments do NOT participate — a rename-only
# resubmit lands the same fingerprint.


class _HashAcc:
    """Accumulates value hashes; remembers when a closure cell could
    only be hashed by TYPE (not value) — such fingerprints are marked
    weak and the store refuses to memoize on them — and the source
    ``files`` of every function hashed (``co_filename``): a function's
    globals are not in its hash, its module's file is what they come
    from."""

    def __init__(self):
        self.weak = False
        self.files: set = set()


def _fn_ast_hash(fn, acc: _HashAcc) -> str:
    """AST-normalized hash of one handler/predicate: decorators and the
    function name and docstring are stripped so a renamed or re-wrapped
    but behaviorally identical function hashes the same.  Closure cell
    VALUES participate (a spec parameterized by ``workload_size``
    captures it), via :func:`_code_hash`."""
    acc.files.add(fn.__code__.co_filename)
    try:
        src = textwrap.dedent(inspect.getsource(fn))
        tree = ast.parse(src)
        fd = tree.body[0]
        if isinstance(fd, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fd.decorator_list = []
            fd.name = "_h"
            body = list(fd.body)
            if (body and isinstance(body[0], ast.Expr)
                    and isinstance(body[0].value, ast.Constant)
                    and isinstance(body[0].value.value, str)):
                body = body[1:] or [ast.Pass()]
            fd.body = body
        dump = ast.dump(tree, include_attributes=False)
    except (OSError, TypeError, SyntaxError, IndentationError,
            ValueError):
        # No retrievable source (REPL, C function, exec'd code): fall
        # back to the bytecode hash, which still normalizes names out.
        return _code_hash(fn, acc)
    cells = _closure_values(fn, acc)
    return _sha({"ast": hashlib.sha256(dump.encode()).hexdigest(),
                 "cells": cells,
                 "defaults": [_value_hash(v, acc)
                              for v in (fn.__defaults__ or ())]})


def _closure_values(fn, acc: _HashAcc) -> list:
    out = []
    for name, cell in zip(fn.__code__.co_freevars,
                          fn.__closure__ or ()):
        try:
            v = cell.cell_contents
        except ValueError:
            out.append([name, "<empty>"])
            continue
        out.append([name, _value_hash(v, acc)])
    return out


def _code_hash(fn, acc: _HashAcc) -> str:
    code = fn.__code__
    acc.files.add(code.co_filename)
    consts = [_value_hash(c, acc) for c in code.co_consts]
    return _sha({"co": hashlib.sha256(code.co_code).hexdigest(),
                 "consts": consts, "names": code.co_names,
                 "nargs": code.co_argcount,
                 "cells": _closure_values(fn, acc),
                 "defaults": [_value_hash(v, acc)
                              for v in (fn.__defaults__ or ())]})


def _value_hash(v, acc: _HashAcc, depth: int = 0) -> str:
    """Hash an arbitrary captured value BY VALUE where possible.  The
    escape hatch (type-only) marks the accumulator weak: two different
    specs could then collide, so the store treats a weak fingerprint as
    non-memoizable rather than risk a stale verdict."""
    import numpy as np

    if depth > 6:
        acc.weak = True
        return f"<deep:{type(v).__name__}>"
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return repr(v)
    if isinstance(v, types_code := type((lambda: 0).__code__)):
        return hashlib.sha256(v.co_code).hexdigest()[:16]
    if callable(v) and hasattr(v, "__code__"):
        return _fn_ast_hash(v, acc)
    if isinstance(v, (tuple, list)):
        return _sha([_value_hash(x, acc, depth + 1) for x in v])
    if isinstance(v, dict):
        return _sha(sorted((repr(k), _value_hash(x, acc, depth + 1))
                           for k, x in v.items()))
    if hasattr(v, "__array__"):
        a = np.asarray(v)
        return _sha({"dtype": str(a.dtype), "shape": a.shape,
                     "sha": hashlib.sha256(a.tobytes()).hexdigest()})
    # Spec-shaped object captured by a predicate wrapper: hash it
    # structurally instead of by identity.
    if hasattr(v, "handlers") and hasattr(v, "messages"):
        try:
            return _sha(_spec_base(v))
        except Exception:  # noqa: BLE001 — fall through to the weak path
            pass
    if isinstance(v, type(os)):  # a module: name is its identity
        return f"<module:{v.__name__}>"
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        # A declaration captured whole (a ``Slots`` block in an election
        # handler's closure): its class by name, its fields by value.
        return _sha([type(v).__module__, type(v).__qualname__,
                     [[f.name, _value_hash(getattr(v, f.name), acc,
                                           depth + 1)]
                      for f in dataclasses.fields(v)]])
    acc.weak = True
    return f"<type:{type(v).__module__}.{type(v).__qualname__}>"


def _recover_spec(proto):
    """A compiled ``ProtocolSpec`` twin carries its spec in the
    ``step_message`` closure — recover it so generated twins fingerprint
    structurally (handler ASTs) instead of through opaque closures."""
    from dslabs_tpu.tpu.compiler import ProtocolSpec

    if isinstance(proto, ProtocolSpec):
        return proto
    fn = getattr(proto, "step_message", None)
    for cell in (getattr(fn, "__closure__", None) or ()):
        try:
            v = cell.cell_contents
        except ValueError:
            continue
        if isinstance(v, ProtocolSpec):
            return v
    return None


def _field_init(f, acc: _HashAcc):
    return (_fn_ast_hash(f.init, acc) if callable(f.init)
            else repr(f.init))


def _slot_decls(spec, acc: _HashAcc) -> list:
    """Slots declarations, fingerprinted from ``spec.slot_blocks`` —
    the EXPANDED node fields carry each record field's lanes and
    domain, but not the ``clear`` value ``slot_clear_upto`` writes or
    the block's logical base, which are read off the declaration at
    trace time.  A declaration missing the expected shape (a duck-typed
    block from a partially-spec'd protocol) marks the fingerprint weak
    so the store refuses to memoize on it."""
    out = []
    for (kind, _bn), b in sorted(getattr(spec, "slot_blocks", {}).items()):
        try:
            out.append([kind, b.name, b.n, b.base,
                        [[sf.name, _field_init(sf, acc), sf.lo,
                          repr(sf.hi), repr(sf.delta), sf.clear]
                         for sf in b.fields]])
        except AttributeError:
            acc.weak = True
            out.append([kind, repr(type(b))])
    return out


def _quorum_decls(spec, acc: _HashAcc) -> list:
    """Quorum declarations: the threshold participates — ``ctx.quorum``
    reads resolve through it, so "majority" -> 2 is a semantic change
    invisible to handler ASTs."""
    out = []
    for q in getattr(spec, "quorums", ()) or ():
        try:
            out.append([q.name, q.over, repr(q.threshold)])
        except AttributeError:
            acc.weak = True
            out.append([repr(type(q))])
    return sorted(out)


def _spec_base(spec, acc: Optional[_HashAcc] = None) -> dict:
    """The structure of a declarative spec MINUS its handlers and
    display name: kinds, fields+domains, slot blocks, quorums, fragment
    composition, message/timer types, caps, symmetry groups, initial
    events."""
    acc = acc or _HashAcc()
    return {
        "fmt": MEMO_FORMAT, "kind": "spec",
        "nodes": [[k.name, k.count,
                   [[f.name, f.size, _field_init(f, acc), f.lo,
                     repr(f.hi), repr(getattr(f, "index_group", None))]
                    for f in k.fields]] for k in spec.nodes],
        "slots": _slot_decls(spec, acc),
        "quorums": _quorum_decls(spec, acc),
        "fragments": sorted(list(getattr(spec, "fragments", []) or [])),
        "messages": [[m.name, list(m.fields),
                      sorted((k, list(v)) for k, v in
                             (m.bounds or {}).items())]
                     for m in spec.messages],
        "timers": [[t.name, list(t.fields), t.min_ms, t.max_ms,
                    sorted((k, list(v)) for k, v in
                           (t.bounds or {}).items())]
                   for t in spec.timers],
        "net_cap": spec.net_cap, "timer_cap": spec.timer_cap,
        "symmetry": repr(getattr(spec, "symmetry", None)),
        "initial_messages": repr(spec.initial_messages),
        "initial_timers": repr(spec.initial_timers),
    }


def _twin_base(proto, acc: _HashAcc) -> dict:
    """Structural base for a HAND-WRITTEN TensorProtocol twin: the lane
    layout, the concrete initial arrays, and the step closures hashed
    by code + captured values.  The protocol's display name is
    excluded from the MEMO fingerprint (it still rides the checkpoint
    config fingerprint, which guards warm-start seeding)."""
    import numpy as np

    def _arr(fn):
        a = np.asarray(fn())
        return {"dtype": str(a.dtype), "shape": a.shape,
                "sha": hashlib.sha256(a.tobytes()).hexdigest()}

    return {
        "fmt": MEMO_FORMAT, "kind": "twin",
        "n_nodes": proto.n_nodes, "node_width": proto.node_width,
        "msg_width": proto.msg_width, "timer_width": proto.timer_width,
        "net_cap": proto.net_cap, "timer_cap": proto.timer_cap,
        "max_sends": proto.max_sends, "max_sets": proto.max_sets,
        "max_live_sends": getattr(proto, "max_live_sends", None),
        "init_nodes": _arr(proto.init_nodes),
        "init_messages": _arr(proto.init_messages),
        "init_timers": _arr(proto.init_timers),
        "symmetry": repr(getattr(proto, "symmetry", None)),
        "lane_domains": repr(sorted(
            (getattr(proto, "lane_domains", None) or {}).items())),
    }


def _unwrap_pred(fn):
    """The spec compiler wraps each predicate in a ``_pred`` closure —
    hash the tenant's function, not the wrapper, so the same predicate
    attached pre- or post-compile fingerprints identically."""
    code = getattr(fn, "__code__", None)
    if code is not None and "fn" in code.co_freevars:
        idx = code.co_freevars.index("fn")
        try:
            inner = (fn.__closure__ or ())[idx].cell_contents
        except (ValueError, IndexError):
            return fn
        if callable(inner):
            return inner
    return fn


def _proto_predicates(proto, acc: _HashAcc) -> Dict[str, str]:
    preds: Dict[str, str] = {}
    for role in ("goals", "invariants", "prunes"):
        for name, fn in sorted(
                (getattr(proto, role, None) or {}).items()):
            preds[f"{role}:{name}"] = _fn_ast_hash(_unwrap_pred(fn), acc)
    for role in ("deliver_message", "deliver_timer",
                 "deliver_message_rt", "deliver_timer_rt", "msg_dest"):
        fn = getattr(proto, role, None)
        if fn is not None:
            preds[f"mask:{role}"] = _fn_ast_hash(fn, acc)
    return preds


def _handler_effects(spec) -> Dict[str, dict]:
    """The compiled spec's event table: run every handler ONCE with a
    dummy context (the ``_count_budgets`` discipline — handlers are
    straight-line over the combinators) and read the concrete message
    tag (row lane 0) / timer tag (row lane 1) off each effect row.
    Nested ``ctx.cond`` children share the same effect lists, so
    conditional sends are captured too."""
    import jax.numpy as jnp

    from dslabs_tpu.tpu.compiler import Ctx

    table, _ = spec._layout()

    def dummy_state():
        return {key: (jnp.zeros((), jnp.int32) if size == 1
                      else jnp.zeros((size,), jnp.int32))
                for key, (_, size) in table.items()}

    false = jnp.asarray(False)
    eff: Dict[str, dict] = {}
    seen = set()
    for kind, i in spec._instances():
        if kind.name in seen:
            continue
        seen.add(kind.name)
        for m in spec.messages:
            fn = spec.handlers.get((kind.name, m.name))
            if fn is None:
                continue
            sends: list = []
            sets: list = []
            ctx = Ctx(spec, dummy_state(), kind.name, i, false, sends,
                      sets, handler=spec._handler_id(fn))
            spec._invoke(
                fn, ctx,
                {f: jnp.zeros((), jnp.int32) for f in m.fields}
                | {"_from": jnp.zeros((), jnp.int32)}, m.name)
            eff[f"m:{kind.name}:{m.name}"] = {
                "trigger": f"m{spec._mtag[m.name]}",
                "sends": sorted({f"m{int(r[0])}" for r, _ in sends}),
                "sets": sorted({f"t{int(r[1])}" for r, _ in sets})}
        for t in spec.timers:
            fn = spec.timer_handlers.get((kind.name, t.name))
            if fn is None:
                continue
            sends, sets = [], []
            ctx = Ctx(spec, dummy_state(), kind.name, i, false, sends,
                      sets, handler=spec._handler_id(fn))
            spec._invoke(
                fn, ctx,
                {f: jnp.zeros((), jnp.int32) for f in t.fields},
                t.name)
            eff[f"t:{kind.name}:{t.name}"] = {
                "trigger": f"t{spec._ttag[t.name]}",
                "sends": sorted({f"m{int(r[0])}" for r, _ in sends}),
                "sets": sorted({f"t{int(r[1])}" for r, _ in sets})}
    return eff


def _initial_events(spec) -> List[str]:
    ev = sorted({f"m{spec._mtag[name]}"
                 for name, _, _, _ in spec.initial_messages}
                | {f"t{spec._ttag[name]}"
                   for name, _, _ in spec.initial_timers})
    return ev


_INF = 1 << 30


def divergence_depth(effects: Dict[str, dict], initial: List[str],
                     changed: List[str]) -> int:
    """Lower bound on the first search depth whose EXPANSION can fire a
    changed handler: Bellman-Ford over event-type availability.  An
    event type is available at depth 0 if initial, else one past the
    earliest firing of ANY handler (changed or not) that emits it —
    using the UNION effect table of the old and new spec keeps the
    bound a true lower bound for both state spaces, so every level at
    or below it is shared and resumable.  Returns ``_INF`` when no
    changed handler's trigger is reachable at all (the edit is dead
    code for this initial condition)."""
    avail = {ev: 0 for ev in initial}
    for _ in range(len(effects) + len(avail) + 2):
        moved = False
        for e in effects.values():
            d = avail.get(e["trigger"])
            if d is None:
                continue
            for out_ev in list(e["sends"]) + list(e["sets"]):
                if avail.get(out_ev, _INF) > d + 1:
                    avail[out_ev] = d + 1
                    moved = True
        if not moved:
            break
    fires = [avail.get(effects[h]["trigger"], _INF)
             for h in changed if h in effects]
    return min(fires) if fires else _INF


def _union_effects(a: Dict[str, dict],
                   b: Dict[str, dict]) -> Dict[str, dict]:
    out: Dict[str, dict] = {}
    for k in set(a) | set(b):
        ea, eb = a.get(k), b.get(k)
        if ea is None or eb is None:
            e = ea or eb
            out[k] = {"trigger": e["trigger"],
                      "sends": list(e["sends"]), "sets": list(e["sets"])}
            continue
        out[k] = {"trigger": ea["trigger"],
                  "sends": sorted(set(ea["sends"]) | set(eb["sends"])),
                  "sets": sorted(set(ea["sets"]) | set(eb["sets"]))}
    return out


def _structure(proto, acc: _HashAcc):
    """``(spec or None, base, handlers)`` of one live protocol object:
    what :func:`introspect_protocol` and :func:`program_fingerprint`
    both hash, read without running a handler."""
    spec = _recover_spec(proto)
    if spec is not None:
        base = _spec_base(spec, acc)
        handlers = {
            f"m:{k}:{m}": _fn_ast_hash(fn, acc)
            for (k, m), fn in sorted(spec.handlers.items())}
        handlers.update({
            f"t:{k}:{t}": _fn_ast_hash(fn, acc)
            for (k, t), fn in sorted(spec.timer_handlers.items())})
    else:
        base = _twin_base(proto, acc)
        handlers = {
            "step_message": _fn_ast_hash(proto.step_message, acc),
            "step_timer": _fn_ast_hash(proto.step_timer, acc)}
    return spec, base, handlers


def _predicate_hash(proto, fn, acc: _HashAcc) -> str:
    """One state predicate of ``proto`` for :func:`program_fingerprint`:
    its AST and closure values where those hash by value, else its
    TRACE — a lab binding's predicates close over the binding itself
    (an object, hashed by type alone), whose commands' values the twin
    is blind to; the jaxpr of the predicate over the protocol's
    abstract state, constants by their bytes, is all a program can take
    from it, and is the same for every such value.  A predicate that
    cannot be traced marks the fingerprint weak."""
    own = _HashAcc()
    structural = _fn_ast_hash(fn, own)   # the wrapper too: it is traced
    acc.files |= own.files
    if not own.weak:
        return structural
    try:
        import jax
        import jax.numpy as jnp

        def lanes(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        closed = jax.make_jaxpr(fn)({
            "nodes": lanes(proto.node_width),
            "net": lanes(proto.net_cap, proto.msg_width),
            "timers": lanes(proto.n_nodes, proto.timer_cap,
                            proto.timer_width),
            "exc": lanes()})
        return _sha({"jaxpr": str(closed.jaxpr),
                     "consts": [_value_hash(c, acc) for c in closed.consts]})
    except Exception:  # noqa: BLE001 — no trace, no voucher
        acc.weak = True
        return structural


# Fields of a ``TensorProtocol`` that no traced program reads (the
# display name and the host-side decoders of a witness), and those
# :func:`_predicate_hash` reads.
_HOST_ONLY = ("name", "decode_message", "decode_timer")
_PREDICATES = ("goals", "invariants", "prunes")


def program_fingerprint(proto) -> dict:
    """What the programs an engine traces from ``proto`` are a function
    of, as far as the protocol goes, for the executable store
    (tpu/compile_cache.py): the structural fingerprint the verdict
    cache uses — spec base or twin base, handler ASTs with closure
    values hashed by VALUE — without the handler-effect pass (that one
    RUNS the handlers); the state predicates (:func:`_predicate_hash`);
    and what a compiled twin carries beside its spec: its widths, caps
    and initial arrays (``_twin_base``) and EVERY other field of the
    dataclass by value — the masks, and whatever is added later — less
    :data:`_HOST_ONLY` (a field left out here by mistake is a stale
    program; one hashed twice is nothing).  ``weak``: some value could
    only be hashed by type; ``files``: the source file of every function
    hashed."""
    acc = _HashAcc()
    spec, base, handlers = _structure(proto, acc)
    twin = _twin_base(proto, acc) if spec is not None else base
    predicates = [[role, name, _predicate_hash(proto, fn, acc)]
                  for role in _PREDICATES
                  for name, fn in sorted(getattr(proto, role).items())]
    fields = [[f.name, _value_hash(getattr(proto, f.name), acc)]
              for f in dataclasses.fields(proto)
              if f.name not in _HOST_ONLY + _PREDICATES]
    fp = _sha({"base": base, "twin": twin,
               "handlers": sorted(handlers.items()),
               "predicates": predicates, "fields": fields})
    return {"fp": fp, "weak": acc.weak, "files": sorted(acc.files)}


def introspect_protocol(proto, env: Optional[dict] = None) -> dict:
    """The full memo view of one live protocol object: structural
    fingerprint (base + handlers + predicates), handler effect table
    (spec twins only), and the engine checkpoint fingerprints the
    warm-start guard compares (strict and beam, under the pack/symmetry
    env the warden child will actually see)."""
    from dslabs_tpu.tpu import checkpoint as ckpt_mod

    e = env if env is not None else os.environ
    acc = _HashAcc()
    spec, base, handlers = _structure(proto, acc)
    predicates = _proto_predicates(proto, acc)
    if spec is not None:
        effects = _handler_effects(spec)
        initial = _initial_events(spec)
        kind = "spec"
    else:
        effects = None
        initial = None
        kind = "twin"
    base_fp = _sha(base)
    spec_fp = _sha({"base": base_fp, "handlers": sorted(handlers.items()),
                    "predicates": sorted(predicates.items())})
    sym = 0
    sym_on = str(e.get("DSLABS_SYMMETRY", "")).strip().lower() in (
        "1", "on", "true", "yes")
    if sym_on and getattr(proto, "symmetry", None) is not None:
        try:
            sym = int(proto.symmetry.n_perms)
        except Exception:  # noqa: BLE001 — symmetry spec may be spec-level
            sym = -1  # unknown: poisons the ckpt_fp match, forcing cold
    ckpt_fp = {
        "strict": ckpt_mod.config_fingerprint(
            proto, True, False, symmetry=max(sym, 0)),
        "beam": ckpt_mod.config_fingerprint(
            proto, False, False, symmetry=max(sym, 0))}
    if sym < 0:
        ckpt_fp = {"strict": "<unknown-symmetry>",
                   "beam": "<unknown-symmetry>"}
    return {"ok": True, "fmt": MEMO_FORMAT, "kind": kind,
            "weak": acc.weak, "name": proto.name,
            "base_fp": base_fp, "spec_fp": spec_fp,
            "handlers": handlers, "predicates": predicates,
            "effects": effects, "initial": initial,
            "ckpt_fp": ckpt_fp, "sym": sym}


# --------------------------------------------------------- source keying
#
# The server caches introspection per (factory ref, kwargs, transform,
# FACTORY MODULE FILE HASH): a student editing the module in place gets
# a fresh introspection child (a fresh interpreter — no stale
# sys.modules), so an edited spec can NEVER ride a stale fingerprint
# into the verdict cache.

def factory_source_hash(factory: str,
                        extra_sys_path: Optional[List[str]] = None
                        ) -> Optional[str]:
    import importlib.util

    mod_name = factory.partition(":")[0]
    old = sys.path[:]
    try:
        sys.path[:0] = list(extra_sys_path or [])
        try:
            spec = importlib.util.find_spec(mod_name)
        except (ImportError, ValueError, AttributeError):
            return None
    finally:
        sys.path[:] = old
    origin = getattr(spec, "origin", None) if spec else None
    if not origin or not os.path.isfile(origin):
        return None
    try:
        with open(origin, "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return None


def introspect_child(factory: str, factory_kwargs: Optional[dict],
                     transform: Optional[str],
                     extra_sys_path: Optional[List[str]] = None,
                     env: Optional[dict] = None,
                     timeout: Optional[float] = None) -> dict:
    """Run the introspection in a CPU-pinned subprocess (the admission
    child's sandbox discipline: tenant code never runs in the server
    process, and a hung or crashing child is a structured miss — the
    job just runs cold)."""
    import subprocess

    if timeout is None:
        try:
            timeout = float(os.environ.get(
                "DSLABS_MEMO_INTROSPECT_SECS", "") or 120.0)
        except ValueError:
            timeout = 120.0
    child_env = dict(os.environ)
    child_env["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    paths = [repo_root] + list(extra_sys_path or [])
    if child_env.get("PYTHONPATH"):
        paths.append(child_env["PYTHONPATH"])
    child_env["PYTHONPATH"] = os.pathsep.join(paths)
    child_env.update(env or {})
    spec = {"factory": factory, "factory_kwargs": factory_kwargs or {},
            "transform": transform}
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "dslabs_tpu.service.memo"],
            input=json.dumps(spec), capture_output=True, text=True,
            env=child_env, timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": f"introspection child exceeded "
                                      f"{timeout:.0f}s"}
    except OSError as e:
        return {"ok": False, "error": f"spawn failed: {e}"}
    if proc.returncode != 0 or not proc.stdout.strip():
        tail = (proc.stderr or "").strip().splitlines()[-1:]
        return {"ok": False,
                "error": f"child rc={proc.returncode} tail={tail}"}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except ValueError:
        return {"ok": False, "error": "unparsable child output"}


def _introspect_main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"         # before jax loads
    spec = json.load(sys.stdin)
    try:
        from dslabs_tpu.service.server import _resolve

        proto = _resolve(spec["factory"])(**(spec.get("factory_kwargs")
                                             or {}))
        if spec.get("transform"):
            proto = _resolve(spec["transform"])(proto)
        out = introspect_protocol(proto)
    except BaseException as e:  # noqa: BLE001 — a raising factory = no memo
        out = {"ok": False,
               "error": f"{type(e).__name__}: {e}"[:300]}
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    return 0


# ------------------------------------------------------------- key schema

def env_fingerprint(env: Optional[dict] = None) -> str:
    """The engine-relevant environment a verdict depends on: the packed
    frontier gate, the symmetry gate, the checkpoint format version,
    and the memo format itself.  Framework upgrades that bump either
    format string invalidate every prior entry — loudly cold, never
    stale."""
    from dslabs_tpu.tpu import checkpoint as ckpt_mod

    e = env if env is not None else os.environ
    packed = str(e.get("DSLABS_PACKED", "1")).strip().lower() \
        not in _FALSY
    sym = str(e.get("DSLABS_SYMMETRY", "")).strip().lower() in (
        "1", "on", "true", "yes")
    return (f"packed={int(packed)},sym={int(sym)},"
            f"ckpt={ckpt_mod.FORMAT_VERSION},memo={MEMO_FORMAT}")


def key_fields(intro: dict, strict: bool, chunk: int,
               frontier_cap: int, visited_cap: int,
               ladder: Tuple[str, ...],
               env: Optional[dict] = None) -> dict:
    """Everything except the depth/time budget: the signature key.  The
    verdict key adds (max_depth, max_secs) on top."""
    return {
        "spec_fp": intro["spec_fp"],
        "strict": bool(strict),
        "chunk": int(chunk),
        "frontier_cap": int(frontier_cap),
        "visited_cap": int(visited_cap),
        "ladder": list(ladder),
        "env_fp": env_fingerprint(env),
        "ckpt_fp": intro["ckpt_fp"]["strict" if strict else "beam"],
    }


def sig_key(fields: dict) -> str:
    return _sha(fields)


def verdict_key(fields: dict, max_depth: Optional[int],
                max_secs: Optional[float]) -> str:
    return _sha({"sig": fields, "max_depth": max_depth,
                 "max_secs": max_secs})


def witness_digest(predicate: Optional[str], violating_state,
                   goal_state, trace) -> Optional[str]:
    """A stable digest of the (minimized) witness attached to a
    verdict, so a cached/incremental verdict can be checked
    bit-identical to its cold run without shipping the full state."""
    import numpy as np

    if (predicate is None and violating_state is None
            and goal_state is None):
        return None

    def _state(s):
        if s is None:
            return None
        return {k: np.asarray(v).tolist() for k, v in s.items()}

    return _sha({"predicate": predicate,
                 "violating": _state(violating_state),
                 "goal": _state(goal_state),
                 "trace": (np.asarray(trace).tolist()
                           if trace is not None else None)})


# ------------------------------------------------------------------ store

class MemoPlan:
    """What the store decided for one submission: ``mode`` is one of
    ``cold`` / ``hit`` / ``warm`` / ``incremental``; warm/incremental
    carry the seed checkpoint to copy into the job's run dir."""

    def __init__(self, mode: str, sig: str, fields: dict,
                 seed_ckpt: Optional[str] = None,
                 seed_depth: int = 0, levels_skipped: int = 0,
                 base_device_secs: float = 0.0, reason: str = "",
                 verdict: Optional[dict] = None):
        self.mode = mode
        self.sig = sig
        self.fields = fields
        self.seed_ckpt = seed_ckpt
        self.seed_depth = seed_depth
        self.levels_skipped = levels_skipped
        self.base_device_secs = base_device_secs
        self.reason = reason
        self.verdict = verdict


class MemoStore:
    """The persistent cross-job memo store.  Torn-tolerant by
    construction: the verdict cache is an append-only JSONL (bad lines
    skipped on read), signature records are atomic tmp+replace, and
    every seed file is guarded by the engine's own checkpoint
    fingerprint check plus the versioned tier CRC — a half-written
    artifact yields a cold run, never a wrong one."""

    def __init__(self, path: str, tier_cap: Optional[int] = None,
                 env: Optional[dict] = None):
        self.path = os.path.abspath(path)
        os.makedirs(os.path.join(self.path, "sigs"), exist_ok=True)
        self.verdicts_path = os.path.join(self.path, "verdicts.jsonl")
        self.tier_cap = (int(tier_cap) if tier_cap is not None
                         else _tier_cap(env))
        self._lock = threading.Lock()
        self.stats = {"hits": 0, "warm_starts": 0, "incremental": 0,
                      "levels_skipped": 0, "device_secs_saved": 0.0,
                      "misses": 0, "stores": 0}

    # ---------------------------------------------------------- stats

    def stats_block(self) -> dict:
        with self._lock:
            st = dict(self.stats)
        st["device_secs_saved"] = round(st["device_secs_saved"], 3)
        lookups = st["hits"] + st["warm_starts"] + st["incremental"] \
            + st["misses"]
        st["hit_rate"] = (round(
            (st["hits"] + st["warm_starts"] + st["incremental"])
            / lookups, 3) if lookups else None)
        st["enabled"] = True
        st["dir"] = self.path
        return st

    def bump(self, counter: str, by=1) -> None:
        with self._lock:
            self.stats[counter] += by

    # -------------------------------------------------------- verdicts

    def _iter_verdicts(self):
        try:
            with open(self.verdicts_path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        yield json.loads(line)
                    except ValueError:
                        continue  # torn tail line: skip, stay sound
        except OSError:
            return

    def lookup_verdict(self, fields: dict, max_depth: Optional[int],
                       max_secs: Optional[float]) -> Optional[dict]:
        key = verdict_key(fields, max_depth, max_secs)
        found = None
        for rec in self._iter_verdicts():
            if rec.get("key") == key:
                found = rec
        return found

    def record_verdict(self, fields: dict, max_depth: Optional[int],
                       max_secs: Optional[float], verdict: dict,
                       device_secs: float) -> bool:
        if verdict.get("status") != "done":
            return False
        if verdict.get("end") in UNCACHEABLE_ENDS:
            return False
        if verdict.get("degraded") or verdict.get("deaths"):
            return False
        keep = {k: verdict.get(k) for k in (
            "end", "unique", "explored", "depth", "engine",
            "platform", "device_kind", "predicate", "witness")}
        rec = {"t": "memo_verdict",
               "key": verdict_key(fields, max_depth, max_secs),
               "sig": sig_key(fields), "fields": fields,
               "max_depth": max_depth, "max_secs": max_secs,
               "verdict": keep,
               "device_secs": round(float(device_secs), 4)}
        line = json.dumps(rec) + "\n"
        with self._lock:
            try:
                with open(self.verdicts_path, "a") as f:
                    f.write(line)
            except OSError:
                return False
            self.stats["stores"] += 1
        return True

    # ------------------------------------------------------ signatures

    def sig_dir(self, sig: str) -> str:
        return os.path.join(self.path, "sigs", sig)

    def _load_sig(self, sig: str) -> Optional[dict]:
        try:
            with open(os.path.join(self.sig_dir(sig), "sig.json")) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def _sig_levels(self, sig: str) -> Dict[int, str]:
        d = os.path.join(self.sig_dir(sig), "levels")
        out: Dict[int, str] = {}
        try:
            names = os.listdir(d)
        except OSError:
            return out
        for n in names:
            if n.startswith("level_") and n.endswith(".npz"):
                try:
                    out[int(n[len("level_"):-len(".npz")])] = \
                        os.path.join(d, n)
                except ValueError:
                    continue
        return out

    def _tier_ok(self, sig: str, rec: dict) -> Tuple[bool, str]:
        """Validate the signature's archived visited tier (versioned
        format, tpu/spill.py): a CRC/meta refusal means the seed
        lineage is suspect, so the plan falls back to cold — loudly."""
        tier_path = os.path.join(self.sig_dir(sig), "tier.npz")
        if not os.path.exists(tier_path):
            return True, ""  # tier is optional (cap-skipped archives)
        from dslabs_tpu.tpu import spill as spill_mod

        try:
            spill_mod.load_tier(tier_path, expect_meta={
                "pack": rec.get("pack", "identity"),
                "sym": rec.get("sym", 0)})
        except (spill_mod.TierMismatch, spill_mod.TierCorrupt) as e:
            return False, f"{type(e).__name__}: {e}"[:200]
        except Exception as e:  # noqa: BLE001 — any doubt = cold run
            return False, f"{type(e).__name__}: {e}"[:200]
        return True, ""

    # ------------------------------------------------------------ plan

    def plan(self, intro: dict, strict: bool, chunk: int,
             frontier_cap: int, visited_cap: int,
             ladder: Tuple[str, ...],
             max_depth: Optional[int], max_secs: Optional[float],
             env: Optional[dict] = None) -> MemoPlan:
        """Decide the reuse mode for one submission.  Precedence:
        exact verdict hit > warm start (same signature, new budget) >
        incremental (handler-localized diff) > cold.  Every guard
        failure degrades toward cold with a reason string the server
        journals — never an exception, never a stale seed."""
        fields = key_fields(intro, strict, chunk, frontier_cap,
                            visited_cap, ladder, env)
        sig = sig_key(fields)
        if intro.get("weak"):
            return MemoPlan("cold", sig, fields,
                            reason="weak_fingerprint")
        hit = self.lookup_verdict(fields, max_depth, max_secs)
        if hit is not None:
            return MemoPlan(
                "hit", sig, fields,
                base_device_secs=float(hit.get("device_secs", 0.0)),
                verdict=dict(hit.get("verdict") or {}))
        rec = self._load_sig(sig)
        if rec is not None:
            plan = self._plan_same_sig(sig, rec, fields, max_depth)
            if plan is not None:
                return plan
        plan = self._plan_incremental(intro, fields, sig, max_depth)
        if plan is not None:
            return plan
        return MemoPlan("cold", sig, fields, reason="miss")

    def _plan_same_sig(self, sig: str, rec: dict, fields: dict,
                       max_depth: Optional[int]) -> Optional[MemoPlan]:
        if rec.get("ckpt_fp") != fields["ckpt_fp"]:
            return MemoPlan("cold", sig, fields,
                            reason="ckpt_fingerprint_mismatch")
        ok, why = self._tier_ok(sig, rec)
        if not ok:
            return MemoPlan("cold", sig, fields,
                            reason=f"tier_refused:{why}")
        depth = int(rec.get("depth", 0))
        ck = os.path.join(self.sig_dir(sig), "ckpt.npz")
        if os.path.exists(ck) and depth > 0 and (
                max_depth is None or depth <= max_depth):
            return MemoPlan("warm", sig, fields, seed_ckpt=ck,
                            seed_depth=depth, levels_skipped=depth,
                            base_device_secs=float(
                                rec.get("device_secs", 0.0)))
        # Deepest checkpoint overshoots the new (smaller) depth budget:
        # fall back to the deepest archived LEVEL inside it.
        levels = self._sig_levels(sig)
        usable = [d for d in levels
                  if d > 0 and (max_depth is None or d <= max_depth)]
        if usable:
            d = max(usable)
            return MemoPlan("warm", sig, fields, seed_ckpt=levels[d],
                            seed_depth=d, levels_skipped=d,
                            base_device_secs=float(
                                rec.get("device_secs", 0.0)))
        return None

    def _plan_incremental(self, intro: dict, fields: dict,
                          new_sig: str, max_depth: Optional[int]
                          ) -> Optional[MemoPlan]:
        if intro.get("kind") != "spec" or not intro.get("effects"):
            return None
        try:
            sigs = os.listdir(os.path.join(self.path, "sigs"))
        except OSError:
            return None
        for sig in sorted(sigs)[:256]:
            if sig == new_sig:
                continue
            rec = self._load_sig(sig)
            if rec is None:
                continue
            f_old = rec.get("fields") or {}
            if any(f_old.get(k) != fields[k] for k in (
                    "strict", "chunk", "frontier_cap", "visited_cap",
                    "ladder", "env_fp", "ckpt_fp")):
                continue
            if rec.get("base_fp") != intro["base_fp"]:
                continue
            if rec.get("predicates") != intro["predicates"]:
                continue
            old_h = rec.get("handlers") or {}
            new_h = intro["handlers"]
            if set(old_h) != set(new_h):
                continue  # handler added/removed: structure changed
            changed = sorted(k for k in new_h if old_h[k] != new_h[k])
            if not changed:
                continue  # same spec_fp would have matched _plan_same_sig
            ok, why = self._tier_ok(sig, rec)
            if not ok:
                return MemoPlan("cold", new_sig, fields,
                                reason=f"tier_refused:{why}")
            union = _union_effects(rec.get("effects") or {},
                                   intro["effects"])
            e_low = divergence_depth(
                union, intro.get("initial") or [], changed)
            levels = self._sig_levels(sig)
            usable = [d for d in levels
                      if 0 < d <= e_low
                      and (max_depth is None or d <= max_depth)]
            if not usable:
                continue
            d = max(usable)
            return MemoPlan(
                "incremental", new_sig, fields, seed_ckpt=levels[d],
                seed_depth=d, levels_skipped=d,
                base_device_secs=float(rec.get("device_secs", 0.0)),
                reason=f"changed={','.join(changed)[:120]} "
                       f"divergence>={e_low}")
        return None

    # --------------------------------------------------------- archive

    def archive(self, intro: dict, fields: dict, verdict: dict,
                run_dir: str, device_secs: float) -> Optional[str]:
        """Persist one finished cold/warm run for future reuse: the
        deepest checkpoint, the per-level dumps the warden child
        archived (``DSLABS_MEMO_LEVELS``), the versioned visited tier,
        and the signature record — all atomic, never fatal."""
        from dslabs_tpu.tpu import checkpoint as ckpt_mod

        if verdict.get("status") != "done" or verdict.get("deaths"):
            return None
        if intro.get("weak"):
            return None
        src = os.path.join(run_dir, "ckpt.npz")
        if not os.path.exists(src):
            return None
        try:
            fp = ckpt_mod.peek_fingerprint(src)
            depth = ckpt_mod.peek_depth(src)
        except Exception:  # noqa: BLE001 — unreadable dump: skip archive
            return None
        if fp != fields["ckpt_fp"]:
            return None  # foreign dump (e.g. env drifted): never seed it
        sig = sig_key(fields)
        sd = self.sig_dir(sig)
        os.makedirs(os.path.join(sd, "levels"), exist_ok=True)
        old = self._load_sig(sig)
        if old is not None and int(old.get("depth", 0)) >= int(depth):
            self._merge_levels(sig, run_dir)
            return sig  # keep the deeper archive, still adopt levels
        try:
            tmp = os.path.join(sd, "ckpt.npz.tmp")
            shutil.copyfile(src, tmp)
            os.replace(tmp, os.path.join(sd, "ckpt.npz"))
        except OSError:
            return None
        self._merge_levels(sig, run_dir)
        pack, sym, n_keys = self._archive_tier(sd, src, fp)
        rec = {"fmt": MEMO_FORMAT, "sig": sig, "fields": fields,
               "spec_fp": intro["spec_fp"], "base_fp": intro["base_fp"],
               "handlers": intro["handlers"],
               "predicates": intro["predicates"],
               "effects": intro.get("effects"),
               "initial": intro.get("initial"),
               "kind": intro.get("kind"), "name": intro.get("name"),
               "ckpt_fp": fp, "depth": int(depth),
               "pack": pack, "sym": sym, "tier_keys": n_keys,
               "device_secs": round(float(device_secs), 4),
               "end": verdict.get("end")}
        try:
            tmp = os.path.join(sd, "sig.json.tmp")
            with open(tmp, "w") as f:
                f.write(json.dumps(rec))
            os.replace(tmp, os.path.join(sd, "sig.json"))
        except OSError:
            return None
        with self._lock:
            self.stats["stores"] += 1
        return sig

    def _merge_levels(self, sig: str, run_dir: str) -> None:
        src_dir = os.path.join(run_dir, "levels")
        dst_dir = os.path.join(self.sig_dir(sig), "levels")
        try:
            names = os.listdir(src_dir)
        except OSError:
            return
        os.makedirs(dst_dir, exist_ok=True)
        for n in names:
            if not (n.startswith("level_") and n.endswith(".npz")):
                continue
            try:
                tmp = os.path.join(dst_dir, n + ".tmp")
                shutil.copyfile(os.path.join(src_dir, n), tmp)
                os.replace(tmp, os.path.join(dst_dir, n))
            except OSError:
                continue

    def _archive_tier(self, sig_dir: str, ckpt_path: str,
                      fp: str) -> Tuple[str, int, int]:
        """Write the signature's exact visited tier in the versioned
        on-disk format (tpu/spill.py ``save_tier``): the (h1, h2)
        fingerprint union from the checkpoint's ``visited_keys``, with
        the pack descriptor + symmetry flag pinned in the meta so a
        foreign consumer is refused loudly.  Skipped (not truncated!)
        past ``DSLABS_MEMO_TIER_CAP``."""
        import numpy as np

        from dslabs_tpu.tpu import checkpoint as ckpt_mod
        from dslabs_tpu.tpu import spill as spill_mod

        pack, sym = "identity", 0
        try:
            ck = ckpt_mod.load(ckpt_path, fp)
        except Exception:  # noqa: BLE001 — tier is an optional artifact
            return pack, sym, 0
        if ck is None:
            return pack, sym, 0
        if ck.extra and "frontier_encoding" in ck.extra:
            try:
                pack = np.asarray(
                    ck.extra["frontier_encoding"]).tobytes().decode()
            except Exception:  # noqa: BLE001
                pack = "unknown"
        if "sym" in fp:
            # config_fingerprint appends 'symN' for reduced dumps.
            try:
                sym = int(fp.rsplit("sym", 1)[-1].rstrip("'\") ,"))
            except ValueError:
                sym = 1
        keys = np.asarray(ck.visited_keys, np.uint32)
        n = int(keys.shape[0])
        if n > self.tier_cap:
            return pack, sym, 0
        h1, h2 = spill_mod._rows_to_u64(keys)
        try:
            spill_mod.save_tier(
                os.path.join(sig_dir, "tier.npz"), h1, h2,
                meta={"pack": pack, "sym": sym, "ckpt_fp": fp})
        except OSError:
            return pack, sym, 0
        return pack, sym, n


if __name__ == "__main__":
    sys.exit(_introspect_main())
