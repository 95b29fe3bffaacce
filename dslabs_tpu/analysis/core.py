"""Shared sanitizer plumbing: the finding record, the rule catalog,
and the waiver file (ISSUE 10).

A finding is one rule violation at one location.  Conformance findings
(C1-C4) locate as ``<repo-relative-path>::<qualname>``; jaxpr-audit
findings (J0-J5) locate as ``<engine-class>::<dispatch-tag>``.  Either
way ``Finding.target`` is the string waiver patterns match against.

Waiver file (default ``<repo root>/.sanitizer-waivers``), one waiver
per line::

    # comment
    <CODE> <target-glob> <one-line justification>

e.g. ::

    C2 dslabs_tpu/labs/paxos/paxos.py::*  tie-break seeded by harness

``<CODE>`` is a rule code or ``*``; ``<target-glob>`` is an
``fnmatch`` pattern over ``Finding.target``.  A waived finding still
prints (marked ``waived``) but does not fail the CLI or the compile
gate — the waiver IS the documentation of
the justified exception (docs/analysis.md).
"""

from __future__ import annotations

import dataclasses
import fnmatch
import os
from typing import List, Optional, Sequence

__all__ = ["Finding", "Waiver", "RULES", "load_waivers", "apply_waivers",
           "render_findings", "default_waiver_path", "repo_root"]

# The rule catalog — docs/analysis.md mirrors this table.
RULES = {
    "C1": "handler purity: mutation of a received message/timer "
          "payload, or aliasing mutable node state into a send",
    "C2": "nondeterminism: random/time/id()/unordered set iteration "
          "inside a handler (breaks replay, minimization, and "
          "fingerprint determinism)",
    "C3": "dedup soundness: public node-state field that defeats "
          "structural freeze/hash (utils.structural.sfreeze)",
    "C4": "spec hygiene: declared message/timer with no handler, "
          "put/get of undeclared fields, handler for unknown "
          "kind/message",
    "C5": "symmetry hygiene: a handler on a kind inside a declared "
          "symmetry group branches on the raw node id (node_index() "
          "compared against a constant) — breaks member "
          "interchangeability, so the canonicalize pass would merge "
          "states with DIFFERENT behavior",
    "C6": "fault-model opacity: a handler reads or branches on fault "
          "controller internals (the '$fault' kind or its "
          "pcut/eras/crashes/drops/dups/down_* lanes) — protocols "
          "must observe faults only through message loss and timer "
          "silence, or the scenario stops modeling a real network",
    "J0": "site-registry coverage: dispatch site missing from "
          "telemetry.DISPATCH_SITES, or its program failed to lower",
    "J1": "host callback inside a lowered device program",
    "J2": "float64 upcast in a lowered device program",
    "J3": "donation audit: large carry declared donated but the "
          "lowering kept no input/output aliasing",
    "J4": "unexpected cross-device collective in a single-device "
          "program",
    "J5": "retrace hazard: rebuilding the program lowers to different "
          "HLO (compile-cache key churn after AOT warm-up)",
}


@dataclasses.dataclass
class Finding:
    code: str                  # rule code, RULES key
    leg: str                   # "conformance" | "jaxpr"
    path: str                  # repo-relative file, or engine class
    obj: str                   # qualname, or dispatch tag
    message: str
    line: int = 0
    waived: bool = False
    waiver: str = ""           # justification of the matching waiver

    @property
    def target(self) -> str:
        return f"{self.path}::{self.obj}"

    def render(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        tag = f"[{self.code}]"
        w = f"  (waived: {self.waiver})" if self.waived else ""
        return f"{tag} {loc} {self.obj}: {self.message}{w}"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class Waiver:
    code: str                  # rule code or "*"
    pattern: str               # fnmatch glob over Finding.target
    reason: str

    def matches(self, f: Finding) -> bool:
        return (self.code in ("*", f.code)
                and fnmatch.fnmatch(f.target, self.pattern))


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def default_waiver_path() -> str:
    return os.environ.get("DSLABS_SANITIZE_WAIVERS") or os.path.join(
        repo_root(), ".sanitizer-waivers")


def load_waivers(path: Optional[str] = None) -> List[Waiver]:
    """Parse the waiver file; a missing file is an empty waiver set, a
    malformed LINE is a loud ValueError (a silently-dropped waiver
    would flip the CLI red with no hint why)."""
    path = path or default_waiver_path()
    out: List[Waiver] = []
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for n, raw in enumerate(f, 1):
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            parts = line.split(None, 2)
            if len(parts) < 3:
                raise ValueError(
                    f"{path}:{n}: waiver needs '<CODE> <target-glob> "
                    f"<justification>', got {line!r}")
            code, pattern, reason = parts
            if code != "*" and code not in RULES:
                raise ValueError(
                    f"{path}:{n}: unknown rule code {code!r} "
                    f"(known: {sorted(RULES)})")
            out.append(Waiver(code, pattern, reason))
    return out


def apply_waivers(findings: Sequence[Finding],
                  waivers: Sequence[Waiver]) -> List[Finding]:
    for f in findings:
        for w in waivers:
            if w.matches(f):
                f.waived = True
                f.waiver = w.reason
                break
    return list(findings)


def render_findings(findings: Sequence[Finding],
                    header: str = "sanitizer") -> str:
    live = [f for f in findings if not f.waived]
    waived = [f for f in findings if f.waived]
    out = [f"== {header}: {len(live)} finding(s)"
           + (f", {len(waived)} waived" if waived else "") + " =="]
    for f in findings:
        out.append(f.render())
    if not findings:
        out.append("clean: no findings")
    return "\n".join(out)
