"""Soundness sanitizer (ISSUE 10): static correctness tooling with two
legs behind one CLI —

* **Leg A, conformance linter** (:mod:`.conformance`): AST +
  spec-introspection rules C1–C4 over ``ProtocolSpec`` handlers, the
  hand-written tensor twins, their adapters, and object-level ``Node``
  code.  The hard half of C4 is also the ``ProtocolSpec.compile()``
  gate (tpu/compiler.py ``SpecError``) — the conformance authority
  ROADMAP #3's arbitrary-user-protocol twin generation rides on.
* **Leg B, jaxpr auditor** (:mod:`.jaxpr_audit`): rules J0–J5 over the
  lowered StableHLO of every registered dispatch-site program,
  enumerated from ``tpu/telemetry.py DISPATCH_SITES`` via each
  engine's ``dispatch_site_programs()``.  ``DSLABS_SANITIZE=1`` runs
  it at engine build time and records findings as telemetry events.

CLI::

    python -m dslabs_tpu.analysis {conformance,jaxpr,all}
        [--waivers FILE] [--json] [--paths P ...]

Exit 1 on unwaived findings; the waiver file
(``.sanitizer-waivers``, format in :mod:`.core`) documents justified
exceptions.  docs/analysis.md is the field guide; ``make lint`` and
``run_tests.py --lint`` are the entry points CI and students use.
"""

from __future__ import annotations

import json as _json
import sys
from typing import List, Optional, Sequence

from dslabs_tpu.analysis.core import (Finding, RULES, Waiver,  # noqa: F401
                                      apply_waivers, default_waiver_path,
                                      load_waivers, render_findings,
                                      repo_root)

__all__ = ["Finding", "Waiver", "RULES", "load_waivers", "apply_waivers",
           "render_findings", "default_waiver_path", "run_conformance",
           "run_jaxpr", "run_all", "main"]


def run_conformance(paths: Optional[Sequence[str]] = None,
                    waivers: Optional[str] = None) -> List[Finding]:
    """Leg A over the shipped tree (or ``paths``): AST lint + the C4
    spec introspection of every ``tpu/specs.py`` factory."""
    from dslabs_tpu.analysis import conformance as conf

    findings = conf.lint_paths(paths)
    if paths is None:
        findings += conf.lint_specs()
    return apply_waivers(findings, load_waivers(waivers))


def run_jaxpr(waivers: Optional[str] = None, deep: bool = True,
              mesh_devices: int = 2) -> List[Finding]:
    """Leg B over the CLI's standard engine set (pingpong twins,
    single-device + spill + sharded superstep + swarm), J5 retrace
    check included."""
    from dslabs_tpu.analysis.jaxpr_audit import (audit_search,
                                                 build_audit_engines)

    findings: List[Finding] = []
    for search in build_audit_engines(mesh_devices=mesh_devices):
        findings += audit_search(search, deep=deep)
    return apply_waivers(findings, load_waivers(waivers))


def run_all(paths: Optional[Sequence[str]] = None,
            waivers: Optional[str] = None) -> List[Finding]:
    return (run_conformance(paths, waivers=waivers)
            + run_jaxpr(waivers=waivers))


# ------------------------------------------------------------------ CLI

_USAGE = """usage: python -m dslabs_tpu.analysis <command> [options]

  conformance   Leg A: protocol conformance linter (C1-C4)
  jaxpr         Leg B: jaxpr hot-path auditor (J0-J5)
  all           both legs

options:
  --waivers FILE   waiver file (default: <repo>/.sanitizer-waivers)
  --paths P [P..]  conformance: lint these files/dirs instead of the
                   shipped default set
  --json           one machine-readable JSON line instead of the report

exit code: 0 clean (waived findings allowed), 1 unwaived findings,
2 usage/crash.  Rule catalog + waiver format: docs/analysis.md.
"""


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in ("conformance", "jaxpr", "all"):
        print(_USAGE, file=sys.stderr)
        return 2
    cmd = argv[0]
    flags = argv[1:]
    waivers = None
    paths: Optional[List[str]] = None
    if "--waivers" in flags:
        waivers = flags[flags.index("--waivers") + 1]
    if "--paths" in flags:
        i = flags.index("--paths") + 1
        paths = []
        while i < len(flags) and not flags[i].startswith("--"):
            paths.append(flags[i])
            i += 1
    as_json = "--json" in flags

    findings: List[Finding] = []
    if cmd in ("conformance", "all"):
        findings += run_conformance(paths, waivers=waivers)
    if cmd in ("jaxpr", "all"):
        findings += run_jaxpr(waivers=waivers)

    live = [f for f in findings if not f.waived]
    if as_json:
        print(_json.dumps({
            "cmd": cmd,
            "findings": len(live),
            "waived": sum(1 for f in findings if f.waived),
            "conformance": sum(1 for f in live
                               if f.leg == "conformance"),
            "jaxpr": sum(1 for f in live if f.leg == "jaxpr"),
            "detail": [f.as_dict() for f in findings],
        }))
    else:
        print(render_findings(findings, header=f"sanitizer {cmd}"))
    return 1 if live else 0
