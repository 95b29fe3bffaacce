"""Assemble the student handout distribution (the reference's
handout-files/ + build.gradle handout assembly, re-designed for a pure
Python tree): copy the framework, tests, and driver, and replace every
lab SOLUTION with an AST-stripped SKELETON — class/function signatures
and docstrings kept, every solution method body replaced by
``raise NotImplementedError`` — so students receive exactly the surface
the scored tests drive.

    python tools/handout.py [--out handout] [--tar]

What ships:
  dslabs_tpu/            framework (core/testing/search/runner/harness/
                         viz/utils/tpu) — unchanged
  dslabs_tpu/labs/       SKELETONS (bodies stripped)
  tests/ run_tests.py    the scored suites + CLI driver, unchanged
  Makefile README.md     entry points

What is kept verbatim inside labs/ (students build on top of these the
way the reference hands out AMOCommand/KVStore scaffolding): module
docstrings, dataclass field declarations, constants, and __init__
bodies — only handler/logic methods are stripped.
"""

from __future__ import annotations

import argparse
import ast
import os
import shutil
import sys
import tarfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHIP = ["dslabs_tpu", "tests", "run_tests.py", "Makefile",
        "README.md", "docs", "__graft_entry__.py"]
# Instructor-only material and SOLUTION MIRRORS never ship: the tensor
# protocol twins + compiler specs are handler-for-handler readable
# reimplementations of the lab solutions (their module docstrings say
# so), and the adapters embed the same logic — handing them out would
# defeat the skeleton stripping.  The tensor ENGINE ships (it is
# framework); twin resolution then fails loudly with NoTensorTwin for
# students, and the default object search path is unaffected.
OMIT = [
    "dslabs_tpu/tpu/protocols",
    "dslabs_tpu/tpu/specs.py",
    "dslabs_tpu/tpu/adapters",
    "grading",
]
# Lab modules whose logic methods are the assignment (stripped); the
# scaffolding modules (amo, kv_workload, workloads, predicates) ship
# verbatim like the reference's handed-out utility classes.
STRIP = {
    "dslabs_tpu/labs/pingpong/pingpong.py",
    "dslabs_tpu/labs/clientserver/clientserver.py",
    "dslabs_tpu/labs/primarybackup/viewserver.py",
    "dslabs_tpu/labs/primarybackup/pb.py",
    "dslabs_tpu/labs/paxos/paxos.py",
    "dslabs_tpu/labs/shardedstore/shardmaster.py",
    "dslabs_tpu/labs/shardedstore/shardstore.py",
    "dslabs_tpu/labs/shardedstore/txkvstore.py",
}
# Methods every node needs untouched for the harness to even load.
KEEP_METHODS = {"__init__", "__post_init__"}


class _Stripper(ast.NodeTransformer):
    """Replace function bodies with docstring + raise NotImplementedError
    (the skeleton shape of the reference's handed-out lab sources)."""

    def _strip(self, node):
        body = []
        if (node.body and isinstance(node.body[0], ast.Expr)
                and isinstance(node.body[0].value, ast.Constant)
                and isinstance(node.body[0].value.value, str)):
            body.append(node.body[0])
        body.append(ast.Raise(
            exc=ast.Call(
                func=ast.Name(id="NotImplementedError", ctx=ast.Load()),
                args=[ast.Constant(value="Your code here...")],
                keywords=[]),
            cause=None))
        node.body = body
        return node

    def visit_FunctionDef(self, node):
        if node.name in KEEP_METHODS:
            return node
        return self._strip(node)

    visit_AsyncFunctionDef = visit_FunctionDef


def build(out_dir: str, make_tar: bool) -> str:
    out = os.path.abspath(out_dir)
    if os.path.exists(out):
        shutil.rmtree(out)
    os.makedirs(out)
    for item in SHIP:
        src = os.path.join(ROOT, item)
        dst = os.path.join(out, item)
        if not os.path.exists(src):
            continue
        if os.path.isdir(src):
            shutil.copytree(src, dst, ignore=shutil.ignore_patterns(
                "__pycache__", "*.pyc"))
        else:
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            shutil.copy2(src, dst)
    for rel in OMIT:
        path = os.path.join(out, rel)
        if os.path.isdir(path):
            shutil.rmtree(path)
        elif os.path.exists(path):
            os.remove(path)
    stripped = []
    for rel in sorted(STRIP):
        path = os.path.join(out, rel)
        with open(path) as f:
            tree = ast.parse(f.read())
        tree = _Stripper().visit(tree)
        ast.fix_missing_locations(tree)
        with open(path, "w") as f:
            f.write("# HANDOUT SKELETON — solution bodies stripped; "
                    "implement the raises.\n" + ast.unparse(tree) + "\n")
        stripped.append(rel)
    print(f"handout: {out} ({len(stripped)} lab files stripped)")
    if make_tar:
        tar_path = out + ".tar.gz"
        with tarfile.open(tar_path, "w:gz") as t:
            t.add(out, arcname=os.path.basename(out))
        print(f"handout: {tar_path}")
        return tar_path
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="handout")
    ap.add_argument("--tar", action="store_true")
    args = ap.parse_args(argv)
    build(args.out, args.tar)
    return 0


if __name__ == "__main__":
    sys.exit(main())
