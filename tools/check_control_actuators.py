#!/usr/bin/env python
"""AST gate: control-plane actuators are reachable ONLY from the
controller's decision-applying helpers, and every decision site logs.

The self-driving serving loop (``deepspeed_tpu/serving/control/``) is only
auditable if actuations cannot bypass it: a stray ``replica.drain()`` in a
request handler, or an admission override applied from a bench script
inside the package, would mutate the fleet with no decision record. Two
rules keep the loop closed:

  1. Anywhere in ``deepspeed_tpu/``, a call to a GATED actuator method
     (``pause`` / ``resume`` / ``drain`` / ``undrain`` / ``restart`` /
     ``set_depth_override`` / ``clear_depth_override`` /
     ``set_spec_params``) is a violation unless (a) it sits inside a
     ``serving/control/`` function named ``_apply_*`` (the sanctioned
     decision-applying helpers), or (b) the calling module itself DEFINES
     a function of that name (the defining module and its internal
     plumbing — e.g. ``replica.py``'s goodput-ledger ``resume`` calls).

  2. Every ``_apply_*`` function in ``serving/control/`` must contain at
     least one ``.emit(`` call — an actuation without a decision record
     is structurally impossible.

Tests and tools outside the package are exempt on purpose: drills and
operators may pause/restart replicas; the invariant is about the serving
package's own request/sensor paths.

Run from the repo root (or pass a package dir):

    python tools/check_control_actuators.py [pkg_dir]

Exit 0 = clean, 1 = violations (printed one per line). Wired into tier-1
via ``tests/test_control_plane.py``.
"""

import ast
import os
import sys

DEFAULT_PKG_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               os.pardir, "deepspeed_tpu")

GATED_ACTUATORS = frozenset({
    "pause", "resume", "drain", "undrain", "restart",
    "set_depth_override", "clear_depth_override", "set_spec_params",
})


def _is_control_file(rel: str) -> bool:
    rel = rel.replace(os.sep, "/")
    return "serving/control/" in rel or rel.startswith("serving/control/")


def _defined_names(tree: ast.AST):
    """Every function/method name defined anywhere in the module."""
    return {n.name for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}


def find_violations(pkg_dir: str):
    violations = []
    for root, _dirs, files in os.walk(pkg_dir):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            rel = os.path.relpath(path, pkg_dir)
            with open(path, encoding="utf-8") as f:
                src = f.read()
            try:
                tree = ast.parse(src)
            except SyntaxError as e:
                violations.append((rel, e.lineno or 0, "<unparseable>",
                                   f"syntax error: {e.msg}"))
                continue
            lines = src.splitlines()
            in_control = _is_control_file(rel)
            defined = _defined_names(tree)

            def flag(node, why):
                snippet = (lines[node.lineno - 1].strip()
                           if 0 < node.lineno <= len(lines) else "")
                violations.append((rel, node.lineno, snippet, why))

            def walk(node, func_stack):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    func_stack = func_stack + [node.name]
                    if in_control and node.name.startswith("_apply_"):
                        # rule 2: the helper must emit a decision record
                        emits = [c for c in ast.walk(node)
                                 if isinstance(c, ast.Call)
                                 and isinstance(c.func, ast.Attribute)
                                 and c.func.attr == "emit"]
                        if not emits:
                            flag(node, f"decision helper {node.name} never "
                                       "emits a decision record (rule 2)")
                elif isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute):
                    name = node.func.attr
                    in_apply = any(f.startswith("_apply_") for f in func_stack)
                    if name in GATED_ACTUATORS:
                        sanctioned = (in_control and in_apply) or name in defined
                        if not sanctioned:
                            flag(node, f"actuator .{name}() outside a "
                                       "serving/control/ _apply_* helper (rule 1)")
                for child in ast.iter_child_nodes(node):
                    walk(child, func_stack)

            walk(tree, [])
    return violations


def check(pkg_dir: str = DEFAULT_PKG_DIR):
    return find_violations(pkg_dir)


def main(argv) -> int:
    pkg_dir = argv[1] if len(argv) > 1 else DEFAULT_PKG_DIR
    violations = find_violations(pkg_dir)
    if violations:
        print(f"check_control_actuators: {len(violations)} violation(s):")
        for rel, lineno, snippet, why in violations:
            print(f"  {rel}:{lineno}: {why}\n      {snippet}")
        return 1
    print("check_control_actuators: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
