"""Static check: request-id discipline in the serving request plane.

Companion to ``check_gateway_api.py`` (same lesson: structural invariants
rot silently unless CI asserts them). Two invariants, both AST-checked with
no package imports so the gate runs anywhere:

  1. **One respond helper.** Every HTTP response ``serving/gateway.py``
     writes — success, 400/404/429/503/504, the catch-all 500, the GET
     endpoints, the SSE header block — must go through the single
     id-attaching helper (``_respond``): no call to ``send_response`` /
     ``send_header`` / ``end_headers`` may exist outside it. The moment an
     error branch added later writes its own status line, the
     ``X-Request-Id`` echo contract silently breaks for exactly the
     responses (errors) where correlation matters most.

  2. **Every serving span carries the request id.** Any tracer emission
     from ``deepspeed_tpu/serving/`` (``.instant(...)`` / ``.span(...)``
     keyword form, ``.complete(...)`` args-dict form) must carry a
     ``request_id`` field — a span that cannot be joined back to a request
     is dead weight in a request-scoped trace.

A tier-1 test (``tests/test_request_tracing.py``) runs this on every CI
pass.
"""

import ast
import os
import sys

DEFAULT_SERVING_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                                   "deepspeed_tpu", "serving")

# the ONE function allowed to write response lines/headers
RESPOND_HELPER = "_respond"
RAW_RESPONSE_CALLS = ("send_response", "send_header", "end_headers")

# tracer emitters that take the id as a keyword vs inside an args= dict
KEYWORD_EMITTERS = ("instant", "span")
ARGSDICT_EMITTERS = ("complete",)

# the replica driver's spans of its own loop: each covers every request in
# flight (what was pulled, fanned out or waited for), so none has ONE id to
# carry; the per-request overlays (serving/reqtrace.py) join through uids
LOOP_SPANS = ("serving/loop_pull", "serving/loop_fanout", "serving/loop_idle")


def _call_attr_name(node):
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
        return node.func.attr
    return None


def _args_dict_has_request_id(node):
    """True when a ``.complete(...)`` call passes ``args={...}`` as a dict
    LITERAL containing a ``"request_id"`` key (the only statically
    checkable form — emission sites must keep it literal)."""
    for kw in node.keywords:
        if kw.arg == "args" and isinstance(kw.value, ast.Dict):
            for key in kw.value.keys:
                if isinstance(key, ast.Constant) and key.value == "request_id":
                    return True
    return False


def _check_gateway_respond_helper(path, src, tree):
    """Invariant 1: raw response-writing calls only inside RESPOND_HELPER."""
    violations = []
    lines = src.splitlines()

    class Walker(ast.NodeVisitor):
        def __init__(self):
            self.stack = []

        def _visit_func(self, node):
            self.stack.append(node.name)
            self.generic_visit(node)
            self.stack.pop()

        visit_FunctionDef = _visit_func
        visit_AsyncFunctionDef = _visit_func

        def visit_Call(self, node):
            name = _call_attr_name(node)
            if name in RAW_RESPONSE_CALLS and RESPOND_HELPER not in self.stack:
                snippet = (lines[node.lineno - 1].strip()
                           if node.lineno <= len(lines) else "")
                violations.append(
                    (os.path.basename(path), node.lineno, snippet,
                     f"raw '{name}' outside the {RESPOND_HELPER} helper "
                     f"(X-Request-Id echo bypassed)"))
            self.generic_visit(node)

    Walker().visit(tree)
    helper_defined = any(isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                         and n.name == RESPOND_HELPER for n in ast.walk(tree))
    if not helper_defined:
        violations.append((os.path.basename(path), 1, "",
                           f"no {RESPOND_HELPER} helper defined in gateway.py"))
    return violations


def _check_span_request_ids(path, src, tree):
    """Invariant 2: serving-plane tracer emissions carry request_id."""
    violations = []
    lines = src.splitlines()
    for node in ast.walk(tree):
        name = _call_attr_name(node)
        if name is None:
            continue
        why = None
        if name == "span" and node.args and isinstance(node.args[0], ast.Constant) \
                and node.args[0].value in LOOP_SPANS:
            continue
        if name in KEYWORD_EMITTERS:
            if not any(kw.arg == "request_id" for kw in node.keywords):
                why = f"'{name}' emission without a request_id= keyword"
        elif name in ARGSDICT_EMITTERS:
            if not _args_dict_has_request_id(node):
                why = (f"'{name}' emission without a literal "
                       f"args={{'request_id': ...}} entry")
        if why:
            snippet = (lines[node.lineno - 1].strip()
                       if node.lineno <= len(lines) else "")
            violations.append((os.path.basename(path), node.lineno, snippet, why))
    return violations


def find_violations(serving_dir=DEFAULT_SERVING_DIR):
    """[(file, lineno, snippet, why)] across the serving package."""
    violations = []
    for root, _dirs, files in os.walk(serving_dir):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            with open(path) as f:
                src = f.read()
            tree = ast.parse(src, filename=path)
            if fname == "gateway.py":
                violations.extend(_check_gateway_respond_helper(path, src, tree))
            violations.extend(_check_span_request_ids(path, src, tree))
    return violations


def check(serving_dir=DEFAULT_SERVING_DIR):
    """Return the violation list (empty = the request plane is clean)."""
    return find_violations(serving_dir)


def main(argv=None):
    argv = argv if argv is not None else sys.argv[1:]
    serving_dir = argv[0] if argv else DEFAULT_SERVING_DIR
    bad = check(serving_dir)
    if bad:
        print(f"check_request_tracing: request-id discipline violated in {serving_dir}:")
        for rel, lineno, snippet, why in bad:
            print(f"  {rel}:{lineno}: {why}: {snippet}")
        return 1
    print("check_request_tracing: every response path attaches X-Request-Id and "
          "every serving span carries request_id")
    return 0


if __name__ == "__main__":
    sys.exit(main())
