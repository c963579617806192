#!/usr/bin/env python
"""Lint: no host syncs inside the async dispatch phase of the query path.

The throughput of the sequential and batched query phases rests on jax's
async dispatch: every segment's program is LAUNCHED without waiting, and
results are converted host-side in ONE sync region afterwards.  A stray
``np.asarray(...)``, ``.block_until_ready()``, or ``float()``/``int()``
on a device array inside the dispatch loop serializes the pipeline —
each segment then waits for the previous one (the exact regression r4
hit with per-query D2H transfers).

Scope: the segment-dispatch ``for`` loops (any ``for`` whose iterable
mentions ``segments`` or ``prep["segs"]``) inside the hot entry points
``ShardSearcher._topk`` / ``ShardSearcher.msearch``
(opensearch_tpu/search/executor.py) and ``BatchGroup.run``
(opensearch_tpu/search/batch.py).  Flagged calls:

- ``np.asarray(...)`` / ``numpy.asarray(...)``
- ``<expr>.block_until_ready()``
- ``float(...)`` / ``int(...)``  (device scalars sync on conversion)

A deliberate host read (e.g. harvesting an ``is_ready()`` result, which
is already on the host) carries a ``# sync-ok`` annotation on the same
line or the line above.

Sibling of ``check_monotonic.py`` / ``check_sleep_loops.py`` /
``check_ad_hoc_caches.py`` / ``check_thread_hygiene.py``; new
un-annotated sites fail tier-1 (tests/test_impacts.py runs this check).

Usage: python tools/check_hot_path_sync.py [root]   (exit 0 = clean)
"""

from __future__ import annotations

import ast
import os
import sys

ANNOTATION = "# sync-ok"

# (relative file, function name) pairs whose dispatch loops are linted
HOT_FUNCTIONS = {
    ("search/executor.py", "_topk"),
    ("search/executor.py", "msearch"),
    ("search/batch.py", "run"),
}

_BANNED_NAMES = {"float", "int"}
_BANNED_ATTRS = {"asarray", "block_until_ready"}


def _is_dispatch_loop(node: ast.For) -> bool:
    """A ``for`` whose iterable mentions the segment list."""
    src = ast.dump(node.iter)
    return "segments" in src or "'segs'" in src


def _banned_calls(loop: ast.For):
    for node in ast.walk(loop):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id in _BANNED_NAMES:
            yield node.lineno, f"{fn.id}(...)"
        elif isinstance(fn, ast.Attribute) and fn.attr in _BANNED_ATTRS:
            yield node.lineno, f".{fn.attr}(...)"


def check_file(path: str, rel: str) -> list[str]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: syntax error: {e.msg}"]
    lines = src.splitlines()
    wanted = {fn for (f_rel, fn) in HOT_FUNCTIONS if f_rel == rel}
    if not wanted:
        return []
    problems = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name not in wanted:
            continue
        for stmt in ast.walk(node):
            if not isinstance(stmt, ast.For) or not _is_dispatch_loop(stmt):
                continue
            for lineno, what in _banned_calls(stmt):
                line = lines[lineno - 1] if lineno <= len(lines) else ""
                prev = lines[lineno - 2] if lineno >= 2 else ""
                if ANNOTATION in line or ANNOTATION in prev:
                    continue
                problems.append(
                    f"{path}:{lineno}: {what} inside the async dispatch "
                    f"loop of {node.name}() — a host sync here "
                    "serializes the per-segment pipeline; move it to "
                    "the phase-2 sync region or annotate with "
                    f"'{ANNOTATION}'")
    return problems


def main(argv: list[str]) -> int:
    root = argv[1] if len(argv) > 1 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "opensearch_tpu")
    problems = []
    for dirpath, _dirs, files in os.walk(root):
        if "__pycache__" in dirpath:
            continue
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            problems.extend(check_file(path, rel))
    for p in problems:
        print(p)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
