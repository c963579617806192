#!/usr/bin/env python
"""Lint: scoring kernels may only be invoked via the unified query engine.

PR "one query engine" collapsed the execution paths (sequential,
msearch-batched, device mesh) into backend decisions inside
``search/engine.py``'s single entry; a scored term bag has one lowering
on every backend, and ``host_topk`` is its recovery and its parity
reference.  The refactor only stays collapsed if no NEW code path
starts calling the scoring kernels directly — that is exactly how the
paths grew in the first place.

Therefore: any call of a scoring-kernel function —

    impact_scores / impact_score_count / impact_topk_sorted /
    bm25_scores / bm25_score_count / match_count (ops/bm25.py),
    batch_impact_union_topk
    (search/batch.py), or a plan's host_topk

— anywhere under ``opensearch_tpu/`` must either live in
``search/engine.py`` itself, in ``ops/bm25.py`` (the definitions), or
carry a ``# engine-ok: <why>`` annotation on the same line or the line
above, asserting the site is one of the engine's sanctioned lowering
layers (plan lowering, batch backend, mesh backend).  Tests are out of
scope (they pin kernel parity directly on purpose).

Sibling of ``check_hot_path_sync.py`` / ``check_device_staging.py``;
new un-annotated sites fail tier-1 (tests/test_query_engine.py runs
this check).

Usage: python tools/check_execution_paths.py [root]   (exit 0 = clean)
"""

from __future__ import annotations

import ast
import os
import sys

ANNOTATION = "# engine-ok"

KERNELS = frozenset({
    "impact_scores", "impact_score_count", "impact_topk_sorted",
    "bm25_scores", "bm25_score_count", "match_count",
    "batch_impact_union_topk", "host_topk",
})

# modules allowed to touch kernels without annotation: the engine entry
# itself and the kernel definitions module
_EXEMPT_SUFFIXES = (
    os.path.join("search", "engine.py"),
    os.path.join("ops", "bm25.py"),
)


def _kernel_calls(tree: ast.AST) -> list[int]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if name in KERNELS:
            out.append(node.lineno)
    return out


def check_file(path: str) -> list[str]:
    if any(path.endswith(sfx) for sfx in _EXEMPT_SUFFIXES):
        return []
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: syntax error: {e.msg}"]
    lines = src.splitlines()
    problems = []
    for lineno in _kernel_calls(tree):
        line = lines[lineno - 1] if lineno <= len(lines) else ""
        prev = lines[lineno - 2] if lineno >= 2 else ""
        if ANNOTATION in line or ANNOTATION in prev:
            continue
        problems.append(
            f"{path}:{lineno}: scoring kernel invoked outside the "
            "unified query engine — route through search/engine.py "
            "(QueryEngine.execute/msearch) or annotate the sanctioned "
            f"lowering site with '{ANNOTATION}: <why>'")
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
            if fname.endswith(".py"):
                problems.extend(check_file(os.path.join(dirpath, fname)))
    for p in problems:
        print(p)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
