"""Script scoring: a safe expression subset compiled to jnp programs.

The reference compiles Painless to JVM bytecode per script
(ref script/ScriptService.java:438, modules/lang-painless/.../
PainlessScriptEngine.java:139) and runs it doc-at-a-time inside the
collector.  The TPU formulation compiles the script ONCE into a pure
jnp expression over dense per-doc columns, so scoring stays a fused
vector program — no per-doc interpreter in the hot loop.

Supported surface (the score-context essentials):

- arithmetic / comparisons / ternaries over ``_score``, ``params.*``,
  and ``doc['field'].value`` (numeric doc values; missing -> 0.0, with
  ``doc['field'].size()`` for explicit missing checks);
- ``Math.log/log10/sqrt/exp/abs/min/max/pow/floor/ceil`` plus bare
  ``min/max/abs``;
- vector helpers matching the k-NN plugin's whitelist:
  ``cosineSimilarity(params.qv, doc['vec'])``,
  ``dotProduct(params.qv, doc['vec'])``,
  ``l2Squared(params.qv, doc['vec'])``, ``sigmoid(x)``;
- the plugin's pre-baked ``{"lang": "knn", "source": "knn_score"}``
  script (params: field / query_value / space_type) — BASELINE
  config #2's exact shape — lowered onto the same exact-knn kernel the
  ``knn`` query uses (ops/knn.py).

Anything outside the subset raises ``ScriptException`` (400), never an
engine crash: unknown scripts are a client error, not a server one.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field as dc_field
from typing import Optional

import numpy as np

import opensearch_tpu.common.jaxenv  # noqa: F401
import jax.numpy as jnp
from jax import lax

from opensearch_tpu.common.errors import OpenSearchTpuError


class ScriptException(OpenSearchTpuError):
    status = 400


_MATH_FNS = {
    "log": jnp.log, "log10": jnp.log10, "sqrt": jnp.sqrt, "exp": jnp.exp,
    "abs": jnp.abs, "min": jnp.minimum, "max": jnp.maximum,
    "pow": jnp.power, "floor": jnp.floor, "ceil": jnp.ceil,
}
_BARE_FNS = {"min": jnp.minimum, "max": jnp.maximum, "abs": jnp.abs,
             "sigmoid": lambda x: 1.0 / (1.0 + jnp.exp(-x))}
_VECTOR_FNS = ("cosineSimilarity", "dotProduct", "l2Squared")


@dataclass(frozen=True)
class ScriptProgram:
    """Compiled script: hashable by (source, param NAMES) — not values —
    so every query vector / numeric param is a DYNAMIC program input and
    identical scripts share one XLA program across queries (the same
    static-structure/dynamic-binding split as the plan tree itself)."""

    source: str
    param_names: tuple                     # sorted numeric param names
    numeric_fields: tuple                  # doc['f'].value fields used
    vector_fields: tuple                   # doc['f'] vector fields used
    uses_score: bool
    _tree: object = dc_field(compare=False, hash=False, repr=False,
                             default=None)
    _params: dict = dc_field(compare=False, hash=False, repr=False,
                             default=None)

    def param_values(self):
        """Dynamic inputs in ``param_names`` order (host-side prepare)."""
        out = []
        for name in self.param_names:
            v = self._params[name]
            try:
                out.append(jnp.asarray(np.asarray(v, np.float32)))  # staging-ok: script literal
            except (ValueError, TypeError):
                raise ScriptException(
                    f"script param [{name}] is not numeric") from None
        return tuple(out)

    def eval(self, score, numeric_cols: dict, vector_cols: dict,
             param_vals: tuple):
        """Pure jnp evaluation; traced inside the plan's jitted eval."""
        params = dict(zip(self.param_names, param_vals))
        return _Evaluator(params, numeric_cols, vector_cols,
                          score).visit(self._tree)


class _FieldCollector(ast.NodeVisitor):
    """First pass: find doc[...] references and whether _score is used,
    and reject every node kind outside the whitelist."""

    _ALLOWED = (ast.Expression, ast.BinOp, ast.UnaryOp, ast.BoolOp,
                ast.Compare, ast.IfExp, ast.Call, ast.Attribute,
                ast.Subscript, ast.Name, ast.Constant, ast.Load,
                ast.Add, ast.Sub, ast.Mult, ast.Div, ast.Mod, ast.Pow,
                ast.USub, ast.UAdd, ast.And, ast.Or, ast.Not,
                ast.Eq, ast.NotEq, ast.Lt, ast.LtE, ast.Gt, ast.GtE,
                ast.List, ast.Tuple)

    def __init__(self):
        self.numeric: list[str] = []
        self.vectors: list[str] = []
        self.uses_score = False

    def generic_visit(self, node):
        if not isinstance(node, self._ALLOWED):
            raise ScriptException(
                f"unsupported script construct [{type(node).__name__}]")
        super().generic_visit(node)

    def visit_Name(self, node):
        if node.id == "_score":
            self.uses_score = True
        elif node.id not in ("doc", "params", "Math") and \
                node.id not in _BARE_FNS and node.id not in _VECTOR_FNS:
            raise ScriptException(f"unknown variable [{node.id}]")

    def visit_Call(self, node):
        fname = None
        if isinstance(node.func, ast.Name):
            fname = node.func.id
        if fname in _VECTOR_FNS:
            if len(node.args) != 2:
                raise ScriptException(f"[{fname}] takes (query, doc_field)")
            f = _doc_field_of(node.args[1])
            if f is None:
                raise ScriptException(
                    f"[{fname}] second argument must be doc['field']")
            self.vectors.append(f)
            self.visit(node.args[0])
            return
        self.generic_visit(node)

    def visit_Attribute(self, node):
        # doc['f'].value / doc['f'].size() / Math.fn / params.x
        f = _doc_field_of(node.value)
        if f is not None:
            if node.attr in ("value", "size"):
                self.numeric.append(f)
                return
            raise ScriptException(
                f"doc['{f}'].{node.attr} is not supported "
                "(use .value or .size())")
        self.generic_visit(node)


def _doc_field_of(node) -> Optional[str]:
    if (isinstance(node, ast.Subscript)
            and isinstance(node.value, ast.Name) and node.value.id == "doc"):
        sl = node.slice
        if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
            return sl.value
    return None


class _Evaluator(ast.NodeVisitor):
    """Second pass: evaluate over jnp arrays (called inside the trace)."""

    def __init__(self, params, numeric_cols, vector_cols, score):
        self.params = params
        self.numeric = numeric_cols        # field -> (values, exists)
        self.vectors = vector_cols         # field -> (matrix, exists)
        self.score = score

    def visit(self, node):  # noqa: D102 — dispatch only
        fn = getattr(self, f"visit_{type(node).__name__}", None)
        if fn is None:
            raise ScriptException(
                f"unsupported script construct [{type(node).__name__}]")
        return fn(node)

    def visit_Expression(self, node):
        return self.visit(node.body)

    def visit_Constant(self, node):
        if isinstance(node.value, (int, float, bool)):
            return node.value
        raise ScriptException(
            f"unsupported literal [{node.value!r}] in score script")

    def visit_Name(self, node):
        if node.id == "_score":
            return self.score
        raise ScriptException(f"unknown variable [{node.id}]")

    def visit_List(self, node):
        return jnp.asarray([self.visit(e) for e in node.elts],  # staging-ok: script literal
                           jnp.float32)

    visit_Tuple = visit_List

    def _param(self, name):
        if name not in self.params:
            raise ScriptException(f"missing script param [{name}]")
        return self.params[name]

    def visit_Attribute(self, node):
        f = _doc_field_of(node.value)
        if f is not None and node.attr == "value":
            return self.numeric[f][0]
        if isinstance(node.value, ast.Name) and node.value.id == "params":
            return self._param(node.attr)
        raise ScriptException("unsupported attribute access in script")

    def visit_Subscript(self, node):
        if isinstance(node.value, ast.Name) and node.value.id == "params":
            sl = node.slice
            if isinstance(sl, ast.Constant):
                return self._param(sl.value)
        raise ScriptException("unsupported subscript in script")

    def visit_BinOp(self, node):
        a, b = self.visit(node.left), self.visit(node.right)
        op = type(node.op)
        if op is ast.Add:
            return a + b
        if op is ast.Sub:
            return a - b
        if op is ast.Mult:
            return a * b
        if op is ast.Div:
            return a / b
        if op is ast.Mod:
            return a % b
        if op is ast.Pow:
            return a ** b
        raise ScriptException("unsupported operator")

    def visit_UnaryOp(self, node):
        v = self.visit(node.operand)
        if isinstance(node.op, ast.USub):
            return -v
        if isinstance(node.op, ast.UAdd):
            return v
        if isinstance(node.op, ast.Not):
            return jnp.logical_not(v)
        raise ScriptException("unsupported unary operator")

    def visit_Compare(self, node):
        if len(node.ops) != 1:
            raise ScriptException("chained comparisons are not supported")
        a, b = self.visit(node.left), self.visit(node.comparators[0])
        op = type(node.ops[0])
        table = {ast.Eq: jnp.equal, ast.NotEq: jnp.not_equal,
                 ast.Lt: jnp.less, ast.LtE: jnp.less_equal,
                 ast.Gt: jnp.greater, ast.GtE: jnp.greater_equal}
        return table[op](a, b)

    def visit_BoolOp(self, node):
        vals = [self.visit(v) for v in node.values]
        out = vals[0]
        for v in vals[1:]:
            out = (jnp.logical_and(out, v) if isinstance(node.op, ast.And)
                   else jnp.logical_or(out, v))
        return out

    def visit_IfExp(self, node):
        return jnp.where(self.visit(node.test), self.visit(node.body),
                         self.visit(node.orelse))

    def visit_Call(self, node):
        if isinstance(node.func, ast.Name):
            name = node.func.id
            if name in _VECTOR_FNS:
                q = self.visit(node.args[0])
                f = _doc_field_of(node.args[1])
                vec, exists = self.vectors[f]
                # exact scoring: see ops/knn.py on matmul precision
                dots = jnp.matmul(vec, q,
                                  precision=lax.Precision.HIGHEST)
                q2 = jnp.sum(q * q)
                if name == "dotProduct":
                    return dots
                if name == "l2Squared":
                    v2 = jnp.sum(vec * vec, axis=1)
                    return jnp.maximum(v2 - 2.0 * dots + q2, 0.0)
                norms = jnp.sqrt(jnp.sum(vec * vec, axis=1))
                qn = jnp.sqrt(q2)
                return dots / jnp.maximum(norms * qn, 1e-30)
            if name in _BARE_FNS:
                args = [self.visit(a) for a in node.args]
                try:
                    return _BARE_FNS[name](*args)
                except TypeError as e:
                    raise ScriptException(
                        f"bad arguments to [{name}]: {e}") from None
        if isinstance(node.func, ast.Attribute):
            recv = node.func.value
            # doc['f'].size()
            f = _doc_field_of(recv)
            if f is not None and node.func.attr == "size":
                return self.numeric[f][1].astype(jnp.int32)
            if isinstance(recv, ast.Name) and recv.id == "Math":
                fn = _MATH_FNS.get(node.func.attr)
                if fn is None:
                    raise ScriptException(
                        f"Math.{node.func.attr} is not supported")
                try:
                    return fn(*[self.visit(a) for a in node.args])
                except TypeError as e:
                    raise ScriptException(
                        f"bad arguments to [Math.{node.func.attr}]: "
                        f"{e}") from None
        raise ScriptException("unsupported function call in script")


def _split_ternary(src: str):
    """Find the outermost Java ternary ``cond ? a : b`` (depth 0, outside
    quotes); returns (cond, a, b) or None."""
    depth = 0
    quote = None
    for i, ch in enumerate(src):
        if quote:
            if ch == quote:
                quote = None
            continue
        if ch in "'\"":
            quote = ch
        elif ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "?" and depth == 0:
            level = 1
            d2, q2 = 0, None
            for j in range(i + 1, len(src)):
                c2 = src[j]
                if q2:
                    if c2 == q2:
                        q2 = None
                    continue
                if c2 in "'\"":
                    q2 = c2
                elif c2 in "([{":
                    d2 += 1
                elif c2 in ")]}":
                    d2 -= 1
                elif c2 == "?" and d2 == 0:
                    level += 1
                elif c2 == ":" and d2 == 0:
                    level -= 1
                    if level == 0:
                        return src[:i], src[i + 1: j], src[j + 1:]
            raise ScriptException("unterminated ternary in script")
    return None


def _sub_outside_quotes(src: str, fn) -> str:
    """Apply ``fn`` to each maximal unquoted chunk, leaving quoted spans
    (doc['field'] names!) byte-for-byte intact."""
    out = []
    chunk_start = 0
    quote = None
    for i, ch in enumerate(src):
        if quote:
            if ch == quote:
                out.append(src[chunk_start: i + 1])
                chunk_start = i + 1
                quote = None
        elif ch in "'\"":
            out.append(fn(src[chunk_start: i]))
            chunk_start = i
            quote = ch
    if quote:
        raise ScriptException("unterminated string literal in script")
    out.append(fn(src[chunk_start:]))
    return "".join(out)


def _painless_to_python(src: str) -> str:
    """Painless/Java surface syntax -> the equivalent Python expression:
    ``?:`` ternaries, ``&&``/``||``/``!``, true/false/null literals.
    Substitutions never touch quoted spans, so field names like
    doc['true'] survive."""
    import re as _re

    t = _split_ternary(src)
    if t is not None:
        cond, a, b = t
        return (f"(({_painless_to_python(a)}) if "
                f"({_painless_to_python(cond)}) else "
                f"({_painless_to_python(b)}))")

    def repl(chunk: str) -> str:
        chunk = _re.sub(r"&&", " and ", chunk)
        chunk = _re.sub(r"\|\|", " or ", chunk)
        chunk = _re.sub(r"!(?![=])", " not ", chunk)
        chunk = _re.sub(r"\btrue\b", "True", chunk)
        chunk = _re.sub(r"\bfalse\b", "False", chunk)
        chunk = _re.sub(r"\bnull\b", "None", chunk)
        return chunk

    return _sub_outside_quotes(src, repl)


def compile_score_script(script: dict) -> ScriptProgram:
    """Parse + whitelist a score script; raises ScriptException (400) on
    anything outside the subset."""
    if not isinstance(script, dict):
        raise ScriptException("[script] must be an object")
    lang = script.get("lang", "painless")
    source = script.get("source") or script.get("inline") or ""
    params = script.get("params") or {}
    if lang == "knn" or source == "knn_score":
        # the k-NN plugin's pre-baked script (BASELINE config #2)
        field = params.get("field")
        qv = params.get("query_value")
        if not field or qv is None:
            raise ScriptException(
                "knn_score requires params.field and params.query_value")
        space = params.get("space_type", "l2")
        src = {"l2": f"1 / (1 + l2Squared(params.query_value, doc['{field}']))",
               "cosinesimil":
                   f"(1 + cosineSimilarity(params.query_value, doc['{field}'])) / 2",
               "innerproduct":
                   f"dotProduct(params.query_value, doc['{field}'])",
               }.get(space)
        if src is None:
            raise ScriptException(f"unknown space_type [{space}]")
        source = src
    elif lang not in ("painless", "expression"):
        raise ScriptException(f"script lang [{lang}] is not supported")
    if not source:
        raise ScriptException("script [source] is required")
    try:
        tree = ast.parse(_painless_to_python(source), mode="eval")
    except SyntaxError as e:
        raise ScriptException(f"script compile error: {e}") from None
    coll = _FieldCollector()
    coll.visit(tree)
    numeric_params = {k: v for k, v in params.items()
                      if isinstance(v, (int, float, bool, list, tuple))
                      and not isinstance(v, str)}
    return ScriptProgram(
        source=source, param_names=tuple(sorted(numeric_params)),
        numeric_fields=tuple(sorted(set(coll.numeric))),
        vector_fields=tuple(sorted(set(coll.vectors))),
        uses_score=coll.uses_score, _tree=tree, _params=numeric_params)
