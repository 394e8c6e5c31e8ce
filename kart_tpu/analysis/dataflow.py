"""Taint dataflow for the KTL030-series rules (docs/ANALYSIS.md §5).

An intra-procedural, flow-sensitive pass over the shared per-file parses:
taint enters at the functions declared in :data:`registry.TAINT_SOURCES`
(wire bytes, request fields, peer responses), propagates through
assignments and arithmetic, and is reported when it reaches a sink
(allocation, wrapping sum, struct/slice access, filesystem name) while
still *unchecked*. A value becomes checked when a raising guard bounds it::

    if count > MAX_DECODE_ROWS:          # upper bound -> `count` checked
        raise TileEncodeError(...)
    if len(raw) != HEADER.size:          # length pin  -> `raw` checked
        raise HttpTransportError(...)

Precision contract (deliberate, documented in docs/ANALYSIS.md §5):

- One linear pass per function, no loop fixpoint. Branch states merge
  conservatively: a variable is checked after an ``if``/``else`` only if
  both arms checked it; tainted if either arm tainted it.
- Checked-ness never survives value extraction: the *result* of
  ``struct.unpack``/``np.frombuffer``/aggregation (``.sum()``) over a
  checked buffer is tainted-unchecked again — a pinned buffer length says
  nothing about the magnitudes inside it.
- A raising compare sanitizes only the bounded side, and only in the
  bounding direction (``t > U`` / ``U < t`` / ``t != U`` / ``t not in S``
  before ``raise``). Lower-bound-only guards (``if t < 0: raise``) do not
  sanitize — they were exactly the shape that let the PR 14/15 wrapping
  sums through. A compare involving ``len(x)`` is a remaining-length
  precheck and sanitizes every name it mentions, in either direction.
- Taint crosses call edges exactly one level: a call from a source
  function into a resolvable callee (same file, or cross-file through the
  PR 10 interprocedural model on full runs) analyzes the callee with the
  argument taints seeded, memoized per (function, taint signature).
  Callees of callees are opaque: their results are tainted-unchecked.

Sources are declared in the registry for tree code, or — for fixtures and
out-of-tree snippets — with a docstring tag::

    def decode(data):
        '''taint-source: data'''
"""

import ast
import os
import re

from kart_tpu.analysis import interproc, registry
from kart_tpu.analysis.core import (
    children,
    dotted_name,
    enclosing,
    subtree,
    unparse,
)

#: run-wide counter (reset per lint run by KTL030's constructor); bench.py
#: records it as ``lint_taint_functions_analyzed``.
_STATS = {"functions_analyzed": 0}


def reset_stats():
    _STATS["functions_analyzed"] = 0


def last_run_functions_analyzed():
    return _STATS["functions_analyzed"]


# -- taint values ------------------------------------------------------------


class Taint:
    """A tainted value: where it came from, and what bounds have run on
    every path reaching here. ``checked`` bounds the *magnitudes* (safe
    as a size/offset); ``len_ok`` lower-bounds the *byte length* (safe as
    an unpack buffer). They are distinct: ``if len(data) < 9: raise``
    licenses ``unpack_from(data, 0)`` but says nothing about the values
    decoded out of ``data``, and ``if count > CAP: raise`` bounds the
    count without making any buffer longer."""

    __slots__ = ("roots", "checked", "len_ok")

    def __init__(self, roots, checked=False, len_ok=False):
        self.roots = frozenset(roots)
        self.checked = checked
        self.len_ok = len_ok

    def __repr__(self):  # pragma: no cover - debugging aid
        flag = "checked" if self.checked else "UNCHECKED"
        return f"<taint {','.join(sorted(self.roots))} {flag}>"


def _merge(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return Taint(
        a.roots | b.roots, a.checked and b.checked, a.len_ok and b.len_ok
    )


def _roots_text(taint):
    return ", ".join(sorted(taint.roots))


# -- source declarations -----------------------------------------------------

_TAG_SOURCE_RE = re.compile(r"taint-source:\s*([A-Za-z0-9_.,\s]+)")
_TAG_EXACT = "taint-consume-exact"


def _norm_entry(entry):
    return {
        "kind": entry.get("kind", "declared"),
        "params": tuple(entry.get("params", ())),
        "attrs": tuple(entry.get("attrs", ())),
        "calls": tuple(entry.get("calls", ())),
        "consume_exact": bool(entry.get("consume_exact")),
        "error": entry.get("error"),
    }


def _in_tree(rel):
    return rel.startswith("kart_tpu/") or rel == "bench.py"


def sources_for(ctx):
    """Declared taint sources in one file: ``{qualname-in-file: entry}``.

    Registry keys match on the exact repo-relative path; for files outside
    the tree (regression-replay copies of real modules linted from a temp
    dir) a basename match applies, so surgically edited copies of
    ``streams.py`` keep their declarations. Docstring ``taint-source:``
    tags add fixture-local sources.
    """
    cached = getattr(ctx, "_taint_sources", None)
    if cached is not None:
        return cached
    out = {}
    base = os.path.basename(ctx.rel)
    for key, entry in registry.TAINT_SOURCES.items():
        rel, qual = key.split("::", 1)
        if ctx.rel == rel or (
            not _in_tree(ctx.rel) and os.path.basename(rel) == base
        ):
            out[qual] = _norm_entry(entry)
    for f in interproc.file_summary(ctx).functions:
        doc = ast.get_docstring(f.node) or ""
        m = _TAG_SOURCE_RE.search(doc)
        if not m and _TAG_EXACT not in doc:
            continue
        tail = f.qual.split("::", 1)[1]
        names = (
            [n.strip() for n in m.group(1).split(",") if n.strip()]
            if m
            else []
        )
        out[tail] = {
            "kind": "declared",
            "params": tuple(n for n in names if "." not in n),
            "attrs": tuple(n for n in names if "." in n),
            "calls": (),
            "consume_exact": _TAG_EXACT in doc,
            "error": None,
        }
    ctx._taint_sources = out
    return out


def validator_names():
    return {
        key.split("::", 1)[1] for key in registry.SANITIZERS["validators"]
    }


# -- sink tables -------------------------------------------------------------

#: np.<name>(n) allocating O(n) memory from its size argument(s)
_ALLOC_NP = {"repeat", "zeros", "empty", "ones", "full", "arange"}
#: aggregations whose result wraps/overflows in a fixed-width dtype
_AGG_METHODS = {"sum", "prod", "cumsum", "cumprod", "dot"}
#: methods whose result stays within the receiver's checked bounds
_PRESERVE_METHODS = {
    "astype", "view", "copy", "item", "max", "min", "tobytes",
    "strip", "rstrip", "lstrip",
}
#: np.<name> that reshuffle/extend values without changing their bounds
#: (np.repeat(starts, reps) holds values *from* starts; np.arange(a, b)
#: is bounded by its endpoints) — unlike aggregations, checked survives
_PRESERVE_NP = {
    "arange", "repeat", "concatenate", "where", "sort", "unique",
    "flatnonzero", "ascontiguousarray", "asarray", "array", "clip",
    "minimum", "maximum", "abs",
}
#: bare calls that preserve the argument's checked-ness
_PRESERVE_CALLS = {"int", "float", "abs", "round", "bool", "np.int64",
                   "np.uint64", "np.int32", "np.uint32", "np.intp"}
#: filesystem / path sinks for wire-derived names (KTL034)
_FS_CALLS = {
    "open", "os.open", "os.remove", "os.unlink", "os.rename",
    "os.replace", "os.makedirs", "os.rmdir", "os.path.join",
    "shutil.rmtree",
}

_NP_PREFIXES = ("np", "numpy")


def _np_call(dn):
    """'np.repeat' -> 'repeat'; None for non-numpy dotted names."""
    if dn is None or "." not in dn:
        return None
    head, _, tail = dn.partition(".")
    if head in _NP_PREFIXES and "." not in tail:
        return tail
    return None


# -- guard analysis ----------------------------------------------------------

#: for ``if COND: raise`` the survivor path has NOT COND — these operator
#: sets bound the left / right side respectively
_RAISE_UPPER_LEFT = (ast.Gt, ast.GtE, ast.NotEq, ast.NotIn)
_RAISE_UPPER_RIGHT = (ast.Lt, ast.LtE, ast.NotEq)
#: for ``assert COND`` the survivor path has COND
_ASSERT_UPPER_LEFT = (ast.Lt, ast.LtE, ast.Eq, ast.In)
_ASSERT_UPPER_RIGHT = (ast.Gt, ast.GtE, ast.Eq)
#: directions under which a guard *lower*-bounds (or pins) ``len(x)`` on
#: the survivor path — `if len(data) < 9: raise` / `if pos + 5 >
#: len(data): raise` — licensing buffer access on x (Taint.len_ok)
_RAISE_LEN_LEFT = (ast.Lt, ast.LtE, ast.NotEq)
_RAISE_LEN_RIGHT = (ast.Gt, ast.GtE, ast.NotEq)
_ASSERT_LEN_LEFT = (ast.Gt, ast.GtE, ast.Eq)
_ASSERT_LEN_RIGHT = (ast.Lt, ast.LtE, ast.Eq)


def _side_names(expr):
    """(plain names, len-wrapped names) referenced by one compare side."""
    plain, lens = set(), set()

    def walk(node, in_len=False):
        if isinstance(node, ast.Name):
            (lens if in_len else plain).add(node.id)
            return
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Name):
                if fn.id == "len":
                    for a in node.args:
                        walk(a, in_len=True)
                    return
                # skip the function name itself (int, min, ...)
            elif isinstance(fn, ast.Attribute):
                walk(fn.value, in_len)
            for a in node.args:
                walk(a, in_len)
            for kw in node.keywords:
                walk(kw.value, in_len)
            return
        for child in children(node):
            walk(child, in_len)

    walk(expr)
    return plain, lens


def _pure_arith(expr):
    """True when ``expr`` is built only from names, constants, and
    arithmetic — an invertible-enough derivation for pin propagation."""
    for node in subtree(expr):
        if not isinstance(node, (ast.BinOp, ast.UnaryOp, ast.Constant,
                                 ast.Name, ast.operator, ast.unaryop,
                                 ast.expr_context)):
            return False
    return True


def _unwrap_any(expr):
    """np.any(c) / any(c) / np.all(c) / all(c) -> c."""
    if isinstance(expr, ast.Call) and expr.args:
        dn = dotted_name(expr.func)
        if dn in ("np.any", "np.all", "numpy.any", "numpy.all",
                  "any", "all"):
            return expr.args[0]
    return expr


class _FnPass:
    """One function analyzed under one taint signature."""

    def __init__(self, eng, fninfo, seeds, attr_roots, call_roots, depth,
                 closure_env=None):
        self.eng = eng
        self.fn = fninfo
        self.env = dict(closure_env or {})
        self.env.update(seeds)
        self.attr_roots = dict(attr_roots)  # dotted -> root label
        self.call_roots = dict(call_roots)  # call name -> root label
        self.depth = depth
        self.nested = {}  # name -> FunctionInfo for defs nested right here
        self.ret = None
        #: per-position taints when every `return` is a same-arity tuple,
        #: so `codes, pos = varint_decode(...)` keeps a checked position
        #: distinct from the unchecked values; False once shapes diverge
        self.ret_elems = None
        #: id(call) -> callee ret_elems, for tuple-unpacking assignments
        self._call_elems = {}
        #: name -> source names, for assignments that are pure arithmetic
        #: (`expected = 8 + count * 24`): pinning `expected` (e.g. by
        #: `len(data) != expected`) pins `count` through it
        self.arith_src = {}

    def run(self):
        _STATS["functions_analyzed"] += 1
        self.eng.functions += 1
        # nested defs are their own scopes, analyzed on call with the
        # enclosing env as closure state (read_pack's pull() reads the
        # tainted fileobj through its closure, not a parameter)
        prefix = self.fn.qual + "."
        for f in self.eng.summary.functions:
            tail = f.qual
            if tail.startswith(prefix) and "." not in tail[len(prefix):]:
                self.nested[f.name] = f
        self._stmts(self.fn.node.body)
        return self

    # -- statements ----------------------------------------------------------

    def _stmts(self, body):
        for stmt in body:
            self._stmt(stmt)

    def _stmt(self, stmt):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # analyzed on call (self.nested), never inline
        if isinstance(stmt, ast.Assign):
            self._check_sinks(stmt.value)
            t = self._taint(stmt.value)
            elems = (
                self._call_elems.get(id(stmt.value))
                if isinstance(stmt.value, ast.Call)
                else None
            )
            for tgt in stmt.targets:
                if (
                    elems
                    and isinstance(tgt, ast.Tuple)
                    and len(tgt.elts) == len(elems)
                ):
                    for elt, et in zip(tgt.elts, elems):
                        self._bind(elt, et)
                else:
                    self._bind(tgt, t)
                if isinstance(tgt, ast.Name):
                    self.arith_src.pop(tgt.id, None)
                    if t is not None and _pure_arith(stmt.value):
                        srcs, _ = _side_names(stmt.value)
                        self.arith_src[tgt.id] = srcs - {tgt.id}
            self._validator_effects(stmt.value)
            return
        if isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                self._check_sinks(stmt.value)
                self._bind(stmt.target, self._taint(stmt.value))
            return
        if isinstance(stmt, ast.AugAssign):
            self._check_sinks(stmt.value)
            t = self._taint(stmt.value)
            if isinstance(stmt.target, ast.Name):
                t = _merge(self.env.get(stmt.target.id), t)
                self._bind(stmt.target, t)
            return
        if isinstance(stmt, ast.Expr):
            self._check_sinks(stmt.value)
            self._validator_effects(stmt.value)
            return
        if isinstance(stmt, ast.Return):
            if stmt.value is not None:
                self._check_sinks(stmt.value)
                self.ret = _merge(self.ret, self._taint(stmt.value))
                if isinstance(stmt.value, ast.Tuple):
                    elems = [self._taint(e) for e in stmt.value.elts]
                    if self.ret_elems is None:
                        self.ret_elems = elems
                    elif (
                        self.ret_elems is not False
                        and len(self.ret_elems) == len(elems)
                    ):
                        self.ret_elems = [
                            _merge(a, b)
                            for a, b in zip(self.ret_elems, elems)
                        ]
                    else:
                        self.ret_elems = False
                else:
                    self.ret_elems = False
            return
        if isinstance(stmt, ast.If):
            self._if(stmt)
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._check_sinks(stmt.iter)
            self._bind(stmt.target, self._iter_taint(stmt.iter))
            self._stmts(stmt.body)
            self._stmts(stmt.orelse)
            return
        if isinstance(stmt, ast.While):
            self._check_sinks(stmt.test)
            # `while pos < len(data):` — the loop condition is the
            # remaining-length bound for the body
            self._apply_marks(self._guard_marks(stmt.test, assert_form=True))
            self._stmts(stmt.body)
            self._stmts(stmt.orelse)
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._check_sinks(item.context_expr)
                if item.optional_vars is not None:
                    self._bind(
                        item.optional_vars, self._taint(item.context_expr)
                    )
            self._stmts(stmt.body)
            return
        if isinstance(stmt, ast.Try):
            self._stmts(stmt.body)
            for h in stmt.handlers:
                self._stmts(h.body)
            self._stmts(stmt.orelse)
            self._stmts(stmt.finalbody)
            return
        if isinstance(stmt, ast.Assert):
            self._apply_marks(self._guard_marks(stmt.test, assert_form=True))
            return
        if isinstance(stmt, ast.Raise):
            if stmt.exc is not None:
                self._check_sinks(stmt.exc)
            return
        # Pass / Break / Continue / Delete / Global / Import / ...: no flow

    def _if(self, stmt):
        self._check_sinks(stmt.test)

        def exits(body):
            return any(
                isinstance(s, (ast.Raise, ast.Continue)) for s in body
            )

        env0 = dict(self.env)
        # the body runs with the test true: `elif enc == RLE:` pins enc
        # for the branch (and for the merge, when every live arm pins it)
        self._apply_marks(self._guard_marks(stmt.test, assert_form=True))
        self._stmts(stmt.body)
        env_body = self.env
        self.env = dict(env0)
        self._stmts(stmt.orelse)
        env_else = self.env
        if exits(stmt.body) and not exits(stmt.orelse):
            # the guard shape: only the else/fallthrough path survives,
            # with the test's bounds established
            self.env = env_else
            self._apply_marks(
                self._guard_marks(stmt.test, assert_form=False)
            )
        elif exits(stmt.orelse) and not exits(stmt.body):
            # `else: raise` dispatch tail: the body path survives, test true
            self.env = env_body
        else:
            merged = {}
            for name in set(env_body) | set(env_else):
                a, b = env_body.get(name), env_else.get(name)
                if a is None or b is None:
                    # tainted on one path only: tainted, keep its flag
                    merged[name] = a if a is not None else b
                else:
                    merged[name] = Taint(
                        a.roots | b.roots,
                        a.checked and b.checked,
                        a.len_ok and b.len_ok,
                    )
            self.env = merged

    def _guard_marks(self, test, assert_form):
        """(value marks, len marks) a guard establishes on the survivor
        path — ``assert_form`` False for ``if COND: raise`` (survivor has
        NOT COND), True for ``assert COND`` / branch entry (survivor has
        COND)."""
        if assert_form:
            upper_left, upper_right = _ASSERT_UPPER_LEFT, _ASSERT_UPPER_RIGHT
            len_left, len_right = _ASSERT_LEN_LEFT, _ASSERT_LEN_RIGHT
        else:
            upper_left, upper_right = _RAISE_UPPER_LEFT, _RAISE_UPPER_RIGHT
            len_left, len_right = _RAISE_LEN_LEFT, _RAISE_LEN_RIGHT
        marks, len_marks = set(), set()
        for cond in self._conds(test):
            cond = _unwrap_any(cond)
            if not isinstance(cond, ast.Compare):
                continue
            left = cond.left
            for op, right in zip(cond.ops, cond.comparators):
                lp, ll = _side_names(left)
                rp, rl = _side_names(right)
                # a `len(x)` term is a trusted quantity (bounded by the
                # buffer), so it never disqualifies the other side's
                # bound; a guard that lower-bounds len(x) licenses buffer
                # access on x (len_ok) but never blesses the *values*
                # inside x — `if len(ends) < count: raise` says nothing
                # about the magnitudes in ends
                l_t = {n for n in lp if self._unchecked(n)}
                r_t = {n for n in rp if self._unchecked(n)}
                if l_t and not r_t and isinstance(op, upper_left):
                    marks |= lp
                if r_t and not l_t and isinstance(op, upper_right):
                    marks |= rp
                if ll and isinstance(op, len_left):
                    len_marks |= ll
                if rl and isinstance(op, len_right):
                    len_marks |= rl
                left = right
        return marks, len_marks

    def _conds(self, test):
        """Compares a guard establishes on the survivor path. ``or``
        distributes soundly (the survivor negates every disjunct). ``and``
        flattens *optimistically*: ``if n_runs and lens.max() > count:
        raise`` is credited with the bound even though a zero ``n_runs``
        skips it — on that path the sequence is empty anyway. Documented
        as a precision limit in docs/ANALYSIS.md §5."""
        if isinstance(test, ast.BoolOp):
            out = []
            for v in test.values:
                out.extend(self._conds(v))
            return out
        return [test]

    def _unchecked(self, name):
        t = self.env.get(name)
        return t is not None and not t.checked

    def _apply_marks(self, marks):
        value_marks, len_marks = marks
        for name in value_marks:
            self._mark_checked(name)
        for name in len_marks:
            t = self.env.get(name)
            if t is not None and not t.len_ok:
                self.env[name] = Taint(t.roots, t.checked, True)

    def _mark_checked(self, name, _seen=None):
        t = self.env.get(name)
        if t is not None and not t.checked:
            self.env[name] = Taint(t.roots, True, t.len_ok)
        # pinning a pure-arithmetic derivation pins what it was built from
        seen = _seen or {name}
        for src in self.arith_src.get(name, ()):
            if src not in seen:
                seen.add(src)
                self._mark_checked(src, seen)

    def _bind(self, target, taint):
        if isinstance(target, ast.Name):
            if taint is None:
                self.env.pop(target.id, None)
            else:
                self.env[target.id] = taint
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind(elt, taint)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, taint)
        # attribute/subscript targets: no tracked state

    def _iter_taint(self, iter_expr):
        # `for i in range(n)` draws its values from n
        if isinstance(iter_expr, ast.Call):
            dn = dotted_name(iter_expr.func)
            if dn in ("range", "enumerate", "reversed", "sorted", "zip",
                      "iter"):
                t = None
                for a in iter_expr.args:
                    t = _merge(t, self._taint(a))
                return t
        return self._taint(iter_expr)

    # -- expressions ---------------------------------------------------------

    def _taint(self, expr):
        if expr is None or isinstance(expr, ast.Constant):
            return None
        if isinstance(expr, ast.Name):
            return self.env.get(expr.id)
        if isinstance(expr, ast.Attribute):
            dn = dotted_name(expr)
            if dn is not None and dn in self.attr_roots:
                return Taint({self.attr_roots[dn]}, False)
            return self._taint(expr.value)
        if isinstance(expr, ast.Subscript):
            return self._taint(expr.value)
        if isinstance(expr, ast.BinOp):
            return _merge(self._taint(expr.left), self._taint(expr.right))
        if isinstance(expr, ast.UnaryOp):
            return self._taint(expr.operand)
        if isinstance(expr, ast.BoolOp):
            t = None
            for v in expr.values:
                t = _merge(t, self._taint(v))
            return t
        if isinstance(expr, ast.Compare):
            # an elementwise mask (`buf < 0x80`) is positionally tainted:
            # np.flatnonzero of it yields attacker-chosen indices
            t = self._taint(expr.left)
            for c in expr.comparators:
                t = _merge(t, self._taint(c))
            return t
        if isinstance(expr, ast.Call):
            return self._call_taint(expr)
        if isinstance(expr, ast.IfExp):
            return _merge(self._taint(expr.body), self._taint(expr.orelse))
        if isinstance(expr, (ast.Tuple, ast.List, ast.Set)):
            t = None
            for elt in expr.elts:
                t = _merge(t, self._taint(elt))
            return t
        if isinstance(expr, ast.Dict):
            t = None
            for v in expr.values:
                t = _merge(t, self._taint(v))
            return t
        if isinstance(expr, ast.JoinedStr):
            t = None
            for v in expr.values:
                if isinstance(v, ast.FormattedValue):
                    t = _merge(t, self._taint(v.value))
            return t
        if isinstance(expr, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                             ast.DictComp)):
            t = None
            for gen in expr.generators:
                t = _merge(t, self._taint(gen.iter))
            # a comprehension re-shapes its input: bounds don't survive
            return Taint(t.roots, False) if t is not None else None
        if isinstance(expr, ast.Starred):
            return self._taint(expr.value)
        if isinstance(expr, ast.Await):
            return self._taint(expr.value)
        return None

    def _args_taint(self, call):
        t = None
        for a in call.args:
            t = _merge(t, self._taint(a))
        for kw in call.keywords:
            t = _merge(t, self._taint(kw.value))
        return t

    def _call_taint(self, call):
        dn = dotted_name(call.func)
        last = dn.rsplit(".", 1)[-1] if dn else None

        if dn == "len" or dn in ("isinstance", "hasattr", "id", "type",
                                 "callable"):
            return None
        if last in self.eng.validators:
            # a declared validator raises on anything malformed: its
            # argument names come out checked
            for a in call.args:
                if isinstance(a, ast.Name):
                    self._mark_checked(a.id)
            t = self._args_taint(call)
            return Taint(t.roots, True) if t is not None else None
        if dn is not None and (dn in self.call_roots
                               or last in self.call_roots):
            root = self.call_roots.get(dn) or self.call_roots.get(last)
            return Taint({root}, False)
        if dn in ("min",) or last == "clip":
            # min(t, CAP) / np.clip(t, lo, hi): bounded by construction
            # when any bound is untainted
            args = [self._taint(a) for a in call.args]
            tainted = [t for t in args if t is not None]
            if tainted and len(tainted) < len(call.args):
                roots = frozenset().union(*(t.roots for t in tainted))
                return Taint(roots, True)

        # one call level: a resolvable callee runs under the argument
        # taints; everything deeper is opaque (tainted-unchecked result)
        if self.depth == 0:
            ret = self._cross_call(call)
            if ret is not NotImplemented:
                return ret

        recv = (
            self._taint(call.func.value)
            if isinstance(call.func, ast.Attribute)
            else None
        )
        t = _merge(recv, self._args_taint(call))
        if t is None:
            return None
        if last in _AGG_METHODS:
            return Taint(t.roots, False)
        if isinstance(call.func, ast.Attribute) and last in _PRESERVE_METHODS:
            return Taint(t.roots, t.checked)
        if dn in _PRESERVE_CALLS or _np_call(dn) in _PRESERVE_NP:
            return Taint(t.roots, t.checked)
        return Taint(t.roots, False)

    # -- call crossing -------------------------------------------------------

    def _cross_call(self, call):
        """Resolve + analyze one callee with the argument taints seeded.
        NotImplemented = not locally resolvable (recorded for the
        cross-file finalize pass when any argument is tainted)."""
        arg_taints = self._arg_taint_list(call)
        if not any(t is not None for _, _, t in arg_taints):
            return NotImplemented

        func = call.func
        info, closure = None, None
        if isinstance(func, ast.Name):
            info = self.nested.get(func.id)
            if info is not None:
                closure = {
                    k: v for k, v in self.env.items() if v is not None
                }
            else:
                for f in self.eng.summary.functions:
                    tail = f.qual.split("::", 1)[1]
                    if f.cls is None and tail == func.id:
                        info = f
                        break
        elif (
            isinstance(func, ast.Attribute)
            and isinstance(func.value, ast.Name)
            and func.value.id == "self"
            and self.fn.cls is not None
        ):
            cls = self.eng.summary.classes.get(self.fn.cls)
            if cls is not None:
                info = cls.methods.get(func.attr)

        if info is None:
            if any(t is not None and not t.checked for _, _, t in arg_taints):
                self.eng.outcalls.append((call, self.fn, arg_taints))
            return NotImplemented

        seeds = map_call_args(info, call, arg_taints)
        sub = self.eng.analyze_callee(info, seeds, closure_env=closure)
        if sub is None:  # nothing unchecked flowed in: opaque result
            return NotImplemented
        if getattr(sub, "ret_elems", False):
            self._call_elems[id(call)] = sub.ret_elems
        return sub.ret

    def _arg_taint_list(self, call):
        out = []
        for i, a in enumerate(call.args):
            out.append(("pos", i, self._taint(a)))
        for kw in call.keywords:
            if kw.arg is not None:
                out.append(("kw", kw.arg, self._taint(kw.value)))
        return out

    def _validator_effects(self, expr):
        for node in subtree(expr):
            if isinstance(node, ast.Call):
                dn = dotted_name(node.func)
                if dn and dn.rsplit(".", 1)[-1] in self.eng.validators:
                    for a in node.args:
                        if isinstance(a, ast.Name):
                            self._mark_checked(a.id)

    # -- sinks ---------------------------------------------------------------

    def _unchecked_expr(self, expr):
        t = self._taint(expr)
        return t if (t is not None and not t.checked) else None

    def _check_sinks(self, expr):
        for node in subtree(expr):
            if isinstance(node, ast.Call):
                self._sink_call(node)
            elif isinstance(node, ast.BinOp):
                self._sink_binop(node)
            elif isinstance(node, ast.Subscript):
                self._sink_subscript(node)

    def _emit(self, rule, node, what, taint):
        self.eng.emit(
            rule, node,
            f"{what} [tainted by {_roots_text(taint)}]",
        )

    def _sink_call(self, call):
        dn = dotted_name(call.func)
        if dn is None:
            return
        last = dn.rsplit(".", 1)[-1]
        npfn = _np_call(dn)

        # KTL030 — allocation sized by an unchecked wire value; only the
        # size-shaped arguments count (np.repeat's first arg is *values*)
        if npfn in _ALLOC_NP:
            if npfn == "repeat":
                size_args = list(call.args[1:2]) + [
                    k.value for k in call.keywords if k.arg == "repeats"
                ]
            elif npfn == "arange":
                size_args = list(call.args)
            else:  # zeros/empty/ones/full: the shape argument
                size_args = list(call.args[:1]) + [
                    k.value for k in call.keywords if k.arg == "shape"
                ]
            for a in size_args:
                t = self._unchecked_expr(a)
                if t is not None:
                    self._emit(
                        "KTL030", call,
                        f"`{dn}` allocates from an unchecked wire-derived "
                        f"size `{unparse(a)}` — cap it against a declared "
                        "ceiling before allocating", t,
                    )
                    return
        if npfn == "frombuffer":
            cands = list(call.args[2:3]) + [
                k.value for k in call.keywords if k.arg == "count"
            ]
            for a in cands:
                t = self._unchecked_expr(a)
                if t is not None:
                    self._emit(
                        "KTL030", call,
                        "`np.frombuffer` count is an unchecked wire value "
                        f"`{unparse(a)}`", t,
                    )
                    return
        if dn in ("bytes", "bytearray") and len(call.args) == 1:
            a = call.args[0]
            # bytes(buf[i:j]) copies bytes; bytes(n) allocates n zeros
            if not isinstance(a, (ast.Subscript, ast.Attribute,
                                  ast.Constant)):
                t = self._unchecked_expr(a)
                if t is not None:
                    self._emit(
                        "KTL030", call,
                        f"`{dn}(n)` allocates an unchecked wire-derived "
                        f"count `{unparse(a)}` of zero bytes", t,
                    )
        if dn == "range":
            for a in call.args:
                t = self._unchecked_expr(a)
                if t is not None:
                    self._emit(
                        "KTL030", call,
                        "`range()` over an unchecked wire-derived count "
                        f"`{unparse(a)}`", t,
                    )
                    return

        # KTL031 — wrapping aggregation of unchecked lengths
        if last in ("sum", "prod") and isinstance(call.func, ast.Attribute):
            t = self._unchecked_expr(call.func.value)
            if t is not None:
                self._emit(
                    "KTL031", call,
                    f"`.{last}()` aggregates unchecked wire-derived "
                    "lengths in a wrapping dtype — use a non-wrapping "
                    "Python sum or bound the elements first", t,
                )
        if npfn in ("sum", "prod"):
            for a in call.args:
                t = self._unchecked_expr(a)
                if t is not None:
                    self._emit(
                        "KTL031", call,
                        f"`{dn}` aggregates unchecked wire-derived values "
                        "in a wrapping dtype", t,
                    )
                    return

        # KTL032 — struct access without a remaining-length precheck:
        # the buffer needs its *length* lower-bounded (len_ok), offsets
        # need their *magnitude* bounded (checked)
        if last in ("unpack", "unpack_from"):
            buf_idx = 1 if dn.startswith("struct.") else 0
            if len(call.args) > buf_idx:
                t = self._taint(call.args[buf_idx])
                if t is not None and not t.len_ok:
                    self._emit(
                        "KTL032", call,
                        f"`{last}` over a wire buffer with no length "
                        "precheck — a truncated payload raises "
                        "struct.error instead of the declared error", t,
                    )
                    return
            if last == "unpack_from":
                offsets = list(call.args[buf_idx + 1:]) + [
                    k.value for k in call.keywords if k.arg == "offset"
                ]
                for a in offsets:
                    t = self._unchecked_expr(a)
                    if t is not None:
                        self._emit(
                            "KTL032", call,
                            "`unpack_from` offset unchecked against "
                            "the remaining length", t,
                        )
                        return

        # KTL034 — wire-derived names reaching the filesystem
        if dn in _FS_CALLS:
            for a in list(call.args) + [k.value for k in call.keywords]:
                t = self._unchecked_expr(a)
                if t is not None:
                    self._emit(
                        "KTL034", call,
                        f"wire-derived name reaches `{dn}` without a "
                        "declared validator (check_ref_format & friends)",
                        t,
                    )
                    return

    def _sink_binop(self, binop):
        if isinstance(binop.op, ast.Mult):
            for const, other in ((binop.left, binop.right),
                                 (binop.right, binop.left)):
                if isinstance(const, ast.Constant) and isinstance(
                    const.value, (bytes, str)
                ) or isinstance(const, ast.List):
                    t = self._unchecked_expr(other)
                    if t is not None:
                        self._emit(
                            "KTL030", binop,
                            "sequence repetition sized by an unchecked "
                            f"wire value `{unparse(other)}`", t,
                        )
                        return
        elif isinstance(binop.op, ast.LShift):
            t = self._unchecked_expr(binop.right)
            if t is not None:
                self._emit(
                    "KTL032", binop,
                    "shift by an unchecked wire-derived amount "
                    f"`{unparse(binop.right)}` — >64-bit varint shape", t,
                )

    def _sink_subscript(self, sub):
        if isinstance(sub.ctx, ast.Store):
            return
        sl = sub.slice
        exprs = (
            [sl.lower, sl.upper, sl.step]
            if isinstance(sl, ast.Slice)
            else [sl]
        )
        hit = None
        for e in exprs:
            if e is None or isinstance(e, ast.Constant):
                continue
            if isinstance(e, ast.UnaryOp) and isinstance(
                e.operand, ast.Constant
            ):
                continue  # x[-1]
            t = self._unchecked_expr(e)
            if t is not None:
                hit = (e, t)
                break
        if hit is None:
            return
        # an index under a try/except that converts the failure is the
        # sanctioned truncation guard (mvt read_uvarint)
        guard = enclosing(self.eng.ctx, sub, ast.Try)
        if guard is not None and guard.handlers:
            return
        e, t = hit
        # `for name in sorted(sizes): ... sizes[name]` — a key drawn from
        # the mapping it indexes cannot miss
        if isinstance(e, ast.Name) and isinstance(sub.value, ast.Name):
            loop = self.eng.ctx.parents.get(sub)
            while loop is not None:
                if (
                    isinstance(loop, (ast.For, ast.AsyncFor))
                    and isinstance(loop.target, ast.Name)
                    and loop.target.id == e.id
                    and any(
                        isinstance(n, ast.Name) and n.id == sub.value.id
                        for n in subtree(loop.iter)
                    )
                ):
                    return
                loop = self.eng.ctx.parents.get(loop)
        self._emit(
            "KTL032", sub,
            f"subscript/slice bound `{unparse(e)}` is an unchecked wire "
            "value — precheck it against the remaining length", t,
        )


def map_call_args(info, call, arg_taints):
    """Seed dict for ``info``'s parameters from a call's argument taints."""
    a = info.node.args
    params = [p.arg for p in getattr(a, "posonlyargs", [])] + [
        p.arg for p in a.args
    ]
    if info.cls is not None and params and params[0] in ("self", "cls"):
        params = params[1:]
    seeds = {}
    for kind, key, t in arg_taints:
        if t is None:
            continue
        if kind == "pos" and key < len(params):
            seeds[params[key]] = t
        elif kind == "kw" and isinstance(key, str):
            seeds[key] = t
    return seeds


class _Engine:
    """Per-file driver: analyses, memoization, event dedup."""

    def __init__(self, ctx, summary):
        self.ctx = ctx
        self.summary = summary
        self.validators = validator_names()
        self.memo = {}
        self.functions = 0
        self.events = []  # (rule, node, message)
        self.outcalls = []  # (call, caller FunctionInfo, arg taints)
        self._seen = set()

    def emit(self, rule, node, message):
        key = (rule, id(node))
        if key in self._seen:
            return
        self._seen.add(key)
        self.events.append((rule, node, message))

    def analyze_source(self, fninfo, entry):
        seeds = {
            p: Taint({f"{entry['kind']}:{p}"}, False)
            for p in entry["params"]
        }
        attr_roots = {
            a: f"{entry['kind']}:{a}" for a in entry["attrs"]
        }
        call_roots = {
            c: f"{entry['kind']}:{c}()" for c in entry["calls"]
        }
        p = _FnPass(self, fninfo, seeds, attr_roots, call_roots, depth=0)
        return p.run()

    def analyze_callee(self, fninfo, seeds, closure_env=None):
        """Depth-1 analysis of a callee under caller taints; None when no
        unchecked taint flows in (nothing new to learn). Memoized per
        (function, taint signature)."""
        if not seeds and not closure_env:
            return None
        sig = (
            fninfo.qual,
            tuple(sorted((k, t.checked) for k, t in seeds.items())),
            tuple(
                sorted((k, t.checked) for k, t in (closure_env or {}).items())
            ),
        )
        got = self.memo.get(sig)
        if got is not None:
            return got
        self.memo[sig] = _SENTINEL  # recursion cut: nested self-calls
        p = _FnPass(self, fninfo, seeds, {}, {}, depth=1,
                    closure_env=closure_env)
        p.run()
        self.memo[sig] = p
        return p


class _Sentinel:
    ret = None


_SENTINEL = _Sentinel()


# -- entry points ------------------------------------------------------------


def file_taint(ctx):
    """Per-file taint result, computed once and shared by every KTL03x
    rule: ``{"events": [(rule, node, msg)], "outcalls": [...],
    "functions": n}``. Files with no declared source are skipped outright
    — the pass costs nothing on the bulk of the tree."""
    cached = getattr(ctx, "_taint_file", None)
    if cached is not None:
        return cached
    res = {"events": [], "outcalls": [], "functions": 0, "engine": None}
    srcs = sources_for(ctx)
    if srcs:
        summary = interproc.file_summary(ctx)
        eng = _Engine(ctx, summary)
        for f in summary.functions:
            tail = f.qual.split("::", 1)[1]
            entry = srcs.get(tail)
            if entry is not None:
                eng.analyze_source(f, entry)
        res["events"] = eng.events
        res["outcalls"] = eng.outcalls
        res["functions"] = eng.functions
        res["engine"] = eng
    ctx._taint_file = res
    return res


def project_taint(project):
    """Cross-file leg (full runs only): resolve each source's tainted
    out-calls through the interprocedural model and analyze the callee
    one level deep in its own file. -> [(rule, rel, node, message)],
    cached on the project."""
    cached = getattr(project, "_taint_project", None)
    if cached is not None:
        return cached
    model = interproc.project_model(project)
    # reuse each file's own engine so cross-file events dedupe against the
    # per-file pass (same node is never reported twice)
    engines = {}
    bases = {}
    for ctx in project.contexts:
        res = file_taint(ctx)
        if not res["outcalls"]:
            continue
        summary = interproc.file_summary(ctx)
        for call, caller, arg_taints in res["outcalls"]:
            for cand in model.resolve_call(summary, call, caller.cls):
                if cand is None or cand.ctx is ctx:
                    continue
                eng = engines.get(cand.rel)
                if eng is None:
                    cres = file_taint(cand.ctx)
                    eng = cres["engine"]
                    if eng is None:
                        eng = _Engine(
                            cand.ctx, interproc.file_summary(cand.ctx)
                        )
                        cres["engine"] = eng
                    engines[cand.rel] = eng
                    bases[cand.rel] = len(eng.events)
                seeds = map_call_args(cand, call, arg_taints)
                if not seeds:
                    continue
                eng.analyze_callee(cand, seeds)
    out = []
    for rel, eng in sorted(engines.items()):
        for rule, node, msg in eng.events[bases[rel]:]:
            out.append((rule, rel, node, msg))
    project._taint_project = out
    return out


def consume_exact_ok(ctx, fn_node):
    """KTL033: does the decoder contain a consumed-vs-declared mismatch
    raise (`if consumed != expected: raise ...`) on some path?"""
    for node in subtree(fn_node):
        if not isinstance(node, ast.Raise):
            continue
        guard = enclosing(ctx, node, ast.If)
        if guard is None:
            continue
        for sub in subtree(guard.test):
            if isinstance(sub, ast.Compare) and any(
                isinstance(op, ast.NotEq) for op in sub.ops
            ):
                return True
    return False
