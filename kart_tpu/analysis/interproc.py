"""Interprocedural layer for `kart lint` (docs/ANALYSIS.md §"The
interprocedural model"): a project-wide call graph over the shared per-file
parses, decorator resolution for ``@jax.jit``/``shard_map``/thread targets,
and a lock-alias analysis that tracks module- and instance-attribute
``Lock``/``RLock`` objects across files.

The model is deliberately *named*, not pointer-precise — the repo's own
conventions make that sound enough to be useful:

* **Functions** are indexed by qualified name (``rel::func`` /
  ``rel::Class.method``, nested defs as ``rel::outer.inner``). Calls
  resolve through from-imports (including package ``__init__``
  re-exports), module aliases (``from kart_tpu import telemetry as tm``),
  and ``self.m(...)`` dispatch over the class hierarchy (bases *and*
  overriding subclasses — a base holding its lock while calling an
  abstract hook runs the subclass's body). An attribute call on an
  arbitrary expression resolves by bare method name only when that name is
  rare project-wide (``_MAX_FUZZY`` definitions), so common verbs like
  ``get``/``read`` never fan the graph out to everything.
* **Locks** are canonicalised to their *defining* site: a module-level
  ``X = threading.Lock()`` is ``rel::X``; ``self._lock = Lock()`` assigned
  in class C (possibly a base in another file) makes every ``with
  self._lock`` in C **and its subclasses** the single id ``rel::C._lock``.
  All instances of a class share one id — conservative for ordering (two
  instances of one class locked in opposite orders would be a real
  hazard anyway). Locks that reach a function as a parameter or an
  unresolvable attribute merge by name (``param::thread_lock`` /
  ``attr::push_lock``).

Known precision limits (also in docs/ANALYSIS.md): ``lock.acquire()``
without ``with`` is not tracked; dict-element locks
(``line["cond"]``) are invisible; resolution is name-based, so two
same-named distinctive methods merge. Each limit trades a bounded false-
negative for a near-zero false-positive rate — the rules built on top
(KTL010-KTL013, KTL020-KTL021) must hold the tree at zero findings.
"""

import ast
import re

from kart_tpu.analysis.core import children, dotted_name, subtree, unparse

#: resolve a bare-name method call only when the project defines that
#: method name in at most this many places (keeps common verbs inert)
_MAX_FUZZY = 3

#: identifier shapes we treat as lock-like even without a resolved
#: definition — THE "lock-ish" notion: KTL005 (rules.py) and the
#: KTL010-KTL012 family all import this one regex, so what counts as a
#: lock can never fork between rules
LOCKISH_RE = re.compile(r"^(r?lock|.*_lock|lock_.*|.*mutex.*|.*semaphore.*)$")

IDENT_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")

#: method names whose call mutates the receiver in place (KTL005/KTL012)
MUTATORS = frozenset(
    {"append", "add", "update", "setdefault", "extend", "clear", "pop",
     "insert", "popitem", "discard", "remove", "move_to_end"}
)


def lockish_expr(expr):
    """Does this expression *name* a lock (lock, _lock, probe_lock, a
    mutex/semaphore) — not any word merely containing the letters
    (``blocker``, ``clock``)?"""
    return any(
        LOCKISH_RE.match(i.lower()) for i in IDENT_RE.findall(unparse(expr))
    )


def under_lockish_with(ctx, node):
    """Is ``node`` lexically inside a ``with <something lock-ish>``?  The
    shared KTL005/KTL012 guard test."""
    cur = ctx.parents.get(node)
    while cur is not None:
        if isinstance(cur, ast.With) and any(
            lockish_expr(item.context_expr) for item in cur.items
        ):
            return True
        cur = ctx.parents.get(cur)
    return False

_LOCK_CTORS = {
    "threading.Lock",
    "threading.RLock",
    "Lock",
    "RLock",
}

_RLOCK_CTORS = {"threading.RLock", "RLock"}


class FunctionInfo:
    """One function/method definition, with its lint context."""

    __slots__ = ("ctx", "rel", "qual", "name", "cls", "node", "summary")

    def __init__(self, ctx, qual, name, cls, node):
        self.ctx = ctx
        self.rel = ctx.rel
        self.qual = qual  # "rel::Class.method" / "rel::func" / "rel::f.g"
        self.name = name
        self.cls = cls  # enclosing class name or None
        self.node = node
        self.summary = None  # LockSummary, attached lazily by the rules

    def __repr__(self):
        return f"<fn {self.qual}>"


class ClassInfo:
    __slots__ = ("ctx", "rel", "name", "node", "bases", "methods")

    def __init__(self, ctx, name, node, bases):
        self.ctx = ctx
        self.rel = ctx.rel
        self.name = name
        self.node = node
        self.bases = bases  # base names as written (last dotted segment)
        self.methods = {}  # name -> FunctionInfo


class FileSummary:
    """Per-file slice of the model; built once per context and shared by
    every rule through :func:`file_summary`."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.rel = ctx.rel
        self.functions = []  # FunctionInfo, source order
        self.classes = {}  # name -> ClassInfo
        self.imports = {}  # local name -> ("module"|"name", dotted, orig)
        self.module_locks = {}  # name -> ("lock"|"rlock", lineno)
        self.attr_locks = {}  # (class name, attr) -> ("lock"|"rlock", line)
        self._build()

    # -- construction -------------------------------------------------------

    def _build(self):
        ctx = self.ctx
        self._collect_imports(ctx.nodes)
        self._collect_defs(ctx.tree, prefix="", cls=None)
        self._collect_locks()

    def _collect_imports(self, nodes):
        for node in nodes:
            if isinstance(node, ast.Import):
                for alias in node.names:
                    local = alias.asname or alias.name.split(".", 1)[0]
                    self.imports[local] = ("module", alias.name, None)
            elif isinstance(node, ast.ImportFrom) and node.module:
                if node.level:  # relative imports: out of model
                    continue
                for alias in node.names:
                    local = alias.asname or alias.name
                    self.imports[local] = ("name", node.module, alias.name)

    def _collect_defs(self, tree, prefix, cls):
        for node in children(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{self.rel}::{prefix}{node.name}"
                info = FunctionInfo(self.ctx, qual, node.name, cls, node)
                self.functions.append(info)
                if cls is not None and prefix == cls + ".":
                    self.classes[cls].methods[node.name] = info
                self._collect_defs(
                    node, prefix=f"{prefix}{node.name}.", cls=cls
                )
            elif isinstance(node, ast.ClassDef):
                bases = []
                for b in node.bases:
                    d = dotted_name(b)
                    if d:
                        bases.append(d.rsplit(".", 1)[-1])
                self.classes[node.name] = ClassInfo(
                    self.ctx, node.name, node, bases
                )
                self._collect_defs(node, prefix=node.name + ".", cls=node.name)
            else:
                self._collect_defs(node, prefix=prefix, cls=cls)

    def _lock_kind(self, value):
        if isinstance(value, ast.Call):
            fn = dotted_name(value.func)
            if fn in _LOCK_CTORS:
                return "rlock" if fn in _RLOCK_CTORS else "lock"
            # threading.Condition() owns a lock: treat as one for ordering
            if fn in ("threading.Condition", "Condition"):
                return "lock"
        return None

    def _collect_locks(self):
        for stmt in self.ctx.tree.body:
            if isinstance(stmt, ast.Assign):
                kind = self._lock_kind(stmt.value)
                if kind:
                    for t in stmt.targets:
                        if isinstance(t, ast.Name):
                            self.module_locks[t.id] = (kind, stmt.lineno)
        for fn in self.functions:
            if fn.cls is None:
                continue
            for node in subtree(fn.node):
                if not isinstance(node, ast.Assign):
                    continue
                kind = self._lock_kind(node.value)
                if not kind:
                    continue
                for t in node.targets:
                    if (
                        isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                    ):
                        self.attr_locks[(fn.cls, t.attr)] = (kind, node.lineno)


def file_summary(ctx):
    """The (cached) :class:`FileSummary` for one lint context."""
    summary = getattr(ctx, "_interproc_summary", None)
    if summary is None:
        summary = ctx._interproc_summary = FileSummary(ctx)
    return summary


def file_model(ctx):
    """The (cached) single-file :class:`ProjectModel` — KTL010 and KTL011
    both scan per file; sharing the model shares the lock summaries and
    call-resolution cache instead of rebuilding them per rule."""
    model = getattr(ctx, "_interproc_file_model", None)
    if model is None:
        model = ctx._interproc_file_model = ProjectModel([ctx])
    return model


def _module_rel(dotted):
    """'kart_tpu.diff.backend' -> candidate repo-relative paths."""
    base = dotted.replace(".", "/")
    return (base + ".py", base + "/__init__.py")


class ProjectModel:
    """The cross-file model: built from whatever contexts the run parsed
    (the full tree on default runs, the explicit files in pre-commit
    mode — resolution degrades gracefully to what is visible)."""

    def __init__(self, contexts):
        self._lock_summaries = {}  # qual -> LockSummary (per-model: lock
        # ids canonicalise differently under single-file vs full-tree views)
        self._resolve_cache = {}  # id(call node) -> [FunctionInfo]
        self.lock_kinds = {}  # lock id -> "lock"|"rlock"|"fuzzy" (KTL010
        # must not call an RLock re-acquire a deadlock)
        self.summaries = [file_summary(c) for c in contexts]
        self.by_rel = {s.rel: s for s in self.summaries}
        self.classes = {}  # name -> [ClassInfo]
        self.functions = {}  # qual -> FunctionInfo
        self.methods_by_name = {}  # bare name -> [FunctionInfo]
        for s in self.summaries:
            for c in s.classes.values():
                self.classes.setdefault(c.name, []).append(c)
            for f in s.functions:
                self.functions[f.qual] = f
                self.methods_by_name.setdefault(f.name, []).append(f)

    # -- module / import resolution ----------------------------------------

    def summary_for_module(self, dotted):
        for rel in _module_rel(dotted):
            s = self.by_rel.get(rel)
            if s is not None:
                return s
        return None

    def resolve_export(self, dotted_module, name, _depth=0):
        """FunctionInfo for ``name`` importable from ``dotted_module`` —
        follows one level of ``__init__`` re-export chains."""
        s = self.summary_for_module(dotted_module)
        if s is None or _depth > 2:
            return None
        for f in s.functions:
            if f.cls is None and f.name == name and "." not in f.qual.split("::")[1]:
                return f
        imp = s.imports.get(name)
        if imp is not None and imp[0] == "name":
            return self.resolve_export(imp[1], imp[2], _depth + 1)
        return None

    # -- class hierarchy ----------------------------------------------------

    def mro_classes(self, cls_name, *, seen=None):
        """ClassInfos for ``cls_name`` and its (name-resolved) ancestors."""
        if seen is None:
            seen = set()
        if cls_name in seen:
            return []
        seen.add(cls_name)
        out = []
        for info in self.classes.get(cls_name, []):
            out.append(info)
            for base in info.bases:
                out.extend(self.mro_classes(base, seen=seen))
        return out

    def subclasses(self, cls_name):
        out = []
        for infos in self.classes.values():
            for info in infos:
                if cls_name in info.bases:
                    out.append(info)
                    out.extend(self.subclasses(info.name))
        return out

    def dispatch_method(self, cls_name, method):
        """Candidate implementations of ``self.method()`` seen from class
        ``cls_name``: the hierarchy's own defs, ancestors', and overriding
        subclasses' (a base calling a hook runs the override)."""
        cands = []
        for info in self.mro_classes(cls_name):
            f = info.methods.get(method)
            if f is not None:
                cands.append(f)
        for info in self.subclasses(cls_name):
            f = info.methods.get(method)
            if f is not None:
                cands.append(f)
        return cands

    # -- call resolution ----------------------------------------------------

    def resolve_call(self, summary, call, enclosing_cls):
        """Candidate FunctionInfos for one ast.Call, bounded; [] when the
        callee is out of model (builtins, stdlib, C extensions). Memoized
        per call node (the rules' fixpoints revisit the same sites)."""
        key = id(call)
        cached = self._resolve_cache.get(key)
        if cached is not None:
            return cached
        out = self._resolve_call_uncached(summary, call, enclosing_cls)
        self._resolve_cache[key] = out
        return out

    def _resolve_call_uncached(self, summary, call, enclosing_cls):
        func = call.func
        if isinstance(func, ast.Name):
            name = func.id
            # local def?
            for f in summary.functions:
                if f.name == name and f.cls is None:
                    return [f]
            imp = summary.imports.get(name)
            if imp is not None and imp[0] == "name":
                f = self.resolve_export(imp[1], imp[2])
                return [f] if f is not None else []
            return []
        if isinstance(func, ast.Attribute):
            if isinstance(func.value, ast.Name):
                recv = func.value.id
                if recv == "self" and enclosing_cls is not None:
                    cands = self.dispatch_method(enclosing_cls, func.attr)
                    if cands:
                        return cands[:_MAX_FUZZY * 2]
                imp = summary.imports.get(recv)
                if imp is not None:
                    if imp[0] == "module":
                        f = self.resolve_export(imp[1], func.attr)
                        return [f] if f is not None else []
                    if imp[0] == "name":
                        # `from kart_tpu import telemetry` via name-import
                        f = self.resolve_export(
                            imp[1] + "." + imp[2], func.attr
                        )
                        return [f] if f is not None else []
            # arbitrary receiver: fuzzy by rare method name only
            cands = self.methods_by_name.get(func.attr, [])
            if 0 < len(cands) <= _MAX_FUZZY:
                return list(cands)
        return []

    # -- lock aliasing -------------------------------------------------------

    def lock_defining_class(self, cls_name, attr):
        """The ClassInfo whose methods assign ``self.<attr> = Lock()``,
        searching the hierarchy from ``cls_name`` upward."""
        for info in self.mro_classes(cls_name):
            entry = self.by_rel[info.rel].attr_locks.get((info.name, attr))
            if entry is not None:
                return info, entry[0]
        return None, None

    def lock_id(self, summary, expr, enclosing_cls):
        """Canonical lock identity for a ``with`` item expression, or
        (None, None). -> (lock_id, kind) where kind is "lock"/"rlock"/
        "fuzzy" (name-matched but definition unseen)."""
        if isinstance(expr, ast.Call):
            # with Lock():  (anonymous: no ordering identity)
            # with push_file_lock(repo): / with closing(x):
            fn = dotted_name(expr.func)
            if fn and LOCKISH_RE.match(fn.rsplit(".", 1)[-1].lower()):
                return f"call::{fn.rsplit('.', 1)[-1]}", "fuzzy"
            return None, None
        if isinstance(expr, ast.IfExp):
            # with (lock if cond else nullcontext()): either branch
            for branch in (expr.body, expr.orelse):
                lid, kind = self.lock_id(summary, branch, enclosing_cls)
                if lid is not None:
                    return lid, kind
            return None, None
        if isinstance(expr, ast.Name):
            entry = summary.module_locks.get(expr.id)
            if entry is not None:
                return f"{summary.rel}::{expr.id}", entry[0]
            if LOCKISH_RE.match(expr.id.lower()):
                return f"param::{expr.id}", "fuzzy"
            return None, None
        if isinstance(expr, ast.Attribute):
            if (
                isinstance(expr.value, ast.Name)
                and expr.value.id == "self"
                and enclosing_cls is not None
            ):
                owner, kind = self.lock_defining_class(
                    enclosing_cls, expr.attr
                )
                if owner is not None:
                    return f"{owner.rel}::{owner.name}.{expr.attr}", kind
            if isinstance(expr.value, ast.Name):
                recv = expr.value.id
                imp = summary.imports.get(recv)
                if imp is not None and imp[0] == "module":
                    target = self.summary_for_module(imp[1])
                    if target is not None:
                        entry = target.module_locks.get(expr.attr)
                        if entry is not None:
                            return f"{target.rel}::{expr.attr}", entry[0]
            if LOCKISH_RE.match(expr.attr.lower()):
                return f"attr::{expr.attr}", "fuzzy"
            return None, None
        return None, None


def project_model(contexts_or_project):
    """Build (or fetch the cached) :class:`ProjectModel`. Accepts the
    framework's ``Project`` (finalize) or a list of contexts."""
    contexts = getattr(contexts_or_project, "contexts", contexts_or_project)
    holder = (
        contexts_or_project
        if hasattr(contexts_or_project, "contexts")
        else None
    )
    if holder is not None:
        model = getattr(holder, "_interproc_model", None)
        if model is not None:
            return model
    model = ProjectModel(contexts)
    if holder is not None:
        holder._interproc_model = model
    return model


# ---------------------------------------------------------------------------
# decorator / wrapper resolution: traced functions and thread entry points
# ---------------------------------------------------------------------------

#: decorator / wrapper callables that stage a function for jax tracing
_TRACE_WRAPPERS = frozenset({"jit", "pmap", "lazy_jit", "vmap"})


def _is_trace_wrapper(func_expr):
    """Does calling this expression trace its function argument?  Covers
    ``jax.jit`` / ``jax.pmap`` / ``lazy_jit`` and any ``shard_map``-shaped
    callable."""
    d = dotted_name(func_expr)
    if d is not None:
        leaf = d.rsplit(".", 1)[-1]
        return leaf in _TRACE_WRAPPERS or "shard_map" in leaf
    return "shard_map" in unparse(func_expr)


def traced_functions(summary):
    """FunctionInfos in this file that jax traces: ``@jax.jit``-style
    decorators, ``lazy_jit(fn)`` / ``jax.pmap(fn)`` wrapping, and
    ``shard_map(...)(fn)`` / ``jax.shard_map(fn, ...)`` bodies. A name
    passed to a wrapper resolves to the def sharing the wrapper call's
    enclosing function (several factories nest their own ``_step``)."""
    by_name = {}
    for f in summary.functions:
        by_name.setdefault(f.name, []).append(f)
    parents = summary.ctx.parents

    def enclosing_fn(node):
        cur = parents.get(node)
        while cur is not None and not isinstance(
            cur, (ast.FunctionDef, ast.AsyncFunctionDef)
        ):
            cur = parents.get(cur)
        return cur

    def resolve(name_node):
        cands = by_name.get(name_node.id, [])
        if len(cands) == 1:
            return cands[0]
        scope = enclosing_fn(name_node)
        for f in cands:
            if enclosing_fn(f.node) is scope:
                return f
        return cands[0] if cands else None

    traced = {}

    def mark(fn_info, how):
        traced.setdefault(fn_info.qual, (fn_info, how))

    for f in summary.functions:
        for dec in f.node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if isinstance(target, ast.Call):  # functools.partial(jax.jit,…)
                for a in target.args:
                    if _is_trace_wrapper(a):
                        mark(f, unparse(dec))
                continue
            if _is_trace_wrapper(target):
                mark(f, unparse(dec))
            elif isinstance(dec, ast.Call) and any(
                _is_trace_wrapper(a) for a in dec.args
            ):
                mark(f, unparse(dec))
    for node in summary.ctx.nodes:
        if not (isinstance(node, ast.Call) and node.args):
            continue
        if not _is_trace_wrapper(node.func):
            continue
        first = node.args[0]
        if isinstance(first, ast.Name):
            target = resolve(first)
            if target is not None:
                mark(target, unparse(node.func))
    return [entry for _q, entry in sorted(traced.items())]


#: executor/pool methods that take a worker callable (shared with KTL005)
SUBMITTERS = frozenset(
    {"submit", "map", "imap", "imap_unordered", "apply_async", "starmap"}
)

def thread_entry_functions(summary):
    """Function *names* in this file handed to Thread/Process targets,
    executor submits, pool maps or initializers (the KTL005 notion, shared
    here so thread-reachability means one thing)."""
    names = set()
    for node in summary.ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        fn = (dotted_name(node.func) or "").rsplit(".", 1)[-1]
        if fn in ("Thread", "Process", "Timer"):
            for kw in node.keywords:
                if kw.arg == "target":
                    if isinstance(kw.value, ast.Name):
                        names.add(kw.value.id)
                    elif isinstance(kw.value, ast.Attribute):
                        names.add(kw.value.attr)
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in SUBMITTERS
            and node.args
            and isinstance(node.args[0], ast.Name)
        ):
            names.add(node.args[0].id)
        for kw in node.keywords:
            if kw.arg == "initializer" and isinstance(kw.value, ast.Name):
                names.add(kw.value.id)
    return names


# ---------------------------------------------------------------------------
# lock summaries: held-set tracking per function
# ---------------------------------------------------------------------------


class LockSummary:
    """What one function does with locks: ``acquires`` [(lock, node,
    held-before)], ``calls`` [(call node, held-set)], ``blocking``
    [(reason, node, held-set)], ``yields`` [(node, held-set)]."""

    __slots__ = ("acquires", "calls", "blocking", "yields")

    def __init__(self):
        self.acquires = []
        self.calls = []
        self.blocking = []
        self.yields = []


def lock_summary(model, fn_info, blocking_reason):
    """Build (and cache, per model) the :class:`LockSummary` for one
    function. ``blocking_reason(call_node) -> str|None`` classifies direct
    blocking primitives (owned by the KTL011 rule so its list stays in one
    place)."""
    cached = model._lock_summaries.get(fn_info.qual)
    if cached is not None:
        return cached
    summary = model.by_rel[fn_info.rel]
    out = LockSummary()

    def walk(stmts, held):
        for node in stmts:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested defs: their own summaries
            if isinstance(node, ast.With):
                inner = list(held)
                for item in node.items:
                    lid, kind = model.lock_id(
                        summary, item.context_expr, fn_info.cls
                    )
                    if lid is not None:
                        model.lock_kinds.setdefault(lid, kind)
                    self_recv = isinstance(
                        item.context_expr, ast.Attribute
                    ) and isinstance(
                        item.context_expr.value, ast.Name
                    ) and item.context_expr.value.id == "self"
                    if lid is not None:
                        out.acquires.append(
                            (lid, node, frozenset(h for h, _s in inner),
                             self_recv)
                        )
                        inner.append((lid, self_recv))
                    else:
                        walk_expr(item.context_expr, held, include_self=True)
                walk(node.body, inner)
                continue
            # expression-level scan of this statement's own expressions,
            # then recurse into compound bodies (nested statements keep
            # their own — possibly larger — held sets via walk())
            walk_expr(node, held)
            for field in ("body", "orelse", "finalbody"):
                sub = getattr(node, field, None)
                if sub and all(isinstance(x, ast.stmt) for x in sub):
                    walk(sub, held)
            for handler in getattr(node, "handlers", []) or []:
                walk(handler.body, held)

    def walk_expr(node, held, include_self=False):
        held_ids = frozenset(h for h, _s in held)
        if include_self:
            stack = [node]
        else:
            stack = [
                c
                for c in children(node)
                if not isinstance(c, ast.stmt)
            ]
        while stack:
            sub = stack.pop()
            if isinstance(
                sub, (ast.FunctionDef, ast.AsyncFunctionDef, ast.stmt)
            ):
                continue
            if isinstance(sub, ast.Call):
                out.calls.append((sub, held_ids))
                reason = blocking_reason(sub)
                if reason is not None:
                    out.blocking.append((reason, sub, held_ids))
            elif isinstance(sub, (ast.Yield, ast.YieldFrom)):
                out.yields.append((sub, held_ids))
            stack.extend(children(sub))

    walk(fn_info.node.body, [])
    model._lock_summaries[fn_info.qual] = out
    return out
