"""The `kart lint` rules (KTL001-KTL007). Each is grounded in a bug class
this repo has actually shipped or explicitly guards against — see
docs/ANALYSIS.md for the catalogue with rationale and example findings.
"""

import ast
import glob
import json
import os
import re

from kart_tpu.analysis import interproc, registry
from kart_tpu.analysis.core import (
    Rule,
    children,
    dotted_name,
    enclosing,
    register,
    str_const,
    subtree,
    unparse,
)

_ENV_NAME_RE = re.compile(r"^KART_[A-Z0-9_]+$")


def _env_read_name(node):
    """The literal env-var name this AST node reads/writes, or None.
    Covers ``os.environ.get/pop/setdefault``, ``os.getenv``,
    ``os.environ[...]`` and ``"X" in os.environ``."""
    if isinstance(node, ast.Call):
        fn = dotted_name(node.func)
        if fn is not None and node.args:
            leaf = fn.rsplit(".", 1)[-1]
            if fn in (
                "os.environ.get",
                "os.environ.pop",
                "os.environ.setdefault",
                "environ.get",
                "environ.pop",
                "os.getenv",
                "getenv",
            ) or leaf.startswith(("_env_", "env_")):
                # the last group covers the local typed helpers
                # (_env_int/_env_float in retry.py, device_batch.py, ...)
                return str_const(node.args[0])
    elif isinstance(node, ast.Subscript):
        if dotted_name(node.value) in ("os.environ", "environ"):
            return str_const(node.slice)
    elif isinstance(node, ast.Compare):
        if (
            len(node.ops) == 1
            and isinstance(node.ops[0], (ast.In, ast.NotIn))
            and dotted_name(node.comparators[0]) in ("os.environ", "environ")
        ):
            return str_const(node.left)
    return None


@register
class EnvVarDrift(Rule):
    id = "KTL001"
    name = "env-var-drift"
    description = (
        "every os.environ-read KART_* name is declared in "
        "kart_tpu/analysis/registry.py and documented in "
        "docs/OBSERVABILITY.md's env index — and vice versa"
    )

    def __init__(self):
        self.used = {}  # name -> first (rel, line)

    def visit_file(self, ctx):
        findings = []
        for node in ctx.nodes:
            name = _env_read_name(node)
            if name is None or not _ENV_NAME_RE.match(name):
                continue
            self.used.setdefault(name, (ctx.rel, node.lineno))
            if not registry.env_declared(name):
                findings.append(
                    ctx.finding(
                        self.id,
                        node,
                        f"undeclared env var {name}: add it to "
                        "analysis/registry.py ENV_VARS and the "
                        "docs/OBSERVABILITY.md index",
                    )
                )
        return findings

    def _doc_index(self, project):
        """-> ({token: line}, heading_line) for `KART_*` tokens inside the
        env-index section of the docs file."""
        doc_rel, section = registry.ENV_DOC
        text = project.read(doc_rel)
        if text is None:
            return None, None
        tokens, heading_line, in_section = {}, None, False
        for i, line in enumerate(text.splitlines(), start=1):
            if line.startswith("## "):
                in_section = section.lower() in line.lower()
                if in_section:
                    heading_line = i
                continue
            if in_section:
                for tok in re.findall(r"`(KART_[A-Z0-9_*]+)`", line):
                    tokens.setdefault(tok, i)
        return tokens, heading_line

    def finalize(self, project):
        from kart_tpu.analysis.core import Finding

        findings = []
        doc_rel, _section = registry.ENV_DOC
        reg_rel = "kart_tpu/analysis/registry.py"
        tokens, heading_line = self._doc_index(project)
        if tokens is None:
            return [Finding(self.id, doc_rel, 1, 0, "env index missing")]

        declared = dict(registry.ENV_VARS)
        declared.update(
            {p + "*": scope for p, scope in registry.ENV_PREFIXES.items()}
        )
        # registry -> docs: every declaration has an index row
        for name in sorted(declared):
            if name not in tokens:
                findings.append(
                    Finding(
                        self.id,
                        doc_rel,
                        heading_line or 1,
                        0,
                        f"declared env var {name} missing from the "
                        f"{doc_rel} index",
                    )
                )
        # docs -> registry: every index row is a live declaration
        for tok, line in sorted(tokens.items()):
            if tok == "KART_*":  # the section heading's own tag
                continue
            if tok not in declared:
                findings.append(
                    Finding(
                        self.id,
                        doc_rel,
                        line,
                        0,
                        f"documented env var {tok} is not declared in "
                        "analysis/registry.py ENV_VARS",
                    )
                )
        # registry -> code: every "source"-scope declaration is read
        for name, scope in sorted(registry.ENV_VARS.items()):
            if scope == "source" and name not in self.used:
                findings.append(
                    Finding(
                        self.id,
                        reg_rel,
                        1,
                        0,
                        f"declared env var {name} has no read site under "
                        "kart_tpu//bench.py — dead declaration?",
                    )
                )
        for prefix, scope in sorted(registry.ENV_PREFIXES.items()):
            if scope == "source" and not any(
                u.startswith(prefix) for u in self.used
            ):
                findings.append(
                    Finding(
                        self.id,
                        reg_rel,
                        1,
                        0,
                        f"declared env prefix {prefix}* has no read site",
                    )
                )
        return findings


@register
class TelemetryGrammar(Rule):
    id = "KTL002"
    name = "telemetry-naming-grammar"
    description = (
        "every literal span/metric name passed to telemetry span()/incr()/"
        "gauge_set()/observe() is dotted lowercase with a registered "
        "subsystem first segment (docs/OBSERVABILITY.md §2)"
    )

    METHODS = frozenset({"span", "incr", "gauge_set", "observe"})
    RECEIVERS = frozenset({"tm", "telemetry"})

    def __init__(self):
        self.names_seen = []  # (name, rel, line) — the grammar-test hook

    def visit_file(self, ctx):
        from kart_tpu.telemetry import NAME_RE, SUBSYSTEMS

        findings = []
        for node in ctx.nodes:
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in self.METHODS
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in self.RECEIVERS
                and node.args
            ):
                continue
            arg = node.args[0]
            name = str_const(arg)
            if name is None:
                if isinstance(arg, ast.JoinedStr):
                    # f-string names: the subsystem prefix must still be a
                    # literal, and the rendered shape (placeholders as one
                    # segment-safe token) must obey the grammar — parity
                    # with the regex guard this rule replaced
                    rendered = "".join(
                        str(v.value) if isinstance(v, ast.Constant) else "x"
                        for v in arg.values
                    )
                    self.names_seen.append((rendered, ctx.rel, node.lineno))
                    lead = arg.values[0] if arg.values else None
                    lead_const = (
                        str_const(lead) if isinstance(lead, ast.Constant)
                        else None
                    )
                    if not NAME_RE.match(rendered):
                        findings.append(
                            ctx.finding(
                                self.id,
                                node,
                                f"f-string metric name (~{rendered!r}) "
                                "violates the grammar (dotted lowercase "
                                "`subsystem.metric`)",
                            )
                        )
                    elif (
                        lead_const is None
                        or "." not in lead_const
                        or lead_const.split(".", 1)[0] not in SUBSYSTEMS
                    ):
                        findings.append(
                            ctx.finding(
                                self.id,
                                node,
                                "f-string metric name must start with a "
                                "literal registered `subsystem.` prefix "
                                "so dashboards can key on it",
                            )
                        )
                continue
            self.names_seen.append((name, ctx.rel, node.lineno))
            if not NAME_RE.match(name):
                findings.append(
                    ctx.finding(
                        self.id,
                        node,
                        f"metric name {name!r} violates the grammar "
                        "(dotted lowercase `subsystem.metric`)",
                    )
                )
            elif name.split(".", 1)[0] not in SUBSYSTEMS:
                findings.append(
                    ctx.finding(
                        self.id,
                        node,
                        f"metric name {name!r}: first segment is not a "
                        f"registered subsystem ({sorted(SUBSYSTEMS)})",
                    )
                )
        return findings


@register
class FaultPointCoverage(Rule):
    id = "KTL003"
    name = "fault-point-coverage"
    description = (
        "every faults.hook()/faults.fire() point is declared in "
        "analysis/registry.py FAULT_POINTS and exercised by the "
        "tests/test_faults.py kill matrix — and vice versa"
    )

    def __init__(self):
        self.sites = {}  # point -> first (rel, line)

    def visit_file(self, ctx):
        findings = []
        for node in ctx.nodes:
            if not (
                isinstance(node, ast.Call)
                and dotted_name(node.func) in ("faults.hook", "faults.fire")
                and node.args
            ):
                continue
            point = str_const(node.args[0])
            if point is None:
                findings.append(
                    ctx.finding(
                        self.id,
                        node,
                        "fault point name must be a string literal so the "
                        "kill matrix can enumerate it",
                    )
                )
                continue
            self.sites.setdefault(point, (ctx.rel, node.lineno))
            if point not in registry.FAULT_POINTS:
                findings.append(
                    ctx.finding(
                        self.id,
                        node,
                        f"undeclared fault point {point!r}: add it to "
                        "analysis/registry.py FAULT_POINTS and the "
                        f"{registry.FAULT_TESTS} kill matrix",
                    )
                )
        return findings

    def finalize(self, project):
        from kart_tpu.analysis.core import Finding

        findings = []
        reg_rel = "kart_tpu/analysis/registry.py"
        tests = project.read(registry.FAULT_TESTS)
        if tests is None:
            # the coverage direction must fail loudly, not silently skip
            # (mirrors KTL001's missing-docs-index finding)
            return [
                Finding(
                    self.id,
                    registry.FAULT_TESTS,
                    1,
                    0,
                    f"kill matrix {registry.FAULT_TESTS} is missing — "
                    "no fault point has crash-path coverage; update "
                    "analysis/registry.py FAULT_TESTS if it moved",
                )
            ]
        for point in sorted(registry.FAULT_POINTS):
            if point not in self.sites:
                findings.append(
                    Finding(
                        self.id,
                        reg_rel,
                        1,
                        0,
                        f"registered fault point {point!r} has no "
                        "faults.hook()/fire() site",
                    )
                )
            if not self._injected(tests, point):
                findings.append(
                    Finding(
                        self.id,
                        registry.FAULT_TESTS,
                        1,
                        0,
                        f"fault point {point!r} is never injected by the "
                        "kill matrix (no KART_FAULTS spec arms it) — its "
                        "crash path is untested",
                    )
                )
        return findings

    @staticmethod
    def _injected(tests, point):
        """Does the kill matrix actually *arm* this point? An ordinary call
        like ``repo.odb.write_raw(...)`` mentions the point name without
        testing its crash path — only a KART_FAULTS spec on the same line
        counts."""
        return re.search(
            r"KART_FAULTS[^\n]*" + re.escape(point), tests
        ) is not None


# -- KTL004 ------------------------------------------------------------------

_OPENERS = {
    "open": "file handle",
    "io.open": "file handle",
    "subprocess.Popen": "subprocess",
    "Popen": "subprocess",
    "tempfile.NamedTemporaryFile": "temp file",
    "NamedTemporaryFile": "temp file",
    "tempfile.TemporaryFile": "temp file",
    "socket.socket": "socket",
    "socket.create_connection": "socket",
}

#: wrappers that take ownership and hand it to an enclosing ``with``
_OWNERSHIP_WRAPPERS = frozenset(
    {"closing", "contextlib.closing", "enter_context"}
)


@register
class ResourceLifecycle(Rule):
    id = "KTL004"
    name = "resource-lifecycle"
    description = (
        "file handles / subprocesses / temp files / sockets are opened "
        "under `with`, closed somewhere in their scope, or ownership-"
        "transferred (returned / stored on self); and any *.tmp/*.lock "
        "path the code writes matches the gc/fsck crash-leftover sweep "
        "pattern"
    )

    def visit_file(self, ctx):
        findings = []
        findings.extend(self._check_openers(ctx))
        findings.extend(self._check_tmp_patterns(ctx))
        return findings

    # -- unclosed-resource half ---------------------------------------------

    def _check_openers(self, ctx):
        findings = []
        for scope in self._scopes(ctx):
            names = None  # computed only if this scope opens anything
            for node in self._scope_walk(scope):
                if not isinstance(node, ast.Call):
                    continue
                fn = dotted_name(node.func)
                kind = _OPENERS.get(fn)
                if kind is None:
                    continue
                if names is None:
                    names = self._name_uses(scope)
                ok, why = self._acquisition_ok(ctx, node, scope, names)
                if not ok:
                    findings.append(
                        ctx.finding(
                            self.id,
                            node,
                            f"{kind} from {fn}() {why} — use `with`, "
                            "close in try/finally, or transfer ownership",
                        )
                    )
        return findings

    def _scopes(self, ctx):
        yield ctx.tree
        for node in ctx.nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield node

    def _scope_walk(self, scope):
        """Nodes belonging to this scope, not to nested functions (those
        are their own scopes and get their own walk)."""
        stack = list(scope.body)
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            stack.extend(children(node))

    def _name_uses(self, scope):
        """name -> {"close", "with", "return", "yield", "arg", "attr"}:
        the ways each local name is consumed in this scope."""
        uses = {}

        def mark(name, how):
            uses.setdefault(name, set()).add(how)

        for node in self._scope_walk(scope):
            if isinstance(node, ast.Call):
                f = node.func
                if (
                    isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)
                    and f.attr in ("close", "terminate", "kill", "shutdown")
                ):
                    mark(f.value.id, "close")
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    if isinstance(arg, ast.Name):
                        mark(arg.id, "arg")
            elif isinstance(node, ast.With):
                for item in node.items:
                    e = item.context_expr
                    if isinstance(e, ast.Name):
                        mark(e.id, "with")
                    elif isinstance(e, ast.Call):
                        for arg in e.args:
                            if isinstance(arg, ast.Name):
                                mark(arg.id, "with")
            elif isinstance(node, (ast.Return, ast.Yield, ast.YieldFrom)):
                # only the object itself escaping counts as ownership
                # transfer — `return proc.pid` hands back an int, not the
                # process
                v = getattr(node, "value", None)
                elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
                for e in elts:
                    if isinstance(e, ast.Name):
                        mark(e.id, "return")
            elif isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Attribute) and isinstance(
                        node.value, ast.Name
                    ):
                        mark(node.value.id, "attr")
        return uses

    def _acquisition_ok(self, ctx, call, scope, names):
        """Climb from the opener call through pure-expression ancestors
        (IfExp, BoolOp, parens) to the node that decides ownership."""
        parents = ctx.parents
        node, parent = call, parents.get(call)
        while isinstance(parent, (ast.IfExp, ast.BoolOp, ast.Starred)):
            node, parent = parent, parents.get(parent)
        # with open(...) as f / with closing(sock):
        if isinstance(parent, ast.withitem):
            return True, None
        if isinstance(parent, ast.Call):
            outer = dotted_name(parent.func) or ""
            if outer.rsplit(".", 1)[-1] in _OWNERSHIP_WRAPPERS or isinstance(
                parents.get(parent), ast.withitem
            ):
                return True, None
            return False, "is consumed inline so nothing can close it"
        if isinstance(parent, (ast.Return, ast.Yield)):
            return True, None  # ownership to the caller
        if isinstance(parent, ast.Expr):
            return False, "is discarded unreferenced"
        if isinstance(parent, ast.Assign):
            targets = parent.targets
            if any(isinstance(t, ast.Attribute) for t in targets):
                return True, None  # self.proc = Popen(...): owner closes
            for t in targets:
                if isinstance(t, ast.Name):
                    # merely *using* the handle (json.load(f)) is not a
                    # transfer — only closing, with-managing, returning it,
                    # or storing it on an owner counts
                    if names.get(t.id, set()) & {
                        "close", "with", "return", "attr"
                    }:
                        return True, None
                    return False, f"bound to {t.id!r} which is never closed"
        # anything more exotic: require an explicit decision
        return False, "escapes lifecycle analysis"

    # -- gc-sweep half --------------------------------------------------------

    _CHECK_METHODS = frozenset({"endswith", "startswith"})

    def _check_tmp_patterns(self, ctx):
        findings = []
        for node in ctx.nodes:
            rendered = self._rendered_pattern(ctx, node)
            if rendered is None:
                continue
            if not registry.GC_SWEEP_RE.search(rendered):
                findings.append(
                    ctx.finding(
                        self.id,
                        node,
                        f"writes temp/lock pattern {rendered!r} the "
                        "gc/fsck crash-leftover sweep "
                        f"({registry.GC_SWEEP_RE.pattern}) will never "
                        "collect",
                    )
                )
        return findings

    def _rendered_pattern(self, ctx, node):
        """A ``.tmp``/``.lock`` filename suffix this node *builds* (vs
        merely tests), rendered with formatted values as ``0`` — or None."""
        # f".tmp{os.getpid()}" or ".lock" + ... used in string building
        if isinstance(node, ast.JoinedStr):
            parts = []
            for v in node.values:
                if isinstance(v, ast.Constant):
                    parts.append(str(v.value))
                else:
                    parts.append("0")
            rendered = "".join(parts)
        else:
            s = str_const(node)
            if s is None:
                return None
            rendered = s
        if ".tmp" not in rendered and ".lock" not in rendered:
            return None
        parent = ctx.parents.get(node)
        if isinstance(node, ast.JoinedStr):
            # whole-path f-strings (f"{path}.tmp{pid}") and fragments alike
            # — but not prose that merely mentions the suffixes
            if isinstance(parent, (ast.Compare, ast.Call)):
                return None
            if " " in rendered:
                return None
            return rendered.rsplit("/", 1)[-1]
        if not rendered.startswith("."):
            return None  # only suffix/prefix fragments are patterns
        # path + ".tmp..." under concatenation
        if isinstance(parent, ast.BinOp) and isinstance(parent.op, ast.Add):
            return rendered
        # mkstemp/NamedTemporaryFile(prefix=".tmp-...", dir=<in-repo>)
        if isinstance(parent, ast.keyword) and parent.arg in (
            "prefix",
            "suffix",
        ):
            call = ctx.parents.get(parent)
            if isinstance(call, ast.Call) and any(
                k.arg == "dir" for k in call.keywords
            ):
                return rendered
        return None

    def finalize(self, project):
        """The sweep regex this registry declares must be the one
        core/repo.py actually sweeps with."""
        from kart_tpu.analysis.core import Finding

        ctx = project.context_for("kart_tpu/core/repo.py")
        if ctx is None:
            return []
        for node in ctx.nodes:
            if (
                isinstance(node, ast.Assign)
                and any(
                    isinstance(t, ast.Name) and t.id == "_STALE_FILE_RE"
                    for t in node.targets
                )
                and isinstance(node.value, ast.Call)
                and node.value.args
            ):
                actual = str_const(node.value.args[0])
                if actual != registry.GC_SWEEP_RE.pattern:
                    return [
                        Finding(
                            self.id,
                            ctx.rel,
                            node.lineno,
                            node.col_offset,
                            "core/repo.py _STALE_FILE_RE "
                            f"({actual!r}) has drifted from "
                            "analysis/registry.py GC_SWEEP_RE "
                            f"({registry.GC_SWEEP_RE.pattern!r})",
                        )
                    ]
                return []
        return [
            Finding(
                self.id,
                ctx.rel,
                1,
                0,
                "core/repo.py no longer defines _STALE_FILE_RE — the "
                "crash-leftover sweep contract moved without updating "
                "analysis/registry.py",
            )
        ]


# -- KTL005 ------------------------------------------------------------------

def _own_scope_walk(fn):
    """Nodes of ``fn``'s own body, excluding nested function subtrees —
    a nested def's locals must not shadow (or stand in for) the outer
    scope's bindings."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            stack.extend(children(node))


# the thread-entry / mutation / lock-ish notions are shared with the
# KTL010-KTL012 interprocedural family — one definition each, in
# kart_tpu.analysis.interproc


@register
class ThreadForkSafety(Rule):
    id = "KTL005"
    name = "thread-fork-safety"
    description = (
        "code running on spawned threads / pool workers must not write "
        "module-level mutable state without holding a lock; os.fork / "
        "fork-context pools need a thread-awareness guard (forking a "
        "multithreaded process can inherit a held lock and deadlock)"
    )

    def visit_file(self, ctx):
        findings = []
        mutables = self._module_mutables(ctx.tree)
        entry_names = interproc.thread_entry_functions(
            interproc.file_summary(ctx)
        )
        defs = {}
        for node in ctx.nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, node)
        for name in sorted(entry_names):
            fn = defs.get(name)
            if fn is None:
                continue  # cross-module target: out of scope
            findings.extend(self._check_entry(ctx, fn, mutables))
        findings.extend(self._check_fork_sites(ctx))
        return findings

    def _module_mutables(self, tree):
        out = set()
        for stmt in tree.body:
            if not isinstance(stmt, ast.Assign):
                continue
            value_ok = isinstance(
                stmt.value, (ast.Dict, ast.List, ast.Set)
            ) or (
                isinstance(stmt.value, ast.Call)
                and (dotted_name(stmt.value.func) or "").rsplit(".", 1)[-1]
                in ("dict", "list", "set", "defaultdict", "deque", "Counter",
                    "OrderedDict")
            )
            if value_ok:
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
        return out

    def _locked(self, ctx, node):
        """Is ``node`` lexically under a ``with <something lock-ish>``?
        (The shared interproc notion: an identifier *named* like a lock —
        lock, _lock, probe_lock, a mutex/semaphore — not any word merely
        containing the letters, like ``blocker`` or ``clock``.)"""
        return interproc.under_lockish_with(ctx, node)

    def _check_entry(self, ctx, fn, mutables):
        findings = []
        declared_global = set()
        local_shadows = set()
        for node in _own_scope_walk(fn):
            if isinstance(node, ast.Global):
                declared_global.update(node.names)
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        local_shadows.add(t.id)
            elif isinstance(node, (ast.For, ast.comprehension)):
                t = node.target
                if isinstance(t, ast.Name):
                    local_shadows.add(t.id)
        # a bare-name assignment (without `global`) rebinds a local that
        # merely shadows the module name — mutations of it are thread-safe
        mutables = (mutables - local_shadows) | declared_global
        for node in _own_scope_walk(fn):
            written = None
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets
                    if isinstance(node, ast.Assign)
                    else [node.target]
                )
                for t in targets:
                    base = t
                    while isinstance(base, (ast.Subscript, ast.Attribute)):
                        base = base.value
                    if not isinstance(base, ast.Name):
                        continue
                    if isinstance(t, ast.Name):
                        # a bare-name assignment without `global` rebinds a
                        # LOCAL — only a declared global write is shared
                        if t.id in declared_global:
                            written = t.id
                    elif (
                        base.id in mutables or base.id in declared_global
                    ):
                        # cache[k] = v / cache.attr = v mutates the shared
                        # object itself
                        written = base.id
            elif (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in interproc.MUTATORS
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in mutables
            ):
                written = node.func.value.id
            if written and not self._locked(ctx, node):
                findings.append(
                    ctx.finding(
                        self.id,
                        node,
                        f"thread/worker entry point {fn.name!r} writes "
                        f"module-level mutable {written!r} without a lock",
                    )
                )
        return findings

    def _check_fork_sites(self, ctx):
        findings = []
        for node in ctx.nodes:
            if not isinstance(node, ast.Call):
                continue
            fn = dotted_name(node.func) or ""
            is_fork = fn in ("os.fork",) or (
                fn.endswith("get_context")
                and node.args
                and str_const(node.args[0]) == "fork"
            )
            if not is_fork:
                continue
            scope = enclosing(
                ctx, node, (ast.FunctionDef, ast.AsyncFunctionDef)
            )
            guard_nodes = subtree(scope) if scope is not None else ctx.nodes
            # a real reference to threading.active_count (not a string
            # merely mentioning it) counts as the guard
            if any(
                (isinstance(g, ast.Attribute) and g.attr == "active_count")
                or (isinstance(g, ast.Name) and g.id == "active_count")
                for g in guard_nodes
            ):
                continue
            findings.append(
                ctx.finding(
                    self.id,
                    node,
                    "fork in a process that may already run threads "
                    "(prefetch, probe): a forked child can inherit a held "
                    "lock mid-flight — guard with threading.active_count() "
                    "or bound-and-fallback, and say so in a suppression",
                )
            )
        return findings


# -- KTL006 ------------------------------------------------------------------


def _catches(handler, *names):
    t = handler.type
    if t is None:
        return "bare" in names
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    for e in elts:
        base = (dotted_name(e) or "").rsplit(".", 1)[-1]
        if base in names:
            return True
    return False


def _body_is_silent(handler):
    """Only pass/.../docstring statements — the swallow-and-continue shape."""
    for stmt in handler.body:
        if isinstance(stmt, ast.Pass):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant):
            continue
        return False
    return True


def _body_reraises(handler):
    # only raises in the handler's own suite count — a nested def that
    # happens to raise when *later called* does not re-raise here
    return any(isinstance(n, ast.Raise) for n in _own_scope_walk(handler))


@register
class ExceptionHygiene(Rule):
    id = "KTL006"
    name = "exception-hygiene"
    description = (
        "no bare `except:`; KeyboardInterrupt/SystemExit are re-raised, "
        "never swallowed; `except Exception: pass` must narrow the type, "
        "count/log the swallow, or carry a suppression rationale"
    )

    def visit_file(self, ctx):
        findings = []
        for node in ctx.nodes:
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None and not _body_reraises(node):
                findings.append(
                    ctx.finding(
                        self.id,
                        node,
                        "bare `except:` swallows KeyboardInterrupt and "
                        "SystemExit — catch Exception (or narrower), or "
                        "re-raise",
                    )
                )
                continue
            if (
                _catches(node, "BaseException")
                or (
                    _catches(node, "KeyboardInterrupt", "SystemExit")
                    and _body_is_silent(node)
                )
            ) and not _body_reraises(node):
                # an explicit `except KeyboardInterrupt:` with a real body
                # (a serve loop printing "Stopped.") is a deliberate exit
                # path; catching BaseException, or silently eating ^C, is
                # the hazard
                findings.append(
                    ctx.finding(
                        self.id,
                        node,
                        "handler swallows KeyboardInterrupt/SystemExit "
                        "without re-raising: ^C / shutdown would be eaten "
                        "here",
                    )
                )
                continue
            if (
                _catches(node, "Exception", "BaseException", "bare")
                and _body_is_silent(node)
            ):
                findings.append(
                    ctx.finding(
                        self.id,
                        node,
                        "silently swallows every Exception — narrow the "
                        "type, or count/log the swallow so production "
                        "failures are visible",
                    )
                )
        return findings


# -- KTL007 ------------------------------------------------------------------

_BENCH_KEY_RE = re.compile(r"^[a-z][a-z0-9_]+$")


@register
class BenchKeySchemaDrift(Rule):
    id = "KTL007"
    name = "bench-key-schema-drift"
    description = (
        "every result key bench.py emits is pinned by the "
        "tests/test_bench_schema.py guard (its NEW_KEYS list or the "
        "latest BENCH_r*.json record) — headline metrics cannot silently "
        "appear without a schema guard, or drop out of it"
    )

    def __init__(self):
        self._pinned = None  # lazy: guard literals + latest record keys

    def visit_file(self, ctx):
        """Runs per file (so single-file `kart lint bench.py` and the
        golden corpus exercise it) against the repo's schema guard."""
        if os.path.basename(ctx.rel) != "bench.py":
            return []
        findings = []
        pinned = self._pinned_keys()
        seen = set()
        for node in self._record_dicts(ctx.tree):
            for k in node.keys:
                key = str_const(k)
                if (
                    key
                    and _BENCH_KEY_RE.match(key)
                    and key not in pinned
                    and key not in seen
                ):
                    seen.add(key)
                    findings.append(
                        ctx.finding(
                            self.id,
                            k,
                            f"bench result key {key!r} is not pinned by "
                            f"{registry.BENCH_SCHEMA_TEST} (NEW_KEYS) nor "
                            "present in the latest BENCH record — add it "
                            "to the schema guard",
                        )
                    )
        return findings

    def _pinned_keys(self):
        from kart_tpu.analysis.core import repo_root

        if self._pinned is not None:
            return self._pinned
        root = repo_root()
        pinned = set()
        try:
            with open(os.path.join(root, registry.BENCH_SCHEMA_TEST)) as f:
                guard_tree = ast.parse(f.read())
            # only literals in the guard's NEW_KEYS list assignments pin a
            # key — an incidentally quoted word elsewhere in the test file
            # must not count as schema coverage
            for node in subtree(guard_tree):
                target = None
                if isinstance(node, ast.Assign) and node.targets:
                    target = node.targets[0]
                elif isinstance(node, ast.AugAssign):
                    target = node.target
                if (
                    isinstance(target, ast.Name)
                    and target.id == "NEW_KEYS"
                    and isinstance(node.value, (ast.List, ast.Tuple))
                ):
                    for elt in node.value.elts:
                        key = str_const(elt)
                        if key:
                            pinned.add(key)
        except (OSError, SyntaxError, ValueError):
            pass  # missing/unparseable guard: keys report as unpinned
        records = sorted(
            glob.glob(os.path.join(root, registry.BENCH_RECORD_GLOB))
        )
        if records:
            try:
                with open(records[-1]) as f:
                    pinned |= set(json.load(f).get("parsed", {}))
            except (OSError, ValueError):
                pass  # unparseable record: fall back to the guard alone
        self._pinned = pinned
        return pinned

    def _record_dicts(self, tree):
        """Dict literals that flow into the emitted bench record: returned
        dicts, dicts bound to a returned name, and ``record = {...}``.
        (Dicts built for other purposes — synthetic feature JSON, config
        blocks — never reach a Return or the record assignment.)"""
        for fn in subtree(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            returned_names = set()
            for node in subtree(fn):
                if isinstance(node, ast.Return) and isinstance(
                    node.value, ast.Name
                ):
                    returned_names.add(node.value.id)
            for node in subtree(fn):
                if isinstance(node, ast.Return) and isinstance(
                    node.value, ast.Dict
                ):
                    yield node.value
                elif isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Dict
                ):
                    for t in node.targets:
                        if isinstance(t, ast.Name) and (
                            t.id == "record" or t.id in returned_names
                        ):
                            yield node.value

