"""Concurrency-soundness rules (KTL010-KTL014) — each grounded in a bug
this repo actually shipped (docs/ANALYSIS.md):

* KTL010 lock-order inversion: the interprocedural lock graph must stay
  acyclic (a cycle is a latent deadlock between server threads).
* KTL011 blocking-call-under-lock: subprocesses, sockets, fdatasync,
  ``device_put``, sleeps and ODB batch reads must not run while a lock is
  held (registry.BLOCKING_ALLOW carries the deliberate serialisation
  sections, with rationale).
* KTL012 atomic publication: the PR 9 ``PackCollection.packs`` race —
  incrementally filling a shared attribute that concurrent readers can
  see. Build local, assign once.
* KTL013 single-flight fill-token lifecycle: the PR 7 wedge — a token
  from ``lookup_or_begin`` must be abandoned on **every** exception path,
  or every later request for that key blocks on an event nobody sets.
* KTL014 cache-invalidation coverage: every byte-budgeted cache joins
  registry.CACHES, keys pin a commit/ref fingerprint, and the declared
  drop hook runs in ``_apply_validated_updates`` (or carries a written
  rationale for why none is needed).
"""

import ast

from kart_tpu.analysis import interproc, registry
from kart_tpu.analysis.core import (
    MIN_RATIONALE,
    Finding,
    Rule,
    dotted_name,
    register,
    str_const,
    subtree,
    unparse,
)

# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

_BROAD_CATCHES = frozenset({"Exception", "BaseException"})

#: receivers that look like a Condition (its .wait releases the lock)
_CONDISH = ("cond", "condition")

#: receiver shapes whose .join() blocks on another thread (NOT str.join:
#: `os.path.join`, `", ".join` — matched by whole name / suffix, never by
#: bare substring)
_THREADISH_EXACT = frozenset({"t", "thread", "proc", "worker", "flusher"})
_THREADISH_SUBSTR = ("thread", "flusher", "worker")


def _blocking_reason(call):
    """Classify a direct blocking primitive, or None. The KTL011 list from
    the issue: subprocess, socket/HTTP, fdatasync, device_put, sleep, ODB
    batch reads — plus thread joins and Event waits (same hazard: the lock
    holder parks on something unbounded)."""
    fn = dotted_name(call.func) or ""
    leaf = fn.rsplit(".", 1)[-1]
    if fn in ("time.sleep", "sleep"):
        return "time.sleep()"
    if leaf in ("fdatasync", "fsync"):
        return f"os.{leaf}()"
    if fn.startswith("subprocess.") or leaf == "Popen":
        return f"subprocess ({leaf})"
    if leaf in ("urlopen", "create_connection"):
        return f"network I/O ({leaf})"
    if fn in ("jax.device_put", "device_put"):
        return "jax.device_put() (host->device transfer)"
    if isinstance(call.func, ast.Attribute):
        attr = call.func.attr
        recv = unparse(call.func.value).lower()
        if attr in ("connect", "recv", "sendall", "accept", "makefile"):
            return f"socket/connection I/O (.{attr}())"
        if attr in (
            "read_blobs_batch",
            "read_blobs_data_ordered",
            "read_blobs_data_into",
            "read_batch",
        ):
            return f"ODB batch read (.{attr}())"
        if attr == "wait" and not any(c in recv for c in _CONDISH):
            # Condition.wait releases the lock it guards; Event.wait parks
            return "Event.wait()"
        if attr == "join":
            bare = recv.rsplit(".", 1)[-1].lstrip("_")
            if bare in _THREADISH_EXACT or any(
                s in bare for s in _THREADISH_SUBSTR
            ):
                return "thread join"
    return None


def _uses_locks(ctx):
    """Cheap pre-filter: does this file define or enter any lock?  Files
    that don't cannot produce KTL010/KTL011 findings in per-file mode, and
    skipping them keeps the whole-tree run inside the 5s bound."""
    summary = interproc.file_summary(ctx)
    if summary.module_locks or summary.attr_locks:
        return True
    for node in ctx.nodes:
        if isinstance(node, ast.With):
            if any(
                interproc.lockish_expr(item.context_expr)
                for item in node.items
            ):
                return True
    return False


_MAX_CHAIN = 40


def _recv_is_self(call):
    return (
        isinstance(call.func, ast.Attribute)
        and isinstance(call.func.value, ast.Name)
        and call.func.value.id == "self"
    )


def _block_facts(model, f, memo, stack):
    """(reason, via) when ``f`` transitively reaches a blocking primitive,
    else None. Demand-driven: only functions actually called from a
    held-lock region are ever visited."""
    if f.qual in memo:
        return memo[f.qual]
    if f.qual in stack or len(stack) > _MAX_CHAIN:
        return None  # cycle / runaway chain: partial answer is sound here
    stack.add(f.qual)
    try:
        summ = interproc.lock_summary(model, f, _blocking_reason)
        fact = None
        if summ.blocking:
            fact = (summ.blocking[0][0], f.qual)
        else:
            s = model.by_rel[f.rel]
            for call, _held in summ.calls:
                for cand in model.resolve_call(s, call, f.cls):
                    hit = _block_facts(model, cand, memo, stack)
                    if hit is not None:
                        fact = (hit[0], cand.qual)
                        break
                if fact is not None:
                    break
        memo[f.qual] = fact
        return fact
    finally:
        stack.discard(f.qual)


def _acq_facts(model, f, memo, stack):
    """{(lock_id, via_self)} ``f`` may (transitively) acquire."""
    if f.qual in memo:
        return memo[f.qual]
    if f.qual in stack or len(stack) > _MAX_CHAIN:
        return frozenset()  # cycle: the partial set is sound
    stack.add(f.qual)
    try:
        summ = interproc.lock_summary(model, f, _blocking_reason)
        facts = {
            (lid, self_recv)
            for lid, _node, _held, self_recv in summ.acquires
        }
        s = model.by_rel[f.rel]
        for call, _held in summ.calls:
            on_self = _recv_is_self(call)
            for cand in model.resolve_call(s, call, f.cls):
                for lid, via_self in _acq_facts(model, cand, memo, stack):
                    # a self-received lock stays "same instance" only
                    # while the call chain stays on self
                    facts.add((lid, via_self and on_self))
        facts = frozenset(facts)
        memo[f.qual] = facts
        return facts
    finally:
        stack.discard(f.qual)


def _locky_functions(model):
    """Functions living in files that use locks at all — the only possible
    holders of a lock, so the only roots the rules must scan."""
    for s in model.summaries:
        if _uses_locks(s.ctx):
            for f in s.functions:
                yield s, f


# ---------------------------------------------------------------------------
# KTL010 — lock-order inversion
# ---------------------------------------------------------------------------


@register
class LockOrderInversion(Rule):
    id = "KTL010"
    name = "lock-order-inversion"
    description = (
        "the project-wide lock acquisition graph (module and instance "
        "locks, interprocedural via the call graph) must be free of "
        "cycles — an A->B / B->A inversion between server threads is a "
        "latent deadlock; re-acquiring a non-reentrant lock on the same "
        "object is an instant one"
    )

    def __init__(self):
        self._reported = set()  # canonical cycle keys already reported

    def visit_file(self, ctx):
        if not _uses_locks(ctx):
            return []
        return self._check(interproc.file_model(ctx), intra_file=ctx.rel)

    def finalize(self, project):
        model = interproc.project_model(project)
        return self._check(model, intra_file=None)

    def _edges(self, model):
        """(L1, L2) -> (rel, line, description) witness edges."""
        memo, stack = {}, set()
        edges = {}

        def add(a, b, rel, line, desc, same_object):
            if a == b and not same_object:
                return  # two instances of one class: not a self-deadlock
            edges.setdefault((a, b), (rel, line, desc))

        for s, f in _locky_functions(model):
            summ = interproc.lock_summary(model, f, _blocking_reason)
            for lid, node, held, self_recv in summ.acquires:
                for h in held:
                    add(
                        h,
                        lid,
                        f.rel,
                        node.lineno,
                        f"{f.qual} acquires {lid} while holding {h}",
                        self._same_object(h, lid, True, self_recv),
                    )
            for call, held in summ.calls:
                if not held:
                    continue
                on_self = _recv_is_self(call)
                for cand in model.resolve_call(s, call, f.cls):
                    for lid, via_self in _acq_facts(
                        model, cand, memo, stack
                    ):
                        for h in held:
                            add(
                                h,
                                lid,
                                f.rel,
                                call.lineno,
                                f"{f.qual} holds {h} and calls "
                                f"{cand.qual} which acquires {lid}",
                                self._same_object(
                                    h, lid, True, via_self and on_self
                                ),
                            )
        return edges

    @staticmethod
    def _same_object(held_id, acq_id, held_self, acq_self):
        """Is a held==acquired pair provably the same lock object?  Module
        locks are singletons; instance-attribute locks only when both the
        hold and the (possibly transitive) re-acquire ride ``self``."""
        if held_id != acq_id:
            return True  # distinct ids: ordering edge, always meaningful
        if "." not in held_id.split("::")[-1]:
            return True  # module-level lock: one object
        return bool(held_self and acq_self)

    def _check(self, model, intra_file):
        findings = []
        edges = self._edges(model)
        graph = {}
        for (a, b), _w in edges.items():
            graph.setdefault(a, set()).add(b)

        # self-loops: immediate deadlock on a non-reentrant lock (an
        # RLock re-acquire is the one thing RLock exists for — skip)
        for (a, b), (rel, line, desc) in sorted(edges.items()):
            if a != b:
                continue
            if model.lock_kinds.get(a) == "rlock":
                continue
            if intra_file is not None and rel != intra_file:
                continue
            # key on location, not lock id: the per-file and full-tree
            # models may canonicalise an inherited lock differently, and
            # one defect must not report twice
            key = ("self", rel, line)
            if key in self._reported:
                continue
            self._reported.add(key)
            findings.append(
                Finding(
                    self.id, rel, line, 0,
                    f"re-entrant acquisition of non-reentrant lock: {desc}",
                )
            )

        # cycles among distinct locks
        for cycle in self._cycles(graph):
            witness = [
                edges[(cycle[i], cycle[(i + 1) % len(cycle)])]
                for i in range(len(cycle))
            ]
            # key on the witness locations (model-independent), not the
            # lock ids (model-dependent for inherited attribute locks)
            key = ("cycle", frozenset((w[0], w[1]) for w in witness))
            if key in self._reported:
                continue
            rels = {w[0] for w in witness}
            if intra_file is not None and rels != {intra_file}:
                continue  # cross-file cycles report on the full run only
            self._reported.add(key)
            rel, line, _ = witness[0]
            chain = "; ".join(w[2] for w in witness)
            findings.append(
                Finding(
                    self.id, rel, line, 0,
                    "lock-order inversion (deadlock cycle): " + chain,
                )
            )
        return findings

    @staticmethod
    def _cycles(graph):
        """Elementary cycles (as rotated-canonical node tuples), via DFS
        from each node — the lock graph is tiny, no need for Johnson's."""
        out = []
        seen = set()
        for start in sorted(graph):
            stack = [(start, [start])]
            while stack:
                node, path = stack.pop()
                for nxt in sorted(graph.get(node, ())):
                    if nxt == start and len(path) > 1:
                        i = path.index(min(path))
                        canon = tuple(path[i:] + path[:i])
                        if canon not in seen:
                            seen.add(canon)
                            out.append(path)
                    elif nxt not in path and len(path) < 6:
                        stack.append((nxt, path + [nxt]))
        return out


# ---------------------------------------------------------------------------
# KTL011 — blocking call under a held lock
# ---------------------------------------------------------------------------


@register
class BlockingUnderLock(Rule):
    id = "KTL011"
    name = "blocking-under-lock"
    description = (
        "no subprocess / socket / fdatasync / jax.device_put / sleep / "
        "Event.wait / ODB-batch-read (or a call that transitively reaches "
        "one, or a generator yield) while holding a lock — deliberate "
        "serialisation sections live in registry.BLOCKING_ALLOW with a "
        "rationale"
    )

    def __init__(self):
        self._reported = set()  # (rel, line) de-dup between the two passes

    def visit_file(self, ctx):
        if not _uses_locks(ctx):
            return []
        return self._check(interproc.file_model(ctx))

    def finalize(self, project):
        model = interproc.project_model(project)
        findings = self._check(model)
        # allowlist round-trip: a stale entry is a finding (the deliberate
        # section moved/was renarrowed without updating the declaration)
        for qual in sorted(registry.BLOCKING_ALLOW):
            if qual not in model.functions:
                findings.append(
                    Finding(
                        self.id,
                        "kart_tpu/analysis/registry.py",
                        1,
                        0,
                        f"BLOCKING_ALLOW entry {qual!r} names no existing "
                        "function — stale allowlist entry",
                    )
                )
        return findings

    def _check(self, model):
        findings = []
        memo, stack = {}, set()
        for s, f in _locky_functions(model):
            if f.qual in registry.BLOCKING_ALLOW:
                continue
            summ = interproc.lock_summary(model, f, _blocking_reason)
            for reason, node, held in summ.blocking:
                if held:
                    findings.extend(
                        self._finding(
                            f, node, held,
                            f"{reason} while holding {sorted(held)[0]}",
                        )
                    )
            for node, held in summ.yields:
                if held:
                    findings.extend(
                        self._finding(
                            f, node, held,
                            f"generator yields while holding "
                            f"{sorted(held)[0]} — arbitrary caller "
                            "code runs under the lock",
                        )
                    )
            for call, held in summ.calls:
                if not held:
                    continue
                for cand in model.resolve_call(s, call, f.cls):
                    if cand.qual in registry.BLOCKING_ALLOW:
                        continue
                    hit = _block_facts(model, cand, memo, stack)
                    if hit is None:
                        continue
                    reason, via = hit
                    findings.extend(
                        self._finding(
                            f, call, held,
                            f"calls {cand.qual} while holding "
                            f"{sorted(held)[0]}, which reaches "
                            f"{reason} (via {via})",
                        )
                    )
                    break
        return findings

    def _finding(self, f, node, held, message):
        key = (f.rel, node.lineno)
        if key in self._reported:
            return []
        self._reported.add(key)
        return [
            Finding(
                self.id, f.rel, node.lineno, getattr(node, "col_offset", 0),
                message + " — move the blocking work outside the lock, or "
                "add a registry.BLOCKING_ALLOW entry with a rationale",
            )
        ]


# ---------------------------------------------------------------------------
# KTL012 — atomic publication of shared state
# ---------------------------------------------------------------------------


_FRESH_CONTAINERS = frozenset(
    {"dict", "list", "set", "OrderedDict", "defaultdict", "deque", "Counter"}
)


def _is_fresh_container(value):
    if isinstance(value, (ast.Dict, ast.List, ast.Set)):
        return not getattr(value, "keys", None) and not getattr(
            value, "elts", None
        )
    if isinstance(value, ast.Call) and not value.args and not value.keywords:
        return (dotted_name(value.func) or "").rsplit(".", 1)[
            -1
        ] in _FRESH_CONTAINERS
    return False


@register
class AtomicPublication(Rule):
    id = "KTL012"
    name = "atomic-publication"
    description = (
        "a shared instance attribute visible to other threads must not be "
        "initialised empty and then filled in place (concurrent readers "
        "see the half-built value — the shipped PR 9 PackCollection.packs "
        "race): build a local, assign once"
    )

    def visit_file(self, ctx):
        summary = interproc.file_summary(ctx)
        # sharedness gate: a module that never touches threading has no
        # concurrent readers to publish to; threading-importing files are
        # exactly where the shipped PR 9 bug lived (docs/ANALYSIS.md
        # records this as the rule's precision limit)
        if not any(v[1] == "threading" for v in summary.imports.values()):
            return []
        findings = []
        for f in summary.functions:
            if f.name in ("__init__", "__new__"):
                continue  # the object is not yet published during init
            findings.extend(self._check_function(ctx, f))
        return findings

    def _check_function(self, ctx, f):
        from kart_tpu.analysis.rules import _own_scope_walk

        findings = []
        # own-scope walks: a nested def is its own FunctionInfo and gets
        # its own check — descending into it here would double-report and
        # cross-match inits/mutations between sibling scopes
        # pass 1: self.X = <fresh empty container>, unlocked
        inits = {}  # attr -> assign node
        for node in _own_scope_walk(f.node):
            if not isinstance(node, ast.Assign):
                continue
            for t in node.targets:
                if (
                    isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                    and _is_fresh_container(node.value)
                    and not interproc.under_lockish_with(ctx, node)
                ):
                    inits.setdefault(t.attr, node)
        if not inits:
            return findings
        # pass 2: later in-place mutation of the same self.X, unlocked
        flagged = set()
        for node in _own_scope_walk(f.node):
            attr = self._mutated_self_attr(node)
            if attr is None or attr not in inits or attr in flagged:
                continue
            init = inits[attr]
            if node.lineno <= init.lineno:
                continue
            if interproc.under_lockish_with(ctx, node):
                continue
            flagged.add(attr)
            findings.append(
                Finding(
                    self.id,
                    ctx.rel,
                    init.lineno,
                    init.col_offset,
                    f"incremental publication of shared attribute "
                    f"{attr!r}: assigned empty here, then mutated in "
                    f"place at line {node.lineno} — concurrent readers "
                    "see a partially-built value; build a local and "
                    "assign it once at the end",
                )
            )
        return findings

    @staticmethod
    def _mutated_self_attr(node):
        """'X' when node mutates ``self.X`` in place, else None."""
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in interproc.MUTATORS
            and isinstance(node.func.value, ast.Attribute)
            and isinstance(node.func.value.value, ast.Name)
            and node.func.value.value.id == "self"
        ):
            return node.func.value.attr
        target = None
        if isinstance(node, ast.Assign) and node.targets:
            target = node.targets[0]
        elif isinstance(node, ast.AugAssign):
            target = node.target
        if (
            isinstance(target, ast.Subscript)
            and isinstance(target.value, ast.Attribute)
            and isinstance(target.value.value, ast.Name)
            and target.value.value.id == "self"
        ):
            return target.value.attr
        return None


# ---------------------------------------------------------------------------
# KTL013 — single-flight fill-token lifecycle
# ---------------------------------------------------------------------------

_SAFE_CALLS = frozenset({"isinstance", "len", "getattr", "hasattr"})

#: the single-flight machinery subclasses must not re-implement — the
#: abandon-on-exception and poison-barrier guarantees are asserted ONCE on
#: the base (finalize); an override silently forks the contract
_SF_MACHINERY = ("lookup_or_begin", "_publish", "_abandon")

_SF_FILE = "kart_tpu/core/singleflight.py"


@register
class FillTokenLifecycle(Rule):
    id = "KTL013"
    name = "fill-token-lifecycle"
    description = (
        "every fill token from lookup_or_begin() must be published, "
        "abandoned, or ownership-transferred on EVERY path — including "
        "exception edges (the shipped PR 7 wedge: a pre-walk failure left "
        "the token live and every later request blocked on it); the "
        "SingleFlightLRU machinery itself must not be overridden"
    )

    def visit_file(self, ctx):
        findings = []
        summary = interproc.file_summary(ctx)
        for f in summary.functions:
            for node in subtree(f.node):
                if (
                    isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)
                    and isinstance(node.value.func, ast.Attribute)
                    and node.value.func.attr == "lookup_or_begin"
                ):
                    findings.extend(self._check_acquire(ctx, f, node))
        return findings

    # -- the exception-edge traversal ---------------------------------------

    def _check_acquire(self, ctx, f, acquire):
        target = acquire.targets[0]
        if not (
            isinstance(target, ast.Tuple)
            and len(target.elts) == 2
            and all(isinstance(e, ast.Name) for e in target.elts)
        ):
            return [
                ctx.finding(
                    self.id,
                    acquire,
                    "lookup_or_begin() result must unpack as "
                    "`mode, token = ...` so the token's lifecycle is "
                    "trackable",
                )
            ]
        mode_var = target.elts[0].id
        aliases = {target.elts[1].id}
        findings = []
        state = {"alive": True}

        def consumed(stmt):
            """publish/abandon/escape anywhere in this statement?  Also
            grows the alias set for `token = got` renames."""
            if isinstance(stmt, ast.Assign) and isinstance(
                stmt.value, ast.Name
            ) and stmt.value.id in aliases:
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        aliases.add(t.id)
                        return False
                    if isinstance(t, ast.Attribute):
                        return True  # stored on an owner object
            for node in subtree(stmt):
                if isinstance(node, ast.Call):
                    fn = node.func
                    if (
                        isinstance(fn, ast.Attribute)
                        and isinstance(fn.value, ast.Name)
                        and fn.value.id in aliases
                        and fn.attr in ("publish", "abandon")
                    ):
                        return True
                    for arg in list(node.args) + [
                        kw.value for kw in node.keywords
                    ]:
                        if isinstance(arg, ast.Name) and arg.id in aliases:
                            return True  # ownership transfer by argument
            return False

        def risky(stmt):
            for node in subtree(stmt):
                if isinstance(node, ast.Raise):
                    return True
                if isinstance(node, ast.Call):
                    fn = node.func
                    if (
                        isinstance(fn, ast.Attribute)
                        and isinstance(fn.value, ast.Name)
                        and fn.value.id in aliases
                    ):
                        continue  # calls on the token itself
                    if (dotted_name(fn) or "") in _SAFE_CALLS:
                        continue
                    return True
            return False

        def abandons(stmts):
            for s in stmts:
                for node in subtree(s):
                    if (
                        isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "abandon"
                        and isinstance(node.func.value, ast.Name)
                        and node.func.value.id in aliases
                    ):
                        return True
            return False

        def try_protects(stmt):
            for h in stmt.handlers:
                if h.type is None or any(
                    (dotted_name(t) or "").rsplit(".", 1)[-1]
                    in _BROAD_CATCHES
                    for t in (
                        h.type.elts
                        if isinstance(h.type, ast.Tuple)
                        else [h.type]
                    )
                ):
                    if abandons(h.body):
                        return True
            return abandons(stmt.finalbody)

        def ancestor_protects(stmt):
            """Is ``stmt`` inside the body of any enclosing (within the
            function) Try whose handler/finally abandons?  Covers both
            tries entered during the scan AND a try already enclosing the
            acquire itself (`try: mode, got = …; build() / except
            BaseException: got.abandon(); raise` is a correct idiom)."""
            child, cur = stmt, ctx.parents.get(stmt)
            while cur is not None and cur is not f.node:
                if isinstance(cur, ast.Try) and child in cur.body:
                    if try_protects(cur):
                        return True
                child, cur = cur, ctx.parents.get(cur)
            return False

        def branch_token_dead(test):
            """True for the `mode == "hit"` guard (entry, not a token)."""
            if (
                isinstance(test, ast.Compare)
                and len(test.ops) == 1
                and isinstance(test.left, ast.Name)
                and test.left.id == mode_var
            ):
                lit = str_const(test.comparators[0])
                if isinstance(test.ops[0], ast.Eq) and lit == "hit":
                    return "body"
                if isinstance(test.ops[0], ast.NotEq) and lit == "hit":
                    return "orelse"
                if isinstance(test.ops[0], ast.Eq) and lit == "fill":
                    return "orelse"
            return None

        def flag(stmt):
            findings.append(
                ctx.finding(
                    self.id,
                    stmt,
                    f"fill token {sorted(aliases)[0]!r} (acquired "
                    f"line {acquire.lineno}) is live across this "
                    "statement with no abandon() on its exception "
                    "edge — a failure here wedges every waiter "
                    "for the key; wrap in try/except BaseException "
                    "that abandons, or transfer ownership first",
                )
            )
            state["alive"] = False  # one finding per acquire

        def scan(stmts, protected):
            for stmt in stmts:
                if not state["alive"]:
                    return
                if isinstance(stmt, ast.If):
                    dead = branch_token_dead(stmt.test)
                    if dead != "body":
                        scan(stmt.body, protected)
                    if state["alive"] and dead != "orelse":
                        scan(stmt.orelse, protected)
                    continue
                if isinstance(stmt, ast.Try):
                    scan(stmt.body, protected or try_protects(stmt))
                    # handler bodies run on paths where the try already
                    # failed; their abandon is what try_protects checks
                    if state["alive"]:
                        scan(stmt.orelse, protected)
                        scan(stmt.finalbody, protected)
                    continue
                if isinstance(stmt, (ast.With, ast.For, ast.While)):
                    # descend: a publish deep in the block must not hide
                    # risky statements executed before it (the token is
                    # still live while they run)
                    items = getattr(stmt, "items", None)
                    if items and any(
                        consumed(ast.Expr(value=i.context_expr))
                        for i in items
                    ):
                        state["alive"] = False
                        return
                    scan(stmt.body, protected)
                    if state["alive"]:
                        scan(getattr(stmt, "orelse", []) or [], protected)
                    continue
                if consumed(stmt):
                    state["alive"] = False
                    return
                if risky(stmt) and not protected and not ancestor_protects(
                    stmt
                ):
                    flag(stmt)
                    return

        scan(self._statements_after(ctx, f.node, acquire), False)
        return findings

    @staticmethod
    def _statements_after(ctx, fn_node, acquire):
        """Execution-ordered statements following ``acquire``: the suffix
        of every enclosing block, innermost first."""
        parents = ctx.parents
        out = []
        node = acquire
        while node is not fn_node:
            parent = parents.get(node)
            if parent is None:
                break
            for field in ("body", "orelse", "finalbody"):
                block = getattr(parent, field, None)
                if isinstance(block, list) and node in block:
                    out.extend(block[block.index(node) + 1 :])
            node = parent
        return out

    # -- the subclass contract, asserted once -------------------------------

    def finalize(self, project):
        findings = []
        model = interproc.project_model(project)
        base_file = model.by_rel.get(_SF_FILE)
        if base_file is None or "SingleFlightLRU" not in base_file.classes:
            return [
                Finding(
                    self.id,
                    _SF_FILE,
                    1,
                    0,
                    "SingleFlightLRU (the single-flight contract holder) "
                    "is missing — the fill-token machinery moved without "
                    "updating the analyzer",
                )
            ]
        base = base_file.classes["SingleFlightLRU"]
        publish = base.methods.get("_publish")
        ok = False
        if publish is not None:
            for node in subtree(publish.node):
                if isinstance(node, ast.Try):
                    for h in node.handlers:
                        if any(
                            isinstance(c, ast.Call)
                            and isinstance(c.func, ast.Attribute)
                            and c.func.attr == "_abandon"
                            for b in h.body
                            for c in subtree(b)
                        ):
                            ok = True
        if not ok:
            findings.append(
                Finding(
                    self.id,
                    _SF_FILE,
                    publish.node.lineno if publish else base.node.lineno,
                    0,
                    "SingleFlightLRU._publish no longer abandons the token "
                    "on an exception edge — the poison barrier is gone",
                )
            )
        for sub in model.subclasses("SingleFlightLRU"):
            for m in _SF_MACHINERY:
                if m in sub.methods:
                    findings.append(
                        Finding(
                            self.id,
                            sub.rel,
                            sub.methods[m].node.lineno,
                            0,
                            f"{sub.name} overrides SingleFlightLRU.{m} — "
                            "the single-flight machinery must stay in the "
                            "base class, where its abandon-on-exception "
                            "contract is asserted once",
                        )
                    )
        return findings


# ---------------------------------------------------------------------------
# KTL014 — commit/ref-addressed cache coverage
# ---------------------------------------------------------------------------


@register
class CacheInvalidationCoverage(Rule):
    id = "KTL014"
    name = "cache-invalidation-coverage"
    description = (
        "every byte-budgeted cache (SingleFlightLRU subclass or LRU-shaped "
        "module OrderedDict) is declared in registry.CACHES with a "
        "commit/ref-pinning key builder and a ref-update drop hook called "
        "from _apply_validated_updates (or a written rationale) — checked "
        "in both directions, like KTL001/KTL003"
    )

    def visit_file(self, ctx):
        findings = []
        summary = interproc.file_summary(ctx)
        declared_classes = {
            e["cls"] for e in registry.CACHES.values() if e.get("cls")
        }
        declared_globals = {
            e["registry_global"]
            for e in registry.CACHES.values()
            if e.get("registry_global")
        }
        exempt_names = {
            q.split("::", 1)[1] for q in registry.CACHE_EXEMPT_GLOBALS
        }
        for cls in summary.classes.values():
            if "SingleFlightLRU" not in cls.bases:
                continue
            if cls.name not in declared_classes:
                findings.append(
                    ctx.finding(
                        self.id,
                        cls.node,
                        f"byte-budgeted cache {cls.name} (SingleFlightLRU "
                        "subclass) is not declared in registry.CACHES — "
                        "declare its key builder and ref-update drop hook",
                    )
                )
        for name, node in self._lru_globals(ctx):
            if name in declared_globals or name in exempt_names:
                continue
            findings.append(
                ctx.finding(
                    self.id,
                    node,
                    f"LRU-shaped module global {name!r} (OrderedDict with "
                    "popitem eviction) is neither declared in "
                    "registry.CACHES nor exempted in CACHE_EXEMPT_GLOBALS",
                )
            )
        return findings

    @staticmethod
    def _lru_globals(ctx):
        """Module-level OrderedDict()s this file evicts from."""
        candidates = {}
        for stmt in ctx.tree.body:
            if isinstance(stmt, ast.Assign) and isinstance(
                stmt.value, ast.Call
            ):
                if (dotted_name(stmt.value.func) or "").rsplit(".", 1)[
                    -1
                ] == "OrderedDict":
                    for t in stmt.targets:
                        if isinstance(t, ast.Name):
                            candidates[t.id] = stmt
        if not candidates:
            return []
        evicted = set()
        for node in ctx.nodes:
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "popitem"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in candidates
            ):
                evicted.add(node.func.value.id)
        return sorted(
            (name, candidates[name]) for name in evicted
        )

    def finalize(self, project):
        findings = []
        model = interproc.project_model(project)
        reg_rel = "kart_tpu/analysis/registry.py"

        hook_rel, hook_name = registry.REF_UPDATE_HOOK
        hook_fn = model.functions.get(f"{hook_rel}::{hook_name}")
        if hook_fn is None:
            findings.append(
                Finding(
                    self.id, hook_rel, 1, 0,
                    f"ref-update hook {hook_name!r} is missing from "
                    f"{hook_rel} — no cache drop can run on a ref update; "
                    "update registry.REF_UPDATE_HOOK if it moved",
                )
            )
        if hook_fn is not None:
            # the live-update emission hook rides the same funnel as the
            # cache drops (registry.EVENT_EMIT_HOOK): a ref update that
            # skipped booking would strand subscribers on poll fallback
            emit_hook = getattr(registry, "EVENT_EMIT_HOOK", None)
            if emit_hook:
                called = any(
                    isinstance(n, ast.Call)
                    and (dotted_name(n.func) or "").rsplit(".", 1)[-1]
                    == emit_hook
                    for n in subtree(hook_fn.node)
                )
                if not called:
                    findings.append(
                        Finding(
                            self.id,
                            hook_fn.rel,
                            hook_fn.node.lineno,
                            0,
                            f"event emission hook {emit_hook!r} is never "
                            f"called from {registry.REF_UPDATE_HOOK[1]} — "
                            "a landed push would announce nothing "
                            "(docs/EVENTS.md §3)",
                        )
                    )
        for cache_name, entry in sorted(registry.CACHES.items()):
            findings.extend(
                self._check_entry(model, reg_rel, cache_name, entry, hook_fn)
            )
        for qual, rationale in sorted(registry.CACHE_EXEMPT_GLOBALS.items()):
            rel, name = qual.split("::", 1)
            s = model.by_rel.get(rel)
            live = s is not None and any(
                name == n
                for ctx in [s.ctx]
                for n, _node in self._lru_globals(ctx)
            )
            if not live:
                findings.append(
                    Finding(
                        self.id, reg_rel, 1, 0,
                        f"CACHE_EXEMPT_GLOBALS entry {qual!r} names no "
                        "live LRU-shaped global — stale exemption",
                    )
                )
            if not rationale or len(rationale) < MIN_RATIONALE:
                findings.append(
                    Finding(
                        self.id, reg_rel, 1, 0,
                        f"CACHE_EXEMPT_GLOBALS entry {qual!r} has no "
                        "rationale",
                    )
                )
        return findings

    def _check_entry(self, model, reg_rel, cache_name, entry, hook_fn):
        findings = []
        s = model.by_rel.get(entry["module"])
        if s is None:
            return [
                Finding(
                    self.id, reg_rel, 1, 0,
                    f"CACHES[{cache_name!r}] names missing module "
                    f"{entry['module']!r}",
                )
            ]
        if entry.get("cls") and entry["cls"] not in s.classes:
            findings.append(
                Finding(
                    self.id, reg_rel, 1, 0,
                    f"CACHES[{cache_name!r}] class {entry['cls']!r} is not "
                    f"defined in {entry['module']}",
                )
            )
        glob = entry.get("registry_global")
        if glob and glob not in {
            n for n, _x in self._lru_globals(s.ctx)
        }:
            findings.append(
                Finding(
                    self.id, reg_rel, 1, 0,
                    f"CACHES[{cache_name!r}] registry global {glob!r} is "
                    f"not a live LRU-shaped global in {entry['module']}",
                )
            )
        key_fn = None
        for f in s.functions:
            if f.name == entry.get("key_fn"):
                key_fn = f
                break
        if key_fn is None:
            findings.append(
                Finding(
                    self.id, reg_rel, 1, 0,
                    f"CACHES[{cache_name!r}] key builder "
                    f"{entry.get('key_fn')!r} is not defined in "
                    f"{entry['module']}",
                )
            )
        else:
            idents = {
                n.id
                for n in subtree(key_fn.node)
                if isinstance(n, ast.Name)
            } | {
                n.arg for n in subtree(key_fn.node)
                if isinstance(n, ast.arg)
            } | {
                n.attr
                for n in subtree(key_fn.node)
                if isinstance(n, ast.Attribute)
            }
            for token in entry.get("key_tokens", ()):
                if token not in idents:
                    findings.append(
                        Finding(
                            self.id,
                            key_fn.rel,
                            key_fn.node.lineno,
                            0,
                            f"cache {cache_name!r} key builder "
                            f"{entry['key_fn']} no longer references "
                            f"{token!r} — keys must pin a commit/ref "
                            "identity (invalidation by construction)",
                        )
                    )
        drop = entry.get("ref_drop")
        if drop is None:
            rationale = entry.get("ref_drop_rationale")
            if not rationale or len(rationale) < MIN_RATIONALE:
                findings.append(
                    Finding(
                        self.id, reg_rel, 1, 0,
                        f"CACHES[{cache_name!r}] declares no ref-update "
                        "drop hook and no rationale for why none is needed",
                    )
                )
        elif hook_fn is not None:
            called = any(
                isinstance(n, ast.Call)
                and (dotted_name(n.func) or "").rsplit(".", 1)[-1] == drop
                for n in subtree(hook_fn.node)
            )
            if not called:
                findings.append(
                    Finding(
                        self.id,
                        hook_fn.rel,
                        hook_fn.node.lineno,
                        0,
                        f"cache {cache_name!r} drop hook {drop!r} is never "
                        f"called from {registry.REF_UPDATE_HOOK[1]} — a "
                        "ref update would leave its entries squatting in "
                        "the LRU",
                    )
                )
        return findings
