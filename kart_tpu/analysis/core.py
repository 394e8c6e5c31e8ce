"""The `kart lint` framework: file loading, the rule registry, suppression
handling, and the run driver (docs/ANALYSIS.md).

Rules are AST visitors over a shared per-file parse. Each rule sees every
file once (``visit_file``) and, on a full-tree run, gets one ``finalize``
pass for the cross-file round-trip checks (registry <-> docs <-> code).
Findings are suppressed per line with::

    dangerous_thing()  # kart: noqa(KTL004): rationale for why this is safe

The rationale is mandatory — a bare ``noqa`` is itself a finding (KTL000)
that cannot be suppressed, so every exception to a contract is explained in
the tree where reviewers read it.
"""

import ast
import gc
import io
import os
import re
import tokenize

#: framework-level findings (suppression hygiene); not a registered Rule —
#: KTL000 cannot be suppressed.
SUPPRESSION_RULE_ID = "KTL000"

#: a target that cannot be read/parsed at all — its own id so external CI
#: triages syntax errors as such, not as suppression-hygiene problems.
PARSE_RULE_ID = "KTL099"

#: suppression comment shape (matched against whole COMMENT tokens, and
#: anchored at the token start, so prose in strings or documentation
#: comments that merely *mentions* the syntax never parses as one).
#: Ids must look like rule ids (KTL###).
_NOQA_RE = re.compile(
    r"^#\s*kart:\s*noqa\(\s*(KTL\d+(?:\s*,\s*KTL\d+)*)\s*\)\s*(?::\s*(.*\S))?\s*$"
)

#: a rationale must say something: at least this many characters.
MIN_RATIONALE = 10


class Finding:
    """One rule violation at a location. Sorted by (path, line, rule)."""

    __slots__ = ("rule", "path", "line", "col", "message")

    def __init__(self, rule, path, line, col, message):
        self.rule = rule
        self.path = path
        self.line = line
        self.col = col
        self.message = message

    def sort_key(self):
        return (self.path, self.line, self.col, self.rule)

    def to_dict(self):
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "message": self.message,
        }

    def __repr__(self):
        return f"{self.path}:{self.line}:{self.col} {self.rule} {self.message}"


class FileContext:
    """One parsed lint target: source, AST, parent links, suppressions."""

    def __init__(self, path, rel, source):
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=rel)
        self._parents = None
        self._nodes = None
        # line -> (frozenset of rule ids, rationale or None). Scanned from
        # COMMENT tokens, not raw lines — prose *inside a string* that
        # documents the noqa syntax must neither suppress nor trip KTL000.
        self.noqa = {}
        if "noqa" not in source:
            return  # no comment can match: skip the tokenize pass
        try:
            tokens = tokenize.generate_tokens(io.StringIO(source).readline)
            for tok in tokens:
                if tok.type != tokenize.COMMENT:
                    continue
                m = _NOQA_RE.match(tok.string)
                if m:
                    ids = frozenset(
                        t.strip() for t in m.group(1).split(",") if t.strip()
                    )
                    self.noqa[tok.start[0]] = (ids, m.group(2))
        except tokenize.TokenError:  # pragma: no cover - parse succeeded
            pass

    @property
    def nodes(self):
        """Flat node list — one tree walk shared by every rule."""
        if self._nodes is None:
            self._nodes = subtree(self.tree)
        return self._nodes

    @property
    def parents(self):
        """child AST node -> parent node (built lazily, shared by rules)."""
        if self._parents is None:
            self._parents = {}
            for parent in self.nodes:
                for child in children(parent):
                    self._parents[child] = parent
        return self._parents

    def finding(self, rule, node_or_line, message, col=None):
        line = getattr(node_or_line, "lineno", node_or_line)
        if col is None:
            col = getattr(node_or_line, "col_offset", 0)
        return Finding(rule, self.rel, line, col, message)


class Project:
    """The aggregate a full run hands to ``Rule.finalize``."""

    def __init__(self, root, contexts, full):
        self.root = root
        self.contexts = contexts
        self.full = full  # True when the default whole-tree target set ran
        self._by_rel = {c.rel: c for c in contexts}

    def context_for(self, rel):
        return self._by_rel.get(rel)

    def read(self, rel):
        """Source of a repo file that may be outside the lint targets
        (docs, test files) — None if absent."""
        ctx = self._by_rel.get(rel)
        if ctx is not None:
            return ctx.source
        p = os.path.join(self.root, rel)
        if not os.path.exists(p):
            return None
        with open(p, encoding="utf-8") as f:
            return f.read()


class Rule:
    """Base class; subclasses set ``id``/``name``/``description`` and are
    added via :func:`register`. One instance lives per run, so rules may
    accumulate state in ``visit_file`` for ``finalize``."""

    id = None
    name = None
    description = None

    def visit_file(self, ctx):
        return []

    def finalize(self, project):
        return []


_RULE_CLASSES = []


def register(cls):
    _RULE_CLASSES.append(cls)
    return cls


def all_rule_classes():
    # importing registers (KTL001-007 contract, KTL01x concurrency,
    # KTL02x device, KTL03x taint)
    from kart_tpu.analysis import rules  # noqa: F401
    from kart_tpu.analysis import rules_concurrency  # noqa: F401
    from kart_tpu.analysis import rules_device  # noqa: F401
    from kart_tpu.analysis import rules_taint  # noqa: F401

    return list(_RULE_CLASSES)


def rule_family(rule_id):
    """Rule family from the id's numeric band: KTL00x contract, KTL01x
    concurrency, KTL02x device, KTL03x taint; KTL000/KTL099 framework."""
    n = int(rule_id[3:])
    if n in (0, 99):
        return "framework"
    if n < 10:
        return "contract"
    if n < 20:
        return "concurrency"
    if n < 30:
        return "device"
    if n < 40:
        return "taint"
    return "other"


def rule_catalogue():
    """[{id, name, description, family}] for every registered rule plus
    KTL000/KTL099, in numeric KTL order (registration order interleaves
    families, which made ``--rules`` unreadable once four families
    existed)."""
    cat = [
        {
            "id": SUPPRESSION_RULE_ID,
            "name": "suppression-hygiene",
            "description": (
                "every `# kart: noqa(RULE)` names known rules and carries "
                "a rationale (`: why this is safe`); not suppressible"
            ),
        },
        {
            "id": PARSE_RULE_ID,
            "name": "parse-error",
            "description": (
                "the target could not be read or parsed; nothing else "
                "was checked in it"
            ),
        },
    ]
    for cls in all_rule_classes():
        cat.append(
            {"id": cls.id, "name": cls.name, "description": cls.description}
        )
    for entry in cat:
        entry["family"] = rule_family(entry["id"])
    cat.sort(key=lambda e: int(e["id"][3:]))
    return cat


def repo_root():
    """The directory holding the ``kart_tpu`` package and ``bench.py``."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.dirname(pkg)


def default_targets(root):
    """Full-tree target set: every .py under kart_tpu/ plus bench.py."""
    targets = []
    pkg = os.path.join(root, "kart_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                targets.append(os.path.join(dirpath, fn))
    bench = os.path.join(root, "bench.py")
    if os.path.exists(bench):
        targets.append(bench)
    return targets


def _expand(paths, root):
    out = []
    for p in paths:
        p = os.path.abspath(p)
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = sorted(
                    d for d in dirnames if d != "__pycache__"
                )
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        out.append(os.path.join(dirpath, fn))
        else:
            out.append(p)
    return out


class Report:
    def __init__(self, findings, scanned, rules, rule_seconds=None):
        self.findings = sorted(findings, key=Finding.sort_key)
        self.scanned = list(scanned)  # repo-relative paths actually parsed
        self.files_scanned = len(self.scanned)
        self.rules = rules  # catalogue dicts
        # per-rule wall-clock (visit_file sums + finalize), so the <5s
        # tier-1 bound stays attributable as the rule count grows; shared
        # lazy model builds bill to whichever rule touches them first
        self.rule_seconds = dict(rule_seconds or {})

    @property
    def ok(self):
        return not self.findings


def run_lint(paths=None, root=None):
    """Run every registered rule. ``paths=None`` = the full default target
    set (kart_tpu/ + bench.py) including the cross-file ``finalize`` checks;
    explicit paths (pre-commit single-file mode) run per-file checks only.
    """
    # the run builds and walks millions of short-lived AST objects and
    # helper containers; cyclic collection passes over them cost more than
    # the rules themselves, so collection waits until the run is done
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        return _run_lint(paths, root)
    finally:
        if gc_was_enabled:
            gc.enable()


def _run_lint(paths, root):
    root = root or repo_root()
    full = paths is None
    targets = default_targets(root) if full else _expand(paths, root)

    contexts, findings = [], []
    for path in targets:
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
            contexts.append(FileContext(path, rel, source))
        except (OSError, SyntaxError, ValueError) as e:
            findings.append(
                Finding(PARSE_RULE_ID, rel, 1, 0, f"cannot lint: {e}")
            )

    rules = [cls() for cls in all_rule_classes()]
    known_ids = {cls.id for cls in all_rule_classes()} | {
        SUPPRESSION_RULE_ID,
        PARSE_RULE_ID,
    }

    import time

    raw = []
    rule_seconds = {rule.id: 0.0 for rule in rules}
    for ctx in contexts:
        for rule in rules:
            t0 = time.perf_counter()
            raw.extend(rule.visit_file(ctx))
            rule_seconds[rule.id] += time.perf_counter() - t0
    if full:
        project = Project(root, contexts, full)
        for rule in rules:
            t0 = time.perf_counter()
            raw.extend(rule.finalize(project))
            rule_seconds[rule.id] += time.perf_counter() - t0

    # suppression pass: a finding on a line whose noqa lists its rule id
    # is dropped; a missing rationale doesn't resurrect it but does raise
    # its own KTL000 below, so the run still fails with the noqa's line.
    by_rel = {c.rel: c for c in contexts}
    for f in raw:
        ctx = by_rel.get(f.path)
        entry = ctx.noqa.get(f.line) if ctx is not None else None
        if entry is not None and f.rule in entry[0]:
            continue  # suppressed (rationale checked below for all noqas)
        findings.append(f)

    # suppression hygiene (KTL000): every noqa in every scanned file names
    # known rules and explains itself, whether or not it suppressed
    # anything this run.
    for ctx in contexts:
        for line, (ids, rationale) in sorted(ctx.noqa.items()):
            unknown = sorted(ids - known_ids)
            if unknown:
                findings.append(
                    ctx.finding(
                        SUPPRESSION_RULE_ID,
                        line,
                        f"noqa names unknown rule(s): {', '.join(unknown)}",
                    )
                )
            if SUPPRESSION_RULE_ID in ids:
                findings.append(
                    ctx.finding(
                        SUPPRESSION_RULE_ID,
                        line,
                        "KTL000 (suppression hygiene) cannot be suppressed",
                    )
                )
            if not rationale or len(rationale) < MIN_RATIONALE:
                findings.append(
                    ctx.finding(
                        SUPPRESSION_RULE_ID,
                        line,
                        "suppression without a rationale — write "
                        "`# kart: noqa(RULE): why this is safe`",
                    )
                )

    return Report(
        findings, (c.rel for c in contexts), rule_catalogue(), rule_seconds
    )


def changed_targets(root=None, ref="HEAD"):
    """Lint targets touched vs a git ref (`kart lint --changed`): changed
    or untracked .py files that belong to the default target set. -> list
    of absolute paths (may be empty: nothing relevant changed)."""
    import subprocess

    root = root or repo_root()
    cmd = ["git", "-C", root, "diff", "--name-only", "-z", ref, "--"]
    diff_proc = subprocess.run(cmd, capture_output=True, text=True)
    if diff_proc.returncode != 0:
        # a bad ref must be a named error, not a traceback (and never a
        # silently-empty "nothing changed" scan)
        raise ValueError(
            f"cannot diff against {ref!r}: "
            + (diff_proc.stderr.strip() or "git diff failed")
        )
    diff = diff_proc.stdout
    untracked = subprocess.run(
        ["git", "-C", root, "ls-files", "--others", "--exclude-standard", "-z"],
        capture_output=True,
        text=True,
        check=True,
    ).stdout
    names = {n for n in (diff + untracked).split("\0") if n}
    out = []
    for rel in sorted(names):
        if not rel.endswith(".py"):
            continue
        if not (rel.startswith("kart_tpu/") or rel == "bench.py"):
            continue
        path = os.path.join(root, rel)
        if os.path.exists(path):  # deleted files have nothing to lint
            out.append(path)
    return out


# -- shared AST helpers used by the rules -----------------------------------


def children(node):
    """``ast.iter_child_nodes(node)`` as a tuple, cached on the node. The
    rules walk the same (never mutated) trees many times over, and the
    field scan is most of what each walk costs."""
    try:
        return node._lint_children
    except AttributeError:
        kids = node._lint_children = tuple(ast.iter_child_nodes(node))
        return kids


def subtree(node):
    """``ast.walk(node)`` (same breadth-first order) as a tuple, cached on
    the node like :func:`children`."""
    try:
        return node._lint_subtree
    except AttributeError:
        out = [node]
        i = 0
        while i < len(out):
            out.extend(children(out[i]))
            i += 1
        nodes = node._lint_subtree = tuple(out)
        return nodes


def dotted_name(node):
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def str_const(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def enclosing(ctx, node, types):
    """Nearest ancestor of ``node`` that is an instance of ``types``."""
    parents = ctx.parents
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, types):
            return cur
        cur = parents.get(cur)
    return None


def unparse(node):
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - very old interpreters
        return ast.dump(node)
