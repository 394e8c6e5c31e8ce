"""Device-purity rules (KTL020/KTL021) — the jax execution surface
(docs/ANALYSIS.md, docs/DEVICE.md):

* KTL020: no host side effects inside a ``jax.jit``/``pmap``/``shard_map``
  traced function. Telemetry calls, env reads, logging, fault hooks,
  ``.item()``/``np.asarray`` host syncs and data-dependent Python
  branching all execute at *trace* time (once, on tracer values — so the
  branch either crashes or silently bakes one path into the compiled
  kernel) rather than at run time on every batch.
* KTL021: jax stays behind the fallback seam. Only registry.DEVICE_MODULES
  may import jax (always lazily, inside a function); every other module
  reaches device execution exclusively through the registry.DEVICE_SEAMS
  names (``select_backend`` and friends), each of which carries its own
  cost-model routing and host fallback — so a wedged accelerator can
  never take the CLI down with it.
"""

import ast

from kart_tpu.analysis import interproc, registry
from kart_tpu.analysis.core import (
    Finding,
    Rule,
    dotted_name,
    register,
    subtree,
)
from kart_tpu.analysis.rules import _env_read_name

# ---------------------------------------------------------------------------
# KTL020 — trace purity
# ---------------------------------------------------------------------------

#: numpy constructors that only build scalar constants — harmless inside a
#: trace (they fold into the program) and used legitimately for dtypes
_NP_CONST_OK = frozenset(
    {
        "int8", "int16", "int32", "int64",
        "uint8", "uint16", "uint32", "uint64",
        "float16", "float32", "float64", "bool_",
    }
)

_LOG_METHODS = frozenset(
    {"debug", "info", "warning", "error", "exception", "critical"}
)
_LOG_RECEIVERS = frozenset({"L", "log", "logger", "logging"})


@register
class DeviceTracePurity(Rule):
    id = "KTL020"
    name = "device-trace-purity"
    description = (
        "jit/shard_map/pmap-traced functions must be pure: no telemetry, "
        "env reads, logging, fault hooks, host syncs (.item()/np.asarray) "
        "or data-dependent Python branching — host effects inside a trace "
        "run once at compile time, not per batch, and tracer-dependent "
        "branches bake a single path into the kernel"
    )

    def visit_file(self, ctx):
        summary = interproc.file_summary(ctx)
        traced = interproc.traced_functions(summary)
        if not traced:
            return []
        findings = []
        local_defs = {}
        for f in summary.functions:
            local_defs.setdefault(f.name, f)
        checked = set()
        for fn_info, how in traced:
            self._check_fn(
                ctx, summary, fn_info, how, local_defs, checked, findings
            )
        return findings

    def _check_fn(self, ctx, summary, fn_info, how, local_defs, checked,
                  findings, depth=0):
        if fn_info.qual in checked or depth > 4:
            return
        checked.add(fn_info.qual)
        params = {
            a.arg
            for a in (
                fn_info.node.args.args
                + fn_info.node.args.posonlyargs
                + fn_info.node.args.kwonlyargs
            )
            if a.arg not in ("self", "cls")
        }
        # local name -> candidate defs (e.g. `core = A if k else B`)
        name_binds = {}
        for node in subtree(fn_info.node):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                if isinstance(t, ast.Name):
                    for cand in self._name_candidates(node.value):
                        if cand in local_defs:
                            name_binds.setdefault(t.id, set()).add(cand)
        for node in subtree(fn_info.node):
            issue = self._impurity(node, params)
            if issue is not None:
                findings.append(
                    ctx.finding(
                        self.id,
                        node,
                        f"{issue} inside traced function "
                        f"{fn_info.name!r} ({how})",
                    )
                )
            if isinstance(node, ast.Call) and isinstance(
                node.func, ast.Name
            ):
                targets = set()
                if node.func.id in local_defs:
                    targets.add(node.func.id)
                targets |= name_binds.get(node.func.id, set())
                for t in sorted(targets):
                    self._check_fn(
                        ctx, summary, local_defs[t], how, local_defs,
                        checked, findings, depth + 1,
                    )

    @staticmethod
    def _name_candidates(value):
        if isinstance(value, ast.Name):
            return [value.id]
        if isinstance(value, ast.IfExp):
            out = []
            for b in (value.body, value.orelse):
                if isinstance(b, ast.Name):
                    out.append(b.id)
            return out
        return []

    @staticmethod
    def _impurity(node, params):
        """A host side effect / host sync / tracer branch, or None."""
        if _env_read_name(node) is not None:
            return "os.environ read"
        if isinstance(node, ast.Call):
            fn = dotted_name(node.func) or ""
            leaf = fn.rsplit(".", 1)[-1]
            root = fn.split(".", 1)[0]
            if root in ("tm", "telemetry") and leaf in (
                "span", "incr", "gauge_set", "observe",
            ):
                return f"telemetry call {fn}()"
            if root == "faults" and leaf in ("fire", "hook"):
                return f"fault hook {fn}()"
            if fn == "print" or (
                root in _LOG_RECEIVERS and leaf in _LOG_METHODS
            ):
                return f"host logging ({fn})"
            if fn == "open":
                return "file I/O (open())"
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "item"
                and not node.args
            ):
                return "host sync (.item() blocks on device execution)"
            if root in ("np", "numpy") and leaf not in _NP_CONST_OK:
                return (
                    f"host numpy call {fn}() (runs on tracer values at "
                    "compile time, or forces a device->host sync)"
                )
        elif isinstance(node, (ast.If, ast.While, ast.Assert)):
            test = node.test
            for sub in subtree(test):
                if isinstance(sub, ast.Name) and sub.id in params:
                    kind = type(node).__name__.lower()
                    return (
                        f"data-dependent Python `{kind}` on traced "
                        f"argument {sub.id!r} (runs once on the tracer — "
                        "use jnp.where / lax.cond)"
                    )
        return None


# ---------------------------------------------------------------------------
# KTL021 — device-fallback seam coverage
# ---------------------------------------------------------------------------


def _device_module_rel(dotted):
    for rel in (dotted.replace(".", "/") + ".py",
                dotted.replace(".", "/") + "/__init__.py"):
        if rel in registry.DEVICE_MODULES:
            return rel
    return None


@register
class DeviceFallbackSeam(Rule):
    id = "KTL021"
    name = "device-fallback-seam"
    description = (
        "jax is imported only by registry.DEVICE_MODULES and only lazily "
        "(inside a function); every other module reaches device code "
        "exclusively through the registry.DEVICE_SEAMS names, which carry "
        "their own cost-model routing and host fallback — and every "
        "declared seam name must still exist and be imported somewhere"
    )

    def __init__(self):
        self._seam_uses = set()  # (module_rel, name) imported by non-device

    def visit_file(self, ctx):
        findings = []
        in_device_layer = ctx.rel in registry.DEVICE_MODULES
        device_aliases = {}  # local alias -> device module rel
        for node in ctx.nodes:
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "jax" or alias.name.startswith("jax."):
                        findings.extend(
                            self._jax_import(ctx, node, in_device_layer)
                        )
                    rel = _device_module_rel(alias.name)
                    if rel is not None and not in_device_layer:
                        device_aliases[
                            alias.asname or alias.name.split(".")[-1]
                        ] = rel
            elif isinstance(node, ast.ImportFrom) and node.module:
                if node.module == "jax" or node.module.startswith("jax."):
                    findings.extend(
                        self._jax_import(ctx, node, in_device_layer)
                    )
                    continue
                if in_device_layer:
                    continue
                rel = _device_module_rel(node.module)
                if rel is not None:
                    seams = registry.DEVICE_SEAMS.get(rel, frozenset())
                    for alias in node.names:
                        self._seam_uses.add((rel, alias.name))
                        if alias.name not in seams:
                            findings.append(
                                ctx.finding(
                                    self.id,
                                    node,
                                    f"{alias.name!r} imported from device "
                                    f"module {rel} outside the fallback "
                                    "seam — route through a "
                                    "registry.DEVICE_SEAMS name (e.g. "
                                    "select_backend) or declare the seam",
                                )
                            )
                    continue
                # `from kart_tpu import runtime` — a device *module* import
                for alias in node.names:
                    rel = _device_module_rel(
                        node.module + "." + alias.name
                    )
                    if rel is not None:
                        device_aliases[alias.asname or alias.name] = rel
        # attribute uses through a device-module alias must hit seam names
        for node in ctx.nodes:
            if (
                isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id in device_aliases
            ):
                rel = device_aliases[node.value.id]
                seams = registry.DEVICE_SEAMS.get(rel, frozenset())
                self._seam_uses.add((rel, node.attr))
                if node.attr not in seams:
                    findings.append(
                        ctx.finding(
                            self.id,
                            node,
                            f"{node.value.id}.{node.attr} reaches device "
                            f"module {rel} outside the fallback seam",
                        )
                    )
        return findings

    def _jax_import(self, ctx, node, in_device_layer):
        if not in_device_layer:
            return [
                ctx.finding(
                    self.id,
                    node,
                    "jax import outside the device execution layer — only "
                    "registry.DEVICE_MODULES may touch jax; route through "
                    "the select_backend fallback seam instead",
                )
            ]
        # lazy-import contract: even device modules defer the ~1.8s import
        # until a function actually needs a device
        if (
            interproc.file_summary(ctx)  # ensure parents usable
            and self._at_module_level(ctx, node)
        ):
            return [
                ctx.finding(
                    self.id,
                    node,
                    "module-top-level jax import: the ~1.8s import must "
                    "stay off every host-only path — import inside the "
                    "function that needs it (see ops/_lazy.py)",
                )
            ]
        return []

    @staticmethod
    def _at_module_level(ctx, node):
        cur = ctx.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return False
            cur = ctx.parents.get(cur)
        return True

    def finalize(self, project):
        findings = []
        reg_rel = "kart_tpu/analysis/registry.py"
        model = interproc.project_model(project)
        for rel in sorted(registry.DEVICE_MODULES):
            if model.by_rel.get(rel) is None:
                findings.append(
                    Finding(
                        self.id, reg_rel, 1, 0,
                        f"DEVICE_MODULES entry {rel!r} does not exist — "
                        "stale declaration",
                    )
                )
        for rel, names in sorted(registry.DEVICE_SEAMS.items()):
            s = model.by_rel.get(rel)
            if s is None:
                findings.append(
                    Finding(
                        self.id, reg_rel, 1, 0,
                        f"DEVICE_SEAMS module {rel!r} does not exist",
                    )
                )
                continue
            defined = {f.name for f in s.functions if f.cls is None}
            defined |= set(s.classes)
            for stmt in s.ctx.tree.body:
                if isinstance(stmt, ast.Assign):
                    for t in stmt.targets:
                        if isinstance(t, ast.Name):
                            defined.add(t.id)
                        elif isinstance(t, ast.Tuple):
                            # BLOCK_ALL_OUT, BLOCK_ALL_IN, ... = 0, 1, 2
                            defined.update(
                                e.id
                                for e in t.elts
                                if isinstance(e, ast.Name)
                            )
            for name in sorted(names):
                if name not in defined:
                    findings.append(
                        Finding(
                            self.id, reg_rel, 1, 0,
                            f"DEVICE_SEAMS name {rel}::{name} is no longer "
                            "defined in its module — stale seam",
                        )
                    )
                elif (rel, name) not in self._seam_uses:
                    findings.append(
                        Finding(
                            self.id, reg_rel, 1, 0,
                            f"DEVICE_SEAMS name {rel}::{name} is never "
                            "imported by a non-device module — dead seam "
                            "declaration",
                        )
                    )
        return findings
