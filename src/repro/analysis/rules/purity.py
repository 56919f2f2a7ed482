"""jit-purity — no host effects inside jit / shard_map / pallas regions.

A traced region runs ONCE at trace time and then replays as compiled XLA:
``print`` fires once (or never again), ``np.random`` freezes one sample
into the graph as a constant, mutating module state bakes in stale values,
and ``.item()`` / ``float()`` / ``int()`` / ``bool()`` on a traced value
either raises a ConcretizationTypeError or — via ``jax.debug`` shims —
forces a device sync that destroys the async dispatch the ingest pipeline
is built on. This rule walks every function reachable from a jit root and
flags those constructs.

Roots: defs decorated with ``jax.jit`` / ``functools.partial(jax.jit, ...)``,
functions passed to ``jax.jit(...)`` / ``shard_map`` /
``sharding.shard_map_rows`` / ``pl.pallas_call`` (directly or through
``functools.partial``). Reachability: intra-module calls by name plus
cross-module ``module.fn`` calls resolved through imports, iterated to a
fixpoint over the whole parse set.

Host-sync detection is deliberately conservative to stay signal-dense:
``float/int/bool`` is flagged when its argument *contains a jnp./jax. call*
(e.g. ``int(jnp.sum(x))``) or, in a jit-root function, is derived from a
non-static parameter (static = named in the root's ``static_argnums`` /
``static_argnames``). Documented host-side entry points that the
reachability over-approximates belong in the baseline with justification.
"""

from __future__ import annotations

import ast

from repro.analysis.astutil import ImportMap, call_keyword, dotted, literal_int_tuple
from repro.analysis.findings import Finding
from repro.analysis.registry import Rule, register

SCOPE = ("src/repro/",)

JIT_ENTRY = {
    "jax.jit",
    "jax.pmap",
    "jax.shard_map",
    "repro.core.sharding.shard_map_rows",
    "jax.experimental.pallas.pallas_call",
}
_PARTIAL = ("functools.partial", "partial")


def _is_jit_entry(qual: str | None) -> bool:
    if qual is None:
        return False
    return qual in JIT_ENTRY or qual.endswith(".pallas_call") or qual.endswith(
        ".shard_map_rows"
    )


def _contains_traced_call(node: ast.expr, imap: ImportMap) -> bool:
    """True if the expression contains a jnp./jax.-rooted call."""
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            qual = imap.resolve(n.func) or dotted(n.func) or ""
            root = qual.split(".")[0]
            if root in ("jnp", "jax", "lax") or qual.startswith(
                ("jax.numpy.", "jax.lax.", "jax.")
            ):
                return True
    return False


class _FnInfo:
    """One function def plus where it sits (module, statics if jit root)."""

    def __init__(self, mod, qual: str, node):
        self.mod = mod
        self.qual = qual  # module-local qualname
        self.node = node
        self.is_root = False
        self.static_params: set[str] = set()


def _decorator_statics(fn: ast.AST, imap: ImportMap) -> set[str] | None:
    """Static param names if ``fn`` is decorated as a jit root, else None."""
    for dec in getattr(fn, "decorator_list", []):
        if imap.resolve(dec) == "jax.jit":
            return set()
        if isinstance(dec, ast.Call):
            target = dec.func
            if imap.resolve(target) == "jax.jit":
                return _statics_from_call(dec, fn)
            if imap.resolve(target) in _PARTIAL and dec.args:
                if imap.resolve(dec.args[0]) == "jax.jit":
                    return _statics_from_call(dec, fn)
    return None


def _statics_from_call(call: ast.Call, fn: ast.AST) -> set[str]:
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    statics: set[str] = set()
    nums = literal_int_tuple(call_keyword(call, "static_argnums"))
    for i in nums or ():
        if i < len(params):
            statics.add(params[i])
    names = call_keyword(call, "static_argnames")
    if isinstance(names, ast.Constant) and isinstance(names.value, str):
        statics.add(names.value)
    elif isinstance(names, (ast.Tuple, ast.List)):
        for e in names.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                statics.add(e.value)
    return statics


@register
class JitPurityRule(Rule):
    """Flag host-impure constructs in functions reachable from jit roots."""

    name = "jit-purity"
    description = (
        "no print / np.random / module-state mutation / tracer host-syncs "
        "inside functions reachable from jax.jit, shard_map, or pallas_call"
    )

    def run(self, ctx) -> list[Finding]:
        """Run the rule over the context's selected modules."""
        # ---- index every function def across the scope -------------------
        infos: dict[tuple[str, str], _FnInfo] = {}  # (module name, local name)
        imaps: dict[str, ImportMap] = {}
        from repro.analysis.astutil import walk_functions

        for mod in ctx.iter_modules(SCOPE):
            imap = ImportMap(mod.tree, mod.name)
            imaps[mod.name] = imap
            for qual, node in walk_functions(mod.tree):
                info = _FnInfo(mod, qual, node)
                # Index by bare local name: calls use the leaf name. Last
                # writer wins on collision — acceptable for this codebase.
                infos[(mod.name, node.name)] = info
                statics = _decorator_statics(node, imap)
                if statics is not None:
                    info.is_root = True
                    info.static_params = statics

        # ---- roots via jax.jit(fn, ...) / shard_map(fn) / pallas_call(fn)
        for mod in ctx.iter_modules(SCOPE):
            imap = imaps[mod.name]
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                if not _is_jit_entry(imap.resolve(node.func)):
                    continue
                target = node.args[0] if node.args else None
                if isinstance(target, ast.Call) and imap.resolve(
                    target.func
                ) in _PARTIAL:
                    target = target.args[0] if target.args else None
                if isinstance(target, ast.Name):
                    info = infos.get((mod.name, target.id))
                    if info is not None:
                        info.is_root = True
                        if imap.resolve(node.func) == "jax.jit":
                            info.static_params |= _statics_from_call(
                                node, info.node
                            )

        # ---- reachability fixpoint ---------------------------------------
        reachable: set[tuple[str, str]] = {
            k for k, info in infos.items() if info.is_root
        }
        work = list(reachable)
        while work:
            key = work.pop()
            info = infos[key]
            imap = imaps[info.mod.name]
            for node in ast.walk(info.node):
                if not isinstance(node, ast.Call):
                    continue
                callee: tuple[str, str] | None = None
                if isinstance(node.func, ast.Name):
                    callee = (info.mod.name, node.func.id)
                else:
                    qual = imap.resolve(node.func)
                    if qual is not None:
                        owner, _, leaf = qual.rpartition(".")
                        if ctx.module_by_name(owner) is not None:
                            callee = (owner, leaf)
                if callee in infos and callee not in reachable:
                    reachable.add(callee)
                    work.append(callee)

        # ---- flag impurities in reachable bodies -------------------------
        findings: list[Finding] = []
        for key in sorted(reachable):
            info = infos[key]
            if not ctx.is_selected(info.mod.rel):
                continue
            findings += self._check_body(info, imaps[info.mod.name])
        return findings

    def _check_body(self, info: _FnInfo, imap: ImportMap) -> list[Finding]:
        out: list[Finding] = []
        mod = info.mod
        fn = info.node
        params = {a.arg for a in fn.args.posonlyargs + fn.args.args}
        traced = params - info.static_params if info.is_root else set()
        module_mutables = self._module_mutables(mod)

        def flag(node, msg):
            out.append(Finding(self.name, mod.rel, node.lineno, msg))

        def walk_own(root):
            # Like ast.walk but does not descend into nested defs — those
            # are their own reachability nodes (lambdas stay inline).
            stack = list(ast.iter_child_nodes(root))
            while stack:
                n = stack.pop()
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                yield n
                stack.extend(ast.iter_child_nodes(n))

        for node in walk_own(fn):
            if isinstance(node, ast.Global):
                flag(node, f"'global {', '.join(node.names)}' inside a jit-"
                           f"reachable function '{fn.name}'")
            elif isinstance(node, ast.Call):
                qual = imap.resolve(node.func) or dotted(node.func) or ""
                fname = qual.split(".")[-1] if qual else ""
                if qual == "print" or (
                    isinstance(node.func, ast.Name) and node.func.id == "print"
                ):
                    flag(node, f"print() inside jit-reachable '{fn.name}' — "
                               "use jax.debug.print")
                elif qual.startswith(("numpy.random", "np.random")):
                    flag(node, f"np.random inside jit-reachable '{fn.name}' "
                               "freezes one sample at trace time — use "
                               "jax.random with an explicit key")
                elif isinstance(node.func, ast.Attribute) and node.func.attr == "item":
                    flag(node, f".item() inside jit-reachable '{fn.name}' is "
                               "a tracer host-sync")
                elif (
                    isinstance(node.func, ast.Name)
                    and node.func.id in ("float", "int", "bool")
                    and len(node.args) == 1
                ):
                    arg = node.args[0]
                    if _contains_traced_call(arg, imap):
                        flag(node, f"{node.func.id}() over a jnp/jax "
                                   f"expression inside jit-reachable "
                                   f"'{fn.name}' is a tracer host-sync")
                    elif traced:
                        root = (dotted(arg) or "").split(".")[0]
                        if root in traced:
                            flag(node, f"{node.func.id}('{root}') on a traced "
                                       f"parameter of jit root '{fn.name}' is "
                                       "a tracer host-sync")
                elif fname in ("append", "update", "setdefault", "pop") and (
                    isinstance(node.func, ast.Attribute)
                ):
                    base = dotted(node.func.value)
                    if base in module_mutables:
                        flag(node, f"mutation of module-level '{base}' inside "
                                   f"jit-reachable '{fn.name}' bakes in stale "
                                   "state")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign) else [node.target]
                )
                for t in targets:
                    if isinstance(t, ast.Subscript):
                        base = dotted(t.value)
                        if base in module_mutables:
                            flag(t, f"subscript-write to module-level "
                                    f"'{base}' inside jit-reachable "
                                    f"'{fn.name}' bakes in stale state")
        return out

    @staticmethod
    def _module_mutables(mod) -> set[str]:
        """Module-level names bound to dict/list literals or calls."""
        out: set[str] = set()
        for node in mod.tree.body:
            if isinstance(node, ast.Assign) and isinstance(
                node.value, (ast.Dict, ast.List, ast.DictComp, ast.ListComp)
            ):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
            elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                if (dotted(node.value.func) or "") in ("dict", "list"):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            out.add(t.id)
        return out
