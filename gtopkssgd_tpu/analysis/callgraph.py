"""Jit-reachability and value-taint machinery for graftlint rules.

Answers two questions from source alone:

  1. Which functions can run INSIDE a jit/pmap/shard_map trace?  Entry
     points come from decorators (``@jax.jit``, ``@functools.partial(
     jax.jit, ...)``), from wrapper call sites (``jax.jit(f)``,
     ``jax.shard_map(step, ...)`` — including nested defs the trainer's
     step builders produce), and from an explicit seed list for
     functions whose jit context is a calling convention rather than a
     visible wrapper (everything in ``parallel/collectives.py`` runs
     inside a shard_map body by module contract; the optimizer's
     ``update_fn``/``init_fn`` closures are installed as the
     GradientTransformation the jitted step calls).  Reachability is the
     transitive closure over *name references* (not just direct calls),
     so ``lax.scan(body, ...)`` and helpers passed as values are
     followed.

  2. Which local names hold TRACED values?  Per function, a fixpoint
     taint: values produced by jnp/lax calls are traced, and taint flows
     through assignments; lambda parameters count (tree.map/scan
     callbacks run over traced leaves).  Function PARAMETERS are *not*
     assumed traced — in this codebase the static config plumbed through
     jit-reachable helpers (densities, axis sizes, block sizes, layer
     size lists) arrives as parameters, and ``float(density)`` /
     ``int(math.log2(q))`` is trace-time host arithmetic, not a sync.
     Static shape metadata (``x.shape``/``.size``/``.ndim``/``.dtype``)
     is exempt — ``int(leaf.size)`` is host arithmetic at trace time,
     not a sync.

Nested ``def``s are separate functions (a builder method that CONTAINS
a jitted step is not itself hot); ``lambda``s are treated as part of
their enclosing function (they are tree.map/scan callbacks whose
parameters are traced).
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from gtopkssgd_tpu.analysis.engine import SourceFile

# Wrappers whose callee (decorated function / first argument) traces.
JIT_WRAPPERS = {
    "jax.jit", "jax.pmap", "jax.shard_map", "jit", "pmap", "shard_map",
    "pjit", "jax.experimental.pjit.pjit",
    "jax.experimental.shard_map.shard_map",
}
_PARTIALS = {"functools.partial", "partial"}

# Attribute reads that are static at trace time (no device sync).
STATIC_ATTRS = {"shape", "size", "ndim", "dtype", "sharding", "name"}

# Default seeds: (module rel-path suffix, function-name regex).
DEFAULT_SEEDS: Tuple[Tuple[str, str], ...] = (
    # Module contract: every function runs inside a shard_map body.
    ("parallel/collectives.py", r".*"),
    # Installed as the GradientTransformation the jitted step calls.
    # (update_fn reaches the three forms' functions by name; their
    # branches are also handed over as values, so they are named here.)
    ("optimizer.py", r"^(update_fn|init_fn|(flat|leaves|slabs)_form"
                     r"|(bucketed_)?sparse_branch|dense_branch)$"),
    # Wire codec encode/decode run inside every exchange round.
    ("parallel/codec.py", r"^(encode|decode)$"),
)


@dataclasses.dataclass
class FuncInfo:
    sf: SourceFile
    node: ast.AST                  # FunctionDef / AsyncFunctionDef
    qualname: str
    params: Set[str]
    parent: Optional["FuncInfo"]

    @property
    def name(self) -> str:
        return self.node.name  # type: ignore[attr-defined]


class ModuleInfo:
    def __init__(self, sf: SourceFile):
        self.sf = sf
        self.funcs: List[FuncInfo] = []
        self.by_name: Dict[str, List[FuncInfo]] = {}
        self.import_alias: Dict[str, str] = {}   # alias -> dotted module
        self.from_names: Dict[str, Tuple[str, str]] = {}  # name->(mod,orig)
        self._index()

    def _index(self) -> None:
        for node in ast.walk(self.sf.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.import_alias[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
                    if a.asname:
                        self.import_alias[a.asname] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.from_names[a.asname or a.name] = (
                        node.module, a.name)

        def visit(node: ast.AST, parent: Optional[FuncInfo],
                  prefix: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    qual = f"{prefix}{child.name}"
                    fi = FuncInfo(
                        sf=self.sf, node=child, qualname=qual,
                        params=_param_names(child.args), parent=parent)
                    self.funcs.append(fi)
                    self.by_name.setdefault(child.name, []).append(fi)
                    visit(child, fi, qual + ".")
                elif isinstance(child, ast.ClassDef):
                    visit(child, parent, f"{prefix}{child.name}.")
                else:
                    visit(child, parent, prefix)

        visit(self.sf.tree, None, "")

    def full_name(self, node: ast.AST) -> Optional[str]:
        """Dotted name of a Name/Attribute chain with the root resolved
        through this module's imports (``from jax import lax`` makes
        ``lax.psum`` -> ``jax.lax.psum``)."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = node.id
        if root in self.import_alias:
            root = self.import_alias[root]
        elif root in self.from_names:
            mod, orig = self.from_names[root]
            root = f"{mod}.{orig}"
        parts.append(root)
        return ".".join(reversed(parts))


def _param_names(args: ast.arguments) -> Set[str]:
    names = {a.arg for a in args.posonlyargs + args.args + args.kwonlyargs}
    if args.vararg:
        names.add(args.vararg.arg)
    if args.kwarg:
        names.add(args.kwarg.arg)
    names.discard("self")
    names.discard("cls")
    return names


def own_statements(fn: ast.AST) -> Iterable[ast.AST]:
    """Walk a function's body EXCLUDING nested def subtrees (they are
    separate functions) but INCLUDING lambdas (inline callbacks)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def value_bindings(fi: FuncInfo) -> Set[str]:
    """Names bound to VALUES in ``fi`` or an enclosing function scope:
    parameters, assignment/loop/with/except targets.  A bare reference
    to such a name is the local value, never a same-named module-level
    function — ``_loss_fn(params, batch, train=True)``'s ``train`` flag
    must not resolve to ``Trainer.train``.  Nested ``def`` names are
    deliberately NOT included: referencing one is a real call edge."""
    names: Set[str] = set()
    cur: Optional[FuncInfo] = fi
    while cur is not None:
        names |= cur.params
        for node in own_statements(cur.node):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AnnAssign, ast.AugAssign,
                                   ast.NamedExpr)):
                targets = [node.target]
            elif isinstance(node, ast.For):
                targets = [node.target]
            elif isinstance(node, ast.comprehension):
                targets = [node.target]
            elif isinstance(node, ast.withitem) and node.optional_vars:
                targets = [node.optional_vars]
            elif isinstance(node, ast.ExceptHandler) and node.name:
                names.add(node.name)
            for t in targets:
                for leaf in ast.walk(t):
                    if isinstance(leaf, ast.Name):
                        names.add(leaf.id)
        cur = cur.parent
    return names


class CallGraph:
    """Whole-file-set function index + jit reachability."""

    def __init__(self, files: Sequence[SourceFile],
                 seeds: Sequence[Tuple[str, str]] = DEFAULT_SEEDS):
        self.modules = [ModuleInfo(sf) for sf in files]
        self.by_rel = {m.sf.rel: m for m in self.modules}
        # Global bare-name index for cross-module from-import resolution.
        self.global_by_name: Dict[str, List[FuncInfo]] = {}
        for m in self.modules:
            for fi in m.funcs:
                self.global_by_name.setdefault(fi.name, []).append(fi)
        self.entries: Set[int] = set()      # id(FuncInfo.node)
        self.reachable: Dict[int, FuncInfo] = {}
        self._find_entries(seeds)
        self._close_over_references()

    # ----------------------------------------------------------- entries
    def _is_jit_wrapper(self, m: ModuleInfo, func: ast.AST) -> bool:
        name = m.full_name(func)
        if name in JIT_WRAPPERS:
            return True
        # functools.partial(jax.jit, ...) used as a decorator factory.
        if (isinstance(func, ast.Call)
                and m.full_name(func.func) in _PARTIALS and func.args):
            return m.full_name(func.args[0]) in JIT_WRAPPERS
        return False

    def _find_entries(self, seeds: Sequence[Tuple[str, str]]) -> None:
        for m in self.modules:
            for fi in m.funcs:
                for deco in fi.node.decorator_list:  # type: ignore
                    target = deco.func if isinstance(deco, ast.Call) \
                        else deco
                    if self._is_jit_wrapper(m, target) or (
                            isinstance(deco, ast.Call)
                            and self._is_jit_wrapper(m, deco)):
                        self._mark(fi)
                for suffix, pattern in seeds:
                    if (m.sf.rel.endswith(suffix)
                            and re.match(pattern, fi.name)):
                        self._mark(fi)
            for node in ast.walk(m.sf.tree):
                if not isinstance(node, ast.Call):
                    continue
                if not self._is_jit_wrapper(m, node.func):
                    continue
                if node.args:
                    self._mark_callee_expr(m, node.args[0])

    def _mark_callee_expr(self, m: ModuleInfo, expr: ast.AST) -> None:
        if isinstance(expr, ast.Name):
            for fi in m.by_name.get(expr.id, []):
                self._mark(fi)
        elif isinstance(expr, ast.Call):
            # jax.jit(jax.shard_map(f, ...)): the inner call is itself
            # scanned by _find_entries, nothing extra to do — but a
            # plain wrapper like jax.jit(functools.partial(f, ...))
            # still resolves through the partial's first argument.
            if m.full_name(expr.func) in _PARTIALS and expr.args:
                self._mark_callee_expr(m, expr.args[0])
        # Lambdas passed to jax.jit directly have no FuncInfo; their
        # bodies are part of the enclosing function's statements and
        # are covered when that function is reachable.

    def _mark(self, fi: FuncInfo) -> None:
        if id(fi.node) not in self.reachable:
            self.entries.add(id(fi.node))
            self.reachable[id(fi.node)] = fi

    # ------------------------------------------------------- reachability
    def _resolve_reference(self, m: ModuleInfo,
                           node: ast.AST) -> List[FuncInfo]:
        if isinstance(node, ast.Name):
            local = m.by_name.get(node.id)
            if local:
                return local
            if node.id in m.from_names:
                mod, orig = m.from_names[node.id]
                return self._resolve_imported(mod, orig)
            return []
        if isinstance(node, ast.Attribute):
            base = node.value
            if isinstance(base, ast.Name):
                if base.id == "self":
                    return m.by_name.get(node.attr, [])
                dotted = m.import_alias.get(base.id)
                if dotted is None and base.id in m.from_names:
                    fmod, forig = m.from_names[base.id]
                    dotted = f"{fmod}.{forig}"
                if dotted:
                    return self._resolve_imported(dotted, node.attr)
        return []

    def _resolve_imported(self, module: str, name: str) -> List[FuncInfo]:
        rel = module.replace(".", "/") + ".py"
        target = None
        for m in self.modules:
            if m.sf.rel == rel or m.sf.rel.endswith("/" + rel):
                target = m
                break
        if target is not None and name in target.by_name:
            return target.by_name[name]
        # Package __init__ re-exports: fall back to the global bare-name
        # index for package-internal modules only.
        if module.split(".")[0] in {
                m.sf.rel.split("/")[0] for m in self.modules}:
            return self.global_by_name.get(name, [])
        return []

    def _close_over_references(self) -> None:
        work = list(self.reachable.values())
        shadow_cache: Dict[int, Set[str]] = {}
        while work:
            fi = work.pop()
            m = self.by_rel[fi.sf.rel]
            shadowed = shadow_cache.get(id(fi.node))
            if shadowed is None:
                shadowed = shadow_cache[id(fi.node)] = value_bindings(fi)
            for node in own_statements(fi.node):
                if not isinstance(node, (ast.Name, ast.Attribute)):
                    continue
                if isinstance(node, ast.Name) and not isinstance(
                        getattr(node, "ctx", None), ast.Load):
                    continue
                if isinstance(node, ast.Name) and node.id in shadowed:
                    continue  # local value, not a module-level function
                for target in self._resolve_reference(m, node):
                    if id(target.node) not in self.reachable:
                        self.reachable[id(target.node)] = target
                        work.append(target)

    def reachable_functions(self) -> List[FuncInfo]:
        return sorted(self.reachable.values(),
                      key=lambda fi: (fi.sf.rel, fi.node.lineno))


# ---------------------------------------------------------------- taint

# Calls rooted here produce device values no matter the arguments
# (jnp.zeros of a static shape is still a traced array) ...
_ALWAYS_TRACED_ROOTS = {"jnp", "lax"}
# ... while these only propagate taint that flows in through an argument
# (np.asarray of a static python list is host data).
_ARG_TRACED_ROOTS = {"jax", "np", "numpy"}


def traced_names(fi: FuncInfo) -> Set[str]:
    """Fixpoint over simple assignments: which local names (probably)
    hold traced values inside this jit-reachable function.  Parameters
    are NOT seeded (see module docstring): taint originates at jnp/lax
    producers and flows through assignments from there."""
    tainted: Set[str] = set()
    # Lambda parameters inside this function body: callbacks over traced
    # pytrees (tree.map, scan bodies) — treat as traced.
    for node in own_statements(fi.node):
        if isinstance(node, ast.Lambda):
            tainted |= _param_names(node.args)
    changed = True
    while changed:
        changed = False
        for node in own_statements(fi.node):
            targets: List[ast.AST] = []
            value: Optional[ast.AST] = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value:
                targets, value = [node.target], node.value
            elif isinstance(node, ast.AugAssign):
                targets, value = [node.target], node.value
            elif isinstance(node, ast.NamedExpr):
                targets, value = [node.target], node.value
            elif isinstance(node, ast.For):
                targets, value = [node.target], node.iter
            if value is None or not targets:
                continue
            if not expr_is_traced(value, tainted):
                continue
            for t in targets:
                for leaf in ast.walk(t):
                    if isinstance(leaf, ast.Name):
                        if leaf.id not in tainted:
                            tainted.add(leaf.id)
                            changed = True
    return tainted


def expr_is_traced(expr: ast.AST, tainted: Set[str]) -> bool:
    """Does ``expr`` (likely) produce/contain a traced value?  Static
    shape metadata reads are exempt; calls rooted at jnp/jax/lax count
    as traced producers."""
    stack = [expr]
    while stack:
        node = stack.pop()
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                continue  # x.shape / x.size / ... : host-static
            stack.append(node.value)
            continue
        if isinstance(node, ast.Call):
            root = node.func
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name):
                if root.id in _ALWAYS_TRACED_ROOTS:
                    return True
                if (root.id in _ARG_TRACED_ROOTS
                        and any(expr_is_traced(a, tainted)
                                for a in node.args)):
                    return True
            stack.extend(node.args)
            stack.extend(kw.value for kw in node.keywords)
            if not isinstance(node.func, ast.Name):
                stack.append(node.func)
            continue
        if isinstance(node, ast.Name):
            if node.id in tainted:
                return True
            continue
        stack.extend(ast.iter_child_nodes(node))
    return False
