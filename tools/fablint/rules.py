"""The FAB rule set.

Each rule is a class with a ``code``, a one-line ``title``, a docstring
(the catalogue entry rendered by ``--list-rules`` and mirrored in
``docs/invariants.md``), an ``applies_to(relpath)`` path scope, and a
``check(project)`` generator yielding :class:`~tools.fablint.engine
.Violation`.  Suppression filtering happens here, against the flagged
expression's full line span.

The rules are deliberately *idiom-shaped*, not general dataflow: they
encode how this repo writes its data plane (flat ``dst * capacity + slot``
addresses, trash rows, register-gated plans) and flag departures from it.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

from tools.fablint.engine import Project, SourceFile, Violation

# Path scope of the data-plane rules (FAB001/FAB005): the dirs whose
# indexing bugs can cross tenant slots.
_DATA_PLANE_RE = re.compile(
    r"(^|/)(core|fabric|kernels)/|(^|/)models/moe\.py$")


def _dotted(node: ast.AST) -> str:
    """``jnp.take`` -> "jnp.take"; best-effort for Name/Attribute chains."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _kwarg_names(call: ast.Call) -> Set[str]:
    return {kw.arg for kw in call.keywords if kw.arg}


def _contains_computed(index: ast.AST) -> bool:
    """True when an index expression is computed (names/calls/arithmetic)
    rather than constants and constant slices — the shapes XLA will
    silently clip or drop instead of faulting on."""
    items: Sequence[ast.AST]
    items = index.elts if isinstance(index, ast.Tuple) else [index]
    for item in items:
        if isinstance(item, ast.Slice):
            # Static slices are bounds-checked at trace time; not a
            # silent-OOB surface.
            continue
        for sub in ast.walk(item):
            if isinstance(sub, (ast.Name, ast.Call)):
                return True
    return False


class Rule:
    code = "FAB000"
    title = ""

    def applies_to(self, rel: str) -> bool:
        return True

    def check(self, project: Project) -> Iterator[Violation]:
        raise NotImplementedError

    def _emit(self, src: SourceFile, node: ast.AST,
              message: str) -> Iterator[Violation]:
        lineno = getattr(node, "lineno", 1)  # ast.Module anchors at line 1
        if not src.suppressed(self.code, lineno,
                              getattr(node, "end_lineno", None)):
            yield src.violation(node, self.code, message)


# ----------------------------------------------------------------------
# FAB001 — implicit out-of-bounds indexing
# ----------------------------------------------------------------------
class ImplicitOOBIndexing(Rule):
    """Gather/scatter on a computed address without explicit out-of-bounds
    semantics.  XLA *clips* out-of-range gather indices and *drops*
    out-of-range scatter updates instead of faulting — exactly how a
    cross-tenant slot read or a lost packet hides behind plausible
    numbers.  In the data-plane dirs (``core/``, ``fabric/``,
    ``kernels/``, ``models/moe.py``) every ``jnp.take`` /
    ``jnp.take_along_axis`` and every ``.at[...]`` indexed update on a
    computed index must either pass an explicit ``mode=`` (making the
    clip/drop/fill choice visible at the call site) or carry the
    ``# fablint: trash-row`` annotation marking the repo's sanctioned
    scatter idiom: a slab with one extra trash row that absorbs dropped
    packets by construction (``arbiter.flat_slot_addr``)."""

    code = "FAB001"
    title = "implicit out-of-bounds indexing (no mode=, no trash-row)"

    _TAKE_FNS = {"take", "take_along_axis"}
    _AT_METHODS = {"set", "add", "subtract", "multiply", "mul", "divide",
                   "div", "power", "min", "max", "get", "apply"}

    def applies_to(self, rel: str) -> bool:
        return bool(_DATA_PLANE_RE.search(rel))

    def check(self, project: Project) -> Iterator[Violation]:
        for src in project.files:
            if not self.applies_to(src.rel):
                continue
            for node in ast.walk(src.tree):
                if not isinstance(node, ast.Call):
                    continue
                yield from self._check_take(src, node)
                yield from self._check_at(src, node)

    def _check_take(self, src: SourceFile,
                    call: ast.Call) -> Iterator[Violation]:
        name = _dotted(call.func)
        if name.split(".")[-1] not in self._TAKE_FNS or "." not in name:
            return
        if not name.startswith(("jnp.", "jax.numpy.", "np.", "numpy.")):
            return
        index = None
        if len(call.args) >= 2:
            index = call.args[1]
        else:
            for kw in call.keywords:
                if kw.arg == "indices":
                    index = kw.value
        if index is not None and not _contains_computed(index):
            return
        if "mode" in _kwarg_names(call):
            return
        if src.annotated("trash-row", call.lineno, call.end_lineno):
            return
        yield from self._emit(
            src, call,
            f"`{name}` on a computed index relies on XLA's silent clip "
            f"semantics; pass an explicit mode= (e.g. mode=\"clip\" / "
            f"\"fill\") or annotate the trash-row pattern "
            f"(`# fablint: trash-row`)")

    def _check_at(self, src: SourceFile,
                  call: ast.Call) -> Iterator[Violation]:
        # x.at[IDX].add(...)  ==  Call(func=Attribute(value=Subscript(
        #     value=Attribute(attr="at"), slice=IDX), attr="add"))
        func = call.func
        if not (isinstance(func, ast.Attribute)
                and func.attr in self._AT_METHODS
                and isinstance(func.value, ast.Subscript)
                and isinstance(func.value.value, ast.Attribute)
                and func.value.value.attr == "at"):
            return
        index = func.value.slice
        if not _contains_computed(index):
            return
        if "mode" in _kwarg_names(call):
            return
        if src.annotated("trash-row", call.lineno, call.end_lineno):
            return
        yield from self._emit(
            src, call,
            f"`.at[...].{func.attr}` on a computed index relies on XLA's "
            f"silent out-of-bounds drop; pass an explicit mode= (e.g. "
            f"mode=\"drop\") or annotate the trash-row pattern "
            f"(`# fablint: trash-row`)")


# ----------------------------------------------------------------------
# FAB002 — retrace hazards under jit
# ----------------------------------------------------------------------
_ARRAYISH_ANNOT_RE = re.compile(
    r"Array|ndarray|DispatchPlan|CrossbarRegisters")
_ARRAYISH_NAMES = {
    "x", "y", "xs", "ys", "xx", "xf", "xk", "xg", "dg", "wg", "dst", "src",
    "dsts", "srcs", "w", "weights", "slabs", "slab", "plan", "plans",
    "regs", "registers", "logits", "probs", "mask", "addr", "keep", "slot",
    "counts", "granted", "rank", "error", "err",
}
# Attributes whose value is static under tracing even on a traced array.
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "n_ports", "aval",
                 "sharding", "weak_type"}
# Calls whose result is static regardless of argument taint
# (``jnp.issubdtype`` inspects dtypes, never values; ``jax.lax.axis_size``
# is the mesh axis length, a Python int).
_STATIC_CALLS = {"len", "isinstance", "issubclass", "type", "hasattr",
                 "getattr", "id", "repr", "str", "range", "enumerate",
                 "zip", "issubdtype", "result_type", "can_cast",
                 "axis_size"}
_CONCRETIZE_CALLS = {"int", "float", "bool", "complex"}
_CONCRETIZE_METHODS = {"item", "tolist", "__index__"}
_ASARRAY_RE = re.compile(r"^(np|numpy)\.(asarray|array|asanyarray)$")
_JIT_LIKE = {"jit"}
_TRACE_WRAPPERS = {"jit", "pallas_call", "shard_map", "checkify"}


class _FuncInfo:
    def __init__(self, src: SourceFile, node: ast.AST, qual: str):
        self.src = src
        self.node = node
        self.qual = qual
        self.name = node.name
        # Names this function references (call targets, attribute tails,
        # bare loads) — the over-approximate call-graph edge set.
        self.refs: Set[str] = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name):
                self.refs.add(sub.id)
            elif isinstance(sub, ast.Attribute):
                self.refs.add(sub.attr)


def _file_imports(src: SourceFile) -> Tuple[Set[str], Set[str]]:
    """(module identifiers, imported names) for a file — the edge filter
    for cross-file reachability.  Generic method names (``plan``, ``step``,
    ``update``) collide across the tree; a ref in file A only matches a
    function in file B when A imports B's module or that name."""
    tails: Set[str] = set()
    names: Set[str] = set()
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                tails.update(alias.name.split("."))
                names.add((alias.asname or alias.name).split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            if node.module:
                tails.update(node.module.split("."))
            for alias in node.names:
                names.add(alias.asname or alias.name)
    return tails, names


class RetraceHazard(Rule):
    """Concretization of traced values inside jit-reachable code.  A
    ``int()`` / ``float()`` / ``.item()`` / ``np.asarray`` on a traced
    array, or a Python ``if``/``while`` branching on one, forces a
    concrete value at trace time — so the compiled program either fails
    or, worse, silently *bakes the register values in* and recompiles on
    every reconfiguration, breaking the repo's ``fabric_retraces=1`` pin
    (the paper's cheap-reconfiguration surface).  The rule walks every
    function reachable (by name, over-approximately) from a ``jax.jit``
    / ``pallas_call`` / ``shard_map`` entry point and flags
    concretization of array-typed values (parameters annotated
    ``jax.Array`` / ``DispatchPlan`` / ``CrossbarRegisters`` / etc.,
    conventional array names, and locals derived from them); ``.shape``
    / ``.ndim`` / ``len()`` and ``is None`` tests are recognised as
    static and stay allowed."""

    code = "FAB002"
    title = "retrace hazard: traced-value concretization under jit"

    # ---- project-level: roots + reachability ---------------------------
    def check(self, project: Project) -> Iterator[Violation]:
        funcs: List[_FuncInfo] = []
        by_name: Dict[str, List[_FuncInfo]] = {}
        imports: Dict[int, Tuple[Set[str], Set[str]]] = {}
        for src in project.files:
            if not self.applies_to(src.rel):
                continue
            imports[id(src)] = _file_imports(src)
            for info in self._functions(src):
                funcs.append(info)
                by_name.setdefault(info.name, []).append(info)

        def edge_ok(src: SourceFile, target: _FuncInfo) -> bool:
            if target.src is src:
                return True
            tails, names = imports.get(id(src), (set(), set()))
            if target.name in names:
                return True
            stem = target.src.path.stem
            if stem == "__init__":
                stem = target.src.path.parent.name
            return stem in tails or stem in names

        reachable: Set[int] = set()
        frontier = [f for src, name in self._roots(project)
                    for f in by_name.get(name, []) if edge_ok(src, f)]
        while frontier:
            info = frontier.pop()
            if id(info) in reachable:
                continue
            reachable.add(id(info))
            for ref in info.refs:
                frontier.extend(f for f in by_name.get(ref, [])
                                if edge_ok(info.src, f))
        for info in funcs:
            if id(info) in reachable:
                yield from self._scan_function(info)

    def _functions(self, src: SourceFile) -> Iterator[_FuncInfo]:
        stack: List[Tuple[ast.AST, str]] = [(src.tree, "")]
        while stack:
            node, prefix = stack.pop()
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qual = f"{prefix}{child.name}"
                    yield _FuncInfo(src, child, f"{src.rel}::{qual}")
                    stack.append((child, qual + "."))
                elif isinstance(child, ast.ClassDef):
                    stack.append((child, f"{prefix}{child.name}."))
                else:
                    stack.append((child, prefix))

    def _roots(self, project: Project
               ) -> List[Tuple[SourceFile, str]]:
        """(file, function-name) pairs handed to a tracing transform:
        ``jax.jit(f)``, ``@jax.jit``, ``partial(jax.jit, f)``,
        ``pl.pallas_call(kernel, ...)``, ``shard_map``-wrapped bodies.
        The file anchors the import-filtered name match."""
        roots: List[Tuple[SourceFile, str]] = []

        def fn_name(arg: ast.AST) -> Optional[str]:
            if isinstance(arg, ast.Name):
                return arg.id
            if isinstance(arg, ast.Attribute):
                return arg.attr
            return None

        def is_wrapper(node: ast.AST) -> bool:
            tail = _dotted(node).split(".")[-1]
            return tail in _TRACE_WRAPPERS

        for src in project.files:
            for node in ast.walk(src.tree):
                if isinstance(node, ast.Call) and is_wrapper(node.func):
                    for arg in node.args[:1]:
                        name = fn_name(arg)
                        if name:
                            roots.append((src, name))
                elif isinstance(node, ast.Call) and \
                        _dotted(node.func).split(".")[-1] == "partial":
                    # partial(jax.jit, f) / partial(shard_map, ...) used
                    # as a decorator marks the decorated function itself;
                    # handled below via decorator_list.
                    if node.args and is_wrapper(node.args[0]) and \
                            len(node.args) > 1:
                        name = fn_name(node.args[1])
                        if name:
                            roots.append((src, name))
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    for dec in node.decorator_list:
                        target = dec.func if isinstance(dec, ast.Call) \
                            else dec
                        inner = None
                        if isinstance(dec, ast.Call) and dec.args:
                            inner = dec.args[0]
                        if is_wrapper(target) or (
                                _dotted(target).split(".")[-1] == "partial"
                                and inner is not None and is_wrapper(inner)):
                            roots.append((src, node.name))
        return roots

    # ---- function-level taint scan -------------------------------------
    def _seed_taint(self, fn: ast.AST) -> Set[str]:
        taint: Set[str] = set()
        args = fn.args
        for a in list(args.posonlyargs) + list(args.args) + \
                list(args.kwonlyargs):
            if a.arg in ("self", "cls"):
                continue
            if a.annotation is not None:
                if _ARRAYISH_ANNOT_RE.search(ast.dump(a.annotation)):
                    taint.add(a.arg)
            elif a.arg in _ARRAYISH_NAMES:
                taint.add(a.arg)
        return taint

    def _tainted(self, node: ast.AST, taint: Set[str]) -> bool:
        if isinstance(node, ast.Name):
            return node.id in taint
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self._tainted(node.value, taint)
        if isinstance(node, ast.Subscript):
            return (self._tainted(node.value, taint)
                    or self._tainted(node.slice, taint))
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            tail = name.split(".")[-1]
            if tail in _STATIC_CALLS:
                return False
            if name.startswith(("jnp.", "jax.")):
                return True
            if isinstance(node.func, ast.Attribute) and \
                    self._tainted(node.func.value, taint):
                return True
            return any(self._tainted(a, taint) for a in node.args) or any(
                self._tainted(kw.value, taint) for kw in node.keywords)
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None` are static under tracing.
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
            return self._tainted(node.left, taint) or any(
                self._tainted(c, taint) for c in node.comparators)
        if isinstance(node, ast.BoolOp):
            return any(self._tainted(v, taint) for v in node.values)
        if isinstance(node, (ast.BinOp,)):
            return (self._tainted(node.left, taint)
                    or self._tainted(node.right, taint))
        if isinstance(node, ast.UnaryOp):
            return self._tainted(node.operand, taint)
        if isinstance(node, ast.IfExp):
            return (self._tainted(node.body, taint)
                    or self._tainted(node.orelse, taint))
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self._tainted(e, taint) for e in node.elts)
        if isinstance(node, ast.Starred):
            return self._tainted(node.value, taint)
        return False

    def _scan_function(self, info: _FuncInfo) -> Iterator[Violation]:
        src, fn = info.src, info.node
        taint = self._seed_taint(fn)
        # Two passes so loop-carried assignments settle.
        for _ in range(2):
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign):
                    value_tainted = self._tainted(node.value, taint)
                    for target in node.targets:
                        for name in self._target_names(target):
                            (taint.add if value_tainted
                             else taint.discard)(name)
                elif isinstance(node, ast.AugAssign) and \
                        isinstance(node.target, ast.Name):
                    if self._tainted(node.value, taint):
                        taint.add(node.target.id)
                elif isinstance(node, ast.AnnAssign) and \
                        isinstance(node.target, ast.Name) and node.value:
                    if self._tainted(node.value, taint):
                        taint.add(node.target.id)
                elif isinstance(node, ast.For):
                    if self._tainted(node.iter, taint):
                        for name in self._target_names(node.target):
                            taint.add(name)
        for node in ast.walk(fn):
            if node is not fn and isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue          # nested defs are scanned as their own info
            if isinstance(node, ast.Call):
                yield from self._check_call(src, node, taint)
            elif isinstance(node, (ast.If, ast.While, ast.IfExp)):
                if self._tainted(node.test, taint):
                    kind = {"If": "if", "While": "while",
                            "IfExp": "conditional expression"}[
                        type(node).__name__]
                    yield from self._emit(
                        src, node,
                        f"Python `{kind}` on a traced array concretizes "
                        f"it at trace time (retrace per value — breaks "
                        f"the fabric_retraces=1 pin); use jnp.where / "
                        f"lax.cond, or read static .shape instead")

    @staticmethod
    def _target_names(target: ast.AST) -> Iterator[str]:
        if isinstance(target, ast.Name):
            yield target.id
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                yield from RetraceHazard._target_names(elt)
        elif isinstance(target, ast.Starred):
            yield from RetraceHazard._target_names(target.value)

    def _check_call(self, src: SourceFile, call: ast.Call,
                    taint: Set[str]) -> Iterator[Violation]:
        name = _dotted(call.func)
        tail = name.split(".")[-1]
        if name in _CONCRETIZE_CALLS and call.args and \
                self._tainted(call.args[0], taint):
            yield from self._emit(
                src, call,
                f"`{name}()` of a traced value forces a concrete read at "
                f"trace time; keep it an array (jnp ops) or hoist the "
                f"read outside the jitted entry point")
        elif tail in _CONCRETIZE_METHODS and \
                isinstance(call.func, ast.Attribute) and \
                self._tainted(call.func.value, taint):
            yield from self._emit(
                src, call,
                f"`.{tail}()` of a traced value forces a concrete read "
                f"at trace time (retrace hazard)")
        elif _ASARRAY_RE.match(name) and call.args and \
                self._tainted(call.args[0], taint):
            yield from self._emit(
                src, call,
                f"`{name}` materializes a traced array on the host at "
                f"trace time; use jnp.asarray (stays traced) or move "
                f"the conversion outside jit")


# ----------------------------------------------------------------------
# FAB003 — internal imports of deprecated shims
# ----------------------------------------------------------------------
class DeprecatedShimImport(Rule):
    """Non-test internal code importing the deprecated seed shims.  The
    shims (``repro.core.crossbar``, the raw
    ``repro.kernels.crossbar_dispatch`` entry points, ``repro.runtime
    .serve.ServeLoop``) exist for *external* callers during migration;
    internal code routing through them bypasses the fabric seam —
    epoch tracking, plan equivalence, the checkify sanitizer — and is
    exactly how the data plane forks.  Package ``__init__`` re-exports
    kept for back-compat carry an explicit suppression."""

    code = "FAB003"
    title = "internal import of a deprecated shim"

    _SHIM_MODULES = {"repro.core.crossbar"}
    _SHIM_NAMES = {
        "repro.kernels.crossbar_dispatch": {"crossbar_plan",
                                            "crossbar_dispatch",
                                            "crossbar_combine"},
        "repro.kernels.crossbar_dispatch.ops": {"crossbar_plan",
                                                "crossbar_dispatch",
                                                "crossbar_combine"},
        "repro.runtime.serve": {"ServeLoop"},
    }
    # The modules that *define* the shims are exempt.
    _DEFINERS = {"core/crossbar.py", "kernels/crossbar_dispatch/ops.py",
                 "runtime/serve.py"}

    def applies_to(self, rel: str) -> bool:
        name = rel.rsplit("/", 1)[-1]
        return rel not in self._DEFINERS and \
            not name.startswith("test_") and "/tests/" not in f"/{rel}"

    def check(self, project: Project) -> Iterator[Violation]:
        for src in project.files:
            if not self.applies_to(src.rel):
                continue
            for node in ast.walk(src.tree):
                if isinstance(node, ast.Import):
                    for alias in node.names:
                        if alias.name in self._SHIM_MODULES:
                            yield from self._emit(
                                src, node,
                                f"import of deprecated shim module "
                                f"`{alias.name}` from internal code; use "
                                f"repro.fabric.Fabric (docs/migration.md)")
                elif isinstance(node, ast.ImportFrom) and node.module:
                    if node.module in self._SHIM_MODULES:
                        yield from self._emit(
                            src, node,
                            f"import from deprecated shim module "
                            f"`{node.module}`; use repro.fabric.Fabric "
                            f"(docs/migration.md)")
                        continue
                    banned = self._SHIM_NAMES.get(node.module, set())
                    hit = sorted({a.name for a in node.names} & banned)
                    if hit:
                        yield from self._emit(
                            src, node,
                            f"import of deprecated entry point(s) "
                            f"{', '.join(hit)} from `{node.module}`; use "
                            f"the fabric seam instead (docs/migration.md)")


# ----------------------------------------------------------------------
# FAB004 — backend-seam conformance
# ----------------------------------------------------------------------
# Fallback contract when the linted tree does not include a
# ReferenceBackend to parse the ground truth from (fixture subtrees).
_REFERENCE_SIGNATURES = {
    "plan": ["dst", "src", "regs"],
    "dispatch": ["x", "plan", "regs", "capacity"],
    "combine": ["y", "plan", "weights"],
}

# The manager's pluggable seams carry the same conformance obligation as
# fabric backends: anything registered behind the seam must present the
# protocol method with the protocol's positional prefix, or callers break
# only on the implementation that drifted.  registry-dict name /
# decorator name -> (seam label, base class, method, positional prefix
# after self).
_SEAM_REGISTRIES = {
    "_FORECASTERS": ("forecaster", "Forecaster",
                     "forecast", ["series", "horizon"]),
    "register_forecaster": ("forecaster", "Forecaster",
                            "forecast", ["series", "horizon"]),
    "_TRACKERS": ("tracker", "Tracker", "log", ["metrics", "step"]),
    "register_tracker": ("tracker", "Tracker", "log", ["metrics", "step"]),
    "_ATTACKERS": ("attacker", "Attacker", "step", ["view", "rng"]),
    "register_attacker": ("attacker", "Attacker", "step", ["view", "rng"]),
}


class BackendSeamConformance(Rule):
    """Every fabric backend must honour the seam.  Classes registered as
    fabric backends (entries of the ``_BACKENDS`` registry dict or
    ``register_fabric_backend(name, Cls)`` calls) must define ``plan`` /
    ``dispatch`` / ``combine`` with the reference backend's positional
    signatures — ``Fabric`` composes ``transfer`` from exactly these, so
    a drifted signature turns into a runtime break *only on the backend
    that drifted*.  The kernels half of the seam: every ``kernels/*/``
    package must pair its ``kernel.py`` with a ``ref.py`` exporting at
    least one public ``*_ref`` oracle — kernels without a bit-equality
    reference cannot be property-tested against the dense plan.

    The manager's seam registries are held to the same standard: classes
    registered as forecasters (``_FORECASTERS`` entries or
    ``@register_forecaster(...)`` decorations) must define
    ``forecast(series, horizon)``, and registered trackers
    (``_TRACKERS`` / ``@register_tracker(...)``) must define
    ``log(metrics, step)`` — with those exact positional prefixes, since
    the manager calls them positionally every tick."""

    code = "FAB004"
    title = "fabric backend / kernel package breaks the seam contract"

    def check(self, project: Project) -> Iterator[Violation]:
        classes: Dict[str, Tuple[SourceFile, ast.ClassDef]] = {}
        for src in project.files:
            for node in ast.walk(src.tree):
                if isinstance(node, ast.ClassDef):
                    classes.setdefault(node.name, (src, node))
        expected = self._reference_signatures(classes)
        for src, node, clsname in self._registered(project):
            entry = classes.get(clsname)
            if entry is None:
                continue          # class defined outside the linted tree
            yield from self._check_class(entry[0], entry[1], expected)
        yield from self._check_seam_registries(project, classes)
        yield from self._check_kernels(project)
        yield from self._check_custom_vjp(project)

    def _reference_signatures(self, classes) -> Dict[str, List[str]]:
        entry = classes.get("ReferenceBackend")
        if entry is None:
            return dict(_REFERENCE_SIGNATURES)
        sigs: Dict[str, List[str]] = {}
        for item in entry[1].body:
            if isinstance(item, ast.FunctionDef) and \
                    item.name in _REFERENCE_SIGNATURES:
                sigs[item.name] = [a.arg for a in item.args.args
                                   if a.arg != "self"]
        for name, args in _REFERENCE_SIGNATURES.items():
            sigs.setdefault(name, list(args))
        return sigs

    def _registered(self, project: Project
                    ) -> Iterator[Tuple[SourceFile, ast.AST, str]]:
        for src in project.files:
            for node in ast.walk(src.tree):
                if isinstance(node, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == "_BACKENDS"
                        for t in node.targets) and \
                        isinstance(node.value, ast.Dict):
                    for v in node.value.values:
                        name = _dotted(v).split(".")[-1]
                        if name:
                            yield src, node, name
                elif isinstance(node, ast.Call) and _dotted(
                        node.func).split(".")[-1] == \
                        "register_fabric_backend" and len(node.args) >= 2:
                    name = _dotted(node.args[1]).split(".")[-1]
                    if name:
                        yield src, node, name

    def _check_class(self, src: SourceFile, cls: ast.ClassDef,
                     expected: Dict[str, List[str]]) -> Iterator[Violation]:
        methods = {item.name: item for item in cls.body
                   if isinstance(item, ast.FunctionDef)}
        bases = {_dotted(b).split(".")[-1] for b in cls.bases}
        for name, want in expected.items():
            fn = methods.get(name)
            if fn is None:
                if bases & {"ReferenceBackend", "PallasBackend",
                            "ShardedBackend"}:
                    continue      # inherited conforming implementation
                yield from self._emit(
                    src, cls,
                    f"registered fabric backend `{cls.name}` does not "
                    f"define `{name}({', '.join(want)})` — Fabric's "
                    f"transfer composition requires it")
                continue
            got = [a.arg for a in fn.args.args if a.arg != "self"]
            if got[:len(want)] != want:
                yield from self._emit(
                    src, fn,
                    f"backend `{cls.name}.{name}` signature "
                    f"({', '.join(got)}) drifts from the reference seam "
                    f"({', '.join(want)})")

    # ---- manager seam registries (forecasters / trackers) -------------
    def _seam_registered(self, project: Project
                         ) -> Iterator[Tuple[SourceFile, str, str]]:
        """(file, registry key, class name) for every class registered
        behind a manager seam — via registry-dict literal or decorator
        (bare or call form)."""
        for src in project.files:
            for node in ast.walk(src.tree):
                if isinstance(node, ast.Assign) and isinstance(
                        node.value, ast.Dict):
                    for t in node.targets:
                        key = getattr(t, "id", None)
                        if key in _SEAM_REGISTRIES:
                            for v in node.value.values:
                                name = _dotted(v).split(".")[-1]
                                if name:
                                    yield src, key, name
                elif isinstance(node, ast.ClassDef):
                    for deco in node.decorator_list:
                        target = deco.func if isinstance(
                            deco, ast.Call) else deco
                        key = _dotted(target).split(".")[-1]
                        if key in _SEAM_REGISTRIES:
                            yield src, key, node.name

    def _check_seam_registries(self, project: Project,
                               classes) -> Iterator[Violation]:
        seen = set()
        for src, key, clsname in self._seam_registered(project):
            label, base, method, want = _SEAM_REGISTRIES[key]
            if (label, clsname) in seen:
                continue
            seen.add((label, clsname))
            entry = classes.get(clsname)
            if entry is None:
                continue          # class defined outside the linted tree
            csrc, cls = entry
            methods = {item.name: item for item in cls.body
                       if isinstance(item, ast.FunctionDef)}
            fn = methods.get(method)
            if fn is None:
                bases = {_dotted(b).split(".")[-1] for b in cls.bases}
                if base in bases:
                    continue      # inherited conforming implementation
                yield from self._emit(
                    csrc, cls,
                    f"registered {label} `{cls.name}` does not define "
                    f"`{method}({', '.join(want)})` — the manager calls "
                    f"it positionally every tick")
                continue
            got = [a.arg for a in fn.args.args if a.arg != "self"]
            if got[:len(want)] != want:
                yield from self._emit(
                    csrc, fn,
                    f"{label} `{cls.name}.{method}` signature "
                    f"({', '.join(got)}) drifts from the seam protocol "
                    f"({', '.join(want)})")

    def _check_kernels(self, project: Project) -> Iterator[Violation]:
        packages: Dict[str, Dict[str, SourceFile]] = {}
        for src in project.files:
            m = re.match(r"(.*kernels/[^/]+)/([^/]+\.py)$", src.rel)
            if m:
                packages.setdefault(m.group(1), {})[m.group(2)] = src
        for pkg, files in sorted(packages.items()):
            if "__init__.py" not in files:
                continue
            anchor = files["__init__.py"]
            node = anchor.tree
            missing = [f for f in ("kernel.py", "ref.py") if f not in files]
            if missing:
                yield from self._emit(
                    anchor, node,
                    f"kernel package `{pkg}` lacks {', '.join(missing)}: "
                    f"every kernel ships with a reference oracle module")
                continue
            if not self._public_defs(files["ref.py"], suffix="_ref"):
                yield from self._emit(
                    files["ref.py"], files["ref.py"].tree,
                    f"kernel package `{pkg}` ref.py exports no public "
                    f"`*_ref` oracle for its kernels")
            if not self._public_defs(files["kernel.py"]):
                yield from self._emit(
                    files["kernel.py"], files["kernel.py"].tree,
                    f"kernel package `{pkg}` kernel.py exports no public "
                    f"entry point")

    @staticmethod
    def _public_defs(src: SourceFile, suffix: str = "") -> List[str]:
        return [n.name for n in src.tree.body
                if isinstance(n, ast.FunctionDef)
                and not n.name.startswith("_") and n.name.endswith(suffix)]

    # ---- custom_vjp pairing (differentiable fabric entry points) ------
    @staticmethod
    def _is_custom_vjp_decorator(deco: ast.AST) -> bool:
        """``@jax.custom_vjp`` / ``@custom_vjp`` or the nondiff form
        ``@functools.partial(jax.custom_vjp, nondiff_argnums=...)``."""
        if _dotted(deco).split(".")[-1] == "custom_vjp":
            return True
        if isinstance(deco, ast.Call) and \
                _dotted(deco.func).split(".")[-1] == "partial" and deco.args:
            return _dotted(deco.args[0]).split(".")[-1] == "custom_vjp"
        return False

    @staticmethod
    def _bwd_oracle_name(fn_name: str) -> str:
        base = fn_name.lstrip("_")
        if base.endswith("_core"):
            base = base[: -len("_core")]
        return base + "_bwd_ref"

    def _check_custom_vjp(self, project: Project) -> Iterator[Violation]:
        """Every custom_vjp entry point in data-plane scope must wire its
        rules (``F.defvjp(fwd, bwd)`` in the same module) and ship a public
        ``{base}_bwd_ref`` dense oracle — in the owning kernel package's
        ref.py for ``kernels/*/`` files, else in the same module.  A custom
        backward that only exists as a trace-time transform cannot be
        property-tested for bit-equality against the dense plan; the oracle
        is what tests/test_fabric_grad.py sweeps against."""
        ref_by_pkg: Dict[str, SourceFile] = {}
        for src in project.files:
            m = re.match(r"(.*kernels/[^/]+)/ref\.py$", src.rel)
            if m:
                ref_by_pkg[m.group(1)] = src
        for src in project.files:
            if not _DATA_PLANE_RE.search(src.rel):
                continue
            defvjp_wired = set()
            for node in ast.walk(src.tree):
                if isinstance(node, ast.Call) and len(node.args) >= 2:
                    d = _dotted(node.func)
                    if d.endswith(".defvjp"):
                        defvjp_wired.add(d[: -len(".defvjp")])
            pkg = re.match(r"(.*kernels/[^/]+)/[^/]+\.py$", src.rel)
            local_public = set(self._public_defs(src))
            for node in ast.walk(src.tree):
                if not isinstance(node, ast.FunctionDef):
                    continue
                if not any(self._is_custom_vjp_decorator(d)
                           for d in node.decorator_list):
                    continue
                if node.name not in defvjp_wired:
                    yield from self._emit(
                        src, node,
                        f"custom_vjp entry point `{node.name}` never calls "
                        f"`{node.name}.defvjp(fwd, bwd)` in this module — "
                        f"an unwired custom_vjp fails at first grad")
                    continue
                oracle = self._bwd_oracle_name(node.name)
                where = "this module"
                found = oracle in local_public
                if pkg is not None and pkg.group(1) in ref_by_pkg:
                    where = f"{pkg.group(1)}/ref.py"
                    found = oracle in self._public_defs(
                        ref_by_pkg[pkg.group(1)])
                if not found:
                    yield from self._emit(
                        src, node,
                        f"custom_vjp entry point `{node.name}` has no "
                        f"public `{oracle}` dense oracle in {where} — the "
                        f"backward cannot be bit-tested against the plan")


# ----------------------------------------------------------------------
# FAB005 — bare clip on address arithmetic
# ----------------------------------------------------------------------
_ACCOUNTING_NAME_RE = re.compile(
    r"keep|ok\b|_ok|mask|valid|alive|drop|error|trash|in_range")


class BareClipAddress(Rule):
    """``jnp.clip`` on an address that feeds an index, in a function with
    no visible drop accounting.  Clipping an out-of-range address aliases
    the packet onto a *real* row — the last slot of the last port —
    instead of the trash row, so a drop silently becomes a mis-delivery.
    Clip-for-safety is fine only where the clipped cases are provably
    already dropped (a ``keep``-style mask or a ``>= 0`` validity
    comparison in the same function, or an explicit ``# fablint:
    drop-accounted`` annotation when the accounting lives elsewhere)."""

    code = "FAB005"
    title = "bare jnp.clip on an address with no drop accounting"

    def applies_to(self, rel: str) -> bool:
        return bool(_DATA_PLANE_RE.search(rel))

    def check(self, project: Project) -> Iterator[Violation]:
        for src in project.files:
            if not self.applies_to(src.rel):
                continue
            for fn in ast.walk(src.tree):
                if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield from self._scan(src, fn)

    def _scan(self, src: SourceFile, fn: ast.AST) -> Iterator[Violation]:
        clips = [n for n in ast.walk(fn) if isinstance(n, ast.Call)
                 and _dotted(n.func) in ("jnp.clip", "jax.numpy.clip",
                                         "np.clip", "numpy.clip")]
        if not clips:
            return
        clip_names: Dict[str, ast.Call] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and node.value in clips and \
                    len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name):
                clip_names[node.targets[0].id] = node.value
        indexed = self._indexed_clips(fn, clips, clip_names)
        if not indexed:
            return
        if self._has_accounting(fn):
            return
        for node in indexed:
            if src.annotated("drop-accounted", node.lineno, node.end_lineno):
                continue
            yield from self._emit(
                src, node,
                "clipped address feeds an index but this function shows "
                "no drop accounting (keep/ok mask, >= 0 validity test); "
                "clipped packets alias onto a real slot instead of the "
                "trash row — account the drop or annotate "
                "`# fablint: drop-accounted`")

    def _indexed_clips(self, fn: ast.AST, clips: List[ast.Call],
                       clip_names: Dict[str, ast.Call]) -> List[ast.AST]:
        """Clip calls (or names bound to them) appearing in index position:
        a subscript slice, ``.at[...]``, or a take indices argument.  Name
        hits resolve back to their defining ``jnp.clip`` call, so the
        violation (and any annotation/suppression) anchors on the clip
        line itself."""
        hits: List[ast.AST] = []

        def uses_clip(index: ast.AST) -> Optional[ast.AST]:
            for sub in ast.walk(index):
                if sub in clips:
                    return sub
                if isinstance(sub, ast.Name) and sub.id in clip_names:
                    return clip_names[sub.id]
            return None

        for node in ast.walk(fn):
            if isinstance(node, ast.Subscript):
                hit = uses_clip(node.slice)
                if hit is not None:
                    hits.append(hit)
            elif isinstance(node, ast.Call):
                tail = _dotted(node.func).split(".")[-1]
                if tail in ("take", "take_along_axis") and \
                        len(node.args) >= 2:
                    hit = uses_clip(node.args[1])
                    if hit is not None:
                        hits.append(hit)
        return hits

    def _has_accounting(self, fn: ast.AST) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and \
                    _ACCOUNTING_NAME_RE.search(node.id):
                return True
            if isinstance(node, ast.Compare):
                for comp in [node.left] + list(node.comparators):
                    if isinstance(comp, ast.Constant) and comp.value == 0:
                        return True
        return False


RULES: List[type] = [ImplicitOOBIndexing, RetraceHazard,
                     DeprecatedShimImport, BackendSeamConformance,
                     BareClipAddress]
