"""The jaxlint static passes (JL000-JL006).

Each pass mechanizes an invariant that previously lived as prose in
CHANGES.md.  Everything here is stdlib-only ``ast`` analysis — the lint CLI
must run in a bare CI interpreter without jax installed.

Codes
-----
JL000  malformed ``# jaxlint:`` annotation (unknown directive, reasonless
       ``allow-*``, unparseable ``shapes(...)``)
JL001  host sync in a hot-path function (``jax.device_get`` / ``.item()`` /
       ``float()/int()/bool()`` of device values / ``np.asarray`` of device
       values) without ``allow-sync(reason)``
JL002  ``jnp.concatenate``/``jnp.stack`` in a sharded code path — the
       PR 3/5 XLA-CPU SPMD mixed-tiling-concat miscompute class
JL003  cache state escaping a masked scan body without a row mask: neither
       routed through a masked select (``tree_map`` + ``jnp.where``) nor
       produced by a call given the row mask at its writes
       (``decode_step(..., active=...)``)
JL004  Python ``if``/``while``/``assert`` on a traced value inside a jitted
       function
JL005  ``jax.jit`` call in the tick path without a declared shape budget
JL006  dead import
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

from .contracts import AnnotationIndex, parse_annotations, parse_shapes_decl
from .findings import Finding

# Scope defaults ------------------------------------------------------------

# Modules whose every jnp.concatenate/jnp.stack is a JL002 finding: these
# carry batched cache trees whose batch axis may be sharded over "data".
SHARDED_PATH_MODULES: Tuple[str, ...] = (
    "repro/serving/sharded.py",
    "repro/serving/engine.py",
    "repro/serving/dense.py",
    "repro/serving/static_admission.py",
    "repro/launch/specs.py",
    "repro/models/inference.py",
)

# Modules whose jax.jit calls feed the serving tick and therefore need an
# explicit compiled-shape budget declaration (JL005).
TICK_PATH_MODULES: Tuple[str, ...] = (
    "repro/serving/sharded.py",
    "repro/serving/engine.py",
)

# Calls whose outputs count as already-masked cache state for JL003: the
# per-leaf select itself, and the ragged extend whose body masks its rows.
MASKED_PRODUCERS: Tuple[str, ...] = (
    "tree_map",
    "tree_map_with_path",
    "where",
    "select",
    "prefill_extend_ragged",
)

# Calls whose outputs count as masked only when given the row mask as the
# ``ROW_MASK_KEYWORD`` argument: they mask rows at each write they make
# (the decode step, and the stacked dual-cache write it ends in).
ROW_MASKED_PRODUCERS: Tuple[str, ...] = (
    "decode_step",
    "lazy_promote_and_write",
)
ROW_MASK_KEYWORD = "active"

# Parameter names that seed JL003's cache-flow tracking.
CACHE_PARAM_NAMES: FrozenSet[str] = frozenset(
    {"carry", "caches", "cache", "old", "state"}
)

SAFE_TRACER_ATTRS: FrozenSet[str] = frozenset(
    {"shape", "ndim", "dtype", "size", "sharding"}
)
SAFE_TRACER_CALLS: FrozenSet[str] = frozenset(
    {"len", "isinstance", "getattr", "hasattr", "type", "id"}
)

ALL_CODES: Tuple[str, ...] = (
    "JL000", "JL001", "JL002", "JL003", "JL004", "JL005", "JL006",
)


@dataclass
class ModuleContext:
    path: str  # as passed on the CLI, '/'-separated
    source: str
    tree: ast.Module
    ann: AnnotationIndex
    lines: List[str] = field(default_factory=list)
    parents: Dict[ast.AST, ast.AST] = field(default_factory=dict)

    @classmethod
    def parse(cls, path: str, source: str) -> "ModuleContext":
        tree = ast.parse(source, filename=path)
        ctx = cls(
            path=path.replace("\\", "/"),
            source=source,
            tree=tree,
            ann=parse_annotations(source),
            lines=source.splitlines(),
        )
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                ctx.parents[child] = parent
        return ctx

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def in_modules(self, suffixes: Iterable[str]) -> bool:
        return any(self.path.endswith(s) for s in suffixes)

    def finding(self, code: str, lineno: int, message: str) -> Finding:
        return Finding(
            code=code,
            path=self.path,
            line=lineno,
            message=message,
            text=self.line_text(lineno),
        )


# Shared AST helpers --------------------------------------------------------


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _name_targets(target: ast.AST) -> List[str]:
    """Flatten assignment targets into plain names (ignores attrs/subscripts)."""
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out: List[str] = []
        for elt in target.elts:
            out.extend(_name_targets(elt))
        return out
    if isinstance(target, ast.Starred):
        return _name_targets(target.value)
    return []


def _functions(tree: ast.AST):
    """Yield (funcdef, ancestors) for every def/async def, outermost first."""
    stack: List[Tuple[ast.AST, Tuple[ast.AST, ...]]] = [(tree, ())]
    while stack:
        node, anc = stack.pop()
        for child in ast.iter_child_nodes(node):
            child_anc = anc
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                child_anc = anc  # already extended below
            stack.append((child, child_anc + ((node,) if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)) else ())))
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node, anc


def _assignments_in_order(fn: ast.AST) -> List[ast.Assign]:
    out = [n for n in ast.walk(fn) if isinstance(n, ast.Assign)]
    out.sort(key=lambda n: n.lineno)
    return out


# jnp/jax calls that compute on host metadata, not device values
_HOST_SAFE_CALLS: FrozenSet[str] = frozenset(
    {
        "jax.device_get",
        "jnp.dtype",
        "jnp.shape",
        "jnp.ndim",
        "jnp.size",
        "jnp.result_type",
        "jnp.issubdtype",
        "jax.eval_shape",
        "jax.tree_util.tree_structure",
    }
)


def _contains_device_call(node: ast.AST, tainted: Set[str]) -> bool:
    """True if the expression evaluates on-device values: a jnp./jax. call
    (other than the host-safe metadata helpers) or a reference to a
    device-tainted name."""
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            d = _dotted(n.func) or ""
            if d in _HOST_SAFE_CALLS:
                continue
            if d.startswith("jnp.") or d.startswith("jax."):
                return True
        elif isinstance(n, ast.Name) and n.id in tainted:
            return True
    return False


def _parent_map(expr: ast.AST) -> Dict[ast.AST, ast.AST]:
    return {
        child: parent
        for parent in ast.walk(expr)
        for child in ast.iter_child_nodes(parent)
    }


def _tainted_value_uses(expr: ast.AST, tainted: Set[str]) -> List[ast.Name]:
    """Tainted Name nodes used *as values* in `expr` — uses under shape-like
    attributes, len()/isinstance() calls, or `is None` compares don't count."""
    parents = _parent_map(expr)
    hits: List[ast.Name] = []
    for n in ast.walk(expr):
        if not (isinstance(n, ast.Name) and n.id in tainted):
            continue
        parent = parents.get(n)
        if isinstance(parent, ast.Attribute) and parent.attr in SAFE_TRACER_ATTRS:
            continue
        if (
            isinstance(parent, ast.Call)
            and n in parent.args
            and isinstance(parent.func, ast.Name)
            and parent.func.id in SAFE_TRACER_CALLS
        ):
            continue
        if isinstance(parent, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in parent.ops
        ):
            continue
        hits.append(n)
    return hits


def _func_params(fn) -> List[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return [n for n in names if n != "self"]


# JL000 — annotation errors -------------------------------------------------


def check_annotations(ctx: ModuleContext) -> List[Finding]:
    out = [
        ctx.finding(
            "JL000",
            d.line,
            f"malformed jaxlint annotation '{d.name}'"
            + (
                " (allow-* suppressions require a reason in parens)"
                if d.name.startswith("allow-")
                else " (unknown directive)"
            ),
        )
        for d in ctx.ann.errors
    ]
    for directives in ctx.ann.by_line.values():
        for d in directives:
            if d.name == "shapes" and parse_shapes_decl(d.arg) is None:
                out.append(
                    ctx.finding(
                        "JL000",
                        d.line,
                        "unparseable shapes(...) declaration: expected "
                        "shapes(name=COUNT|tag, ...)",
                    )
                )
    return out


# JL001 — host sync in hot path ---------------------------------------------


def _is_hot_function(fn, ctx: ModuleContext, ancestors) -> bool:
    for anc in ancestors:
        if getattr(anc, "__jaxlint_hot__", False):
            return True
    for dec in fn.decorator_list:
        d = _dotted(dec) or _dotted(getattr(dec, "func", ast.Pass())) or ""
        if d == "hot_path" or d.endswith(".hot_path"):
            return True
    return ctx.ann.scope_marker("hot-path", fn.lineno)


def check_host_sync(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    hot_spans: List[Tuple[int, int]] = []  # (lineno, end_lineno) of hot defs
    for fn, anc in _functions(ctx.tree):
        if _is_hot_function(fn, ctx, anc):
            fn.__jaxlint_hot__ = True  # noqa — marker for nested lookups
            hot_spans.append((fn.lineno, fn.end_lineno or fn.lineno))
    if not hot_spans:
        return out

    def in_hot(node) -> bool:
        return any(lo <= node.lineno <= hi for lo, hi in hot_spans)

    # device-taint over local names, assignments in source order
    tainted: Set[str] = set()
    for st in _assignments_in_order(ctx.tree):
        if not in_hot(st):
            continue
        targets: List[str] = []
        for t in st.targets:
            targets.extend(_name_targets(t))
        rhs = _dotted(getattr(st.value, "func", ast.Pass())) or ""
        if rhs == "jax.device_get" or rhs.startswith("np."):
            tainted.difference_update(targets)  # pulled to host
        elif _contains_device_call(st.value, tainted):
            tainted.update(targets)
        else:
            tainted.difference_update(targets)

    def emit(node, what: str) -> None:
        if ctx.ann.suppressed("JL001", node.lineno):
            return
        out.append(
            ctx.finding(
                "JL001",
                node.lineno,
                f"{what} in hot-path function blocks the tick on a host "
                "sync — hoist out of the tick or annotate "
                "`# jaxlint: allow-sync(reason)` (collect() is the only "
                "sanctioned sync point)",
            )
        )

    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call) and in_hot(node)):
            continue
        d = _dotted(node.func) or ""
        if d == "jax.device_get":
            emit(node, "jax.device_get")
        elif d in ("np.asarray", "numpy.asarray") and node.args:
            arg = node.args[0]
            benign = isinstance(
                arg, (ast.Constant, ast.List, ast.Tuple)
            ) or (isinstance(arg, ast.Name) and arg.id not in tainted)
            if not benign:
                emit(node, "np.asarray of device value")
        elif (
            isinstance(node.func, ast.Name)
            and node.func.id in ("float", "int", "bool")
            and len(node.args) == 1
            and _contains_device_call(node.args[0], tainted)
        ):
            emit(node, f"{node.func.id}() of device value")
        elif (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "item"
            and not node.args
            and _contains_device_call(node.func.value, tainted)
        ):
            emit(node, ".item()")
    return out


# JL002 — concat on sharded axis --------------------------------------------


def check_sharded_concat(ctx: ModuleContext) -> List[Finding]:
    spans: List[Tuple[int, int]]
    if ctx.in_modules(SHARDED_PATH_MODULES):
        spans = [(1, len(ctx.lines) or 1)]
    else:
        spans = [
            (fn.lineno, fn.end_lineno or fn.lineno)
            for fn, _ in _functions(ctx.tree)
            if ctx.ann.scope_marker("sharded-path", fn.lineno)
        ]
    if not spans:
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func) or ""
        if d not in ("jnp.concatenate", "jnp.stack"):
            continue
        if not any(lo <= node.lineno <= hi for lo, hi in spans):
            continue
        if ctx.ann.suppressed("JL002", node.lineno):
            continue
        out.append(
            ctx.finding(
                "JL002",
                node.lineno,
                f"{d} in a sharded code path — XLA CPU's SPMD partitioner "
                "miscomputes mixed-tiling concats on sharded batch axes "
                "(PR 3/5); use the splice helpers in launch/specs.py "
                "(splice_caches / alloc_batched_caches) or annotate "
                "`# jaxlint: allow-concat(reason)` for non-batch axes",
            )
        )
    return out


# JL003 — unmasked cache write ----------------------------------------------

_PLAIN, _CACHE, _RAW, _MASKED = "plain", "cache", "raw", "masked"


def _is_masked_producer(call: ast.Call) -> bool:
    d = _dotted(call.func) or ""
    last = d.rsplit(".", 1)[-1]
    if last in MASKED_PRODUCERS:
        return True
    return last in ROW_MASKED_PRODUCERS and any(
        kw.arg == ROW_MASK_KEYWORD
        and not (isinstance(kw.value, ast.Constant) and kw.value.value is None)
        for kw in call.keywords)


def check_masked_scan_body(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    for fn, _anc in _functions(ctx.tree):
        if not ctx.ann.scope_marker("masked-scan-body", fn.lineno):
            continue
        state: Dict[str, str] = {
            p: _CACHE for p in _func_params(fn) if p in CACHE_PARAM_NAMES
        }

        def names_state(expr) -> str:
            worst = _PLAIN
            for n in ast.walk(expr):
                if isinstance(n, ast.Name):
                    s = state.get(n.id, _PLAIN)
                    if s == _RAW:
                        return _RAW
                    if s == _CACHE:
                        worst = _CACHE
            return worst

        for st in _assignments_in_order(fn):
            targets: List[str] = []
            for t in st.targets:
                targets.extend(_name_targets(t))
            if not targets:
                continue
            v = st.value
            if isinstance(v, ast.Call):
                if _is_masked_producer(v):
                    new = _MASKED
                elif names_state(v) in (_CACHE, _RAW):
                    new = _RAW
                else:
                    new = _PLAIN
            else:
                new = names_state(v)
                if new == _PLAIN and isinstance(v, ast.Name):
                    new = state.get(v.id, _PLAIN)
            for t in targets:
                state[t] = new

        # (a) .at[...].set/add without a masked select in the value
        for node in ast.walk(fn):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("set", "add", "mul", "max", "min")
            ):
                continue
            base = node.func.value
            if not (
                isinstance(base, ast.Subscript)
                and isinstance(base.value, ast.Attribute)
                and base.value.attr == "at"
            ):
                continue
            masked = any(
                isinstance(n, ast.Call) and _is_masked_producer(n)
                for a in node.args
                for n in ast.walk(a)
            )
            if masked or ctx.ann.suppressed("JL003", node.lineno):
                continue
            out.append(
                ctx.finding(
                    "JL003",
                    node.lineno,
                    ".at[...] write inside a masked scan body without a "
                    "per-row select — padding rows will be corrupted; wrap "
                    "the value in jnp.where(active, ...) or annotate "
                    "`# jaxlint: allow-unmasked-write(reason)`",
                )
            )

        # (b) raw cache state escaping through the return
        for node in ast.walk(fn):
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            raw = sorted(
                {
                    n.id
                    for n in ast.walk(node.value)
                    if isinstance(n, ast.Name) and state.get(n.id) == _RAW
                }
            )
            if not raw or ctx.ann.suppressed("JL003", node.lineno):
                continue
            out.append(
                ctx.finding(
                    "JL003",
                    node.lineno,
                    f"cache state {raw} escapes the masked scan body without "
                    "a row mask: route it through the per-leaf masked "
                    "select (tree_map + jnp.where over the pre-step tree) "
                    "or produce it with the row mask at the write "
                    "(decode_step(..., active=...)) — short/padding rows "
                    "will see unmasked writes",
                )
            )
    return out


# JL004 — tracer leak -------------------------------------------------------


def _jit_static_params(call: ast.Call, fn) -> Set[str]:
    """Parameter names excluded from tracing by static_argnames/argnums."""
    static: Set[str] = set()
    params = _func_params(fn)
    for kw in call.keywords or []:
        vals: List[ast.AST] = []
        if isinstance(kw.value, (ast.Tuple, ast.List)):
            vals = list(kw.value.elts)
        elif isinstance(kw.value, ast.Constant):
            vals = [kw.value]
        if kw.arg == "static_argnames":
            static.update(
                v.value
                for v in vals
                if isinstance(v, ast.Constant) and isinstance(v.value, str)
            )
        elif kw.arg == "static_argnums":
            for v in vals:
                if isinstance(v, ast.Constant) and isinstance(v.value, int):
                    if 0 <= v.value < len(params):
                        static.add(params[v.value])
    return static


def _jitted_functions(ctx: ModuleContext):
    """Yield (funcdef, static_param_names) for functions traced under jit."""
    defs: Dict[str, List] = {}
    for fn, _ in _functions(ctx.tree):
        defs.setdefault(fn.name, []).append(fn)

    # decorator forms
    for fn, _ in _functions(ctx.tree):
        for dec in fn.decorator_list:
            d = _dotted(dec) or ""
            if d in ("jax.jit", "jit"):
                yield fn, set()
                break
            if isinstance(dec, ast.Call):
                dd = _dotted(dec.func) or ""
                if dd in ("jax.jit", "jit"):
                    yield fn, _jit_static_params(dec, fn)
                    break
                if dd in ("functools.partial", "partial") and dec.args:
                    inner = _dotted(dec.args[0]) or ""
                    if inner in ("jax.jit", "jit"):
                        yield fn, _jit_static_params(dec, fn)
                        break

    # call forms: jax.jit(fn, ...) / self._mesh_jit(fn, kind=...)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func) or ""
        if not (d in ("jax.jit", "jit") or d.rsplit(".", 1)[-1].endswith("mesh_jit")):
            continue
        for arg in node.args[:1]:
            if isinstance(arg, ast.Name) and arg.id in defs:
                for fn in defs[arg.id]:
                    yield fn, _jit_static_params(node, fn)


def check_tracer_leak(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    seen: Set[int] = set()
    for fn, static in _jitted_functions(ctx):
        if id(fn) in seen:
            continue
        seen.add(id(fn))
        tainted: Set[str] = set(_func_params(fn)) - static
        for st in _assignments_in_order(fn):
            targets: List[str] = []
            for t in st.targets:
                targets.extend(_name_targets(t))
            if _tainted_value_uses(st.value, tainted):
                tainted.update(targets)
        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                test = node.test
            elif isinstance(node, ast.Assert):
                test = node.test
            else:
                continue
            hits = _tainted_value_uses(test, tainted)
            if not hits or ctx.ann.suppressed("JL004", node.lineno):
                continue
            kind = type(node).__name__.lower()
            names = sorted({h.id for h in hits})
            out.append(
                ctx.finding(
                    "JL004",
                    node.lineno,
                    f"Python {kind} on traced value(s) {names} inside jitted "
                    f"function '{fn.name}' — leaks a tracer (ConcretizationError "
                    "at best, silent constant-folding at worst); use "
                    "jnp.where/lax.cond, or mark the argument static",
                )
            )
    return out


# JL005 — untracked compiled shape ------------------------------------------


def check_shape_budget(ctx: ModuleContext) -> List[Finding]:
    if not ctx.in_modules(TICK_PATH_MODULES):
        return []
    out: List[Finding] = []

    def decl_covers(lineno: int, enclosing) -> bool:
        if ctx.ann.shapes_decl(lineno) is not None:
            return True
        return any(
            ctx.ann.shapes_decl(fn.lineno) is not None for fn in enclosing
        )

    # walk with an explicit def-stack so each jit call knows its enclosing defs
    def visit(node, stack):
        is_def = isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        if is_def:
            stack = stack + [node]
        if isinstance(node, ast.Call):
            d = _dotted(node.func) or ""
            if d in ("jax.jit", "jit") and not decl_covers(node.lineno, stack):
                out.append(
                    ctx.finding(
                        "JL005",
                        node.lineno,
                        "jax.jit in the tick path without a declared shape "
                        "budget — every compiled shape here is tick latency; "
                        "annotate the enclosing def with "
                        "`# jaxlint: shapes(name=COUNT|per-structure)` and "
                        "account for it in Engine.COMPILE_SHAPE_BUDGETS",
                    )
                )
        if is_def:
            for dec in node.decorator_list:
                dd = _dotted(dec) or _dotted(getattr(dec, "func", ast.Pass()))
                inner = ""
                if isinstance(dec, ast.Call) and dec.args:
                    inner = _dotted(dec.args[0]) or ""
                if (dd in ("jax.jit", "jit")
                        or inner in ("jax.jit", "jit")) and not decl_covers(
                            dec.lineno, stack):
                    out.append(
                        ctx.finding(
                            "JL005",
                            dec.lineno,
                            "jitted def in the tick path without a declared "
                            "shape budget — annotate with "
                            "`# jaxlint: shapes(name=COUNT|per-structure)`",
                        )
                    )
        for child in ast.iter_child_nodes(node):
            visit(child, stack)

    visit(ctx.tree, [])
    return out


# JL006 — dead imports ------------------------------------------------------


def check_dead_imports(ctx: ModuleContext) -> List[Finding]:
    if ctx.path.endswith("__init__.py"):
        return []
    imports: List[Tuple[str, ast.stmt]] = []  # (bound name, stmt)
    import_nodes: Set[ast.AST] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Import):
            import_nodes.add(node)
            for alias in node.names:
                imports.append(
                    (alias.asname or alias.name.split(".")[0], node)
                )
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            import_nodes.add(node)
            for alias in node.names:
                if alias.name == "*":
                    continue
                imports.append((alias.asname or alias.name, node))
    if not imports:
        return []

    # a defensive `try: import x` is a capability probe, not a dead import
    guarded: Set[ast.AST] = set()
    for node in import_nodes:
        p = ctx.parents.get(node)
        while p is not None:
            if isinstance(p, (ast.Try, ast.If)):
                guarded.add(node)
                break
            p = ctx.parents.get(p)

    used: Set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    for n in ast.walk(node.value):
                        if isinstance(n, ast.Constant) and isinstance(
                            n.value, str
                        ):
                            used.add(n.value)

    out: List[Finding] = []
    for name, node in imports:
        if name in used or name == "_" or node in guarded:
            continue
        text = ctx.line_text(node.lineno)
        if "noqa" in text:
            continue
        if ctx.ann.suppressed("JL006", node.lineno):
            continue
        out.append(
            ctx.finding(
                "JL006",
                node.lineno,
                f"imported name '{name}' is unused",
            )
        )
    return out


# Driver --------------------------------------------------------------------

PASSES = {
    "JL000": check_annotations,
    "JL001": check_host_sync,
    "JL002": check_sharded_concat,
    "JL003": check_masked_scan_body,
    "JL004": check_tracer_leak,
    "JL005": check_shape_budget,
    "JL006": check_dead_imports,
}


def run_passes(
    ctx: ModuleContext, select: Optional[Iterable[str]] = None
) -> List[Finding]:
    codes = tuple(select) if select else ALL_CODES
    out: List[Finding] = []
    for code in codes:
        out.extend(PASSES[code](ctx))
    out.sort(key=lambda f: (f.path, f.line, f.code))
    return out
