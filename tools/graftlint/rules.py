#
# graftlint rule implementations (R1-R5) over the stdlib ast.
#
# Design notes:
#   - ModuleIndex resolves local aliases to canonical dotted names once per
#     module ("np" -> numpy, "jnp" -> jax.numpy, `from jax import lax` ->
#     jax.lax, `from jax.lax import psum` -> jax.lax.psum), so every rule
#     matches on canonical names and survives import-style drift.
#   - R1 runs a single forward dataflow pass per function (no fixpoint):
#     names assigned from jnp/jax.lax/jax.random/jitted-function results are
#     device-tainted; host materializers (jax.device_get, np.asarray, ...)
#     both SINK taint (their use in a hot context is the finding) and
#     UNTAINT their result (a fetched value is host data).
#   - Heuristics deliberately under-approximate: a rule that cries wolf gets
#     pragma'd into noise.  Every rule has fixture tests in
#     tests/test_graftlint.py proving it fires on the bad shape and stays
#     silent on the corrected one.
#

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional, Set, Tuple

# R11/R12 (the concurrency pass) live in concurrency.py: they analyze a SET
# of modules as one program, unlike the per-module rules in this file.
RULES = (
    "R1", "R2", "R3", "R4", "R5", "R6", "R7", "R8", "R9", "R10", "R11", "R12",
)
PER_MODULE_RULES = RULES[:10]
CONCURRENCY_RULES = ("R11", "R12")

FindingTuple = Tuple[str, int, str, str]  # (rule, line, message, func-qualname)

# -- canonical-name machinery -------------------------------------------------

_MODULE_CANON = {
    "numpy": "numpy",
    "jax": "jax",
    "jax.numpy": "jax.numpy",
    "jax.lax": "jax.lax",
    "jax.random": "jax.random",
    "functools": "functools",
    "time": "time",
}

# canonical prefixes whose call results live on device
_DEVICE_PREFIXES = ("jax.numpy.", "jax.lax.", "jax.random.")
_DEVICE_CALLS = {"jax.device_put", "jax.jit", "jax.pmap", "jax.vmap"}

# host materializers: calling these ON a device value is the sync point
_HOST_FETCHERS = {"jax.device_get"}
_NUMPY_SINKS = {
    "numpy.asarray", "numpy.array", "numpy.sum", "numpy.mean", "numpy.max",
    "numpy.min", "numpy.any", "numpy.all", "numpy.isfinite", "numpy.isnan",
    "numpy.unique", "numpy.sort", "numpy.argsort", "numpy.concatenate",
}
_BUILTIN_SINKS = {"float", "int", "bool"}
_METHOD_SINKS = {"item", "tolist", "to_py"}

_LAX_COLLECTIVES = {
    "psum", "pmean", "pmax", "pmin", "psum_scatter", "all_gather",
    "all_to_all", "ppermute", "pshuffle", "axis_index",
}

_LEGACY_NP_RANDOM = {
    "seed", "rand", "randn", "randint", "random", "random_sample", "ranf",
    "sample", "uniform", "normal", "standard_normal", "choice", "shuffle",
    "permutation", "beta", "binomial", "exponential", "gamma", "poisson",
    "lognormal", "multivariate_normal", "bytes",
}

_SHAPE_PARAM_RE = re.compile(
    r"^(k|n|m|d|num_\w+|n_\w+|max_iter|max_depth|chunk|chunk_\w+|shape|"
    r"size|rounds|round_size|depth|width|n?dims?|axis)$"
)

_F64_ATTRS = {"numpy.float64", "jax.numpy.float64"}
_F64_STRINGS = {"float64", "f8", "double", ">f8", "<f8"}


class ModuleIndex:
    """Per-module alias resolution + module-level jit-function registry."""

    def __init__(self, tree: ast.Module, path: str):
        self.path = path
        self.aliases: Dict[str, str] = {}       # local name -> canonical dotted
        self.mesh_names: Set[str] = set()       # names imported from parallel/mesh
        self.str_constants: Dict[str, int] = {} # module-level NAME = "literal" lines
        self.jitted: Set[str] = set()           # module-level jit-wrapped defs
        self._collect(tree)

    def _collect(self, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    canon = _MODULE_CANON.get(a.name, a.name)
                    self.aliases[a.asname or a.name.split(".")[0]] = (
                        canon if a.asname else canon.split(".")[0]
                    )
                    if a.asname:
                        self.aliases[a.asname] = canon
            elif isinstance(node, ast.ImportFrom):
                mod = ("." * node.level) + (node.module or "")
                for a in node.names:
                    local = a.asname or a.name
                    base = node.module or ""
                    canon_base = _MODULE_CANON.get(base, base)
                    self.aliases[local] = f"{canon_base}.{a.name}" if canon_base else a.name
                    if _is_mesh_module(mod):
                        self.mesh_names.add(local)
        for stmt in tree.body:
            if isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Constant) \
                    and isinstance(stmt.value.value, str):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        self.str_constants[t.id] = stmt.lineno
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if _jit_decorator_info(stmt, self) is not None:
                    self.jitted.add(stmt.name)
            if isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Call):
                if self.dotted(stmt.value.func) == "jax.jit":
                    for t in stmt.targets:
                        if isinstance(t, ast.Name):
                            self.jitted.add(t.id)

    def dotted(self, node: ast.AST) -> Optional[str]:
        """Canonical dotted name of a Name/Attribute chain, or None."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = self.aliases.get(node.id, node.id)
        parts.append(root)
        return ".".join(reversed(parts))


def _is_mesh_module(mod: str) -> bool:
    m = mod.lstrip(".")
    return (
        m.endswith("parallel.mesh")
        or m == "mesh"
        or m.endswith(".mesh")
    )


def _jit_decorator_info(
    fn: ast.AST, index: "ModuleIndex"
) -> Optional[Tuple[Set[str], bool]]:
    """(static param names, has_any_statics) when `fn` is jit-decorated,
    else None.  Handles @jax.jit, @jit, and @partial(jax.jit, ...)."""
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return None
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = index.dotted(target)
        statics: Set[str] = set()
        has_statics = False
        if name == "jax.jit":
            if isinstance(dec, ast.Call):
                has_statics, statics = _collect_statics(dec, params)
            return statics, has_statics
        if name in ("functools.partial", "partial") and isinstance(dec, ast.Call):
            if dec.args and index.dotted(dec.args[0]) == "jax.jit":
                has_statics, statics = _collect_statics(dec, params)
                return statics, has_statics
    return None


def _collect_statics(call: ast.Call, params: List[str]) -> Tuple[bool, Set[str]]:
    statics: Set[str] = set()
    found = False
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            found = True
            for v in ast.walk(kw.value):
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    statics.add(v.value)
        elif kw.arg == "static_argnums":
            found = True
            for v in ast.walk(kw.value):
                if isinstance(v, ast.Constant) and isinstance(v.value, int):
                    if 0 <= v.value < len(params):
                        statics.add(params[v.value])
    return found, statics


# -- R1: host sync in hot path ------------------------------------------------

class _R1FunctionPass:
    def __init__(self, index: ModuleIndex, fn, qualname: str, in_jit: bool):
        self.index = index
        self.fn = fn
        self.qualname = qualname
        self.in_jit = in_jit
        self.tainted: Set[str] = set()
        self.findings: List[FindingTuple] = []

    # taint evaluation ---------------------------------------------------
    def _is_host_materializer(self, call: ast.Call) -> bool:
        name = self.index.dotted(call.func)
        if name in _HOST_FETCHERS or name in _NUMPY_SINKS:
            return True
        if name in _BUILTIN_SINKS:
            return True
        f = call.func
        return isinstance(f, ast.Attribute) and f.attr in _METHOD_SINKS

    def _expr_tainted(self, node: ast.AST) -> bool:
        """Whether evaluating `node` can yield (or contain) a device value.
        Recursive so untainting boundaries cut their whole subtree: host
        materializers return host data, range/len return host ints, and
        .shape/.ndim/.dtype reads are trace-time constants."""
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return False
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return False
        if isinstance(node, ast.Call):
            if self._is_host_materializer(node):
                return False
            name = self.index.dotted(node.func)
            if name in ("range", "len", "print", "repr", "str"):
                return False
            if name is not None and (
                name.startswith(_DEVICE_PREFIXES)
                or name in _DEVICE_CALLS
                or name in self.index.jitted
            ):
                return True
            # fall through: a call ON a tainted value (x.sum()) or WITH a
            # tainted arg conservatively stays device-valued
        return any(self._expr_tainted(c) for c in ast.iter_child_nodes(node))

    def _assign_targets(self, target: ast.AST, taint: bool) -> None:
        for sub in ast.walk(target):
            if isinstance(sub, ast.Name):
                if taint:
                    self.tainted.add(sub.id)
                else:
                    self.tainted.discard(sub.id)

    # statement walk -----------------------------------------------------
    def run(self) -> List[FindingTuple]:
        self._walk(self.fn.body, loop_depth=0)
        return self.findings

    def _walk(self, body: List[ast.stmt], loop_depth: int) -> None:
        for stmt in body:
            self._check_stmt_exprs(stmt, loop_depth)
            if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                value = stmt.value
                if value is not None:
                    taint = self._expr_tainted(value) and not (
                        isinstance(value, ast.Call)
                        and self._is_host_materializer(value)
                    )
                    targets = (
                        stmt.targets
                        if isinstance(stmt, ast.Assign)
                        else [stmt.target]
                    )
                    for t in targets:
                        self._assign_targets(t, taint)
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                if self._expr_tainted(stmt.iter):
                    self._assign_targets(stmt.target, True)
                self._walk(stmt.body, loop_depth + 1)
                self._walk(stmt.orelse, loop_depth)
            elif isinstance(stmt, ast.While):
                self._walk(stmt.body, loop_depth + 1)
                self._walk(stmt.orelse, loop_depth)
            elif isinstance(stmt, ast.If):
                self._walk(stmt.body, loop_depth)
                self._walk(stmt.orelse, loop_depth)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                self._walk(stmt.body, loop_depth)
            elif isinstance(stmt, ast.Try):
                self._walk(stmt.body, loop_depth)
                for h in stmt.handlers:
                    self._walk(h.body, loop_depth)
                self._walk(stmt.orelse, loop_depth)
                self._walk(stmt.finalbody, loop_depth)
            # nested defs get their own pass (module driver); skip here

    def _own_expr_nodes(self, stmt: ast.stmt) -> Iterator[ast.AST]:
        """The statement's OWN expressions: compound statements yield only
        their header (iter/test/items) — their bodies are checked per child
        statement by _walk, at the right loop depth — and nested function
        defs are skipped entirely (they get their own pass)."""
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            roots: List[ast.AST] = [stmt.iter]
        elif isinstance(stmt, (ast.While, ast.If)):
            roots = [stmt.test]
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            roots = [i.context_expr for i in stmt.items]
        elif isinstance(stmt, ast.Try):
            return
        else:
            roots = [stmt]
        stack = list(roots)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))

    def _check_stmt_exprs(self, stmt: ast.stmt, loop_depth: int) -> None:
        hot = loop_depth > 0 or self.in_jit
        if not hot:
            return
        for node in self._own_expr_nodes(stmt):
            if not isinstance(node, ast.Call):
                continue
            name = self.index.dotted(node.func)
            is_fetch = name in _HOST_FETCHERS
            is_sink = (
                name in _NUMPY_SINKS
                or (name in _BUILTIN_SINKS and isinstance(node.func, ast.Name))
                or (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in _METHOD_SINKS
                )
            )
            if not (is_fetch or is_sink):
                continue
            # device_get outside a loop is the sanctioned batched fetch
            if is_fetch and loop_depth == 0:
                continue
            args_tainted = any(self._expr_tainted(a) for a in node.args) or (
                isinstance(node.func, ast.Attribute)
                and self._expr_tainted(node.func.value)
            )
            if args_tainted:
                where = "inside a loop" if loop_depth > 0 else "inside a jitted body"
                label = name or f".{node.func.attr}()"  # type: ignore[union-attr]
                self.findings.append(
                    (
                        "R1",
                        node.lineno,
                        f"{label} on a device-array value {where}: hidden "
                        "device->host sync per iteration — batch ONE "
                        "jax.device_get after the loop (docs/graftlint.md#r1)",
                        self.qualname,
                    )
                )


# -- R2: recompile risk -------------------------------------------------------

def _r2_check_function(
    fn: ast.FunctionDef, index: ModuleIndex, qualname: str
) -> Iterator[FindingTuple]:
    info = _jit_decorator_info(fn, index)
    if info is None:
        return
    statics, _has = info
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    for p in params:
        if p in statics:
            continue
        if _SHAPE_PARAM_RE.match(p):
            yield (
                "R2",
                fn.lineno,
                f"jit param '{p}' of '{fn.name}' looks like a Python "
                "shape/config scalar: every distinct value recompiles — add "
                "it to static_argnames or hoist it out of the jitted "
                "signature (docs/graftlint.md#r2)",
                qualname,
            )
    dynamic = {p for p in params if p not in statics}
    for node in _walk_own_body(fn):
        if isinstance(node, (ast.If, ast.While)):
            test = node.test
            if _is_structural_test(test, index):
                continue
            names = _dynamic_value_names(test)
            hits = sorted(names & dynamic)
            if hits:
                kind = "while" if isinstance(node, ast.While) else "if"
                yield (
                    "R2",
                    node.lineno,
                    f"Python {kind} on non-static jit arg(s) "
                    f"{', '.join(hits)} inside '{fn.name}': the branch "
                    "traces one side only (or fails on a tracer) — use "
                    "jax.lax.cond/while_loop or mark the arg static "
                    "(docs/graftlint.md#r2)",
                    qualname,
                )


def _walk_own_body(fn: ast.FunctionDef) -> Iterator[ast.AST]:
    """Walk fn's statements without descending into nested function defs
    (nested defs are usually lax.scan/while bodies with their own rules)."""
    stack: List[ast.AST] = list(fn.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield node
        for child in ast.iter_child_nodes(node):
            stack.append(child)


_STATIC_ATTRS = {"ndim", "shape", "dtype", "size", "sharding"}


def _dynamic_value_names(test: ast.AST) -> Set[str]:
    """Names whose VALUE the test depends on.  `x.shape`/`x.ndim`/`x.dtype`
    reads are trace-time constants of a traced arg, so their base name does
    not count."""
    static_bases: Set[int] = set()
    for node in ast.walk(test):
        if (
            isinstance(node, ast.Attribute)
            and node.attr in _STATIC_ATTRS
            and isinstance(node.value, ast.Name)
        ):
            static_bases.add(id(node.value))
    return {
        n.id
        for n in ast.walk(test)
        if isinstance(n, ast.Name) and id(n) not in static_bases
    }


def _is_structural_test(test: ast.AST, index: ModuleIndex) -> bool:
    """Tests that are static under jit: isinstance/hasattr checks, `is
    None` comparisons, attribute-only conditions (config flags)."""
    for node in ast.walk(test):
        if isinstance(node, ast.Call):
            name = index.dotted(node.func)
            if name in ("isinstance", "hasattr", "callable", "len"):
                return True
        if isinstance(node, ast.Compare):
            if any(
                isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
            ):
                return True
    return False


# -- R3: collective axis names must be bound through parallel/mesh ------------

def _r3_axis_arg(call: ast.Call, fname: str) -> Optional[ast.AST]:
    short = fname.rsplit(".", 1)[-1]
    for kw in call.keywords:
        if kw.arg in ("axis_name", "axis_names"):
            return kw.value
    pos = 0 if short == "axis_index" else 1
    if len(call.args) > pos:
        return call.args[pos]
    return None


def _r3_check_call(
    call: ast.Call, index: ModuleIndex, qualname: str
) -> Iterator[FindingTuple]:
    name = index.dotted(call.func)
    if name is None:
        return
    short = name.rsplit(".", 1)[-1]
    is_collective = (
        name.startswith("jax.lax.") or name == f"jax.lax.{short}"
    ) and short in _LAX_COLLECTIVES
    if not is_collective and short in _LAX_COLLECTIVES and name == short:
        # `from jax.lax import psum` resolves through aliases to jax.lax.psum
        is_collective = True
    if is_collective:
        axis = _r3_axis_arg(call, name)
        if axis is not None:
            yield from _r3_flag_literals(axis, short, index, qualname, call.lineno)
        return
    if short in ("PartitionSpec", "P", "NamedSharding") or short == "Mesh":
        source = call.args[1] if short == "Mesh" and len(call.args) > 1 else None
        nodes = [source] if source is not None else list(call.args) + [
            kw.value for kw in call.keywords
        ]
        for n in nodes:
            if n is None:
                continue
            yield from _r3_flag_literals(n, short, index, qualname, call.lineno)


_R3_CONSTRUCTORS = ("PartitionSpec", "P", "NamedSharding", "Mesh")


def _iter_pruning_nested_constructors(node: ast.AST) -> Iterator[ast.AST]:
    """Walk `node` but skip subtrees of nested PartitionSpec/Mesh/... calls:
    ast.walk visits those Call nodes in their own right, so descending into
    them here would report each literal twice (e.g. P("data") inside
    NamedSharding(mesh, P("data"))) and inflate --baseline budgets."""
    stack = [node]
    while stack:
        n = stack.pop()
        if isinstance(n, ast.Call):
            f = n.func
            short = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None
            )
            if short in _R3_CONSTRUCTORS:
                continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


def _r3_flag_literals(
    node: ast.AST, context: str, index: ModuleIndex, qualname: str, line: int
) -> Iterator[FindingTuple]:
    for sub in _iter_pruning_nested_constructors(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            yield (
                "R3",
                getattr(sub, "lineno", line),
                f"string-literal axis name '{sub.value}' in {context}: a "
                "typo only explodes at trace time on a real mesh — bind "
                "through parallel/mesh (DATA_AXIS/MODEL_AXIS) "
                "(docs/graftlint.md#r3)",
                qualname,
            )
        elif isinstance(sub, ast.Name):
            if sub.id in index.str_constants and sub.id not in index.mesh_names:
                yield (
                    "R3",
                    getattr(sub, "lineno", line),
                    f"axis name '{sub.id}' is a module-local string, not "
                    "bound through parallel/mesh — import "
                    "DATA_AXIS/MODEL_AXIS instead (docs/graftlint.md#r3)",
                    qualname,
                )


# -- R4: nondeterminism -------------------------------------------------------

def _r4_check_call(
    call: ast.Call, index: ModuleIndex, qualname: str, at_module_scope: bool
) -> Iterator[FindingTuple]:
    name = index.dotted(call.func)
    if name is None:
        return
    if name.startswith("numpy.random."):
        short = name.rsplit(".", 1)[-1]
        if short in _LEGACY_NP_RANDOM:
            yield (
                "R4",
                call.lineno,
                f"np.random.{short} uses the hidden GLOBAL RNG: results "
                "depend on import/call order across workers — use "
                "np.random.default_rng(seed) threaded from the caller "
                "(docs/graftlint.md#r4)",
                qualname,
            )
            return
        if short == "default_rng" and not call.args and not call.keywords:
            yield (
                "R4",
                call.lineno,
                "np.random.default_rng() without a seed: every rank draws "
                "a different stream — thread an explicit seed "
                "(docs/graftlint.md#r4)",
                qualname,
            )
            return
    if at_module_scope and (
        name.startswith("numpy.random.") or name.startswith("jax.random.")
    ):
        yield (
            "R4",
            call.lineno,
            f"{name} at module scope: RNG state drawn at import time "
            "differs per process — construct RNGs inside the function "
            "that uses them (docs/graftlint.md#r4)",
            qualname,
        )


def _r4_check_for(
    node: ast.For, qualname: str, index: ModuleIndex
) -> Iterator[FindingTuple]:
    it = node.iter
    is_set = isinstance(it, (ast.Set, ast.SetComp)) or (
        isinstance(it, ast.Call)
        and isinstance(it.func, ast.Name)
        and it.func.id == "set"
    )
    if is_set:
        yield (
            "R4",
            node.lineno,
            "iterating a set: order is hash-seed dependent, so anything "
            "derived (collective payloads, encode_attrs dicts) diverges "
            "across ranks — wrap in sorted() (docs/graftlint.md#r4)",
            qualname,
        )


# -- R5: float64 discipline in solver kernels ---------------------------------

def _r5_applies(path: str) -> bool:
    norm = path.replace("\\", "/")
    return "/ops/" in norm or norm.startswith("ops/")


def _r5_check(
    node: ast.AST, index: ModuleIndex, qualname: str
) -> Iterator[FindingTuple]:
    if isinstance(node, ast.Attribute):
        name = index.dotted(node)
        if name in _F64_ATTRS:
            yield (
                "R5",
                node.lineno,
                f"{name.replace('numpy', 'np').replace('jax.np', 'jnp')} in a "
                "solver kernel: TPUs demote f64 to slow emulation, and numpy "
                "f64 scalars silently promote weak-typed jnp math — keep "
                "device math f32/bf16 or pragma host-side use "
                "(docs/graftlint.md#r5)",
                qualname,
            )
    elif isinstance(node, ast.keyword) and node.arg == "dtype":
        v = node.value
        if isinstance(v, ast.Constant) and isinstance(v.value, str) \
                and v.value in _F64_STRINGS:
            yield (
                "R5",
                v.lineno,
                f"dtype='{v.value}' in a solver kernel: f64 on TPU is "
                "emulated — use float32/bfloat16 on device "
                "(docs/graftlint.md#r5)",
                qualname,
            )
        elif isinstance(v, ast.Name) and v.id == "float" \
                and "float" not in index.aliases:
            yield (
                "R5",
                v.lineno,
                "dtype=float is float64: TPUs emulate f64 — spell the "
                "intended width explicitly (docs/graftlint.md#r5)",
                qualname,
            )


# -- R6: raw wall clocks in engine/serving modules ----------------------------
# Every timestamp the framework takes must come from ONE clock so spans,
# counters, duration series, and trace exports are mutually comparable —
# srml-scope's profiling.now()/span().  A module-local time.perf_counter()
# is invisible to the telemetry snapshots and the Chrome-trace export, and
# (worse) time.time() is not even monotonic.  Scoped to the package
# (benchmark/test harness code may time however it likes); profiling.py is
# the clock's home and exempt.  time.monotonic/time.sleep stay allowed —
# deadline polling loops are control flow, not observability.

_R6_CLOCKS = {"time.time", "time.perf_counter", "time.perf_counter_ns"}


def _r6_applies(path: str) -> bool:
    norm = path.replace("\\", "/")
    if norm.endswith("/profiling.py") or norm == "profiling.py":
        return False
    return "spark_rapids_ml_tpu/" in norm or norm.startswith(
        "spark_rapids_ml_tpu"
    )


def _r6_check_call(
    call: ast.Call, index: ModuleIndex, qualname: str
) -> Iterator[FindingTuple]:
    name = index.dotted(call.func)
    if name in _R6_CLOCKS:
        yield (
            "R6",
            call.lineno,
            f"{name}() in an engine/serving module: timing outside "
            "srml-scope is invisible to spans, telemetry snapshots, and "
            "trace exports (and time.time is not monotonic) — use "
            "profiling.now() or profiling.span() (docs/observability.md#r6)",
            qualname,
        )


# -- R7: every thread must be named -------------------------------------------
# The srml-watch flight recorder, trace exports, and watchdog reports all
# attribute events to thread NAMES ("srml-serve-km", "srml-precompile-3",
# "srml-watch-hb-r0").  An unnamed threading.Thread shows up as "Thread-7" —
# useless in a hang dump and unstable across runs — so every Thread
# constructed inside the package must pass name=.  Scoped like R6 to
# spark_rapids_ml_tpu/ (tests/benchmarks may thread however they like).

_R7_THREADS = {"threading.Thread", "threading.Timer"}


def _r7_applies(path: str) -> bool:
    norm = path.replace("\\", "/")
    return "spark_rapids_ml_tpu/" in norm or norm.startswith(
        "spark_rapids_ml_tpu"
    )


def _r7_check_call(
    call: ast.Call, index: ModuleIndex, qualname: str
) -> Iterator[FindingTuple]:
    name = index.dotted(call.func)
    if name not in _R7_THREADS:
        return
    if any(kw.arg == "name" for kw in call.keywords):
        return
    yield (
        "R7",
        call.lineno,
        f"{name}(...) without name=: the flight recorder, trace exports, "
        "and watchdog reports attribute events by thread name — an "
        "anonymous 'Thread-N' is useless in a hang dump.  Pass "
        "name=\"srml-<subsystem>-...\" (docs/observability.md#r7)",
        qualname,
    )


# -- R8: remote-DMA confinement + paired start/wait ---------------------------
# pltpu.make_async_remote_copy is inter-chip RDMA: a wrong device_id or a
# mis-sequenced semaphore does not raise — it wedges the ring (or silently
# corrupts a neighbor's HBM).  The API therefore lives in ONE audited
# module, parallel/exchange.py (DeviceSection.ring_shift), and every other
# engine composes ring exchanges through it.  Additionally, a DMA handle
# (remote or local make_async_copy) that is .start()ed but never .wait()ed
# in the same kernel body races the output block's flush — the same
# undefined-DMA-ordering hazard the qres grid restructure fixed — so the
# pairing is checked per function body.

_R8_REMOTE = "make_async_remote_copy"
_R8_DMA_MAKERS = {"make_async_remote_copy", "make_async_copy"}


def _r8_applies(path: str) -> bool:
    norm = path.replace("\\", "/")
    return "spark_rapids_ml_tpu/" in norm or norm.startswith(
        "spark_rapids_ml_tpu"
    )


def _r8_confined(path: str) -> bool:
    norm = path.replace("\\", "/")
    return norm.endswith("parallel/exchange.py")


def _r8_short(func: ast.AST, index: ModuleIndex) -> Optional[str]:
    name = index.dotted(func)
    if name:
        return name.rsplit(".", 1)[-1]
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _r8_check_call(
    call: ast.Call, index: ModuleIndex, qualname: str, path: str
) -> Iterator[FindingTuple]:
    if _r8_short(call.func, index) == _R8_REMOTE and not _r8_confined(path):
        yield (
            "R8",
            call.lineno,
            "make_async_remote_copy outside parallel/exchange.py: the "
            "inter-chip DMA surface is confined to the ONE audited module "
            "— compose ring exchanges through "
            "exchange.DeviceSection.ring_shift (docs/graftlint.md#r8)",
            qualname,
        )


def _r8_check_function(
    fn: ast.FunctionDef, index: ModuleIndex, qualname: str
) -> Iterator[FindingTuple]:
    dma_vars: Dict[str, int] = {}   # local name -> assignment line
    started: Dict[str, int] = {}    # local name -> first .start() line
    waited: Set[str] = set()
    for node in _walk_own_body(fn):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            if _r8_short(node.value.func, index) in _R8_DMA_MAKERS:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        dma_vars[t.id] = node.lineno
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and isinstance(node.func.value, ast.Name)
        ):
            base = node.func.value.id
            if node.func.attr == "start":
                started.setdefault(base, node.lineno)
            elif node.func.attr == "wait":
                waited.add(base)
    for name, line in sorted(started.items(), key=lambda kv: kv[1]):
        if name in dma_vars and name not in waited:
            yield (
                "R8",
                line,
                f"DMA handle '{name}' is start()ed but never wait()ed in "
                "this kernel body: an unwaited async copy races the output "
                "block's flush (undefined ordering; can wedge the device) "
                "— pair every start() with a wait() before the body "
                "returns (docs/graftlint.md#r8)",
                qualname,
            )


# -- R9: unbounded waits + silent teardown swallows ---------------------------
# The distributed lifecycle's characteristic failure is the HANG: a dead
# peer turns every timeout-less `.result()` / `.wait()` / `.acquire()` /
# `.join()` into a forever-block that no watchdog can attribute ("hang for
# 5 minutes, then die without naming the culprit" — the srml-shield
# motivation).  Scoped to spark_rapids_ml_tpu/{parallel,serving}/ — the
# modules that wait on OTHER processes and threads; solver/engine code
# blocks only on the device runtime, whose waits jax owns.
#
# Two shapes:
#   (a) obj.result()/wait()/acquire()/join() with NO arguments at all —
#       any argument (positional deadline or timeout=) bounds the wait and
#       passes, which also keeps "".join(parts) (always has its iterable)
#       and Condition.wait(remaining) out of scope.  Deliberately
#       under-approximate: a timeout variable that is None at runtime is
#       invisible to the AST.
#   (b) `except Exception:` / `except BaseException:` / bare `except:`
#       whose body performs NO call and NO raise — a teardown error
#       swallowed without even a logged event (the TpuContext.__exit__
#       shape this PR fixed).  Any call in the handler body (logger,
#       counter, cleanup) counts as handling.

_R9_WAITERS = {"result", "wait", "acquire", "join"}
_R9_BROAD_TYPES = {"Exception", "BaseException", "builtins.Exception",
                   "builtins.BaseException"}


def _r9_applies(path: str) -> bool:
    norm = path.replace("\\", "/")
    return (
        "spark_rapids_ml_tpu/parallel/" in norm
        or "spark_rapids_ml_tpu/serving/" in norm
    )


def _r9_check_call(
    call: ast.Call, index: ModuleIndex, qualname: str
) -> Iterator[FindingTuple]:
    if not isinstance(call.func, ast.Attribute):
        return
    attr = call.func.attr
    if attr not in _R9_WAITERS:
        return
    if call.args or call.keywords:
        return  # any deadline/timeout argument bounds the wait
    yield (
        "R9",
        call.lineno,
        f".{attr}() without a timeout: a dead peer or wedged worker turns "
        "this into a forever-block no watchdog can attribute — pass a "
        "timeout (and surface the expiry as a typed error) "
        "(docs/graftlint.md#r9)",
        qualname,
    )


def _r9_check_except(
    handler: ast.ExceptHandler, index: ModuleIndex, qualname: str
) -> Iterator[FindingTuple]:
    t = handler.type
    if t is not None:
        name = index.dotted(t)
        if name not in _R9_BROAD_TYPES:
            return  # narrow handler (or a tuple of specific types): fine
    for node in ast.walk(ast.Module(body=handler.body, type_ignores=[])):
        if isinstance(node, (ast.Call, ast.Raise)):
            return  # logged / counted / re-raised: handled
    caught = index.dotted(t) if t is not None else "everything (bare except)"
    yield (
        "R9",
        handler.lineno,
        f"`except {caught}` swallows the error without a logged event: a "
        "teardown failure that vanishes here is the next silent hang's "
        "root cause — log it (or count it) before suppressing "
        "(docs/graftlint.md#r9)",
        qualname,
    )


# -- R10: raw-socket confinement + bounded socket waits -----------------------
# The srml-wire control plane (parallel/netplane.py) is the ONE audited
# home of the raw socket API inside the package: a stray socket.socket()
# elsewhere is an unbounded, un-lease-fenced, un-fault-injectable side
# channel the chaos matrix can never exercise (the R8 confinement argument,
# ported from remote-DMA to the network).  Within netplane itself, every
# blocking socket wait must be poll-bounded: a `.recv()`/`.accept()` whose
# function body has no PRECEDING `.settimeout()` is the wire analog of
# R9's timeout-less `.result()` — a dead peer turns it into a forever-block
# no watchdog can attribute.

_R10_CONSTRUCTORS = {"socket.socket", "socket.create_connection"}
_R10_WAITERS = {"recv", "accept"}


def _r10_applies(path: str) -> bool:
    norm = path.replace("\\", "/")
    return "spark_rapids_ml_tpu/" in norm or norm.startswith(
        "spark_rapids_ml_tpu"
    )


def _r10_confined(path: str) -> bool:
    norm = path.replace("\\", "/")
    return norm.endswith("parallel/netplane.py")


def _r10_check_call(
    call: ast.Call, index: ModuleIndex, qualname: str, path: str
) -> Iterator[FindingTuple]:
    name = index.dotted(call.func)
    if name in _R10_CONSTRUCTORS and not _r10_confined(path):
        yield (
            "R10",
            call.lineno,
            f"{name} outside parallel/netplane.py: the raw socket surface "
            "is confined to the ONE audited wire module — route control "
            "traffic through TcpControlPlane / CoordinatorServer so it is "
            "lease-fenced, fault-injectable, and bounded "
            "(docs/graftlint.md#r10)",
            qualname,
        )


def _r10_check_function(
    fn: ast.FunctionDef, index: ModuleIndex, qualname: str
) -> Iterator[FindingTuple]:
    """Within netplane.py: every recv/accept must follow a settimeout in
    the SAME function body (the local-invariant discipline — a reader
    helper enforces its own poll bound instead of trusting callers)."""
    first_settimeout: Optional[int] = None
    waits: List[Tuple[int, str]] = []
    for node in _walk_own_body(fn):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
        ):
            continue
        attr = node.func.attr
        if attr == "settimeout":
            if first_settimeout is None or node.lineno < first_settimeout:
                first_settimeout = node.lineno
        elif attr in _R10_WAITERS:
            waits.append((node.lineno, attr))
    for line, attr in sorted(waits):
        if first_settimeout is None or line < first_settimeout:
            yield (
                "R10",
                line,
                f".{attr}() with no preceding .settimeout() in this "
                "function body: a dead peer turns the read into a "
                "forever-block no watchdog can attribute — set the poll "
                "timeout where the wait happens (docs/graftlint.md#r10)",
                qualname,
            )


# -- driver -------------------------------------------------------------------

def lint_tree(
    tree: ast.Module, index: ModuleIndex, selected: Set[str]
) -> List[FindingTuple]:
    findings: List[FindingTuple] = []

    # function-scoped passes (R1 dataflow, R2 jit checks), with qualnames
    def visit_functions(body, prefix: str, enclosing_jit: bool) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{stmt.name}"
                is_jit = (
                    _jit_decorator_info(stmt, index) is not None
                    or enclosing_jit
                )
                if "R1" in selected and isinstance(stmt, ast.FunctionDef):
                    findings.extend(
                        _R1FunctionPass(index, stmt, qual, is_jit).run()
                    )
                if "R2" in selected and isinstance(stmt, ast.FunctionDef):
                    findings.extend(_r2_check_function(stmt, index, qual))
                if (
                    "R8" in selected
                    and isinstance(stmt, ast.FunctionDef)
                    and _r8_applies(index.path)
                ):
                    findings.extend(_r8_check_function(stmt, index, qual))
                if (
                    "R10" in selected
                    and isinstance(stmt, ast.FunctionDef)
                    and _r10_confined(index.path)
                ):
                    findings.extend(_r10_check_function(stmt, index, qual))
                visit_functions(stmt.body, f"{qual}.", is_jit)
            elif isinstance(stmt, ast.ClassDef):
                visit_functions(stmt.body, f"{prefix}{stmt.name}.", enclosing_jit)
            elif hasattr(stmt, "body") and isinstance(
                getattr(stmt, "body"), list
            ):
                visit_functions(stmt.body, prefix, enclosing_jit)
                for extra in ("orelse", "finalbody"):
                    b = getattr(stmt, extra, None)
                    if b:
                        visit_functions(b, prefix, enclosing_jit)
                for h in getattr(stmt, "handlers", []) or []:
                    visit_functions(h.body, prefix, enclosing_jit)

    visit_functions(tree.body, "", False)

    # module-wide single-node rules (R3/R4/R5) with module-scope detection
    module_stmts = set()
    for stmt in tree.body:
        if not isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            for n in ast.walk(stmt):
                module_stmts.add(id(n))

    qual_of: Dict[int, str] = {}

    def map_quals(body, prefix: str) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                qual = f"{prefix}{stmt.name}"
                for n in ast.walk(stmt):
                    qual_of.setdefault(id(n), qual)
                map_quals(stmt.body, f"{qual}.")

    map_quals(tree.body, "")

    for node in ast.walk(tree):
        qual = qual_of.get(id(node), "")
        if isinstance(node, ast.Call):
            if "R3" in selected:
                findings.extend(_r3_check_call(node, index, qual))
            if "R4" in selected:
                findings.extend(
                    _r4_check_call(node, index, qual, id(node) in module_stmts)
                )
            if "R6" in selected and _r6_applies(index.path):
                findings.extend(_r6_check_call(node, index, qual))
            if "R7" in selected and _r7_applies(index.path):
                findings.extend(_r7_check_call(node, index, qual))
            if "R8" in selected and _r8_applies(index.path):
                findings.extend(_r8_check_call(node, index, qual, index.path))
            if "R9" in selected and _r9_applies(index.path):
                findings.extend(_r9_check_call(node, index, qual))
            if "R10" in selected and _r10_applies(index.path):
                findings.extend(_r10_check_call(node, index, qual, index.path))
        if (
            isinstance(node, ast.ExceptHandler)
            and "R9" in selected
            and _r9_applies(index.path)
        ):
            findings.extend(_r9_check_except(node, index, qual))
        if isinstance(node, ast.For) and "R4" in selected:
            findings.extend(_r4_check_for(node, qual, index))
        if "R5" in selected and _r5_applies(index.path):
            findings.extend(_r5_check(node, index, qual))
    return findings
