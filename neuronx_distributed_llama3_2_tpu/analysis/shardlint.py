"""shardlint: AST-based sharding/trace-safety analyzer.

PR 1's hardest bugs were all statically detectable program properties —
a jit trace of an eq-keyed model dataclass silently reused across
different parallel layouts, a ``with_sharding_constraint`` inside a
manual region that the 0.4.x partitioner miscompiles, collectives whose
axis names are only validated at trace time. GSPMD-style annotation
sharding and shard_map's per-axis manual regions make axis/spec
consistency checkable *without a TPU*: this module parses the
framework's own sources with :mod:`ast` and reports violations with
file:line and a fix hint.

Rules (see docs/static_analysis.md for the motivating bug behind each):

SL001  collective axis names must be named constants (``TP_AXIS`` …,
       from ``parallel/state.py``) or function parameters — never
       free-form string literals.
SL002  eq-keyed dataclasses whose methods read global parallel state
       must declare ``__layout_deps__`` (the PR 1 stale-trace class).
SL003  ``PartitionSpec`` arity must not exceed the constrained array's
       rank where both are statically known.
SL004  no host-side nondeterminism or blocking sync (``time.time``,
       ``np.asarray``, ``.block_until_ready()``, ``print``) inside
       jit/shard_map/scan-traced bodies.
SL005  no raw ``with_sharding_constraint`` inside ``shard_map`` bodies
       (the 0.4.x SPMD partitioner miscompiles mixed-manual
       annotations); use ``parallel.layers.constrain``.
SL006  ``lax.axis_index``/``axis_size`` axes must be bound by the
       enclosing ``shard_map``'s explicit ``axis_names``.
SL007  donated ``jax.jit`` calls in ``serving/`` must go through the
       engine's ``_register_program`` registry (anything else is a
       compiled buffer-stealing program graftcheck can never audit).
SL008  the serving engine's device-resident decode arrays
       (``_d_tokens`` …) and their host mirrors (``_tokens`` …) are
       written only inside the blessed funnel methods
       (``RESIDENT_WRITERS`` / ``MIRROR_WRITERS``); any other write is
       a host-state race candidate — it can land between a dispatch
       and its readback or skip the dirty-bit flush discipline. The
       static twin of graftsched's GC010 schedule automaton.

Suppression: append ``# shardlint: disable=SL00x[,SL00y]`` to the
flagged line, or put ``# shardlint: skip-file`` anywhere in the file.
Findings already accepted ship in the gate's baseline file instead
(scripts/shardlint_baseline.txt) so new code can't add to them.

The analyzer is deliberately import-free: it never executes the code it
checks, so it runs identically on a dev laptop, the CPU test tier and a
TPU pod, and it cannot be confused by whatever jax version is installed.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = [
    "AxisEnv",
    "Finding",
    "RULES",
    "lint_file",
    "lint_paths",
    "lint_source",
    "load_axis_env",
]

# rule id -> one-line summary (the catalogue the CLI prints with --rules)
RULES: Dict[str, str] = {
    "SL001": "collective axis name is a free-form string literal",
    "SL002": "eq-keyed dataclass reads parallel state without __layout_deps__",
    "SL003": "PartitionSpec arity exceeds the constrained array rank",
    "SL004": "host-side effect inside a jit/shard_map/scan-traced body",
    "SL005": "raw with_sharding_constraint inside a shard_map body",
    "SL006": "axis_index/axis_size axis not bound by enclosing shard_map",
    "SL007": "ad-hoc donated jax.jit in serving/ outside _register_program",
    "SL008": (
        "write to an engine resident array or host mirror outside the "
        "blessed funnels"
    ),
}

# --- SL008: the serving engine's device-resident decode state and its
# host mirrors are written only through a small set of blessed funnels;
# any other write is a host-state race candidate (it can land between a
# dispatch and its readback, or skip the dirty-bit flush discipline).
# Kept in sync with serving/engine.py — the graftsched automaton checks
# the *dynamic* ordering of these writes, SL008 pins the static surface.
RESIDENT_ARRAYS = frozenset({
    "_d_tokens", "_d_positions", "_d_tables",
    "_d_temps", "_d_topks", "_d_topps", "_d_rng",
})
HOST_MIRRORS = frozenset({
    "_tokens", "_positions", "_tables",
    "_temps", "_topks", "_topps", "_rng",
})
#: methods allowed to rebind/overwrite device residents (dispatch funnels
#: swap the donated outputs back in; flush/prewarm re-upload).
RESIDENT_WRITERS = frozenset({
    "__init__", "prewarm", "_flush_state",
    "_step_async", "_dispatch_sync_decode", "_verify_phase",
    "_mixed_phase",
})
#: methods allowed to write host mirror rows (all of them either mark the
#: lane dirty for _flush_state or are the post-readback commit itself).
MIRROR_WRITERS = frozenset({
    "__init__", "_admit_wave", "_advance_prefills", "_append_block",
    "_read_and_apply", "_release_lane", "_dispatch_sync_decode",
    "_step_async", "_verify_phase", "_mixed_phase",
    "_install_lane_sampling", "_clear_lane_sampling",
})

# functions whose result depends on the live parallel layout: calling one
# from an eq-keyed dataclass method makes the trace layout-dependent while
# the jit cache key (callable __eq__/__hash__ + avals) is not — the PR 1
# stale-trace hazard. Kept in sync with parallel/state.py's getter surface.
LAYOUT_READERS = frozenset(
    {
        "get_parallel_state",
        "get_tensor_model_parallel_size",
        "get_pipeline_model_parallel_size",
        "get_expert_model_parallel_size",
        "get_context_parallel_size",
        "get_data_parallel_size",
        "get_expert_data_parallel_size",
        "get_data_parallel_axes",
        "tensor_parallel_size_or",
        "sequence_parallel_enabled",
        "model_parallel_is_initialized",
        "mesh_is_tp_only",
        "kv_head_shard_size",
    }
)

# collective call -> (positional index, keyword name) of the axis-name
# argument. Covers jax.lax collectives plus the parallel/mappings.py raw
# wrappers (which thread an explicit axis_name through).
_COLLECTIVE_AXIS_ARG: Dict[str, Tuple[int, str]] = {
    "psum": (1, "axis_name"),
    "pmax": (1, "axis_name"),
    "pmin": (1, "axis_name"),
    "pmean": (1, "axis_name"),
    "ppermute": (1, "axis_name"),
    "pshuffle": (1, "axis_name"),
    "all_gather": (1, "axis_name"),
    "psum_scatter": (1, "axis_name"),
    "all_to_all": (1, "axis_name"),
    "axis_index": (0, "axis_name"),
    "axis_size": (0, "axis_name"),
    # parallel/mappings.py raw wrappers
    "_all_gather": (1, "axis_name"),
    "_reduce_scatter": (1, "axis_name"),
    "_split_local": (1, "axis_name"),
}

# host-side calls that must not run under a trace: resolved dotted chain
# (after import-alias resolution) -> why it's flagged.
_HOST_CALL_CHAINS: Dict[str, str] = {
    "time.time": "host clock read folds to a trace-time constant",
    "time.time_ns": "host clock read folds to a trace-time constant",
    "time.monotonic": "host clock read folds to a trace-time constant",
    "time.perf_counter": "host clock read folds to a trace-time constant",
    "datetime.datetime.now": "host clock read folds to a trace-time constant",
    "numpy.asarray": "forces a device->host transfer (blocking sync)",
    "numpy.array": "forces a device->host transfer (blocking sync)",
}

_HOST_BARE_CALLS: Dict[str, str] = {
    "print": "runs at trace time, not per step; use jax.debug.print",
    "input": "blocks the host inside a trace",
    "breakpoint": "blocks the host inside a trace",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation. ``fingerprint`` is line-number-independent
    (rule + path + normalized source text) so the baseline survives
    unrelated edits above the finding."""

    rule: str
    path: str  # repo-relative (or as given)
    line: int
    col: int
    message: str
    hint: str
    source_line: str = ""

    @property
    def fingerprint(self) -> str:
        norm = re.sub(r"\s+", "", self.source_line)
        digest = hashlib.sha1(
            f"{self.rule}|{self.path}|{norm}".encode()
        ).hexdigest()
        return digest[:12]

    def format(self) -> str:
        return (
            f"{self.path}:{self.line}:{self.col}: {self.rule} "
            f"{self.message}\n    hint: {self.hint}"
        )


@dataclasses.dataclass(frozen=True)
class AxisEnv:
    """The axis universe: constant name -> axis string (PP_AXIS -> "pp")
    plus the set of valid axis strings (MESH_AXES)."""

    constants: Dict[str, str]
    axes: frozenset

    @classmethod
    def default(cls) -> "AxisEnv":
        consts = {
            "PP_AXIS": "pp",
            "DP_AXIS": "dp",
            "CP_AXIS": "cp",
            "EP_AXIS": "ep",
            "TP_AXIS": "tp",
        }
        return cls(constants=consts, axes=frozenset(consts.values()))


def load_axis_env(repo_root: str) -> AxisEnv:
    """Parse ``parallel/state.py`` for the ``*_AXIS`` constants and
    ``MESH_AXES`` — the analyzer's single source of axis truth, read the
    same way the runtime reads it (no imports)."""
    state_py = os.path.join(
        repo_root, "neuronx_distributed_llama3_2_tpu", "parallel", "state.py"
    )
    try:
        with open(state_py, "r") as fh:
            tree = ast.parse(fh.read())
    except (OSError, SyntaxError):
        return AxisEnv.default()
    consts: Dict[str, str] = {}
    mesh_axes: Optional[Set[str]] = None
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not isinstance(tgt, ast.Name):
            continue
        if tgt.id.endswith("_AXIS") and isinstance(node.value, ast.Constant):
            if isinstance(node.value.value, str):
                consts[tgt.id] = node.value.value
        elif tgt.id == "MESH_AXES" and isinstance(node.value, (ast.Tuple, ast.List)):
            names = set()
            for elt in node.value.elts:
                if isinstance(elt, ast.Name) and elt.id in consts:
                    names.add(consts[elt.id])
                elif isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                    names.add(elt.value)
            mesh_axes = names
    if not consts:
        return AxisEnv.default()
    return AxisEnv(
        constants=consts, axes=frozenset(mesh_axes or consts.values())
    )


# ---------------------------------------------------------------------------
# Module context: imports, scopes, traced regions
# ---------------------------------------------------------------------------


class _ModuleContext:
    """Per-file AST context shared by all rules: import-alias resolution,
    parent links, function tables, and the traced-region index."""

    def __init__(self, tree: ast.Module, src: str, path: str, axis_env: AxisEnv):
        self.tree = tree
        self.path = path
        self.axis_env = axis_env
        self.lines = src.splitlines()
        # alias -> dotted module/attr it refers to ("np" -> "numpy",
        # "lax" -> "jax.lax", "TP_AXIS" -> "<...>.state.TP_AXIS")
        self.aliases: Dict[str, str] = {}
        # names imported from a parallel ``state`` module that are axis
        # constants per the axis env (local name -> axis string)
        self.axis_constant_names: Dict[str, str] = {}
        self._collect_imports()
        self._parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                self._parents[child] = parent
        # every function/lambda node -> its enclosing function chain params
        self.func_defs: List[ast.AST] = [
            n
            for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        self.defs_by_name: Dict[str, List[ast.AST]] = {}
        for fn in self.func_defs:
            self.defs_by_name.setdefault(fn.name, []).append(fn)
        self.suppressed = self._collect_suppressions(src)
        self.skip_file = any("shardlint: skip-file" in ln for ln in self.lines)
        # traced regions (SL004/005/006)
        self.traced_roots: List[ast.AST] = []  # jit/scan/shard_map bodies
        self.shard_map_sites: List[Tuple[ast.AST, Optional[Set[str]]]] = []
        self._index_traced_regions()

    # -- imports ----------------------------------------------------------

    def _collect_imports(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                for a in node.names:
                    local = a.asname or a.name
                    self.aliases[local] = f"{mod}.{a.name}" if mod else a.name
                    if (
                        a.name in self.axis_env.constants
                        and mod.rsplit(".", 1)[-1] == "state"
                    ):
                        self.axis_constant_names[local] = (
                            self.axis_env.constants[a.name]
                        )

    def resolve_chain(self, node: ast.AST) -> str:
        """Dotted name of an expression ("jax.lax.psum"), with the head
        alias resolved through the import table. Empty string when the
        expression is not a plain name/attribute chain."""
        parts: List[str] = []
        cur = node
        while isinstance(cur, ast.Attribute):
            parts.append(cur.attr)
            cur = cur.value
        if isinstance(cur, ast.Name):
            head = self.aliases.get(cur.id, cur.id)
            parts.append(head)
        else:
            return ""
        return ".".join(reversed(parts))

    # -- suppressions -----------------------------------------------------

    @staticmethod
    def _collect_suppressions(src: str) -> Dict[int, Set[str]]:
        out: Dict[int, Set[str]] = {}
        for i, line in enumerate(src.splitlines(), start=1):
            m = re.search(r"#\s*shardlint:\s*disable=([A-Z0-9, ]+)", line)
            if m:
                out[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
        return out

    def is_suppressed(self, rule: str, line: int) -> bool:
        return rule in self.suppressed.get(line, ())

    # -- traced regions ---------------------------------------------------

    def _resolve_fn_arg(self, arg: ast.AST) -> Optional[ast.AST]:
        """A function-valued argument -> its FunctionDef/Lambda node, or
        None. Follows bare names to a same-file def (first match) and
        unwraps pass-through wrappers (functools.partial, jax.checkpoint,
        jax.remat) one level."""
        if isinstance(arg, ast.Lambda):
            return arg
        if isinstance(arg, ast.Name):
            defs = self.defs_by_name.get(arg.id)
            return defs[0] if defs else None
        if isinstance(arg, ast.Call):
            tail = self.resolve_chain(arg.func).rsplit(".", 1)[-1]
            if tail in ("partial", "checkpoint", "remat") and arg.args:
                return self._resolve_fn_arg(arg.args[0])
        return None

    def _axis_names_set(self, call: ast.Call) -> Optional[Set[str]]:
        """Resolve a shard_map call's ``axis_names`` kwarg to a concrete
        set of axis strings, or None when absent/unresolvable (in both
        cases SL006 has nothing it can say)."""
        expr = None
        for kw in call.keywords:
            if kw.arg == "axis_names":
                expr = kw.value
        if expr is None or not isinstance(expr, (ast.Set, ast.Tuple, ast.List)):
            return None
        out: Set[str] = set()
        for elt in expr.elts:
            val = self.axis_value(elt)
            if val is None:
                return None  # a dynamic element: don't guess
            out.add(val)
        return out

    def axis_value(self, expr: ast.AST) -> Optional[str]:
        """Statically-known axis string of an expression, if any."""
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
            return expr.value
        if isinstance(expr, ast.Name):
            if expr.id in self.axis_constant_names:
                return self.axis_constant_names[expr.id]
            chain = self.aliases.get(expr.id, "")
            tail = chain.rsplit(".", 1)[-1]
            return self.axis_env.constants.get(tail)
        if isinstance(expr, ast.Attribute):
            return self.axis_env.constants.get(expr.attr)
        return None

    def _index_traced_regions(self) -> None:
        # decorator-jitted functions
        for fn in self.func_defs:
            for dec in fn.decorator_list:
                names = {
                    self.resolve_chain(n).rsplit(".", 1)[-1]
                    for n in ast.walk(dec)
                    if isinstance(n, (ast.Name, ast.Attribute))
                }
                if {"jit", "pjit"} & names:
                    self.traced_roots.append(fn)
                    break
        # call-wrapped functions: jit(f), shard_map(f, ...), scan(f, ...)
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            tail = self.resolve_chain(node.func).rsplit(".", 1)[-1]
            if tail not in ("jit", "pjit", "shard_map", "scan"):
                continue
            body = self._resolve_fn_arg(node.args[0])
            if body is None:
                continue
            self.traced_roots.append(body)
            if tail == "shard_map":
                self.shard_map_sites.append((body, self._axis_names_set(node)))

    def region_nodes(self, root: ast.AST) -> Iterable[ast.AST]:
        """All AST nodes inside a traced body (nested defs included —
        a def inside a traced region traces with it)."""
        if isinstance(root, ast.Lambda):
            yield from ast.walk(root.body)
        else:
            for stmt in root.body:
                yield from ast.walk(stmt)


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------


def _src(ctx: _ModuleContext, node: ast.AST) -> str:
    line = getattr(node, "lineno", 0)
    if 1 <= line <= len(ctx.lines):
        return ctx.lines[line - 1]
    return ""


def _finding(
    ctx: _ModuleContext, rule: str, node: ast.AST, message: str, hint: str
) -> Optional[Finding]:
    line = getattr(node, "lineno", 0)
    if ctx.is_suppressed(rule, line):
        return None
    return Finding(
        rule=rule,
        path=ctx.path,
        line=line,
        col=getattr(node, "col_offset", 0),
        message=message,
        hint=hint,
        source_line=_src(ctx, node),
    )


def _rule_sl001(ctx: _ModuleContext) -> List[Finding]:
    """Collective axis names: named constants or parameters only."""
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        tail = ctx.resolve_chain(node.func).rsplit(".", 1)[-1]
        spec = _COLLECTIVE_AXIS_ARG.get(tail)
        if spec is None:
            continue
        pos, kwname = spec
        axis_expr: Optional[ast.AST] = None
        if len(node.args) > pos:
            axis_expr = node.args[pos]
        else:
            for kw in node.keywords:
                if kw.arg == kwname:
                    axis_expr = kw.value
        if axis_expr is None:
            continue
        for sub in ast.walk(axis_expr):
            if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                known = sub.value in ctx.axis_env.axes
                msg = (
                    f"{tail}() axis name is the string literal "
                    f"{sub.value!r}"
                    + ("" if known else " (not a MESH_AXES member)")
                )
                hint = (
                    "import the axis constant from parallel/state.py "
                    "(e.g. TP_AXIS) or take the axis as a parameter"
                    if known
                    else "no such mesh axis exists; this fails only at "
                    "trace time — use a MESH_AXES constant from "
                    "parallel/state.py"
                )
                f = _finding(ctx, "SL001", sub, msg, hint)
                if f:
                    out.append(f)
    return out


def _dataclass_eq_keyed(ctx: _ModuleContext, cls: ast.ClassDef) -> bool:
    """dataclass with eq semantics left on (the jit-cache-key case)."""
    for dec in cls.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        if ctx.resolve_chain(target).rsplit(".", 1)[-1] != "dataclass":
            continue
        if isinstance(dec, ast.Call):
            for kw in dec.keywords:
                if (
                    kw.arg == "eq"
                    and isinstance(kw.value, ast.Constant)
                    and kw.value.value is False
                ):
                    return False
        return True
    return False


def _rule_sl002(ctx: _ModuleContext) -> List[Finding]:
    """eq-keyed dataclasses reading parallel state must declare it."""
    out: List[Finding] = []
    for cls in ast.walk(ctx.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        if not _dataclass_eq_keyed(ctx, cls):
            continue
        declared = any(
            isinstance(stmt, ast.Assign)
            and any(
                isinstance(t, ast.Name) and t.id == "__layout_deps__"
                for t in stmt.targets
            )
            for stmt in cls.body
        )
        if declared:
            continue
        readers: List[str] = []
        for stmt in cls.body:
            if not isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(stmt):
                name = None
                if isinstance(node, ast.Attribute):
                    name = node.attr
                elif isinstance(node, ast.Name):
                    name = node.id
                if name in LAYOUT_READERS and name not in readers:
                    readers.append(name)
        if readers:
            f = _finding(
                ctx,
                "SL002",
                cls,
                f"eq-keyed dataclass {cls.name!r} reads parallel layout "
                f"({', '.join(sorted(readers))}) not reflected in its "
                "jit cache key",
                "declare `__layout_deps__ = (...)` naming the readers "
                "(trace validity then rests on the jax.clear_caches() "
                "fence in initialize/destroy_model_parallel), or make "
                "the layout an eq-participating field",
            )
            if f:
                out.append(f)
    return out


def _walk_scope(stmts: Sequence[ast.stmt]) -> Iterable[ast.AST]:
    """Pre-order walk in SOURCE order (rank inference relies on seeing a
    reassignment after the def it invalidates), without descending into
    nested function/class scopes (those are analyzed as their own
    scope)."""
    stack: List[ast.AST] = list(reversed(stmts))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(
            node,
            (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef),
        ):
            continue
        stack.extend(reversed(list(ast.iter_child_nodes(node))))


def _shape_rank(expr: ast.AST) -> Optional[int]:
    """Rank implied by a shape expression where statically evident."""
    if isinstance(expr, (ast.Tuple, ast.List)):
        if any(isinstance(e, ast.Starred) for e in expr.elts):
            return None
        return len(expr.elts)
    if isinstance(expr, ast.Constant) and isinstance(expr.value, int):
        return 1
    return None


_SHAPE_MAKERS = {"zeros", "ones", "full", "empty", "broadcast_to"}


def _infer_ranks(fn_body: Sequence[ast.stmt]) -> Dict[str, Tuple[int, ast.AST]]:
    """name -> (rank, defining node) for simple local arrays whose rank is
    statically known: jnp.zeros/ones/full/empty with a literal shape,
    x.reshape(...) with literal dims. Reassignment invalidates."""
    ranks: Dict[str, Tuple[int, ast.AST]] = {}
    for node in _walk_scope(fn_body):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not isinstance(tgt, ast.Name):
            continue
        ranks.pop(tgt.id, None)
        val = node.value
        if not isinstance(val, ast.Call):
            continue
        func = val.func
        tail = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else ""
        )
        rank: Optional[int] = None
        if tail in _SHAPE_MAKERS and val.args:
            shape_arg = val.args[1] if tail == "broadcast_to" and len(
                val.args
            ) > 1 else val.args[0]
            rank = _shape_rank(shape_arg)
        elif tail == "reshape" and val.args:
            if len(val.args) == 1:
                rank = _shape_rank(val.args[0])
            elif not any(isinstance(a, ast.Starred) for a in val.args):
                rank = len(val.args)
        if rank is not None:
            ranks[tgt.id] = (rank, node)
    return ranks


def _partition_spec_call(ctx: _ModuleContext, expr: ast.AST) -> Optional[ast.Call]:
    """The innermost PartitionSpec(...) constructor in ``expr``, if any
    (handles NamedSharding(mesh, P(...)) wrapping)."""
    for node in ast.walk(expr):
        if not isinstance(node, ast.Call):
            continue
        chain = ctx.resolve_chain(node.func)
        if chain.rsplit(".", 1)[-1] == "PartitionSpec" or chain.endswith(
            "sharding.PartitionSpec"
        ):
            return node
    return None


def _rule_sl003(ctx: _ModuleContext) -> List[Finding]:
    """Spec arity vs statically-known array rank."""
    out: List[Finding] = []
    scopes: List[Sequence[ast.stmt]] = [ctx.tree.body]
    scopes.extend(
        fn.body
        for fn in ctx.func_defs
    )
    for body in scopes:
        ranks = _infer_ranks(body)
        if not ranks:
            continue
        for node in _walk_scope(body):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            tail = ctx.resolve_chain(node.func).rsplit(".", 1)[-1]
            if tail not in ("with_sharding_constraint", "constrain"):
                continue
            arr = node.args[0]
            if not (isinstance(arr, ast.Name) and arr.id in ranks):
                continue
            if len(node.args) < 2:
                continue
            spec = _partition_spec_call(ctx, node.args[1])
            if spec is None or any(
                isinstance(a, ast.Starred) for a in spec.args
            ):
                continue
            rank, _def_node = ranks[arr.id]
            if len(spec.args) > rank:
                f = _finding(
                    ctx,
                    "SL003",
                    spec,
                    f"PartitionSpec has {len(spec.args)} entries but "
                    f"{arr.id!r} has rank {rank}",
                    "a spec entry per array dim at most (trailing dims "
                    "may be omitted); extra entries fail only at trace "
                    "time on the annotated layout",
                )
                if f:
                    out.append(f)
    return out


def _rule_sl004(ctx: _ModuleContext) -> List[Finding]:
    """Host-side effects inside traced bodies."""
    out: List[Finding] = []
    seen: Set[int] = set()
    for root in ctx.traced_roots:
        for node in ctx.region_nodes(root):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            chain = ctx.resolve_chain(node.func)
            why = None
            what = chain
            if chain in _HOST_CALL_CHAINS:
                why = _HOST_CALL_CHAINS[chain]
            elif chain in _HOST_BARE_CALLS:
                why = _HOST_BARE_CALLS[chain]
            elif chain.startswith("random."):
                why = "host RNG breaks trace determinism; use jax.random"
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready"
            ):
                why = "blocking device sync inside a traced body"
                what = ".block_until_ready()"
            if why is None:
                continue
            seen.add(id(node))
            f = _finding(
                ctx,
                "SL004",
                node,
                f"{what} inside a jit/shard_map/scan-traced body ({why})",
                "move the call outside the traced function; for debug "
                "output use jax.debug.print / jax.debug.callback",
            )
            if f:
                out.append(f)
    return out


def _rule_sl005(ctx: _ModuleContext) -> List[Finding]:
    """with_sharding_constraint inside shard_map bodies."""
    out: List[Finding] = []
    for body, _axes in ctx.shard_map_sites:
        for node in ctx.region_nodes(body):
            if not isinstance(node, ast.Call):
                continue
            tail = ctx.resolve_chain(node.func).rsplit(".", 1)[-1]
            if tail != "with_sharding_constraint":
                continue
            f = _finding(
                ctx,
                "SL005",
                node,
                "raw with_sharding_constraint inside a shard_map body "
                "(the 0.4.x SPMD partitioner miscompiles mixed-manual "
                "annotations; newer jax needs the ambient abstract mesh)",
                "use parallel.layers.constrain — it targets the ambient "
                "abstract mesh — or constrain outside the manual region",
            )
            if f:
                out.append(f)
    return out


def _rule_sl006(ctx: _ModuleContext) -> List[Finding]:
    """axis_index/axis_size axes must be bound by the enclosing shard_map
    when its axis_names are statically known."""
    out: List[Finding] = []
    for body, bound in ctx.shard_map_sites:
        if bound is None:
            continue
        for node in ctx.region_nodes(body):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            tail = ctx.resolve_chain(node.func).rsplit(".", 1)[-1]
            if tail not in ("axis_index", "axis_size"):
                continue
            val = ctx.axis_value(node.args[0])
            if val is None or val in bound:
                continue
            f = _finding(
                ctx,
                "SL006",
                node,
                f"{tail}({val!r}) but the enclosing shard_map binds only "
                f"{sorted(bound)}",
                "add the axis to the shard_map's axis_names (and specs) "
                "or use an axis the region actually binds; unbound axes "
                "fail only at trace time",
            )
            if f:
                out.append(f)
    return out


def _rule_sl007(ctx: _ModuleContext) -> List[Finding]:
    """Donated jits on the serving path must go through the engine's
    ``_register_program`` registry: ``graftcheck.audit_programs`` audits
    exactly the ``_programs`` population (donation aliasing, host
    transfers, purity), so a ``jax.jit(..., donate_argnums=...)`` created
    anywhere else in ``serving/`` is a compiled, buffer-stealing program
    the auditor can never see."""
    norm = ctx.path.replace(os.sep, "/")
    if "/serving/" not in norm and not norm.startswith("serving/"):
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        tail = ctx.resolve_chain(node.func).rsplit(".", 1)[-1]
        if tail != "jit":
            continue
        if not any(
            kw.arg in ("donate_argnums", "donate_argnames")
            for kw in node.keywords
        ):
            continue
        # the registry helper itself is the one sanctioned jit site
        fn = ctx._parents.get(node)
        while fn is not None and not isinstance(
            fn, (ast.FunctionDef, ast.AsyncFunctionDef)
        ):
            fn = ctx._parents.get(fn)
        if fn is not None and fn.name == "_register_program":
            continue
        f = _finding(
            ctx,
            "SL007",
            node,
            "donated jax.jit outside the _programs registry "
            "(_register_program) — invisible to graftcheck's "
            "audit_programs",
            "route the program through PagedServingEngine."
            "_register_program so the registry records its raw fn, "
            "donate_argnums and example avals for the GC002/GC003/GC006 "
            "audits",
        )
        if f:
            out.append(f)
    return out


def _rule_sl008(ctx: _ModuleContext) -> List[Finding]:
    """Writes to the engine's device-resident decode arrays or their host
    mirrors outside the blessed funnels. Every legal write either marks
    the lane dirty for ``_flush_state`` (mirrors) or swaps a dispatched
    program's donated output back in (residents); a write anywhere else
    can land between a dispatch and its readback — exactly the host-state
    race class graftsched's automaton (GC010) catches dynamically, pinned
    here at the source level so it never ships at all."""
    norm = ctx.path.replace(os.sep, "/")
    if "/serving/" not in norm and not norm.startswith("serving/"):
        return []
    out: List[Finding] = []

    def _protected_attr(t: ast.AST) -> Optional[str]:
        if isinstance(t, ast.Subscript):
            t = t.value
        if (
            isinstance(t, ast.Attribute)
            and isinstance(t.value, ast.Name)
            and t.value.id == "self"
        ):
            return t.attr
        return None

    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        else:
            continue
        flat: List[ast.AST] = []
        for t in targets:
            flat.extend(t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t])
        for t in flat:
            attr = _protected_attr(t)
            if attr in RESIDENT_ARRAYS:
                kind, allowed = "resident array", RESIDENT_WRITERS
            elif attr in HOST_MIRRORS:
                kind, allowed = "host mirror", MIRROR_WRITERS
            else:
                continue
            fn = ctx._parents.get(node)
            while fn is not None and not isinstance(
                fn, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                fn = ctx._parents.get(fn)
            if fn is not None and fn.name in allowed:
                continue
            where = fn.name if fn is not None else "<module>"
            f = _finding(
                ctx,
                "SL008",
                node,
                f"write to engine {kind} self.{attr} in {where}() — "
                "outside the blessed funnels",
                "route the write through a blessed funnel "
                "(_release_lane/_install_lane_sampling/... for mirrors, "
                "the dispatch/flush funnels for residents) or, for a new "
                "funnel, add it to shardlint's RESIDENT_WRITERS/"
                "MIRROR_WRITERS with review",
            )
            if f:
                out.append(f)
    return out


_RULE_FNS = (
    _rule_sl001,
    _rule_sl002,
    _rule_sl003,
    _rule_sl004,
    _rule_sl005,
    _rule_sl006,
    _rule_sl007,
    _rule_sl008,
)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def lint_source(
    src: str, path: str = "<string>", axis_env: Optional[AxisEnv] = None
) -> List[Finding]:
    """Lint one source string. Raises SyntaxError on unparsable input."""
    tree = ast.parse(src, filename=path)
    ctx = _ModuleContext(tree, src, path, axis_env or AxisEnv.default())
    if ctx.skip_file:
        return []
    findings: List[Finding] = []
    for rule_fn in _RULE_FNS:
        findings.extend(rule_fn(ctx))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def lint_file(
    path: str,
    repo_root: Optional[str] = None,
    axis_env: Optional[AxisEnv] = None,
) -> List[Finding]:
    with open(path, "r") as fh:
        src = fh.read()
    rel = os.path.relpath(path, repo_root) if repo_root else path
    return lint_source(src, path=rel, axis_env=axis_env)


def lint_paths(
    paths: Sequence[str],
    repo_root: Optional[str] = None,
    axis_env: Optional[AxisEnv] = None,
) -> List[Finding]:
    """Lint files and directories (recursively, ``*.py``)."""
    if axis_env is None and repo_root:
        axis_env = load_axis_env(repo_root)
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, _dirnames, filenames in os.walk(p):
                files.extend(
                    os.path.join(dirpath, f)
                    for f in filenames
                    if f.endswith(".py")
                )
        else:
            files.append(p)
    findings: List[Finding] = []
    for f in sorted(set(files)):
        findings.extend(lint_file(f, repo_root=repo_root, axis_env=axis_env))
    return findings
