"""AST lint engine: repo-specific JAX correctness rules (LX001..LX010).

A small, dependency-free rule framework over `ast`: each rule is a
callable over a parsed file that yields findings; the engine applies
inline waivers (`# lumina: disable=LXnnn -- reason`, on the flagged
line), dedupes, and renders JSON or human output. The rules encode bug
classes this repo has actually shipped — they are deliberately
narrow-scope (precise on THIS codebase) rather than general-purpose:

  LX001  direct `jax.experimental.shard_map` / `jax.shard_map` use
         outside parallel/mesh.py (the one entry point)
  LX002  host-sync calls (.item(), np.asarray, jax.device_get,
         block_until_ready) inside jit/scan/while bodies
  LX003  Python branching or f-string formatting on tracer-typed
         values inside jitted functions
  LX004  wall-clock / stdlib-random nondeterminism in model/step code
  LX005  PRNG key consumed twice without an intervening split
  LX006  step-shaped jit without buffer donation
  LX007  mutable default pytrees on nn.Module fields
  LX008  bare `except:` that would swallow XlaRuntimeError
  LX009  tenant-labeled metric family without a max_label_values
         budget (unbounded /metrics cardinality)
  LX010  direct `lax.all_to_all` / `lax.ppermute` use outside
         parallel/ (collective call sites must stay enumerable for
         the comms auditor and the hierarchical dispatch plan)

The jit-context detector (which functions end up traced) is shared by
LX002/LX003/LX004 and intentionally over-approximates: decorated
functions, functions passed to jit()/pjit(), and scan/while/fori/cond
bodies all count, including through functools.partial and jax.vmap.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import (
    Any,
    Callable,
    Dict,
    Iterable,
    Iterator,
    List,
    Optional,
    Sequence,
    Set,
    Tuple,
)

SEVERITY_ERROR = "error"
SEVERITY_WARNING = "warning"

# Inline waiver: must carry the rule id; the reason after `--` is
# recorded verbatim into reports so CI output shows WHY it is accepted.
_WAIVER_RE = re.compile(
    r"#\s*lumina:\s*disable=([A-Z0-9, ]+?)\s*(?:--\s*(.*?)\s*)?$"
)


@dataclasses.dataclass
class Finding:
    rule: str
    severity: str
    path: str
    line: int
    col: int
    message: str
    waived: bool = False
    waiver_reason: Optional[str] = None
    # Set by the baseline layer (cli.cmd_analyze), not by rules: the
    # finding is real but accepted as legacy debt via --baseline.
    baselined: bool = False

    def key(self) -> Tuple[str, str, int, int]:
        return (self.rule, self.path, self.line, self.col)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    name: str
    severity: str
    description: str
    check: Callable[["FileContext"], Iterator[Finding]]


class FileContext:
    """One parsed file plus the lazily built jit-context index."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source)
        self._jit_contexts: Optional[List["JitContext"]] = None

    @property
    def jit_contexts(self) -> List["JitContext"]:
        if self._jit_contexts is None:
            self._jit_contexts = _collect_jit_contexts(self.tree)
        return self._jit_contexts

    def finding(
        self, rule: "Rule", node: ast.AST, message: str
    ) -> Finding:
        return Finding(
            rule=rule.id,
            severity=rule.severity,
            path=self.path,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0),
            message=message,
        )


# --------------------------------------------------------------------------
# shared AST helpers
# --------------------------------------------------------------------------


def _dotted(node: ast.AST) -> Optional[str]:
    """Name/Attribute chain as 'a.b.c'; None for anything else."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jit_callee(dotted: Optional[str]) -> bool:
    if not dotted:
        return False
    return (
        dotted in ("jit", "pjit")
        or dotted.endswith(".jit")
        or dotted.endswith(".pjit")
    )


_FLOW_BODY_ARGS = {
    # callee basename -> positional indices holding traced bodies
    "scan": (0,),
    "while_loop": (0, 1),
    "fori_loop": (2,),
    "cond": (1, 2, 3, 4, 5),
    "switch": (1, 2, 3, 4, 5),
    "associative_scan": (0,),
    "checkpoint": (0,),
    "remat": (0,),
}


@dataclasses.dataclass
class JitContext:
    node: ast.AST  # FunctionDef / AsyncFunctionDef / Lambda
    kind: str  # "jit" | "scan" | "while_loop" | ...
    static_params: Set[str] = dataclasses.field(default_factory=set)


def _unwrap_fn_expr(
    node: ast.AST, static_out: Optional[Set[str]] = None
) -> ast.AST:
    """Peel functools.partial(f, ...) / jax.vmap(f, ...) wrappers.

    Keyword arguments bound through partial are Python values fixed at
    closure-build time, not traced operands — record them into
    `static_out` so the tracer-name inference skips them."""
    while isinstance(node, ast.Call):
        callee = _dotted(node.func) or ""
        base = callee.rsplit(".", 1)[-1]
        if base in ("partial", "vmap", "pmap", "checkpoint", "remat") and (
            node.args
        ):
            if base == "partial" and static_out is not None:
                for kw in node.keywords:
                    if kw.arg:
                        static_out.add(kw.arg)
            node = node.args[0]
            continue
        break
    return node


def _index_functions(tree: ast.Module) -> Dict[str, List[ast.AST]]:
    byname: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            byname.setdefault(node.name, []).append(node)
    return byname


def _static_params_from_call(
    call: ast.Call, fn_node: ast.AST
) -> Set[str]:
    """Names excluded from the tracer set by static_argnums/argnames."""
    static: Set[str] = set()
    argnames = _positional_param_names(fn_node)
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    static.add(c.value)
        elif kw.arg == "static_argnums":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and isinstance(
                    c.value, int
                ):
                    if 0 <= c.value < len(argnames):
                        static.add(argnames[c.value])
    return static


def _positional_param_names(fn_node: ast.AST) -> List[str]:
    if isinstance(fn_node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        a = fn_node.args
        return [p.arg for p in a.posonlyargs + a.args]
    return []


def _collect_jit_contexts(tree: ast.Module) -> List[JitContext]:
    byname = _index_functions(tree)
    contexts: Dict[int, JitContext] = {}

    def add(fn_expr: ast.AST, kind: str, static: Set[str]) -> None:
        static = set(static)
        fn_expr = _unwrap_fn_expr(fn_expr, static_out=static)
        targets: List[ast.AST] = []
        if isinstance(
            fn_expr, (ast.Lambda, ast.FunctionDef, ast.AsyncFunctionDef)
        ):
            targets = [fn_expr]
        elif isinstance(fn_expr, ast.Name):
            targets = byname.get(fn_expr.id, [])
        elif isinstance(fn_expr, ast.Attribute):
            # self._foo / module.fn: resolve by basename when defined here
            targets = byname.get(fn_expr.attr, [])
        for t in targets:
            ctx = contexts.get(id(t))
            if ctx is None:
                contexts[id(t)] = JitContext(t, kind, set(static))
            else:
                ctx.static_params &= static  # union of tracer params

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                d = dec
                static: Set[str] = set()
                if isinstance(d, ast.Call):
                    inner = _dotted(d.func) or ""
                    if inner.rsplit(".", 1)[-1] == "partial" and d.args:
                        # @partial(jax.jit, static_argnames=...)
                        if _is_jit_callee(_dotted(d.args[0])):
                            static = _static_params_from_call(d, node)
                            add(node, "jit", static)
                        continue
                    if _is_jit_callee(inner):
                        static = _static_params_from_call(d, node)
                        add(node, "jit", static)
                    continue
                if _is_jit_callee(_dotted(d)):
                    add(node, "jit", set())
        if isinstance(node, ast.Call):
            callee = _dotted(node.func)
            if _is_jit_callee(callee) and node.args:
                fn_expr = node.args[0]
                resolved = _unwrap_fn_expr(fn_expr)
                # jax.jit(f, static_argnums=...): `resolved` is a bare
                # Name/Attribute — map it to the local def so argnum
                # indices resolve to parameter names (else the static
                # set silently comes out empty and LX003 false-fires
                # on branches over genuinely static params).
                if isinstance(resolved, ast.Name):
                    defs = byname.get(resolved.id, [])
                    resolved = defs[0] if defs else resolved
                elif isinstance(resolved, ast.Attribute):
                    defs = byname.get(resolved.attr, [])
                    resolved = defs[0] if defs else resolved
                static = _static_params_from_call(node, resolved)
                add(fn_expr, "jit", static)
                continue
            base = (callee or "").rsplit(".", 1)[-1]
            if base in _FLOW_BODY_ARGS and callee and "." in callee:
                for i in _FLOW_BODY_ARGS[base]:
                    if i < len(node.args):
                        add(node.args[i], base, set())
    return list(contexts.values())


def _walk_within(node: ast.AST) -> Iterator[ast.AST]:
    """ast.walk over a function node, including nested defs (anything
    lexically inside a traced function is traced too)."""
    yield from ast.walk(node)


def _parent_map(root: ast.AST) -> Dict[int, ast.AST]:
    parents: Dict[int, ast.AST] = {}
    for node in ast.walk(root):
        for child in ast.iter_child_nodes(node):
            parents[id(child)] = node
    return parents


# --------------------------------------------------------------------------
# tracer-name inference (shared by LX002/LX003)
# --------------------------------------------------------------------------

_STATIC_ATTRS = {
    "shape", "ndim", "dtype", "size", "sharding", "aval", "itemsize",
}

_ARRAY_NS = ("jnp", "jax", "lax", "nn")


def _tracer_names(ctx: JitContext) -> Set[str]:
    """Function params (minus static ones) plus names assigned from
    expressions over them — a single forward pass, no fixpoint."""
    fn = ctx.node
    names: Set[str] = set()
    for p in _positional_param_names(fn):
        if p not in ("self", "cls") and p not in ctx.static_params:
            names.add(p)
    if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        for p in fn.args.kwonlyargs:
            if p.arg not in ctx.static_params:
                names.add(p.arg)
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    for stmt in body:
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Assign):
                continue
            refs_tracer = any(
                isinstance(n, ast.Name) and n.id in names
                for n in ast.walk(node.value)
            )
            from_array_ns = any(
                isinstance(n, ast.Call)
                and (_dotted(n.func) or "").split(".")[0] in _ARRAY_NS
                for n in ast.walk(node.value)
            )
            if refs_tracer or from_array_ns:
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            names.add(n.id)
    return names


def _tracer_name_uses(
    test: ast.AST, tracers: Set[str]
) -> List[ast.Name]:
    """Name nodes in `test` that read a tracer in a value position —
    skipping static uses: `x is None`, `x.shape`/`.dtype`/..., `len(x)`,
    `isinstance(x, ...)`."""
    parents = _parent_map(test)
    out: List[ast.Name] = []
    for node in ast.walk(test):
        if not (isinstance(node, ast.Name) and node.id in tracers):
            continue
        parent = parents.get(id(node))
        if isinstance(parent, ast.Attribute) and parent.attr in _STATIC_ATTRS:
            continue
        if isinstance(parent, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in parent.ops
        ):
            continue
        if isinstance(parent, ast.Call):
            pf = _dotted(parent.func)
            if pf in ("len", "isinstance", "type", "id", "getattr", "hasattr"):
                continue
        out.append(node)
    return out


# --------------------------------------------------------------------------
# LX001 — shard_map outside parallel/mesh.py
# --------------------------------------------------------------------------

_MESH_WRAPPER_SUFFIX = "parallel/mesh.py"


def _check_lx001(ctx: FileContext) -> Iterator[Finding]:
    if ctx.path.replace("\\", "/").endswith(_MESH_WRAPPER_SUFFIX):
        return
    msg = (
        "direct shard_map use: import it from "
        "luminaai_tpu.parallel.mesh — the one entry point, so every "
        "manual-axes region stays enumerable"
    )
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod == "jax.experimental.shard_map":
                yield ctx.finding(LX001, node, msg)
            elif mod in ("jax", "jax.experimental") and any(
                a.name == "shard_map" for a in node.names
            ):
                yield ctx.finding(LX001, node, msg)
        elif isinstance(node, ast.Import):
            if any(
                a.name.startswith("jax.experimental.shard_map")
                for a in node.names
            ):
                yield ctx.finding(LX001, node, msg)
        elif isinstance(node, ast.Call):
            if _dotted(node.func) in (
                "jax.shard_map",
                "jax.experimental.shard_map.shard_map",
            ):
                yield ctx.finding(LX001, node, msg)


# --------------------------------------------------------------------------
# LX002 — host syncs inside traced code
# --------------------------------------------------------------------------

_HOST_SYNC_CALLS = {
    "jax.device_get": "jax.device_get",
    "jax.block_until_ready": "jax.block_until_ready",
}


def _check_lx002(ctx: FileContext) -> Iterator[Finding]:
    seen: Set[Tuple[int, int]] = set()
    for jctx in ctx.jit_contexts:
        tracers = _tracer_names(jctx)
        for node in _walk_within(jctx.node):
            if not isinstance(node, ast.Call):
                continue
            where = (getattr(node, "lineno", 0), getattr(node, "col_offset", 0))
            if where in seen:
                continue
            dotted = _dotted(node.func)
            msg = None
            if isinstance(node.func, ast.Attribute):
                if node.func.attr == "item" and not node.args:
                    msg = ".item() forces a device->host sync"
                elif node.func.attr == "block_until_ready":
                    msg = "block_until_ready() blocks inside traced code"
            if dotted in _HOST_SYNC_CALLS:
                msg = f"{_HOST_SYNC_CALLS[dotted]} is a host transfer"
            if dotted in ("np.asarray", "numpy.asarray", "np.array",
                          "numpy.array"):
                # only when fed a tracer: np constants from Python
                # literals inside a traced fn are legitimate weights
                if any(
                    isinstance(n, ast.Name) and n.id in tracers
                    for a in node.args
                    for n in ast.walk(a)
                ):
                    msg = f"{dotted} on a traced value pulls it to host"
            if msg:
                seen.add(where)
                yield ctx.finding(
                    LX002,
                    node,
                    f"host sync inside {jctx.kind} body: {msg}",
                )


# --------------------------------------------------------------------------
# LX003 — Python control flow / f-strings on tracers
# --------------------------------------------------------------------------


def _check_lx003(ctx: FileContext) -> Iterator[Finding]:
    seen: Set[Tuple[int, int]] = set()
    for jctx in ctx.jit_contexts:
        tracers = _tracer_names(jctx)
        if not tracers:
            continue
        for node in _walk_within(jctx.node):
            test = None
            what = None
            if isinstance(node, (ast.If, ast.While)):
                test, what = node.test, "Python branch"
            elif isinstance(node, ast.IfExp):
                test, what = node.test, "conditional expression"
            elif isinstance(node, ast.Assert):
                test, what = node.test, "assert"
            elif isinstance(node, ast.JoinedStr):
                for fv in node.values:
                    if isinstance(fv, ast.FormattedValue):
                        for n in _tracer_name_uses(fv.value, tracers):
                            where = (node.lineno, node.col_offset)
                            if where not in seen:
                                seen.add(where)
                                yield ctx.finding(
                                    LX003,
                                    node,
                                    f"f-string formats tracer '{n.id}' "
                                    "inside a traced function — it renders "
                                    "as Traced<...>, not a value",
                                )
                continue
            if test is None:
                continue
            uses = _tracer_name_uses(test, tracers)
            if uses:
                where = (node.lineno, node.col_offset)
                if where in seen:
                    continue
                seen.add(where)
                yield ctx.finding(
                    LX003,
                    node,
                    f"{what} on tracer '{uses[0].id}' inside a traced "
                    "function — use lax.cond/jnp.where (or mark the "
                    "argument static)",
                )


# --------------------------------------------------------------------------
# LX004 — nondeterminism in model/step code
# --------------------------------------------------------------------------

_MODEL_PATH_PARTS = ("/models/", "/ops/")

_NONDET_RANDOM_PREFIXES = ("random.", "np.random.", "numpy.random.")
_NONDET_TIME_EXACT = {"time", "perf_counter", "monotonic", "time_ns"}


def _nondet_call(dotted: Optional[str]) -> Optional[str]:
    if not dotted:
        return None
    if (
        dotted.startswith("time.")
        and dotted.split(".", 1)[1] in _NONDET_TIME_EXACT
    ):
        return dotted
    if dotted.startswith(_NONDET_RANDOM_PREFIXES):
        return dotted
    return None


def _check_lx004(ctx: FileContext) -> Iterator[Finding]:
    path = "/" + ctx.path.replace("\\", "/")
    in_model_code = any(p in path for p in _MODEL_PATH_PARTS)
    nodes: Iterable[ast.AST]
    if in_model_code:
        nodes = ast.walk(ctx.tree)
        scope = "model code"
    else:
        nodes = (
            n for jctx in ctx.jit_contexts for n in _walk_within(jctx.node)
        )
        scope = "a traced step body"
    seen: Set[Tuple[int, int]] = set()
    for node in nodes:
        if isinstance(node, ast.Call):
            hit = _nondet_call(_dotted(node.func))
            if hit:
                where = (node.lineno, node.col_offset)
                if where in seen:
                    continue
                seen.add(where)
                yield ctx.finding(
                    LX004,
                    node,
                    f"nondeterministic call {hit}() in {scope} — wall "
                    "clock and stdlib/np RNG break reproducibility and "
                    "bake trace-time values into the executable; use "
                    "jax.random with a threaded key (trainer "
                    "bookkeeping outside traced code is fine)",
                )


# --------------------------------------------------------------------------
# LX005 — PRNG key reuse
# --------------------------------------------------------------------------

_KEY_PRODUCER_SUFFIXES = ("random.PRNGKey", "random.key", "random.split")
_KEY_NONCONSUMING = {"fold_in", "key_data", "wrap_key_data", "clone",
                     "key_impl", "PRNGKey", "key"}


def _is_random_call(dotted: Optional[str]) -> Optional[str]:
    """'jax.random.normal' -> 'normal'; None for non-jax.random calls."""
    if not dotted:
        return None
    parts = dotted.split(".")
    if len(parts) >= 2 and parts[-2] == "random":
        root = parts[0]
        if root in ("jax", "random", "jrandom", "jr") and root != "np":
            if root == "random" and len(parts) == 2:
                # bare stdlib `random.x` — LX004's domain
                return None
            return parts[-1]
    return None


def _check_lx005(ctx: FileContext) -> Iterator[Finding]:
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield from _scan_key_reuse(ctx, fn)


def _scan_key_reuse(
    ctx: FileContext, fn: ast.AST
) -> Iterator[Finding]:
    # name -> (state, def_loop_depth); state in {"live", "consumed"}
    keys: Dict[str, Tuple[str, int]] = {}
    findings: List[Finding] = []

    def handle_call(node: ast.Call, loop_depth: int, targets: Set[str]):
        fname = _is_random_call(_dotted(node.func))
        if fname is None or fname in _KEY_NONCONSUMING:
            return
        if not node.args:
            return
        arg = node.args[0]
        if not isinstance(arg, ast.Name) or arg.id not in keys:
            return
        state, def_depth = keys[arg.id]
        rotated = arg.id in targets  # key, sub = split(key)
        if state == "consumed":
            findings.append(
                ctx.finding(
                    LX005,
                    node,
                    f"PRNG key '{arg.id}' consumed again by "
                    f"jax.random.{fname} without an intervening split — "
                    "identical randomness on both uses",
                )
            )
        elif loop_depth > def_depth and not rotated:
            findings.append(
                ctx.finding(
                    LX005,
                    node,
                    f"PRNG key '{arg.id}' (created outside this loop) "
                    f"consumed by jax.random.{fname} inside it — every "
                    "iteration sees identical randomness; split per "
                    "iteration or fold_in the loop index",
                )
            )
        keys[arg.id] = ("consumed", def_depth)

    def assign_targets(stmt: ast.stmt) -> Set[str]:
        names: Set[str] = set()
        tlist: List[ast.AST] = []
        if isinstance(stmt, ast.Assign):
            tlist = list(stmt.targets)
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            tlist = [stmt.target]
        for t in tlist:
            for n in ast.walk(t):
                if isinstance(n, ast.Name):
                    names.add(n.id)
        return names

    def value_is_key_producer(value: ast.AST) -> bool:
        for n in ast.walk(value):
            if isinstance(n, ast.Call):
                d = _dotted(n.func) or ""
                if any(d.endswith(s) for s in _KEY_PRODUCER_SUFFIXES):
                    return True
        return False

    def calls_pruned(node: ast.AST) -> Iterator[ast.Call]:
        """Call nodes under `node` in SOURCE order (reuse findings must
        land on the later call, not whichever a LIFO pop surfaces), NOT
        descending into nested function/lambda/class scopes (each gets
        its own linear scan)."""
        stack: List[ast.AST] = [node]
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
                continue
            if isinstance(n, ast.Call):
                yield n
            stack.extend(reversed(list(ast.iter_child_nodes(n))))

    def visit_block(stmts: Sequence[ast.stmt], loop_depth: int):
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue  # nested scope: analyzed separately
            targets = assign_targets(stmt)
            # Compound statements: process ONLY the header expressions
            # here (their blocks recurse below with the right depth) —
            # walking the whole subtree at header level would see every
            # inner call twice.
            if isinstance(stmt, (ast.For, ast.AsyncFor)):
                headers: Optional[List[ast.AST]] = [stmt.iter]
            elif isinstance(stmt, (ast.While, ast.If)):
                headers = [stmt.test]
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                headers = [it.context_expr for it in stmt.items]
            elif isinstance(stmt, ast.Try):
                headers = []
            else:
                headers = None
            for node in ([stmt] if headers is None else headers):
                for call in calls_pruned(node):
                    handle_call(call, loop_depth, targets)
            if isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                value = getattr(stmt, "value", None)
                if value is not None and value_is_key_producer(value):
                    for name in targets:
                        keys[name] = ("live", loop_depth)
                else:
                    for name in targets:
                        keys.pop(name, None)
            if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                visit_block(stmt.body, loop_depth + 1)
                visit_block(stmt.orelse, loop_depth)
            elif isinstance(stmt, ast.If):
                # Branches are mutually exclusive at runtime: scan each
                # from the PRE-if key state (one consumption per branch
                # is not reuse), then merge — consumed in either branch
                # means consumed for the code after the if.
                before = dict(keys)
                visit_block(stmt.body, loop_depth)
                after_body = dict(keys)
                keys.clear()
                keys.update(before)
                visit_block(stmt.orelse, loop_depth)
                for name, (state, depth) in after_body.items():
                    cur = keys.get(name)
                    if cur is None:
                        keys[name] = (state, depth)
                    elif state == "consumed":
                        keys[name] = ("consumed", cur[1])
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                visit_block(stmt.body, loop_depth)
            elif isinstance(stmt, ast.Try):
                visit_block(stmt.body, loop_depth)
                for h in stmt.handlers:
                    visit_block(h.body, loop_depth)
                visit_block(stmt.orelse, loop_depth)
                visit_block(stmt.finalbody, loop_depth)

    body = fn.body if isinstance(fn.body, list) else [fn.body]
    visit_block(body, 0)
    yield from findings


# --------------------------------------------------------------------------
# LX006 — step-shaped jit without donation
# --------------------------------------------------------------------------


def _lx006_message(name: str) -> str:
    return (
        f"step-shaped jit of '{name}' without donate_argnums/"
        "donate_argnames — the carried state (params/opt state/"
        "caches) double-buffers every call"
    )


def _donates(call: ast.Call) -> bool:
    return any(
        kw.arg in ("donate_argnums", "donate_argnames")
        for kw in call.keywords
    )


def _check_lx006(ctx: FileContext) -> Iterator[Finding]:
    for node in ast.walk(ctx.tree):
        # Call form: jax.jit(step, ...) / pjit(step) / jit(partial(step)).
        if isinstance(node, ast.Call):
            if not _is_jit_callee(_dotted(node.func)):
                continue
            if _donates(node) or not node.args:
                continue
            fn_expr = _unwrap_fn_expr(node.args[0])
            name = None
            if isinstance(fn_expr, ast.Name):
                name = fn_expr.id
            elif isinstance(fn_expr, (ast.FunctionDef, ast.AsyncFunctionDef)):
                name = fn_expr.name
            elif isinstance(fn_expr, ast.Attribute):
                name = fn_expr.attr
            if name and "step" in name.lower():
                yield ctx.finding(LX006, node, _lx006_message(name))
            continue
        # Decorator forms: @jax.jit, @jax.jit(...), @partial(jax.jit, ...).
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if "step" not in node.name.lower():
            continue
        for dec in node.decorator_list:
            if isinstance(dec, ast.Call):
                callee = _dotted(dec.func) or ""
                if _is_jit_callee(callee) and not _donates(dec):
                    yield ctx.finding(LX006, dec, _lx006_message(node.name))
                elif (
                    callee.rsplit(".", 1)[-1] == "partial"
                    and dec.args
                    and _is_jit_callee(_dotted(dec.args[0]))
                    and not _donates(dec)
                ):
                    yield ctx.finding(LX006, dec, _lx006_message(node.name))
            elif _is_jit_callee(_dotted(dec)):
                yield ctx.finding(LX006, dec, _lx006_message(node.name))


# --------------------------------------------------------------------------
# LX007 — mutable default pytrees on nn.Module fields
# --------------------------------------------------------------------------


def _is_module_class(node: ast.ClassDef) -> bool:
    for base in node.bases:
        d = _dotted(base) or ""
        if d.rsplit(".", 1)[-1] == "Module":
            return True
    return False


def _check_lx007(ctx: FileContext) -> Iterator[Finding]:
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.ClassDef) and _is_module_class(node)):
            continue
        for stmt in node.body:
            default = None
            field = None
            if isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                default, field = stmt.value, stmt.target
            elif isinstance(stmt, ast.Assign):
                default, field = stmt.value, stmt.targets[0]
            if default is None:
                continue
            mutable = isinstance(
                default, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                          ast.DictComp, ast.SetComp)
            ) or (
                isinstance(default, ast.Call)
                and (_dotted(default.func) or "") in ("list", "dict", "set")
            )
            if mutable:
                fname = _dotted(field) or "<field>"
                yield ctx.finding(
                    LX007,
                    stmt,
                    f"mutable default pytree on nn.Module field "
                    f"'{fname}' — shared across instances and unhashable "
                    "as a static jit argument; use a tuple or "
                    "dataclasses.field(default_factory=...)",
                )


# --------------------------------------------------------------------------
# LX008 — bare except
# --------------------------------------------------------------------------


def _check_lx008(ctx: FileContext) -> Iterator[Finding]:
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            yield ctx.finding(
                LX008,
                node,
                "bare `except:` swallows XlaRuntimeError (and "
                "KeyboardInterrupt/SystemExit) — catch a concrete "
                "exception type so device failures surface",
            )


# --------------------------------------------------------------------------
# LX009 — tenant-labeled metric family without a label-value budget
# --------------------------------------------------------------------------


def _labelnames_has_tenant(value: ast.AST) -> bool:
    if isinstance(value, (ast.Tuple, ast.List)):
        return any(
            isinstance(e, ast.Constant) and e.value == "tenant"
            for e in value.elts
        )
    return False


def _check_lx009(ctx: FileContext) -> Iterator[Finding]:
    """Tenant-keyed metric families are unbounded-cardinality hazards:
    every family carrying a 'tenant' label MUST declare a
    max_label_values budget (the registry then collapses the overflow
    into `_overflow`), so tenant-keyed series — request accounting,
    prefix-cache residency — ride under the server's --max-tenants
    bound instead of letting one scan mint unbounded /metrics series.
    Covers the direct registration call and the shared-kwargs dict
    idiom (tk = dict(labelnames=("tenant",), ...))."""
    msg = (
        "metric family labeled by 'tenant' without a max_label_values "
        "budget — tenant cardinality must be bounded (--max-tenants) "
        "or one tenant scan explodes /metrics"
    )
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            kws = {k.arg: k.value for k in node.keywords if k.arg}
            if "labelnames" in kws and _labelnames_has_tenant(
                kws["labelnames"]
            ):
                if "max_label_values" not in kws:
                    yield ctx.finding(LX009, node, msg)
        elif isinstance(node, ast.Dict):
            keys = [
                k.value for k in node.keys
                if isinstance(k, ast.Constant)
            ]
            if "labelnames" in keys and "max_label_values" not in keys:
                for k, v in zip(node.keys, node.values):
                    if (
                        isinstance(k, ast.Constant)
                        and k.value == "labelnames"
                        and _labelnames_has_tenant(v)
                    ):
                        yield ctx.finding(LX009, node, msg)


# --------------------------------------------------------------------------
# LX010 — raw collectives outside parallel/
# --------------------------------------------------------------------------

_COLLECTIVE_NAMES = ("all_to_all", "ppermute")


def _check_lx010(ctx: FileContext) -> Iterator[Finding]:
    """Direct `lax.all_to_all` / `lax.ppermute` use outside `parallel/`:
    explicit collectives must route through parallel/mesh.all_to_all /
    ppermute (or the expert-dispatch subsystem built on them) so every
    collective call site stays enumerable — the comms auditor
    (analysis/jaxpr_audit.enumerate_collectives) and the hierarchical
    dispatch groups both depend on knowing where collectives enter
    model code. Mirrors LX001's shard_map rule."""
    p = "/" + ctx.path.replace("\\", "/")
    if "/parallel/" in p:
        return
    msg = (
        "direct {name} use: route through luminaai_tpu.parallel.mesh."
        "{name} — collective call sites outside parallel/ escape the "
        "comms auditor and the hierarchical dispatch plan"
    )
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod in ("jax.lax", "jax._src.lax.parallel") and any(
                a.name in _COLLECTIVE_NAMES for a in node.names
            ):
                hit = next(
                    a.name for a in node.names
                    if a.name in _COLLECTIVE_NAMES
                )
                yield ctx.finding(LX010, node, msg.format(name=hit))
        elif isinstance(node, ast.Call):
            dotted = _dotted(node.func)
            for name in _COLLECTIVE_NAMES:
                if dotted in (f"lax.{name}", f"jax.lax.{name}"):
                    yield ctx.finding(LX010, node, msg.format(name=name))


# --------------------------------------------------------------------------
# registry / engine
# --------------------------------------------------------------------------

LX001 = Rule(
    "LX001", "shard-map-compat", SEVERITY_ERROR,
    "shard_map must route through luminaai_tpu.parallel.mesh.shard_map",
    _check_lx001,
)
LX002 = Rule(
    "LX002", "host-sync-in-jit", SEVERITY_ERROR,
    "host-sync calls inside jit/scan/while bodies",
    _check_lx002,
)
LX003 = Rule(
    "LX003", "tracer-branch", SEVERITY_ERROR,
    "Python branching / f-string formatting on tracer values in jit",
    _check_lx003,
)
LX004 = Rule(
    "LX004", "nondeterminism", SEVERITY_ERROR,
    "wall-clock / stdlib-random calls in model or traced step code",
    _check_lx004,
)
LX005 = Rule(
    "LX005", "prng-key-reuse", SEVERITY_ERROR,
    "PRNG key consumed more than once without split",
    _check_lx005,
)
LX006 = Rule(
    "LX006", "step-without-donation", SEVERITY_WARNING,
    "step-shaped jit without buffer donation",
    _check_lx006,
)
LX007 = Rule(
    "LX007", "mutable-module-default", SEVERITY_ERROR,
    "mutable default pytrees on nn.Module fields",
    _check_lx007,
)
LX008 = Rule(
    "LX008", "bare-except", SEVERITY_WARNING,
    "bare except swallowing XlaRuntimeError",
    _check_lx008,
)
LX009 = Rule(
    "LX009", "tenant-label-budget", SEVERITY_ERROR,
    "tenant-labeled metric family without max_label_values budget",
    _check_lx009,
)
LX010 = Rule(
    "LX010", "raw-collective-outside-parallel", SEVERITY_ERROR,
    "direct lax.all_to_all/lax.ppermute outside parallel/",
    _check_lx010,
)

ALL_RULES: Tuple[Rule, ...] = (
    LX001, LX002, LX003, LX004, LX005, LX006, LX007, LX008, LX009,
    LX010,
)

RULES_BY_ID: Dict[str, Rule] = {r.id: r for r in ALL_RULES}


def _apply_waivers(ctx: FileContext, findings: List[Finding]) -> None:
    for f in findings:
        if f.line - 1 >= len(ctx.lines):
            continue
        m = _WAIVER_RE.search(ctx.lines[f.line - 1])
        if not m:
            continue
        ids = {s.strip() for s in m.group(1).split(",")}
        if f.rule in ids or "ALL" in ids:
            f.waived = True
            f.waiver_reason = (m.group(2) or "").strip() or None


def lint_source(
    source: str,
    path: str = "<memory>",
    rules: Sequence[Rule] = ALL_RULES,
) -> List[Finding]:
    """Lint one source blob. Returns ALL findings (waived ones carry
    waived=True); syntax errors surface as a single LX000 finding so a
    broken file fails the gate rather than passing silently."""
    try:
        ctx = FileContext(path, source)
    except SyntaxError as e:
        return [
            Finding(
                rule="LX000",
                severity=SEVERITY_ERROR,
                path=path,
                line=e.lineno or 1,
                col=e.offset or 0,
                message=f"syntax error: {e.msg}",
            )
        ]
    findings: List[Finding] = []
    seen: Set[Tuple[str, str, int, int]] = set()
    for rule in rules:
        for f in rule.check(ctx):
            if f.key() in seen:
                continue
            seen.add(f.key())
            findings.append(f)
    _apply_waivers(ctx, findings)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def iter_python_files(paths: Sequence[str]) -> Iterator[str]:
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                yield p
        else:
            for root, dirs, files in os.walk(p):
                # Skip hidden trees (.git, .venv, .tox, ...) and vendored
                # third-party code — `lumina analyze .` must lint what the
                # repo owns, not site-packages.
                dirs[:] = sorted(
                    d for d in dirs
                    if not d.startswith(".")
                    and d not in ("__pycache__", "site-packages",
                                  "node_modules", "venv")
                )
                for name in sorted(files):
                    if name.endswith(".py"):
                        yield os.path.join(root, name)


def lint_paths(
    paths: Sequence[str],
    rules: Sequence[Rule] = ALL_RULES,
    rel_to: Optional[str] = None,
) -> List[Finding]:
    findings: List[Finding] = []
    for path in iter_python_files(paths):
        with open(path, "r", encoding="utf-8") as fh:
            source = fh.read()
        shown = os.path.relpath(path, rel_to) if rel_to else path
        findings.extend(lint_source(source, shown, rules))
    return findings


def findings_to_json(
    findings: Sequence[Finding], extra: Optional[Dict[str, Any]] = None
) -> Dict[str, Any]:
    unwaived = [f for f in findings if not f.waived]
    out: Dict[str, Any] = {
        "findings": [f.to_dict() for f in findings],
        "summary": {
            "total": len(findings),
            "unwaived": len(unwaived),
            "waived": len(findings) - len(unwaived),
            "by_rule": _count_by_rule(findings),
        },
        "rules": {
            r.id: {"name": r.name, "severity": r.severity,
                   "description": r.description}
            for r in ALL_RULES
        },
    }
    if extra:
        out.update(extra)
    return out


def _count_by_rule(findings: Sequence[Finding]) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    return counts


def format_findings(findings: Sequence[Finding]) -> str:
    if not findings:
        return "astlint: clean (0 findings)"
    lines = []
    for f in findings:
        if f.waived:
            tag = " [waived%s]" % (
                f": {f.waiver_reason}" if f.waiver_reason else ""
            )
        elif f.baselined:
            tag = " [baselined: accepted legacy finding]"
        else:
            tag = ""
        lines.append(
            f"{f.path}:{f.line}:{f.col}: {f.rule} ({f.severity}) "
            f"{f.message}{tag}"
        )
    unwaived = sum(1 for f in findings if not (f.waived or f.baselined))
    lines.append(
        f"astlint: {len(findings)} finding(s), {unwaived} unwaived"
    )
    return "\n".join(lines)
