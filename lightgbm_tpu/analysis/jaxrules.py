"""JAX/TPU hazard rules (TPU1xx).

The hazards these catch are the silent wall-clock and correctness
killers of a JAX training stack on TPU:

  * a host sync (``.item()``, ``float()``, ``np.asarray``) on a traced
    value inside a jitted region either fails at trace time or — worse,
    when it sneaks into a host callback — serializes every dispatch;
  * constructing a fresh ``jax.jit`` closure per loop iteration defeats
    the compile cache and re-traces every pass;
  * ``static_argnums``/``static_argnames`` typos silently re-compile per
    call or crash far from the definition site;
  * a float64 literal or ``np.float64`` cast inside jitted math silently
    upcasts (or errors under x64-disabled) and halves MXU throughput;
  * reusing a donated buffer after the jitted call reads freed memory;
  * a collective executed inside a rank-conditional branch desynchronizes
    the workers (the survivors hang in the collective).

Detection is lexical/AST-scoped, not a full dataflow analysis: a
function is a *traced region* when it is jit-decorated, wrapped by a
``jax.jit``/``partial(jax.jit, ...)`` call, passed to a ``lax`` control
-flow combinator / ``vmap`` / ``shard_map``, or lexically nested inside
such a function.  False positives are expected to be rare and are
suppressed inline with a justification.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .core import (FileContext, Rule, SEVERITY_ERROR, SEVERITY_WARNING,
                   Violation, register_rule)

_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)

#: combinators whose function-valued arguments get traced.  Matched by
#: full dotted chain — ``jax.tree.map`` / ``tree_util.tree_map`` must
#: NOT match (they run their function eagerly on host leaves).
_LAX_COMBINATORS = {"scan", "while_loop", "fori_loop", "cond", "switch",
                    "map", "associative_scan"}
_TRACING_CHAINS = set()
for _c in _LAX_COMBINATORS:
    _TRACING_CHAINS.update({f"lax.{_c}", f"jax.lax.{_c}"})
for _c in ("vmap", "pmap", "grad", "value_and_grad", "checkpoint",
           "remat", "shard_map", "custom_jvp", "custom_vjp"):
    _TRACING_CHAINS.update({_c, f"jax.{_c}"})

_HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_NUMPY_NAMES = {"np", "numpy", "onp"}
_NUMPY_SYNC_FUNCS = {"asarray", "array", "ascontiguousarray", "copy"}


def _attr_chain(node: ast.AST) -> Optional[str]:
    """Dotted name for Name/Attribute chains ('jax.jit'), else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _base_name(node: ast.AST) -> Optional[str]:
    """Leftmost Name of an expression (``a.b[0].c`` -> 'a')."""
    while True:
        if isinstance(node, ast.Attribute):
            node = node.value
        elif isinstance(node, ast.Subscript):
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func
        elif isinstance(node, ast.Name):
            return node.id
        else:
            return None


def _is_jit_ref(node: ast.AST) -> bool:
    chain = _attr_chain(node)
    return chain in ("jax.jit", "jit", "jax.pjit", "pjit")


def _jit_call_parts(call: ast.Call) -> Optional[ast.Call]:
    """Return the Call carrying jit kwargs if ``call`` constructs a jit
    wrapper: ``jax.jit(f, ...)`` or ``functools.partial(jax.jit, ...)``."""
    if _is_jit_ref(call.func):
        return call
    chain = _attr_chain(call.func)
    if chain in ("functools.partial", "partial") and call.args \
            and _is_jit_ref(call.args[0]):
        return call
    return None


def _kw(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _const_strs(node: Optional[ast.expr]) -> List[str]:
    """String constants in a literal str/tuple/list, else []."""
    if node is None:
        return []
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append(el.value)
        return out
    return []


def _const_ints(node: Optional[ast.expr]) -> List[int]:
    if node is None:
        return []
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, int) \
                    and not isinstance(el.value, bool):
                out.append(el.value)
        return out
    return []


def _param_names(fn: ast.AST) -> List[str]:
    """Positional parameter names (what static_argnums indexes)."""
    a = fn.args
    return [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]


def _all_param_names(fn: ast.AST) -> List[str]:
    """Every named parameter, incl. keyword-only (what static_argnames
    may reference)."""
    return _param_names(fn) + [p.arg for p in fn.args.kwonlyargs]


class JitIndex:
    """Per-module map of traced regions.

    ``traced`` holds every function node whose body executes under a
    trace; ``static_names[fn]`` the parameter names jit treats as static
    (safe to ``int()``/``float()``); ``jit_wrappers[name]`` the donated
    positional indices of module-visible jitted callables.
    """

    def __init__(self, tree: ast.Module):
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        self.functions: List[ast.AST] = [
            n for n in ast.walk(tree) if isinstance(n, _FUNC_NODES)]
        #: function name -> def nodes (module- or class- or nested-scope)
        self.defs_by_name: Dict[str, List[ast.AST]] = {}
        for fn in self.functions:
            if not isinstance(fn, ast.Lambda):
                self.defs_by_name.setdefault(fn.name, []).append(fn)

        self.traced_roots: Set[ast.AST] = set()
        self.static_names: Dict[ast.AST, Set[str]] = {}
        self.static_nums: Dict[ast.AST, Set[int]] = {}
        self.donate_nums: Dict[ast.AST, Set[int]] = {}
        #: callable name -> set of donated positional indices
        self.jit_wrappers: Dict[str, Set[int]] = {}
        self._index(tree)
        self.traced: Set[ast.AST] = set()
        for fn in self.functions:
            if self._under_traced_root(fn):
                self.traced.add(fn)

    # ------------------------------------------------------------ indexing
    def _mark_named(self, name_node: ast.expr, jit_call: ast.Call) -> None:
        if isinstance(name_node, ast.Name):
            for fn in self.defs_by_name.get(name_node.id, []):
                self.traced_roots.add(fn)
                self._record_statics(fn, jit_call)
        elif isinstance(name_node, ast.Lambda):
            self.traced_roots.add(name_node)

    def _record_statics(self, fn: ast.AST, call: ast.Call) -> None:
        names = set(_const_strs(_kw(call, "static_argnames")))
        nums = set(_const_ints(_kw(call, "static_argnums")))
        params = _param_names(fn)
        for i in nums:
            if 0 <= i < len(params):
                names.add(params[i])
        self.static_names.setdefault(fn, set()).update(names)
        self.static_nums.setdefault(fn, set()).update(nums)
        self.donate_nums.setdefault(fn, set()).update(
            _const_ints(_kw(call, "donate_argnums")))

    def _index(self, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if _is_jit_ref(dec):
                        self.traced_roots.add(node)
                    elif isinstance(dec, ast.Call):
                        jc = _jit_call_parts(dec)
                        if jc is not None:
                            self.traced_roots.add(node)
                            self._record_statics(node, jc)
            if not isinstance(node, ast.Call):
                continue
            jc = _jit_call_parts(node)
            if jc is not None and jc is node and _is_jit_ref(node.func) \
                    and node.args:
                # jax.jit(f, ...) wrapping an existing callable
                self._mark_named(node.args[0], node)
                donated = set(_const_ints(_kw(node, "donate_argnums")))
                # f = jax.jit(g, donate_argnums=...) — only the BOUND
                # name donates; calling plain `g` donates nothing
                parent = self.parents.get(node)
                if isinstance(parent, ast.Assign) and donated:
                    for t in parent.targets:
                        if isinstance(t, ast.Name):
                            self.jit_wrappers[t.id] = donated
            chain = _attr_chain(node.func)
            if chain is not None and chain in _TRACING_CHAINS:
                for arg in node.args:
                    if isinstance(arg, (ast.Name, ast.Lambda)):
                        self._mark_named(arg, node)

    # ------------------------------------------------------------- queries
    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, _FUNC_NODES):
                return cur
            cur = self.parents.get(cur)
        return None

    def _under_traced_root(self, fn: ast.AST) -> bool:
        cur: Optional[ast.AST] = fn
        while cur is not None:
            if cur in self.traced_roots:
                return True
            cur = self.enclosing_function(cur)
        return False

    def in_traced_region(self, node: ast.AST) -> Optional[ast.AST]:
        """The innermost traced function whose body contains ``node``."""
        fn = node if isinstance(node, _FUNC_NODES) \
            else self.enclosing_function(node)
        while fn is not None:
            if fn in self.traced:
                return fn
            fn = self.enclosing_function(fn)
        return None

    def statics_for(self, node: ast.AST) -> Set[str]:
        """Static parameter names visible at ``node`` (union over the
        enclosing traced chain — a name static at the jit boundary stays
        a Python value in nested helpers)."""
        out: Set[str] = set()
        fn = self.in_traced_region(node)
        while fn is not None:
            out |= self.static_names.get(fn, set())
            fn = self.in_traced_region(self.enclosing_function(fn)) \
                if self.enclosing_function(fn) is not None else None
        return out

    def in_loop(self, node: ast.AST,
                stop_at: Optional[ast.AST] = None) -> bool:
        cur = self.parents.get(node)
        while cur is not None and cur is not stop_at:
            if isinstance(cur, (ast.For, ast.While, ast.AsyncFor)):
                return True
            if isinstance(cur, _FUNC_NODES):
                return False
            cur = self.parents.get(cur)
        return False


def get_index(ctx: FileContext) -> JitIndex:
    """Build (or reuse) the JitIndex for a file — cached on the context
    so the six TPU rules share one traversal's worth of work."""
    idx = getattr(ctx, "_jit_index", None)
    if idx is None:
        idx = JitIndex(ctx.tree)
        ctx._jit_index = idx
    return idx


class _JaxRule(Rule):
    """Shared per-file iteration for the hazard rules."""

    def check(self, ctx: FileContext) -> Iterable[Violation]:
        idx = get_index(ctx)
        return self.check_with_index(ctx, idx)

    def check_with_index(self, ctx: FileContext,
                         idx: JitIndex) -> Iterable[Violation]:
        return ()


@register_rule
class HostSyncInJit(_JaxRule):
    id = "TPU101"
    name = "host-sync-in-jit"
    severity = SEVERITY_ERROR
    description = ("host-device sync (`.item()`, `.tolist()`, `float()`, "
                   "`np.asarray`, `jax.device_get`) on a traced value "
                   "inside a jitted region")

    #: attributes that are static Python values under trace — deriving a
    #: scalar from them is the standard JAX idiom, not a host sync
    _STATIC_ATTRS = {"shape", "ndim"}

    @classmethod
    def _is_shape_derived(cls, expr: ast.AST) -> bool:
        """True when ``expr`` is built from `.shape`/`.ndim`/`len()` —
        static under trace, so `float()`/`int()` on it is fine."""
        for node in ast.walk(expr):
            if isinstance(node, ast.Attribute) and \
                    node.attr in cls._STATIC_ATTRS:
                return True
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name) and \
                    node.func.id == "len":
                return True
        return False

    @classmethod
    def _shape_locals(cls, fn: ast.AST) -> Set[str]:
        """Names assigned from shape-derived expressions inside ``fn``
        (``n = x.shape[0]`` makes ``n`` a static Python int)."""
        out: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and cls._is_shape_derived(node.value):
                out.add(node.targets[0].id)
        return out

    def check_with_index(self, ctx, idx):
        shape_locals_cache: Dict[ast.AST, Set[str]] = {}
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            region = idx.in_traced_region(node)
            if region is None:
                continue
            if region not in shape_locals_cache:
                shape_locals_cache[region] = self._shape_locals(region)
            msg = self._classify(node, idx, shape_locals_cache[region])
            if msg:
                yield self.violation(ctx, node.lineno, node.col_offset, msg)

    def _classify(self, call: ast.Call, idx: JitIndex,
                  shape_locals: Set[str] = frozenset()) -> Optional[str]:
        func = call.func
        if isinstance(func, ast.Attribute):
            if func.attr in _HOST_SYNC_METHODS and not call.args:
                return (f"`.{func.attr}()` inside a jitted region forces a "
                        "device->host sync (fails at trace time on traced "
                        "values); compute on device or move the read "
                        "outside the jit boundary")
            base = _base_name(func.value)
            if base in _NUMPY_NAMES and func.attr in _NUMPY_SYNC_FUNCS:
                if call.args and not isinstance(call.args[0], ast.Constant):
                    arg_base = _base_name(call.args[0])
                    if arg_base not in idx.statics_for(call):
                        return (f"`{base}.{func.attr}(...)` inside a jitted "
                                "region materializes a host array (sync + "
                                "constant-folds traced data); use jnp or "
                                "hoist to the caller")
            chain = _attr_chain(func)
            if chain in ("jax.device_get",):
                return ("`jax.device_get` inside a jitted region is a "
                        "host sync; return the value instead")
        elif isinstance(func, ast.Name):
            if func.id in ("float", "int", "bool") and len(call.args) == 1:
                arg = call.args[0]
                if isinstance(arg, ast.Constant):
                    return None
                if self._is_shape_derived(arg):
                    # float(x.shape[0]) etc. — static under trace
                    return None
                base = _base_name(arg)
                if base is not None and (base in idx.statics_for(call)
                                         or base in shape_locals):
                    return None
                if base == "self" and isinstance(arg, (ast.Attribute,
                                                       ast.Call)):
                    # `int(self.config.x)`-style reads are closure
                    # captures of host config state, not traced values
                    return None
                return (f"`{func.id}(...)` on a non-static value inside a "
                        "jitted region forces a concrete host scalar "
                        "(trace error / silent recompile); keep it a "
                        "traced 0-d array or mark the argument static")
            if func.id == "device_get":
                return ("`device_get` inside a jitted region is a host "
                        "sync; return the value instead")
        return None


@register_rule
class JitInLoop(_JaxRule):
    id = "TPU102"
    name = "jit-closure-in-loop"
    severity = SEVERITY_ERROR
    description = ("fresh `jax.jit` closure constructed per loop "
                   "iteration (Python-scalar closure capture) — every "
                   "pass re-traces and re-compiles")

    def check_with_index(self, ctx, idx):
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                # both spellings: `jax.jit(f, ...)` and
                # `partial(jax.jit, ...)` built inside a loop
                if _jit_call_parts(node) is None:
                    continue
                if idx.in_loop(node):
                    yield self.violation(
                        ctx, node.lineno, node.col_offset,
                        "`jax.jit(...)` called inside a loop builds a new "
                        "wrapper (and re-traces) every iteration; hoist "
                        "the jitted callable out of the loop or cache it")
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if node not in idx.traced_roots:
                    continue
                has_jit_dec = any(
                    _is_jit_ref(d) or (isinstance(d, ast.Call)
                                       and _jit_call_parts(d) is not None)
                    for d in node.decorator_list)
                if has_jit_dec and idx.in_loop(node):
                    yield self.violation(
                        ctx, node.lineno, node.col_offset,
                        f"jit-decorated `{node.name}` defined inside a "
                        "loop captures loop-local Python scalars in a new "
                        "closure each iteration and re-compiles; define "
                        "it once outside the loop")


@register_rule
class StaticArgnumsMisuse(_JaxRule):
    id = "TPU103"
    name = "static-argnums-misuse"
    severity = SEVERITY_ERROR
    description = ("`static_argnums`/`static_argnames` that do not match "
                   "the wrapped function's signature, or overlap "
                   "`donate_argnums`")

    def check_with_index(self, ctx, idx):
        for node in ast.walk(ctx.tree):
            target: Optional[ast.AST] = None
            jc: Optional[ast.Call] = None
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call) \
                            and _jit_call_parts(dec) is not None:
                        target, jc = node, dec
                        break
            elif isinstance(node, ast.Call) and _is_jit_ref(node.func) \
                    and node.args and isinstance(node.args[0], ast.Name):
                defs = idx.defs_by_name.get(node.args[0].id, [])
                if len(defs) == 1:
                    target, jc = defs[0], node
            if target is None or jc is None:
                continue
            params = _param_names(target)
            all_params = _all_param_names(target)
            nums = _const_ints(_kw(jc, "static_argnums"))
            names = _const_strs(_kw(jc, "static_argnames"))
            donate = _const_ints(_kw(jc, "donate_argnums"))
            for i in nums:
                if i >= len(params) or i < -len(params):
                    yield self.violation(
                        ctx, jc.lineno, jc.col_offset,
                        f"static_argnums={i} is out of range for "
                        f"`{getattr(target, 'name', '<lambda>')}` "
                        f"({len(params)} positional parameter(s))")
            for nm in names:
                if nm not in all_params and target.args.kwarg is None:
                    yield self.violation(
                        ctx, jc.lineno, jc.col_offset,
                        f"static_argnames={nm!r} does not name a "
                        f"parameter of "
                        f"`{getattr(target, 'name', '<lambda>')}` "
                        f"(has: {', '.join(params) or 'none'})")
            overlap = set(nums) & set(donate)
            if overlap:
                yield self.violation(
                    ctx, jc.lineno, jc.col_offset,
                    f"argument position(s) {sorted(overlap)} are both "
                    "static and donated — a static argument is part of "
                    "the compile key and cannot be donated")


@register_rule
class Float64InJit(_JaxRule):
    id = "TPU104"
    name = "float64-in-jit"
    severity = SEVERITY_ERROR
    description = ("float64 (`np.float64`, `jnp.float64`, "
                   "`dtype='float64'`) inside jitted math — silently "
                   "upcasts or errors without x64, and halves MXU rate")

    _F64_ATTRS = {"float64", "double", "complex128"}

    def check_with_index(self, ctx, idx):
        for node in ast.walk(ctx.tree):
            if idx.in_traced_region(node) is None:
                continue
            if isinstance(node, ast.Attribute) \
                    and node.attr in self._F64_ATTRS:
                base = _base_name(node.value)
                if base in _NUMPY_NAMES | {"jnp", "jax"}:
                    yield self.violation(
                        ctx, node.lineno, node.col_offset,
                        f"`{base}.{node.attr}` inside a jitted region: "
                        "TPU math is float32/bfloat16 — 64-bit dtypes "
                        "either error (x64 disabled) or silently fall "
                        "back to a slow emulated path")
            elif isinstance(node, ast.keyword) and node.arg == "dtype" \
                    and isinstance(node.value, ast.Constant) \
                    and node.value.value in self._F64_ATTRS:
                yield self.violation(
                    ctx, node.value.lineno, node.value.col_offset,
                    "dtype='float64' inside a jitted region (see TPU104: "
                    "keep jitted math in float32/bfloat16)")


@register_rule
class DonatedBufferReuse(_JaxRule):
    id = "TPU105"
    name = "donated-buffer-reuse"
    severity = SEVERITY_ERROR
    description = ("a buffer passed to a `donate_argnums` position is "
                   "read again after the call — donation invalidates "
                   "the source array")

    def check_with_index(self, ctx, idx):
        if not idx.jit_wrappers:
            return
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Module)):
                continue
            yield from self._check_scope(ctx, idx, fn)

    @staticmethod
    def _walk_scope(stmts):
        """Walk statements without descending into nested defs/classes —
        those are separate scopes with their own line ordering (and are
        visited as their own roots by ``check_with_index``)."""
        stack = list(stmts)
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef)):
                continue
            for child in ast.iter_child_nodes(node):
                stack.append(child)

    _SIMPLE_STMTS = (ast.Assign, ast.AnnAssign, ast.AugAssign, ast.Expr,
                     ast.Return, ast.Raise, ast.Assert)

    @staticmethod
    def _walk_stmt(stmt):
        """Subtree of one statement, minus nested function scopes."""
        stack = [stmt]
        while stack:
            node = stack.pop()
            yield node
            for child in ast.iter_child_nodes(node):
                if not isinstance(child, _FUNC_NODES + (ast.ClassDef,)):
                    stack.append(child)

    def _check_scope(self, ctx, idx, scope):
        # buffer name -> source position AFTER which reads are hazardous
        # (the donating call's END, so the call's own arguments never
        # self-report).  Statements are processed in order; loads and
        # donating calls interleave by position within a statement so
        # `step(x, g) + x` reports the trailing read, while Store
        # targets clear at statement end — `x = step(x, g)` retires the
        # name, and a LATER `y = step(x, g)` re-arms it.
        donated: Dict[str, Tuple[int, int]] = {}
        stmts = sorted(
            (n for n in self._walk_scope(scope.body)
             if isinstance(n, self._SIMPLE_STMTS)),
            key=lambda n: (n.lineno, n.col_offset))
        for stmt in stmts:
            events: List[ast.AST] = []
            stores: List[ast.Name] = []
            for node in self._walk_stmt(stmt):
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Name) and \
                        node.func.id in idx.jit_wrappers:
                    events.append(node)
                elif isinstance(node, ast.Name):
                    if isinstance(node.ctx, ast.Store):
                        stores.append(node)
                    elif isinstance(node.ctx, ast.Load):
                        events.append(node)
            events.sort(key=lambda n: (n.lineno, n.col_offset))
            for ev in events:
                if isinstance(ev, ast.Call):
                    end = (ev.end_lineno or ev.lineno,
                           ev.end_col_offset or ev.col_offset)
                    for pos in idx.jit_wrappers[ev.func.id]:
                        if pos < len(ev.args) and \
                                isinstance(ev.args[pos], ast.Name):
                            donated[ev.args[pos].id] = end
                    continue
                at = donated.get(ev.id)
                if at is not None and (ev.lineno, ev.col_offset) > at:
                    yield self.violation(
                        ctx, ev.lineno, ev.col_offset,
                        f"`{ev.id}` was donated to a jitted call on "
                        f"line {at[0]} (donate_argnums) and is read "
                        "afterwards — the buffer may already be reused; "
                        "rebind the result or drop the donation")
                    del donated[ev.id]
            for node in stores:
                donated.pop(node.id, None)


@register_rule
class CollectiveInRankBranch(_JaxRule):
    id = "TPU106"
    name = "collective-in-rank-branch"
    severity = SEVERITY_ERROR
    description = ("collective op executed inside a branch conditioned "
                   "on per-worker identity — the other workers block in "
                   "the collective forever (parallel/ only)")

    _COLLECTIVES = {
        "psum", "pmean", "pmax", "pmin", "all_gather", "allgather",
        "process_allgather", "all_to_all", "ppermute", "pshuffle",
        "axis_index", "broadcast", "broadcast_one_to_all",
        "sync_global_devices", "barrier",
    }
    _RANKY = {"rank", "process_index", "process_id", "worker_id",
              "host_id", "task_id", "local_rank", "node_rank"}

    def _applies(self, ctx: FileContext) -> bool:
        rel = ctx.relpath.replace("\\", "/")
        return "parallel/" in rel or rel.startswith("parallel")

    def _test_is_ranky(self, test: ast.expr) -> bool:
        for node in ast.walk(test):
            name = None
            if isinstance(node, ast.Name):
                name = node.id
            elif isinstance(node, ast.Attribute):
                name = node.attr
            if name is not None and name.lower() in self._RANKY:
                return True
        return False

    def check_with_index(self, ctx, idx):
        if not self._applies(ctx):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.If):
                continue
            if not self._test_is_ranky(node.test):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    chain = _attr_chain(sub.func) or ""
                    tail = chain.rsplit(".", 1)[-1]
                    if tail in self._COLLECTIVES:
                        yield self.violation(
                            ctx, sub.lineno, sub.col_offset,
                            f"collective `{tail}` runs inside a branch on "
                            "per-worker identity — workers that skip the "
                            "branch never join and the collective "
                            "deadlocks; run it unconditionally and mask "
                            "the payload instead")
