"""Level-1 AST rules: HS001, RC001, SM001, PL001 (literal shapes), EP001.

The pass builds a per-module picture of which functions run under a JAX
trace (decorated with jit/vmap, wrapped at a call site, passed to
``shard_map``/``pallas_call``/``lax`` control flow, or nested inside any
of those) and runs a forward taint analysis over each: parameters that
are not static argnames are *traced values*, and anything that would
force one to the host mid-trace is a finding. Host functions on the
serving hot path get the complementary check: device→host coercions
inside loops (a sync per iteration) and repeated transfers of the same
expression (the PR 1 bug class).

The scope detection and taint rules are deliberately calibrated against
this repo's idioms — ``functools.partial(kern, **static)`` bodies handed
to ``pallas_call``, ``jax.shard_map(local, ...)`` closures over static
config, ``.shape``/``len()`` reads that are static under trace — so the
repo lints clean without blanket suppressions.
"""
from __future__ import annotations

import ast
import fnmatch

from repro.analysis.config import LintConfig
from repro.analysis.findings import Finding

JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit"}
VMAP_NAMES = {"jax.vmap", "vmap"}
PARTIAL_NAMES = {"partial", "functools.partial"}
NP_ALIASES = {"np", "numpy", "onp"}
# jax.lax control-flow wrappers whose callable args trace
LAX_CALLEES = {"scan", "fori_loop", "while_loop", "cond", "switch", "map",
               "associative_scan", "custom_root"}
# attributes that read static metadata off a traced value; n_clauses is
# this repo's shape-derived clause count (predicates.PredicateSet.n_clauses
# returns int(active.shape[-2]) — static at trace time by construction)
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval",
                "n_clauses"}
UNTAINTING_CALLS = {"len", "range", "isinstance", "hasattr", "type"}
# builtins whose result is a host scalar (SM001 scalar inference)
SCALAR_CALLS = {"max", "min", "len", "int", "float", "round", "abs", "bool"}
COERCERS = {"int", "float", "bool", "complex"}
# SM001: (callee tail -> positions that consume arrays)
ARRAY_CONSUMERS = {
    "similarity": (0, 1), "eval_mask": (1,), "gather_score_topk": (0, 4),
    "search_local_batch": (1, 2), "filter_first_local_batch": (0, 1),
    "dot": (0, 1), "matmul": (0, 1), "einsum": (1, 2), "take": (0,),
    "sum": (0,), "mean": (0,), "top_k": (0,), "where": (0, 1, 2),
}
DTYPE_BYTES = {"float32": 4, "int32": 4, "uint32": 4, "float64": 8,
               "bfloat16": 2, "float16": 2, "int16": 2, "int8": 1,
               "uint8": 1, "bool_": 1}


def dotted(node) -> str | None:
    """'jax.jit' for Attribute/Name chains, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def _tail(name: str | None) -> str:
    return name.rsplit(".", 1)[-1] if name else ""


def _annotate_parents(tree) -> None:
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._bl_parent = node


def _scope_of(node):
    """Nearest enclosing FunctionDef/Module of a node (excluding itself)."""
    cur = getattr(node, "_bl_parent", None)
    while cur is not None and not isinstance(
            cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module)):
        cur = getattr(cur, "_bl_parent", None)
    return cur


def _qualname(fn) -> str:
    parts = [fn.name]
    cur = getattr(fn, "_bl_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            parts.append(cur.name)
        cur = getattr(cur, "_bl_parent", None)
    return ".".join(reversed(parts))


def _param_names(fn) -> list:
    a = fn.args
    params = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        params.append(a.vararg.arg)
    if a.kwarg:
        params.append(a.kwarg.arg)
    return params


def _static_from_keywords(keywords, fn=None) -> set:
    """static_argnames/static_argnums keyword values -> param-name set."""
    static: set = set()
    for kw in keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                static.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                static.update(e.value for e in v.elts
                              if isinstance(e, ast.Constant)
                              and isinstance(e.value, str))
        elif kw.arg == "static_argnums" and fn is not None:
            nums = []
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                nums = [v.value]
            elif isinstance(v, (ast.Tuple, ast.List)):
                nums = [e.value for e in v.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, int)]
            pos = fn.args.posonlyargs + fn.args.args
            for i in nums:
                if 0 <= i < len(pos):
                    static.add(pos[i].arg)
    return static


class ModuleLint:
    """One source file through every level-1 rule."""

    def __init__(self, path: str, source: str, cfg: LintConfig,
                 relpath: str | None = None):
        self.path = relpath if relpath is not None else path
        self.source = source
        self.cfg = cfg
        self.findings: list = []
        self.tree = ast.parse(source, filename=path)
        _annotate_parents(self.tree)
        self.lines = source.splitlines()
        self._module_names: set = set()
        self._defs: dict = {}  # (id(scope), name) -> FunctionDef
        self._partials: dict = {}  # (id(scope), var) -> (fndef, static set)
        self._shard_map_calls: list = []  # (call node, body def)
        self._jit_entries: dict = {}  # name -> static arg-name set
        self._analyzed: set = set()

    # -- driver -------------------------------------------------------------

    def run(self) -> list:
        self._collect()
        self._mark_traced()
        for fn in self._all_defs():
            if getattr(fn, "_bl_traced", False) and not getattr(
                    _scope_of(fn), "_bl_traced", False):
                self._scan_traced(fn, inherited=frozenset())
            elif not getattr(fn, "_bl_traced", False) and self._is_hot(fn):
                self._scan_hot(fn)
            if self._is_hot(fn):
                self._check_ep001(fn)
                self._check_ep002(fn)
        self._check_rc001()
        for call, body in self._shard_map_calls:
            self._check_sm001(call, body)
        self._check_pl001()
        return self.findings

    def _emit(self, rule, node, message, severity="error"):
        line = getattr(node, "lineno", 1)
        ctx = self.lines[line - 1].strip() if line - 1 < len(self.lines) \
            else ""
        self.findings.append(Finding(rule, self.path, line, message,
                                     severity, ctx))

    # -- collection ---------------------------------------------------------

    def _all_defs(self):
        return [n for n in ast.walk(self.tree)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def _collect(self):
        for node in self.tree.body:
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                for a in node.names:
                    self._module_names.add(a.asname or a.name.split(".")[0])
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                self._module_names.add(node.name)
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            self._module_names.add(n.id)
            elif isinstance(node, ast.AnnAssign) and isinstance(
                    node.target, ast.Name):
                self._module_names.add(node.target.id)
        for fn in self._all_defs():
            scope = _scope_of(fn)
            self._defs[(id(scope), fn.name)] = fn

    def _resolve(self, name: str, from_node):
        cur = _scope_of(from_node)
        while cur is not None:
            fn = self._defs.get((id(cur), name))
            if fn is not None:
                return fn
            cur = _scope_of(cur) if not isinstance(cur, ast.Module) else None
        return None

    def _mark(self, fn, static: set, reason: str):
        fn._bl_traced = True
        fn._bl_static = getattr(fn, "_bl_static", set()) | set(static)
        fn._bl_reason = getattr(fn, "_bl_reason", reason)

    def _mark_callable(self, arg, at_node, static=(), reason="wrapped"):
        """Mark the function a wrapper call-arg refers to as traced."""
        if isinstance(arg, ast.Name):
            fn = self._resolve(arg.id, at_node)
            if fn is None:
                # maybe a partial var: partial(kern, **static) -> pallas_call
                rec = self._lookup_partial(arg.id, at_node)
                if rec is not None:
                    self._mark(rec[0], set(static) | rec[1], reason)
                return
            self._mark(fn, static, reason)
        elif isinstance(arg, ast.Call):
            fd = dotted(arg.func)
            if fd in PARTIAL_NAMES and arg.args:
                kw_static = {k.arg for k in arg.keywords if k.arg}
                self._mark_callable(arg.args[0], at_node,
                                    set(static) | kw_static, reason)
            elif fd in JIT_NAMES or fd in VMAP_NAMES or (
                    fd and _tail(fd) in LAX_CALLEES):
                for sub in arg.args:
                    self._mark_callable(sub, at_node, static, reason)
        elif isinstance(arg, ast.Lambda):
            arg._bl_traced = True
            arg._bl_static = set(static)

    def _lookup_partial(self, name, from_node):
        cur = _scope_of(from_node)
        while cur is not None:
            rec = self._partials.get((id(cur), name))
            if rec is not None:
                return rec
            cur = _scope_of(cur) if not isinstance(cur, ast.Module) else None
        return None

    def _mark_traced(self):
        # decorators
        for fn in self._all_defs():
            for dec in fn.decorator_list:
                d = dotted(dec)
                if d in JIT_NAMES:
                    self._mark(fn, set(), "jit")
                    self._jit_entries.setdefault(fn.name, set())
                elif d in VMAP_NAMES:
                    self._mark(fn, set(), "vmap")
                elif isinstance(dec, ast.Call):
                    fd = dotted(dec.func)
                    if fd in PARTIAL_NAMES and dec.args and (
                            dotted(dec.args[0]) in JIT_NAMES):
                        static = _static_from_keywords(dec.keywords, fn)
                        self._mark(fn, static, "jit")
                        self._jit_entries[fn.name] = static
                    elif fd in PARTIAL_NAMES and dec.args and (
                            dotted(dec.args[0]) in VMAP_NAMES):
                        self._mark(fn, set(), "vmap")
                    elif fd in JIT_NAMES:
                        static = _static_from_keywords(dec.keywords, fn)
                        self._mark(fn, static, "jit")
                        self._jit_entries[fn.name] = static
                    elif fd in VMAP_NAMES:
                        self._mark(fn, set(), "vmap")
        # partial assignments + wrapper call sites
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call):
                fd = dotted(node.value.func)
                if fd in PARTIAL_NAMES and node.value.args and isinstance(
                        node.value.args[0], ast.Name):
                    body = self._resolve(node.value.args[0].id, node)
                    if body is not None and len(node.targets) == 1 and \
                            isinstance(node.targets[0], ast.Name):
                        kw_static = {k.arg for k in node.value.keywords
                                     if k.arg}
                        scope = _scope_of(node)
                        self._partials[(id(scope), node.targets[0].id)] = \
                            (body, kw_static)
            if not isinstance(node, ast.Call):
                continue
            fd = dotted(node.func)
            tail = _tail(fd)
            if fd in JIT_NAMES or fd in VMAP_NAMES:
                static = _static_from_keywords(node.keywords)
                for a in node.args:
                    self._mark_callable(a, node, static, "wrapped")
            elif tail == "shard_map":
                if node.args and isinstance(node.args[0], ast.Name):
                    body = self._resolve(node.args[0].id, node)
                    if body is not None:
                        self._mark(body, set(), "shard_map")
                        self._shard_map_calls.append((node, body))
                elif node.args:
                    self._mark_callable(node.args[0], node, (), "shard_map")
            elif tail == "pallas_call":
                if node.args:
                    self._mark_callable(node.args[0], node, (),
                                        "pallas_call")
            elif tail in LAX_CALLEES and fd and fd not in ("map",):
                for a in node.args:
                    if isinstance(a, (ast.Name, ast.Lambda)) or (
                            isinstance(a, ast.Call)
                            and dotted(a.func) in PARTIAL_NAMES):
                        self._mark_callable(a, node, (), "lax")

    # -- HS001 scope A: traced functions ------------------------------------

    def _scan_traced(self, fn, inherited):
        if id(fn) in self._analyzed:
            return
        self._analyzed.add(id(fn))
        params = set(_param_names(fn))
        static = getattr(fn, "_bl_static", set())
        tainted = (params - set(static)) | set(inherited)
        # pass 1 builds the taint environment, pass 2 emits findings —
        # handles names first used above their (re)binding site
        self._walk_traced_body(fn.body, tainted, emit=False)
        self._walk_traced_body(fn.body, set(tainted), emit=True)

    def _walk_traced_body(self, stmts, tainted, emit):
        for st in stmts:
            self._walk_traced_stmt(st, tainted, emit)

    def _walk_traced_stmt(self, st, tainted, emit):
        t = self._taint  # shorthand
        if isinstance(st, ast.Assign):
            val = t(st.value, tainted, emit)
            for tgt in st.targets:
                self._bind(tgt, val, tainted)
        elif isinstance(st, ast.AugAssign):
            val = t(st.value, tainted, emit) or t(st.target, tainted, False)
            self._bind(st.target, val, tainted)
        elif isinstance(st, ast.AnnAssign):
            if st.value is not None:
                self._bind(st.target, t(st.value, tainted, emit), tainted)
        elif isinstance(st, (ast.If, ast.While)):
            if t(st.test, tainted, emit) and emit:
                kind = "while" if isinstance(st, ast.While) else "if"
                self._emit(
                    "HS001", st.test,
                    f"data-dependent `{kind}` on a traced value forces a "
                    f"host sync (TracerBoolConversionError under jit; a "
                    f"silent device round-trip otherwise) — use lax.cond/"
                    f"jnp.where or hoist the decision")
            self._walk_traced_body(st.body, tainted, emit)
            self._walk_traced_body(st.orelse, tainted, emit)
        elif isinstance(st, ast.For):
            val = t(st.iter, tainted, emit)
            self._bind(st.target, val, tainted)
            self._walk_traced_body(st.body, tainted, emit)
            self._walk_traced_body(st.orelse, tainted, emit)
        elif isinstance(st, ast.Assert):
            if t(st.test, tainted, emit) and emit:
                self._emit(
                    "HS001", st.test,
                    "assert on a traced value forces a host sync — assert "
                    "on static shapes or use checkify")
        elif isinstance(st, (ast.Return, ast.Expr)):
            if st.value is not None:
                t(st.value, tainted, emit)
        elif isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            free = self._free_names(st)
            self._scan_traced(st, inherited=frozenset(tainted & free))
        elif isinstance(st, ast.With):
            for item in st.items:
                t(item.context_expr, tainted, emit)
            self._walk_traced_body(st.body, tainted, emit)
        elif isinstance(st, ast.Try):
            self._walk_traced_body(st.body, tainted, emit)
            for h in st.handlers:
                self._walk_traced_body(h.body, tainted, emit)
            self._walk_traced_body(st.orelse, tainted, emit)
            self._walk_traced_body(st.finalbody, tainted, emit)
        elif isinstance(st, (ast.Raise, ast.Delete, ast.Pass, ast.Break,
                             ast.Continue, ast.Global, ast.Nonlocal,
                             ast.Import, ast.ImportFrom, ast.ClassDef)):
            pass
        else:  # anything exotic: evaluate child expressions for taint flags
            for child in ast.iter_child_nodes(st):
                if isinstance(child, ast.expr):
                    t(child, tainted, emit)

    def _bind(self, target, val: bool, tainted):
        for n in ast.walk(target):
            if isinstance(n, ast.Name):
                if val:
                    tainted.add(n.id)
                else:
                    tainted.discard(n.id)

    def _taint(self, e, tainted, emit) -> bool:
        """Taint of an expression; emits HS001 findings when `emit`."""
        if e is None or isinstance(e, ast.Constant):
            return False
        if isinstance(e, ast.Name):
            return e.id in tainted
        if isinstance(e, ast.Attribute):
            if e.attr in STATIC_ATTRS:
                self._taint(e.value, tainted, emit)
                return False
            return self._taint(e.value, tainted, emit)
        if isinstance(e, ast.Subscript):
            v = self._taint(e.value, tainted, emit)
            s = self._taint(e.slice, tainted, emit)
            return v or s
        if isinstance(e, ast.Call):
            return self._taint_call(e, tainted, emit)
        if isinstance(e, ast.Compare):
            left = self._taint(e.left, tainted, emit)
            base = False
            for op, cmp in zip(e.ops, e.comparators):
                ct = self._taint(cmp, tainted, emit)
                if isinstance(op, (ast.Is, ast.IsNot)):
                    continue  # `x is None` stays a static decision — the
                    # identity test resolves at trace time even when x is a
                    # tracer, so the left operand's taint must not leak out
                base = base or left or ct
            return base
        if isinstance(e, ast.IfExp):
            if self._taint(e.test, tainted, emit) and emit:
                self._emit(
                    "HS001", e.test,
                    "conditional expression on a traced value forces a host "
                    "sync — use jnp.where")
            a = self._taint(e.body, tainted, emit)
            b = self._taint(e.orelse, tainted, emit)
            return a or b
        if isinstance(e, ast.Lambda):
            params = {p.arg for p in e.args.args + e.args.kwonlyargs}
            sub = set(tainted) | params
            self._taint(e.body, sub, emit)
            return False
        if isinstance(e, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                          ast.DictComp)):
            sub = set(tainted)
            for gen in e.generators:
                it = self._taint(gen.iter, sub, emit)
                self._bind(gen.target, it, sub)
                for cond in gen.ifs:
                    self._taint(cond, sub, emit)
            if isinstance(e, ast.DictComp):
                return self._taint(e.key, sub, emit) | \
                    self._taint(e.value, sub, emit)
            return self._taint(e.elt, sub, emit)
        # generic containers / operators: tainted if any child is
        out = False
        for child in ast.iter_child_nodes(e):
            if isinstance(child, ast.expr):
                out = self._taint(child, tainted, emit) or out
            elif isinstance(child, ast.keyword):
                out = self._taint(child.value, tainted, emit) or out
        return out

    def _taint_call(self, e, tainted, emit) -> bool:
        fd = dotted(e.func)
        arg_taints = [self._taint(a, tainted, emit) for a in e.args]
        kw_taints = [self._taint(k.value, tainted, emit)
                     for k in e.keywords]
        any_arg = any(arg_taints) or any(kw_taints)
        recv = False
        if isinstance(e.func, ast.Attribute):
            recv = self._taint(e.func.value, tainted, emit)
            if e.func.attr in ("item", "tolist") and recv:
                if emit:
                    self._emit(
                        "HS001", e,
                        f"`.{e.func.attr}()` on a traced value is a "
                        f"device->host sync inside a traced function")
                return False
        if fd in COERCERS and any_arg:
            if emit:
                self._emit(
                    "HS001", e,
                    f"`{fd}()` coercion of a traced value forces a host "
                    f"sync (ConcretizationTypeError under jit)")
            return False
        if fd and fd.split(".")[0] in NP_ALIASES and any_arg:
            if emit:
                self._emit(
                    "HS001", e,
                    f"`{fd}(...)` pulls a traced value through NumPy — a "
                    f"device->host transfer inside a traced function; use "
                    f"the jnp equivalent")
            return True
        if fd in ("jax.device_get",) and any_arg:
            if emit:
                self._emit("HS001", e,
                           "`jax.device_get` inside a traced function")
            return False
        if fd in UNTAINTING_CALLS:
            return False
        return any_arg or recv

    def _free_names(self, fn) -> frozenset:
        bound = set(_param_names(fn))
        loads = set()
        for n in ast.walk(fn):
            if isinstance(n, ast.Name):
                if isinstance(n.ctx, (ast.Store, ast.Del)):
                    bound.add(n.id)
                else:
                    loads.add(n.id)
            elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and n is not fn:
                bound.add(n.name)
            elif isinstance(n, (ast.Import, ast.ImportFrom)):
                for a in n.names:
                    bound.add(a.asname or a.name.split(".")[0])
        return frozenset(loads - bound)

    # -- HS001 scope B: hot host functions ----------------------------------

    def _is_hot(self, fn) -> bool:
        qn = _qualname(fn)
        for path_suffix, pattern in self.cfg.hot_functions:
            if self.path.endswith(path_suffix) and fnmatch.fnmatch(
                    qn, pattern):
                return True
        return False

    def _scan_hot(self, fn):
        transfers: dict = {}  # unparsed arg -> [nodes]
        own_nodes = [n for n in ast.walk(fn)
                     if self._owner_fn(n) is fn]
        for node in own_nodes:
            if not isinstance(node, ast.Call):
                continue
            fd = dotted(node.func)
            is_np_transfer = fd and fd.split(".")[0] in NP_ALIASES and \
                _tail(fd) in ("asarray", "array")
            is_item = isinstance(node.func, ast.Attribute) and \
                node.func.attr in ("item", "tolist")
            is_get = fd == "jax.device_get"
            is_coerce = fd in COERCERS and node.args and not isinstance(
                node.args[0], ast.Constant)
            if is_np_transfer or is_item:
                arg = node.func.value if is_item else (
                    node.args[0] if node.args else None)
                if arg is not None and not isinstance(arg, ast.Constant):
                    transfers.setdefault(ast.unparse(arg),
                                         []).append((node, arg))
            if (is_np_transfer or is_item or is_get or is_coerce) and \
                    self._loop_depth(node, fn) > 0:
                label = f"`.{node.func.attr}()`" if is_item else f"`{fd}()`"
                self._emit(
                    "HS001", node,
                    f"{label} inside a loop of hot function "
                    f"`{_qualname(fn)}` — a device->host sync per "
                    f"iteration; hoist to one transfer per batch/round")
        # duplicate-transfer grouping: two same-text transfers count only
        # when (a) both can execute in one pass (no mutually exclusive `if`
        # arms between them) and (b) no name the expression reads is
        # reassigned between the two sites (a rebound `ids` is a new value)
        stores = sorted(
            (n.lineno, n.id) for n in own_nodes
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store))
        for src, sites in transfers.items():
            if len(sites) < 2:
                continue
            sites.sort(key=lambda p: p[0].lineno)
            done = False
            for i in range(1, len(sites)):
                cur, arg = sites[i]
                roots = {nm.id for nm in ast.walk(arg)
                         if isinstance(nm, ast.Name)}
                sig_cur = self._branch_sig(cur, fn)
                for prev, _a in sites[:i]:
                    sig_prev = self._branch_sig(prev, fn)
                    if any(sig_cur.get(key, arm) != arm
                           for key, arm in sig_prev.items()):
                        continue  # mutually exclusive branches
                    if any(prev.lineno < ln < cur.lineno and nm in roots
                           for ln, nm in stores):
                        continue  # rebound between the sites
                    if self._assign_targets(prev) & self._none_guards(
                            cur, fn):
                        continue  # lazy-memo idiom: `if x is None: x = ...`
                    self._emit(
                        "HS001", cur,
                        f"repeated host transfer of `{src}` in hot "
                        f"function `{_qualname(fn)}` ({len(sites)} sites) "
                        f"— transfer once and reuse the host value")
                    done = True
                    break
                if done:
                    break

    # -- EP001: epoch-consistency of tiered reads ---------------------------

    def _check_ep001(self, fn):
        """Serving hot paths must read tiered ingest state through ONE
        ``snapshot()`` taken at batch-formation time. A direct read of a
        mutable ``TieredTable`` field (``_hot``/``_cold``/``_sealing``/...)
        can observe a DIFFERENT epoch than the rest of the batch when a
        background compaction swaps mid-flight — mixed-epoch row ids are
        silently wrong, not crashes. The detector is textual by design: any
        attribute access whose base expression mentions ``tiered`` and
        whose attr is a registered mutable field."""
        banned = set(self.cfg.tiered_mutable_fields)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Attribute) or \
                    self._owner_fn(node) is not fn:
                continue
            if node.attr not in banned:
                continue
            base = ast.unparse(node.value)
            if "tiered" not in base:
                continue
            self._emit(
                "EP001", node,
                f"hot function `{_qualname(fn)}` reads mutable tiered "
                f"state `{base}.{node.attr}` directly — a background "
                f"compaction can swap the epoch mid-batch and mix row-id "
                f"spaces; take one `tiered.snapshot()` at batch formation "
                f"and read `(epoch, cold, hot_views)` from it")

    # -- EP002: freshness of semantic-cache reads ----------------------------

    def _check_ep002(self, fn):
        """Serving hot paths must not read semantic-cache entry payloads
        (``ids``/``scores``/``centroids``) without a freshness check: a raw
        entry read can serve a result computed under a PREVIOUS epoch —
        resurrecting pre-compaction row ids — or one that predates a
        hot-tier insert. The sanctioned read is ``SemanticCache.lookup()``
        (it enforces the ``(epoch, n_rows)`` token internally); a function
        that compares an entry's ``token``/``epoch`` explicitly also
        qualifies. Textual like EP001: attribute reads whose base mentions
        ``cache`` or ``entry``."""
        if self._has_freshness_check(fn):
            return
        banned = set(self.cfg.cache_entry_fields)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Attribute) or \
                    self._owner_fn(node) is not fn:
                continue
            if node.attr not in banned:
                continue
            base = ast.unparse(node.value).lower()
            if "cache" not in base and "entry" not in base:
                continue
            self._emit(
                "EP002", node,
                f"hot function `{_qualname(fn)}` reads cache-entry payload "
                f"`{base}.{node.attr}` without a freshness check — a stale "
                f"entry can resurrect pre-compaction results; go through "
                f"`SemanticCache.lookup()` (token-checked) or compare the "
                f"entry's token against the current `(epoch, n_rows)` first")

    def _has_freshness_check(self, fn) -> bool:
        """True when fn reads the cache through lookup() or explicitly
        compares a token/epoch attribute (either side of any comparison)."""
        for node in ast.walk(fn):
            if self._owner_fn(node) is not fn:
                continue
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "lookup":
                return True
            if isinstance(node, ast.Compare):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Attribute) and \
                            sub.attr in ("token", "epoch"):
                        return True
        return False

    @staticmethod
    def _assign_targets(node) -> set:
        """Names the nearest enclosing Assign binds (node on its RHS)."""
        prev, cur = node, getattr(node, "_bl_parent", None)
        while cur is not None and not isinstance(cur, ast.stmt):
            prev, cur = cur, getattr(cur, "_bl_parent", None)
        if isinstance(cur, ast.Assign) and prev is cur.value:
            return {n.id for t in cur.targets for n in ast.walk(t)
                    if isinstance(n, ast.Name)}
        return set()

    def _none_guards(self, node, fn) -> set:
        """Names N where node sits in the body of `if N is None:`."""
        guards: set = set()
        prev, cur = node, getattr(node, "_bl_parent", None)
        while cur is not None and cur is not fn:
            if isinstance(cur, ast.If) and any(prev is s for s in cur.body):
                t = cur.test
                if isinstance(t, ast.Compare) and len(t.ops) == 1 and \
                        isinstance(t.ops[0], ast.Is) and \
                        isinstance(t.left, ast.Name) and isinstance(
                            t.comparators[0], ast.Constant) and \
                        t.comparators[0].value is None:
                    guards.add(t.left.id)
            prev, cur = cur, getattr(cur, "_bl_parent", None)
        return guards

    def _branch_sig(self, node, fn) -> dict:
        """{id(if-node): arm} for every `if` between node and fn — two
        nodes with the same if on different arms never co-execute."""
        sig = {}
        prev, cur = node, getattr(node, "_bl_parent", None)
        while cur is not None and cur is not fn:
            if isinstance(cur, ast.If) and prev is not cur.test:
                in_body = any(prev is s for s in cur.body)
                sig[id(cur)] = "body" if in_body else "orelse"
            prev, cur = cur, getattr(cur, "_bl_parent", None)
        return sig

    def _owner_fn(self, node):
        cur = getattr(node, "_bl_parent", None)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return cur
            cur = getattr(cur, "_bl_parent", None)
        return None

    def _loop_depth(self, node, fn) -> int:
        depth = 0
        prev = node
        cur = getattr(node, "_bl_parent", None)
        while cur is not None and cur is not fn:
            if isinstance(cur, (ast.For, ast.While)):
                depth += 1
            elif isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
            prev, cur = cur, getattr(cur, "_bl_parent", None)
        # a While's test runs every iteration too
        if isinstance(cur, ast.While) and prev is cur.test:
            depth += 1
        return depth

    # -- RC001: recompile hazards -------------------------------------------

    def _check_rc001(self):
        # static_argnames naming a parameter that does not exist
        for fn in self._all_defs():
            static = getattr(fn, "_bl_static", set())
            if not static:
                continue
            params = set(_param_names(fn))
            for s in sorted(static - params):
                self._emit(
                    "RC001", fn,
                    f"static_argnames entry '{s}' does not match any "
                    f"parameter of `{fn.name}` — jit will raise (or worse, "
                    f"silently trace the argument)")
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            entry = self._jit_entry_for(node)
            if entry is None:
                continue
            name, static = entry
            for kw in node.keywords:
                if kw.arg not in static:
                    continue
                v = kw.value
                if isinstance(v, (ast.List, ast.Set, ast.Dict)):
                    self._emit(
                        "RC001", v,
                        f"unhashable {type(v).__name__.lower()} literal "
                        f"passed to static arg '{kw.arg}' of jitted "
                        f"`{name}` — static args must be hashable")
                elif isinstance(v, ast.Constant) and isinstance(
                        v.value, int) and not isinstance(v.value, bool):
                    if not self.cfg.allowed_shape_literal(v.value):
                        self._emit(
                            "RC001", v,
                            f"shape-bearing literal {v.value} passed to "
                            f"static arg '{kw.arg}' of jitted `{name}` is "
                            f"not a registered grid value or pow2 bucket — "
                            f"every novel value is a recompile; draw it "
                            f"from SHAPE_GRIDS / next_bucket "
                            f"(serve/batch.py)")

    def _jit_entry_for(self, call):
        """(name, static set) if the call targets a known jitted entry."""
        fd = dotted(call.func)
        if not fd:
            return None
        tail = _tail(fd)
        if isinstance(call.func, ast.Name):
            fn = self._resolve(tail, call)
            if fn is not None and getattr(fn, "_bl_traced", False):
                static = getattr(fn, "_bl_static", set())
                return (tail, static) if static else None
        if tail in self._cross_module_jits():
            return (tail, self._cross_module_jits()[tail])
        return None

    _XMOD_CACHE: dict = {}

    @classmethod
    def register_jit_entries(cls, entries: dict):
        """Feed jitted-entry signatures collected from other modules (the
        runner collects the whole scan set first, then lints)."""
        cls._XMOD_CACHE.update(entries)

    @classmethod
    def reset_jit_entries(cls):
        cls._XMOD_CACHE.clear()

    def _cross_module_jits(self) -> dict:
        return self._XMOD_CACHE

    def collect_jit_entries(self) -> dict:
        """name -> static names, for decorated jits in this module."""
        self._collect()
        self._mark_traced()
        return dict(self._jit_entries)

    # -- SM001: shard_map closure capture -----------------------------------

    def _check_sm001(self, call, body):
        free = self._free_names(body)
        enclosing_bound: set = set()
        cur = _scope_of(body)
        while cur is not None and not isinstance(cur, ast.Module):
            enclosing_bound.update(_param_names(cur))
            for n in ast.walk(cur):
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store) \
                        and self._owner_fn(n) is cur:
                    enclosing_bound.add(n.id)
            cur = _scope_of(cur)
        candidates = (free & enclosing_bound) - self._module_names
        # host scalars (shape arithmetic, config fields, max/min/len) are
        # broadcast-free closures — only array-like captures replicate
        candidates = {c for c in candidates
                      if not self._scalar_like(c, body)}
        if not candidates:
            return
        flagged = set()
        for n in ast.walk(body):
            if isinstance(n, ast.Subscript) and isinstance(
                    n.value, ast.Name) and n.value.id in candidates:
                flagged.add((n.value.id, n))
            elif isinstance(n, ast.BinOp) and isinstance(n.op, ast.MatMult):
                for side in (n.left, n.right):
                    while isinstance(side, ast.Attribute):
                        side = side.value  # unwrap table.T / x.real / ...
                    if isinstance(side, ast.Name) and side.id in candidates:
                        flagged.add((side.id, n))
            elif isinstance(n, ast.Call):
                tail = _tail(dotted(n.func))
                positions = ARRAY_CONSUMERS.get(tail)
                if positions is None:
                    continue
                for i, a in enumerate(n.args):
                    if i in positions and isinstance(a, ast.Name) and \
                            a.id in candidates:
                        flagged.add((a.id, n))
        for name, node in sorted(flagged, key=lambda x: (x[0],
                                                         x[1].lineno)):
            self._emit(
                "SM001", node,
                f"shard_map body `{body.name}` closes over `{name}` and "
                f"uses it as an array — closed-over arrays replicate to "
                f"every device; pass it through in_specs with a sharded "
                f"PartitionSpec instead")

    # -- SM001 scalar inference ---------------------------------------------

    def _scalar_like(self, name: str, body) -> bool:
        """True when a name free in a shard_map body is provably a host
        scalar in the enclosing scope chain (shape arithmetic, `*Config`
        attribute reads, max/min/len results)."""
        bindings, config_params = self._enclosing_bindings(body)
        return self._expr_scalar(ast.Name(id=name, ctx=ast.Load()),
                                 bindings, config_params, set())

    def _enclosing_bindings(self, body):
        bindings: dict = {}  # name -> [value exprs | True (shape dim)]
        config_params: set = set()
        cur = _scope_of(body)
        while cur is not None and not isinstance(cur, ast.Module):
            a = cur.args
            for arg in (*a.posonlyargs, *a.args, *a.kwonlyargs):
                ann = arg.annotation
                if isinstance(ann, ast.Name) and ann.id.endswith("Config"):
                    config_params.add(arg.arg)
            for n in ast.walk(cur):
                if self._owner_fn(n) is not cur:
                    continue
                if isinstance(n, ast.Assign):
                    for tgt in n.targets:
                        self._record_binding(tgt, n.value, bindings)
                elif isinstance(n, (ast.AugAssign, ast.AnnAssign)) and \
                        isinstance(n.target, ast.Name) and \
                        n.value is not None:
                    bindings.setdefault(n.target.id, []).append(n.value)
            cur = _scope_of(cur)
        return bindings, config_params

    def _record_binding(self, tgt, value, bindings):
        if isinstance(tgt, ast.Name):
            bindings.setdefault(tgt.id, []).append(value)
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            if isinstance(value, (ast.Tuple, ast.List)) and \
                    len(value.elts) == len(tgt.elts):
                for t, v in zip(tgt.elts, value.elts):
                    self._record_binding(t, v, bindings)
            elif isinstance(value, ast.Attribute) and \
                    value.attr == "shape":
                for t in tgt.elts:  # b, s, d = x.shape — each dim an int
                    if isinstance(t, ast.Name):
                        bindings.setdefault(t.id, []).append(True)

    def _expr_scalar(self, e, bindings, config_params, seen) -> bool:
        if e is True:
            return True
        if isinstance(e, ast.Constant):
            return True
        if isinstance(e, ast.Name):
            if e.id in seen:
                return True  # cycle (x *= ...): other bindings decide
            bound = bindings.get(e.id)
            if not bound:
                return False
            seen = seen | {e.id}
            return all(self._expr_scalar(b, bindings, config_params, seen)
                       for b in bound)
        if isinstance(e, ast.BinOp):
            return not isinstance(e.op, ast.MatMult) and \
                self._expr_scalar(e.left, bindings, config_params, seen) \
                and self._expr_scalar(e.right, bindings, config_params,
                                      seen)
        if isinstance(e, ast.UnaryOp):
            return self._expr_scalar(e.operand, bindings, config_params,
                                     seen)
        if isinstance(e, ast.IfExp):
            return self._expr_scalar(e.body, bindings, config_params,
                                     seen) and \
                self._expr_scalar(e.orelse, bindings, config_params, seen)
        if isinstance(e, ast.Compare):
            return True
        if isinstance(e, ast.Call):
            fd = dotted(e.func)
            return (isinstance(e.func, ast.Name)
                    and e.func.id in SCALAR_CALLS) or \
                (fd or "").startswith("math.") or _tail(fd) == "item"
        if isinstance(e, ast.Subscript):
            return isinstance(e.value, ast.Attribute) and \
                e.value.attr == "shape"
        if isinstance(e, ast.Attribute):
            if e.attr in STATIC_ATTRS:
                return True  # static metadata reads (shape/ndim/size/...)
            return isinstance(e.value, ast.Name) and \
                e.value.id in config_params
        return False

    # -- PL001 (AST level): literal Pallas shapes ---------------------------

    def _check_pl001(self):
        budget = self.cfg.budget()
        per_fn: dict = {}
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            tail = _tail(dotted(node.func))
            size = 0
            if tail == "BlockSpec" and node.args and isinstance(
                    node.args[0], ast.Tuple):
                size = self._literal_bytes(node.args[0], 4)
            elif tail == "VMEM" and node.args and isinstance(
                    node.args[0], ast.Tuple):
                itemsize = 4
                if len(node.args) > 1:
                    itemsize = DTYPE_BYTES.get(
                        _tail(dotted(node.args[1])), 4)
                size = self._literal_bytes(node.args[0], itemsize)
            if size:
                owner = self._owner_fn(node) or self.tree
                rec = per_fn.setdefault(id(owner), [owner, 0, node])
                rec[1] += size
        for owner, total, first in per_fn.values():
            if total > budget:
                name = getattr(owner, "name", "<module>")
                self._emit(
                    "PL001", first,
                    f"literal Pallas block shapes in `{name}` sum to "
                    f"{total / 2**20:.1f} MiB of VMEM — over the "
                    f"{budget / 2**20:.0f} MiB budget; shrink the tile or "
                    f"grid it (kernels/shapes.py holds the supported "
                    f"envelope)")

    @staticmethod
    def _literal_bytes(tup, itemsize) -> int:
        total = itemsize
        for e in tup.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                total *= e.value
            else:
                return 0  # symbolic dim: the trace-level estimator owns it
        return total


def lint_source(path: str, source: str, cfg: LintConfig | None = None,
                relpath: str | None = None) -> list:
    """Lint one module's source. Returns raw findings (suppressions are
    applied by the runner)."""
    return ModuleLint(path, source, cfg or LintConfig(), relpath).run()
