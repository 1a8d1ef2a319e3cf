"""The JAX lint rules (RPA001-RPA010), distilled from PR 1-7 incidents.

Each rule is a heuristic AST pass.  The common machinery:

* *traced scope* — a function we believe runs under a JAX trace: it is
  decorated with / wrapped by ``jax.jit`` / ``vmap`` / ``grad`` / ...,
  passed as a body to ``lax.scan`` / ``while_loop`` / ``cond`` /
  ``fori_loop``, or (transitively) called from such a function in the
  same module.
* *taint* — inside a traced scope, the function's parameters (minus
  ``static_argnames``) and everything derived from them are treated as
  traced values; values derived only from ``.shape`` / ``.ndim`` /
  ``.dtype`` / ``len()`` / ``isinstance()`` are static and untainted.

Heuristics can over- or under-approximate — that is what the inline
``# repro: noqa(RULE)`` escape hatch (with a justification) is for; the
known-bad/known-good corpus (:mod:`repro.analysis.corpus`) pins the
intended behavior of every rule.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

# --------------------------------------------------------------- rules --


@dataclasses.dataclass(frozen=True)
class Rule:
    code: str
    name: str
    summary: str
    hint: str


RULES: Dict[str, Rule] = {r.code: r for r in [
    Rule("RPA000", "syntax-error",
         "the file does not parse",
         "fix the syntax error; nothing else can be checked"),
    Rule("RPA001", "prng-key-reuse",
         "a PRNG key is consumed by more than one jax.random call",
         "split the key first: `key, sub = jax.random.split(key)` and "
         "consume each subkey exactly once"),
    Rule("RPA002", "prng-split-without-consume",
         "jax.random.split result is discarded",
         "bind the new keys (`key, sub = jax.random.split(key)`); a "
         "discarded split advances nothing and usually shadows a reuse"),
    Rule("RPA003", "host-sync-in-jit",
         "host-side conversion (float/int/bool/.item()/np.asarray) of a "
         "traced value inside a traced scope",
         "keep the value on device (jnp.*) or move the conversion outside "
         "the jitted function; host syncs break tracing or force a "
         "blocking device round-trip"),
    Rule("RPA004", "python-branch-on-traced",
         "Python `if`/`while`/`assert` on a traced value inside a traced "
         "scope",
         "use jnp.where / lax.cond / lax.while_loop, or mark the argument "
         "static; Python control flow on tracers raises "
         "TracerBoolConversionError or bakes in one branch"),
    Rule("RPA005", "mutable-static-arg",
         "mutable/non-hashable default or partial-bound arg on a jitted "
         "function",
         "use hashable values (tuples, frozen dataclasses) for static "
         "args; mutable defaults are shared across calls and unhashable "
         "statics either TypeError or silently retrace per call"),
    Rule("RPA006", "unregistered-dataclass-in-jit",
         "a non-frozen, non-pytree-registered dataclass instance is "
         "passed into a jitted call",
         "register it (jax.tree_util.register_dataclass / "
         "register_pytree_node) or freeze it and pass it static; "
         "unregistered instances are leaves and fail or silently retrace"),
    Rule("RPA007", "module-import-cycle",
         "module-level import cycle inside the package",
         "move one import into the function that needs it or behind "
         "`if TYPE_CHECKING:` (see solver/sca.py); cycles make import "
         "order load-bearing and broke repro.solver<->repro.core in PR 3"),
    Rule("RPA008", "np-on-traced-value",
         "numpy (host) op applied to a traced value inside a traced scope",
         "use the jnp.* equivalent; np.* forces the tracer to concretize "
         "(TracerArrayConversionError) or silently computes on stale "
         "host copies"),
    Rule("RPA009", "callback-in-hot-scan",
         "a host callback (pure_callback / io_callback / jax.debug.print "
         "/ jax.debug.callback / id_tap) inside a lax.scan / fori_loop / "
         "while_loop body",
         "hoist the callback out of the loop or accumulate into the "
         "carry and report after the loop; a per-iteration host "
         "round-trip serializes the scan and blocks fusion (the jaxpr "
         "twin is audit pass JXP005)"),
    Rule("RPA010", "f64-literal-promotion",
         "a float-literal jnp constructor (array/asarray of a float "
         "list/tuple, linspace/logspace/geomspace) without an explicit "
         "dtype",
         "pin the dtype: `jnp.array([0.5], dtype=jnp.float32)`; bare "
         "float-list literals are STRONG-typed and become f64 the "
         "moment jax_enable_x64 flips, widening the whole downstream "
         "graph (the jaxpr twin is audit pass JXP002; Python scalars — "
         "including `jnp.full(shape, 0.5)` fills — stay weak-typed and "
         "are fine)"),
]}


@dataclasses.dataclass(frozen=True)
class RawFinding:
    """A rule hit before noqa filtering (module-local coordinates)."""
    line: int
    col: int
    code: str
    message: str


# ------------------------------------------------------------ helpers --

_TRACE_ENTRY = {"jit", "vmap", "pmap", "grad", "value_and_grad",
                "jacfwd", "jacrev", "hessian", "checkpoint", "remat"}
_LAX_BODY = {"scan", "while_loop", "cond", "fori_loop", "switch", "map",
             "associative_scan", "custom_root", "custom_linear_solve"}
_LOOP_PRIMS = {"scan", "while_loop", "fori_loop", "map",
               "associative_scan"}       # bodies run per iteration
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval",
                 "weak_type"}
_STATIC_CALLS = {"len", "isinstance", "issubclass", "hasattr", "getattr",
                 "type", "id", "repr", "str"}
_KEY_PRODUCERS = {"PRNGKey", "key", "split", "fold_in", "clone", "wrap_key_data"}
_HOST_CASTS = {"float", "int", "bool", "complex"}
_NP_STATIC_OK = {"dtype", "shape", "ndim", "result_type", "promote_types",
                 "broadcast_shapes", "issubdtype", "iinfo", "finfo"}


def _qualname(node: ast.AST) -> Optional[str]:
    """Dotted name of a Name/Attribute chain, e.g. ``jax.random.split``."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _target_names(target: ast.AST) -> List[str]:
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out: List[str] = []
        for elt in target.elts:
            out.extend(_target_names(elt))
        return out
    if isinstance(target, ast.Starred):
        return _target_names(target.value)
    return []


class _Aliases:
    """Import aliases of one module: what names mean jax / numpy / etc."""

    def __init__(self, tree: ast.Module):
        self.np: Set[str] = set()
        self.jnp: Set[str] = set()
        self.jax: Set[str] = set()
        self.random: Set[str] = set()       # modules that ARE jax.random
        self.lax: Set[str] = set()
        self.partial: Set[str] = {"functools.partial"}
        self.jit: Set[str] = set()          # bare names that are jax.jit
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "numpy":
                        self.np.add(a.asname or "numpy")
                    elif a.name == "jax.numpy" and a.asname:
                        self.jnp.add(a.asname)
                    elif a.name == "jax":
                        self.jax.add(a.asname or "jax")
                    elif a.name == "jax.random" and a.asname:
                        self.random.add(a.asname)
                    elif a.name == "jax.lax" and a.asname:
                        self.lax.add(a.asname)
                    elif a.name == "functools":
                        self.partial.add((a.asname or "functools")
                                         + ".partial")
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    bound = a.asname or a.name
                    if node.module == "jax":
                        if a.name == "numpy":
                            self.jnp.add(bound)
                        elif a.name == "random":
                            self.random.add(bound)
                        elif a.name == "lax":
                            self.lax.add(bound)
                        elif a.name == "jit":
                            self.jit.add(bound)
                    elif node.module == "functools" and a.name == "partial":
                        self.partial.add(bound)
                    elif node.module == "jax.numpy":
                        pass        # from jax.numpy import x — fine
        for j in self.jax:
            self.random.add(f"{j}.random")
            self.lax.add(f"{j}.lax")
            self.jit.add(f"{j}.jit")

    def is_np_call(self, q: Optional[str]) -> Optional[str]:
        """If ``q`` is ``np.<fn>``, return ``<fn>``."""
        if not q or "." not in q:
            return None
        head, _, rest = q.partition(".")
        return rest if head in self.np and "." not in rest else None

    def is_jnp_call(self, q: Optional[str]) -> Optional[str]:
        """If ``q`` is ``jnp.<fn>`` / ``jax.numpy.<fn>``, return ``<fn>``."""
        if not q or "." not in q:
            return None
        head, _, rest = q.partition(".")
        if head in self.jnp and "." not in rest:
            return rest
        if head in self.jax and rest.startswith("numpy."):
            tail = rest[len("numpy."):]
            return tail if "." not in tail else None
        return None

    def is_random_call(self, q: Optional[str]) -> Optional[str]:
        """If ``q`` is ``jax.random.<fn>`` (any alias), return ``<fn>``."""
        if not q:
            return None
        for prefix in self.random:
            if q.startswith(prefix + "."):
                rest = q[len(prefix) + 1:]
                return rest if "." not in rest else None
        return None

    def is_jit(self, q: Optional[str]) -> bool:
        return q in self.jit

    def trace_entry(self, q: Optional[str]) -> bool:
        """jax.jit/vmap/grad/... wrapper call."""
        if q is None:
            return False
        if q in self.jit:
            return True
        head, _, rest = q.partition(".")
        return head in self.jax and rest in _TRACE_ENTRY

    def lax_body_call(self, q: Optional[str]) -> bool:
        if q is None or "." not in q:
            return False
        head, _, rest = q.rpartition(".")
        return head in self.lax and rest in _LAX_BODY


# ------------------------------------------------- traced-scope finder --

def _decorator_static_argnames(dec: ast.AST) -> Set[str]:
    names: Set[str] = set()
    if isinstance(dec, ast.Call):
        for kw in dec.keywords:
            if kw.arg == "static_argnames" and isinstance(
                    kw.value, (ast.Tuple, ast.List)):
                for elt in kw.value.elts:
                    if isinstance(elt, ast.Constant) and \
                            isinstance(elt.value, str):
                        names.add(elt.value)
            elif kw.arg == "static_argnames" and isinstance(
                    kw.value, ast.Constant) and \
                    isinstance(kw.value.value, str):
                names.add(kw.value.value)
    return names


class _Module:
    """Per-module analysis state shared by the rules."""

    def __init__(self, tree: ast.Module):
        self.tree = tree
        self.aliases = _Aliases(tree)
        # every function node in the module, by bare name (last def wins
        # is fine for the heuristic)
        self.functions: Dict[str, ast.FunctionDef] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions.setdefault(node.name, node)
        self.traced: Dict[ast.AST, Set[str]] = {}   # fn node -> static args
        self.jitted_names: Set[str] = set()  # names bound to jitted callables
        self.loop_bodies: Set[ast.AST] = set()  # fn nodes that run per
        #   iteration of a lax loop (scan/fori/while/map/associative_scan)
        self._find_traced()

    def _is_jit_decorator(self, dec: ast.AST) -> bool:
        al = self.aliases
        q = _qualname(dec)
        if al.trace_entry(q):
            return True
        if isinstance(dec, ast.Call):
            q = _qualname(dec.func)
            if al.trace_entry(q):
                return True
            # @partial(jax.jit, ...)
            if q in al.partial and dec.args and \
                    al.trace_entry(_qualname(dec.args[0])):
                return True
        return False

    def _mark(self, name_or_node, static: Set[str] = frozenset()):
        node = self.functions.get(name_or_node) \
            if isinstance(name_or_node, str) else name_or_node
        if node is not None and node not in self.traced:
            self.traced[node] = set(static)

    def _find_traced(self) -> None:
        al = self.aliases
        # 1. decorated functions
        for fn in self.functions.values():
            for dec in fn.decorator_list:
                if self._is_jit_decorator(dec):
                    self._mark(fn, _decorator_static_argnames(dec))
        # 2. wrapper calls: jax.jit(f), jax.vmap(f), lax.scan(f, ...)
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            q = _qualname(node.func)
            if al.trace_entry(q) and node.args:
                target = node.args[0]
                static = _decorator_static_argnames(node)
                tq = _qualname(target)
                if tq and "." not in tq:
                    self._mark(tq, static)
                elif isinstance(target, ast.Lambda):
                    self._mark(target, static)
                elif isinstance(target, ast.Call):
                    # jax.jit(partial(f, ...)) / jax.jit(vmap(f))
                    iq = _qualname(target.func)
                    if (iq in al.partial or al.trace_entry(iq)) and \
                            target.args:
                        inner = _qualname(target.args[0])
                        if inner and "." not in inner:
                            self._mark(inner, static)
            elif al.lax_body_call(q):
                prim = q.rpartition(".")[2]
                if prim == "while_loop":
                    bodies = list(node.args[:2])
                elif prim == "fori_loop":
                    # fori_loop(lower, upper, body_fun, init) — the body
                    # is the THIRD positional (args[:1] would mark
                    # `lower`, a silent no-op)
                    bodies = list(node.args[2:3])
                elif prim == "switch":
                    bodies = [e for a in node.args[1:2]
                              for e in (a.elts if isinstance(
                                  a, (ast.List, ast.Tuple)) else [a])]
                else:
                    bodies = list(node.args[:1])
                bodies += [kw.value for kw in node.keywords
                           if kw.arg in ("f", "body_fun", "cond_fun",
                                         "body", "fn")]
                is_loop = prim in _LOOP_PRIMS
                for b in bodies:
                    bq = _qualname(b)
                    target = None
                    if bq and "." not in bq:
                        self._mark(bq)
                        target = self.functions.get(bq)
                    elif isinstance(b, ast.Lambda):
                        self._mark(b)
                        target = b
                    if is_loop and target is not None:
                        self.loop_bodies.add(target)
        # names bound to jitted callables: g = jax.jit(f, ...)
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call) and \
                    al.trace_entry(_qualname(node.value.func)):
                for t in node.targets:
                    self.jitted_names.update(_target_names(t))
        # 3. transitive closure: plain local functions called from a
        #    traced body run under the same trace
        changed = True
        while changed:
            changed = False
            for fn in list(self.traced):
                body = fn.body if isinstance(
                    fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    else [fn.body]
                for node in (n for stmt in body for n in ast.walk(stmt)):
                    if isinstance(node, ast.Call):
                        cq = _qualname(node.func)
                        if cq and "." not in cq and cq in self.functions:
                            callee = self.functions[cq]
                            if callee not in self.traced:
                                self._mark(callee)
                                changed = True
        # 4. loop-body closure: a function called from a per-iteration
        #    body runs per iteration too (RPA009 scope)
        changed = True
        while changed:
            changed = False
            for fn in list(self.loop_bodies):
                body = fn.body if isinstance(
                    fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    else [fn.body]
                for node in (n for stmt in body for n in ast.walk(stmt)):
                    if isinstance(node, ast.Call):
                        cq = _qualname(node.func)
                        if cq and "." not in cq and cq in self.functions:
                            callee = self.functions[cq]
                            if callee not in self.loop_bodies:
                                self.loop_bodies.add(callee)
                                changed = True


# ------------------------------------------------------ taint analysis --

def _fn_params(fn) -> List[str]:
    if isinstance(fn, ast.Lambda):
        a = fn.args
    else:
        a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


def _expr_tainted(node: ast.AST, tainted: Set[str]) -> bool:
    """Does ``node`` (an expression) derive from a traced value?"""
    if isinstance(node, ast.Name):
        return node.id in tainted
    if isinstance(node, ast.Attribute):
        if node.attr in _STATIC_ATTRS:
            return False
        return _expr_tainted(node.value, tainted)
    if isinstance(node, ast.Call):
        q = _qualname(node.func)
        if q in _STATIC_CALLS:
            return False
        args = list(node.args) + [kw.value for kw in node.keywords]
        fn_tainted = isinstance(node.func, ast.Attribute) and \
            _expr_tainted(node.func, tainted)
        return fn_tainted or any(_expr_tainted(a, tainted) for a in args)
    if isinstance(node, ast.Constant):
        return False
    if isinstance(node, ast.Compare):
        # `x is None` / `x is not None` are static identity checks
        if len(node.ops) == 1 and isinstance(
                node.ops[0], (ast.Is, ast.IsNot)):
            return False
        return any(_expr_tainted(c, tainted)
                   for c in [node.left] + node.comparators)
    for child in ast.iter_child_nodes(node):
        if isinstance(child, (ast.expr, ast.keyword)):
            inner = child.value if isinstance(child, ast.keyword) else child
            if inner is not None and _expr_tainted(inner, tainted):
                return True
    return False


def _propagate_taint(fn, tainted: Set[str]) -> Set[str]:
    """Two fixpoint passes: names assigned from tainted exprs are tainted."""
    body = fn.body if isinstance(fn, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)) else [fn.body]
    for _ in range(2):
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Assign):
                    if _expr_tainted(node.value, tainted):
                        for t in node.targets:
                            tainted.update(_target_names(t))
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    if node.value is not None and \
                            _expr_tainted(node.value, tainted):
                        tainted.update(_target_names(node.target))
                elif isinstance(node, ast.For):
                    if _expr_tainted(node.iter, tainted):
                        tainted.update(_target_names(node.target))
                elif isinstance(node, ast.withitem):
                    if node.optional_vars is not None and \
                            _expr_tainted(node.context_expr, tainted):
                        tainted.update(_target_names(node.optional_vars))
    return tainted


# ------------------------------------------- per-rule implementations --

def _check_traced_scopes(mod: _Module, findings: List[RawFinding]) -> None:
    """RPA003 (host syncs), RPA004 (python branches), RPA008 (np misuse)."""
    al = mod.aliases
    for fn, static in mod.traced.items():
        tainted = set(_fn_params(fn)) - static
        # inner defs get their own traced entry via the closure pass;
        # don't double-report their bodies here
        inner_fns = {n for stmt in (
            fn.body if isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))
            else [fn.body])
            for n in ast.walk(stmt)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)) and n is not fn}
        skip_lines = set()
        for inner in inner_fns:
            for n in ast.walk(inner):
                if hasattr(n, "lineno"):
                    skip_lines.add(n.lineno)
        if hasattr(fn, "lineno"):
            skip_lines.discard(fn.lineno)
        tainted = _propagate_taint(fn, tainted)
        body = fn.body if isinstance(fn, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)) \
            else [fn.body]
        for stmt in body:
            for node in ast.walk(stmt):
                line = getattr(node, "lineno", None)
                if line is None or line in skip_lines:
                    continue
                if isinstance(node, ast.Call):
                    q = _qualname(node.func)
                    # float(x) / int(x) / bool(x) on a traced value
                    if q in _HOST_CASTS and node.args and \
                            _expr_tainted(node.args[0], tainted):
                        findings.append(RawFinding(
                            node.lineno, node.col_offset, "RPA003",
                            f"`{q}()` on a traced value forces a host "
                            f"sync inside a jitted scope"))
                    # x.item() / x.tolist()
                    elif isinstance(node.func, ast.Attribute) and \
                            node.func.attr in ("item", "tolist") and \
                            _expr_tainted(node.func.value, tainted):
                        findings.append(RawFinding(
                            node.lineno, node.col_offset, "RPA003",
                            f"`.{node.func.attr}()` on a traced value "
                            f"inside a jitted scope"))
                    else:
                        np_fn = al.is_np_call(q)
                        if np_fn and any(
                                _expr_tainted(a, tainted)
                                for a in node.args):
                            if np_fn in ("asarray", "array"):
                                findings.append(RawFinding(
                                    node.lineno, node.col_offset,
                                    "RPA003",
                                    f"`{q}()` materializes a traced "
                                    f"value on host inside a jitted "
                                    f"scope"))
                            elif np_fn not in _NP_STATIC_OK:
                                findings.append(RawFinding(
                                    node.lineno, node.col_offset,
                                    "RPA008",
                                    f"`{q}()` is a host numpy op on a "
                                    f"traced value; use jnp.{np_fn}"))
                elif isinstance(node, (ast.If, ast.While)) and \
                        _expr_tainted(node.test, tainted):
                    kw = "if" if isinstance(node, ast.If) else "while"
                    findings.append(RawFinding(
                        node.lineno, node.col_offset, "RPA004",
                        f"Python `{kw}` on a traced value inside a "
                        f"jitted scope"))
                elif isinstance(node, ast.Assert) and \
                        _expr_tainted(node.test, tainted):
                    findings.append(RawFinding(
                        node.lineno, node.col_offset, "RPA004",
                        "Python `assert` on a traced value inside a "
                        "jitted scope"))


class _KeyState:
    """Per-function PRNG bookkeeping for RPA001/RPA002."""

    def __init__(self):
        self.consumed: Dict[str, Tuple[int, int]] = {}  # name -> (line, col)

    def copy(self) -> "_KeyState":
        st = _KeyState()
        st.consumed = dict(self.consumed)
        return st


def _check_prng(mod: _Module, findings: List[RawFinding]) -> None:
    """RPA001 key reuse and RPA002 discarded splits, per function scope."""
    al = mod.aliases
    seen: Set[Tuple[int, int, str]] = set()

    def emit(line, col, code, msg):
        if (line, col, code) not in seen:
            seen.add((line, col, code))
            findings.append(RawFinding(line, col, code, msg))

    def consume(call: ast.Call, state: _KeyState):
        rf = al.is_random_call(_qualname(call.func))
        if rf is None:
            return
        for arg in call.args[:1]:       # the key is the first positional
            if isinstance(arg, ast.Name):
                name = arg.id
                if name in state.consumed:
                    l0, _ = state.consumed[name]
                    emit(call.lineno, call.col_offset, "RPA001",
                         f"PRNG key `{name}` already consumed at line "
                         f"{l0}; every jax.random call needs a fresh "
                         f"subkey")
                state.consumed[name] = (call.lineno, call.col_offset)

    def scan_expr(node: ast.AST, state: _KeyState):
        for n in ast.walk(node):
            if isinstance(n, ast.Call):
                consume(n, state)

    def run_body(body: List[ast.stmt], state: _KeyState):
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue        # nested scopes analyzed separately
            if isinstance(stmt, ast.Expr) and isinstance(
                    stmt.value, ast.Call):
                q = _qualname(stmt.value.func)
                if al.is_random_call(q) == "split":
                    emit(stmt.lineno, stmt.col_offset, "RPA002",
                         "jax.random.split result is discarded")
                scan_expr(stmt.value, state)
            elif isinstance(stmt, ast.Assign):
                scan_expr(stmt.value, state)
                names = [n for t in stmt.targets
                         for n in _target_names(t)]
                if names == ["_"] and isinstance(stmt.value, ast.Call) \
                        and al.is_random_call(
                            _qualname(stmt.value.func)) == "split":
                    emit(stmt.lineno, stmt.col_offset, "RPA002",
                         "jax.random.split result is discarded (bound "
                         "to `_`)")
                for n in names:
                    state.consumed.pop(n, None)     # reassignment refreshes
            elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                if stmt.value is not None:
                    scan_expr(stmt.value, state)
                for n in _target_names(stmt.target):
                    state.consumed.pop(n, None)
            elif isinstance(stmt, ast.If):
                scan_expr(stmt.test, state)
                s1, s2 = state.copy(), state.copy()
                run_body(stmt.body, s1)
                run_body(stmt.orelse, s2)
                state.consumed = {**s1.consumed, **s2.consumed}
            elif isinstance(stmt, (ast.For, ast.While)):
                if isinstance(stmt, ast.For):
                    scan_expr(stmt.iter, state)
                    loop_names = set(_target_names(stmt.target))
                else:
                    scan_expr(stmt.test, state)
                    loop_names = set()
                # two passes: the second catches loop-carried reuse of a
                # key assigned outside the loop
                for _ in range(2):
                    body_state = state.copy()
                    for n in loop_names:
                        body_state.consumed.pop(n, None)
                    run_body(stmt.body, body_state)
                    state.consumed.update({
                        k: v for k, v in body_state.consumed.items()
                        if k not in loop_names})
                run_body(stmt.orelse, state)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    scan_expr(item.context_expr, state)
                run_body(stmt.body, state)
            elif isinstance(stmt, ast.Try):
                run_body(stmt.body, state)
                for h in stmt.handlers:
                    run_body(h.body, state.copy())
                run_body(stmt.orelse, state)
                run_body(stmt.finalbody, state)
            elif isinstance(stmt, ast.Return) and stmt.value is not None:
                scan_expr(stmt.value, state)
            else:
                for n in ast.walk(stmt):
                    if isinstance(n, ast.Call):
                        consume(n, state)

    for fn in mod.functions.values():
        run_body(fn.body, _KeyState())
    # module level too (scripts/benchmarks)
    top = [s for s in mod.tree.body
           if not isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef))]
    run_body(top, _KeyState())


def _mutable_literal(node: ast.AST) -> bool:
    if isinstance(node, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                         ast.DictComp, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        q = _qualname(node.func)
        return q in ("list", "dict", "set", "bytearray") or (
            q is not None and q.split(".")[-1] in ("array", "zeros",
                                                   "ones", "empty")
            and q.split(".")[0] in ("np", "numpy"))
    return False


def _check_static_args(mod: _Module, findings: List[RawFinding]) -> None:
    """RPA005: mutable defaults on jitted functions; mutable partial-bound
    args wrapped in jax.jit."""
    for fn in mod.traced:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        defaults = list(fn.args.defaults) + [
            d for d in fn.args.kw_defaults if d is not None]
        for d in defaults:
            if _mutable_literal(d):
                findings.append(RawFinding(
                    d.lineno, d.col_offset, "RPA005",
                    f"jitted function `{fn.name}` has a mutable default "
                    f"argument; as a static arg it is unhashable and as "
                    f"a traced arg it aliases across calls"))
    al = mod.aliases
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call)
                and al.trace_entry(_qualname(node.func)) and node.args):
            continue
        target = node.args[0]
        if isinstance(target, ast.Call) and \
                _qualname(target.func) in al.partial:
            bound = list(target.args[1:]) + [kw.value
                                             for kw in target.keywords]
            for b in bound:
                if _mutable_literal(b):
                    findings.append(RawFinding(
                        b.lineno, b.col_offset, "RPA005",
                        "mutable value bound via functools.partial "
                        "under jax.jit; partials hash by bound-arg "
                        "identity, so this retraces per construction"))


def _check_dataclass_pytree(mod: _Module,
                            findings: List[RawFinding]) -> None:
    """RPA006: non-frozen, unregistered dataclass instances into jit."""
    dataclasses_local: Dict[str, ast.ClassDef] = {}
    registered: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ClassDef):
            for dec in node.decorator_list:
                q = _qualname(dec) or (
                    _qualname(dec.func) if isinstance(dec, ast.Call)
                    else None)
                if q in ("dataclass", "dataclasses.dataclass",
                         "struct.dataclass", "flax.struct.dataclass"):
                    frozen = isinstance(dec, ast.Call) and any(
                        kw.arg == "frozen" and isinstance(
                            kw.value, ast.Constant) and kw.value.value
                        for kw in dec.keywords)
                    if q in ("struct.dataclass", "flax.struct.dataclass"):
                        registered.add(node.name)
                    elif not frozen:
                        dataclasses_local[node.name] = node
                elif q and q.split(".")[-1] in (
                        "register_pytree_node_class",
                        "register_pytree_with_keys_class"):
                    registered.add(node.name)
        elif isinstance(node, ast.Call):
            q = _qualname(node.func)
            if q and q.split(".")[-1] in (
                    "register_pytree_node", "register_pytree_with_keys",
                    "register_dataclass", "register_static") and node.args:
                reg = _qualname(node.args[0])
                if reg:
                    registered.add(reg.split(".")[-1])
    if not dataclasses_local:
        return
    jitted = set(mod.jitted_names) | {
        fn.name for fn in mod.traced
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))}
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        q = _qualname(node.func)
        direct = q in jitted
        # jax.jit(f)(X(...)) — immediate invocation
        if not direct and isinstance(node.func, ast.Call):
            direct = mod.aliases.trace_entry(_qualname(node.func.func))
        if not direct:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Call):
                cq = _qualname(arg.func)
                if cq in dataclasses_local and cq not in registered:
                    findings.append(RawFinding(
                        arg.lineno, arg.col_offset, "RPA006",
                        f"dataclass `{cq}` is passed into jitted "
                        f"`{q}` but is neither frozen (hashable "
                        f"static) nor pytree-registered"))


_CALLBACK_TAILS = {"pure_callback", "io_callback", "id_tap", "id_print"}
_DEBUG_TAILS = {("debug", "print"), ("debug", "callback"),
                ("debug", "breakpoint")}


def _check_loop_callbacks(mod: _Module,
                          findings: List[RawFinding]) -> None:
    """RPA009: host callbacks inside per-iteration lax loop bodies."""
    seen: Set[Tuple[int, int]] = set()
    for fn in mod.loop_bodies:
        body = fn.body if isinstance(fn, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)) \
            else [fn.body]
        for node in (n for stmt in body for n in ast.walk(stmt)):
            if not isinstance(node, ast.Call):
                continue
            q = _qualname(node.func)
            if not q:
                continue
            parts = q.split(".")
            if parts[-1] in _CALLBACK_TAILS or \
                    tuple(parts[-2:]) in _DEBUG_TAILS:
                key = (node.lineno, node.col_offset)
                if key not in seen:
                    seen.add(key)
                    findings.append(RawFinding(
                        node.lineno, node.col_offset, "RPA009",
                        f"`{q}` runs a host round-trip on EVERY "
                        f"iteration of a lax loop body"))


# dtype's positional slot; `full` is deliberately absent — a Python-
# scalar fill keeps the result WEAK-typed (verified on jax 0.9.0), so
# it cannot widen anything
_RPA010_DTYPE_POS = {"array": 1, "asarray": 1}
_RPA010_FACTORIES = {"linspace", "logspace", "geomspace"}


def _float_literal_in(node: ast.AST) -> bool:
    return any(isinstance(n, ast.Constant) and isinstance(n.value, float)
               for n in ast.walk(node))


def _check_f64_literals(mod: _Module,
                        findings: List[RawFinding]) -> None:
    """RPA010: strong-typed float literals with no explicit dtype.

    Module-wide (not just traced scopes): a bare float-list constant
    anywhere becomes a strong f64 under ``jax_enable_x64`` and widens
    whatever consumes it.  Python scalars (and ``jnp.asarray(0.5)`` of
    one) stay weak-typed and are deliberately NOT flagged.
    """
    al = mod.aliases
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        fn_name = al.is_jnp_call(_qualname(node.func))
        if fn_name is None:
            continue
        if any(kw.arg == "dtype" for kw in node.keywords):
            continue
        if fn_name in _RPA010_DTYPE_POS:
            if len(node.args) > _RPA010_DTYPE_POS[fn_name]:
                continue            # dtype passed positionally
            literal = bool(node.args) and isinstance(
                node.args[0], (ast.List, ast.Tuple)) and \
                _float_literal_in(node.args[0])
            if literal:
                findings.append(RawFinding(
                    node.lineno, node.col_offset, "RPA010",
                    f"`{fn_name}` of a float literal without dtype is "
                    f"STRONG-typed: it becomes f64 and widens the "
                    f"graph under jax_enable_x64"))
        elif fn_name in _RPA010_FACTORIES and any(
                _float_literal_in(a) for a in node.args):
            findings.append(RawFinding(
                node.lineno, node.col_offset, "RPA010",
                f"`{fn_name}` with float-literal bounds and no dtype "
                f"defaults to f64 under jax_enable_x64"))


# -------------------------------------------------------- module pass --

def module_findings(tree: ast.Module) -> List[RawFinding]:
    """All single-module rule findings (everything except RPA007)."""
    mod = _Module(tree)
    findings: List[RawFinding] = []
    _check_traced_scopes(mod, findings)
    _check_prng(mod, findings)
    _check_static_args(mod, findings)
    _check_dataclass_pytree(mod, findings)
    _check_loop_callbacks(mod, findings)
    _check_f64_literals(mod, findings)
    findings.sort(key=lambda f: (f.line, f.col, f.code))
    return findings


# ------------------------------------------------ import-cycle (RPA007) --

def _module_level_imports(tree: ast.Module):
    """(node, stmt) for imports executed at module import time, skipping
    `if TYPE_CHECKING:` guards (the sanctioned cycle-free annotation
    pattern) and anything inside a function/class body."""

    def walk(stmts):
        for stmt in stmts:
            if isinstance(stmt, (ast.Import, ast.ImportFrom)):
                yield stmt
            elif isinstance(stmt, ast.If):
                q = _qualname(stmt.test)
                if q and q.split(".")[-1] == "TYPE_CHECKING":
                    yield from walk(stmt.orelse)
                else:
                    yield from walk(stmt.body)
                    yield from walk(stmt.orelse)
            elif isinstance(stmt, ast.Try):
                yield from walk(stmt.body)
                for h in stmt.handlers:
                    yield from walk(h.body)
                yield from walk(stmt.orelse)
                yield from walk(stmt.finalbody)
            elif isinstance(stmt, (ast.With,)):
                yield from walk(stmt.body)

    yield from walk(tree.body)


def import_edges(modname: str, tree: ast.Module,
                 known: Set[str]):
    """Edges (target_module, line) from module-level imports, restricted
    to modules in ``known`` (the linted set)."""
    pkg_parts = modname.split(".")[:-1]
    for stmt in _module_level_imports(tree):
        if isinstance(stmt, ast.Import):
            for a in stmt.names:
                name = a.name
                while name:
                    if name in known:
                        if not modname.startswith(name + "."):
                            yield name, stmt.lineno
                        break
                    name = name.rpartition(".")[0]
        else:
            if stmt.level:      # relative import
                base = pkg_parts[:len(pkg_parts) - (stmt.level - 1)]
                root = ".".join(base)
            else:
                root = stmt.module or ""
            candidates = []
            if stmt.level and stmt.module:
                root = f"{root}.{stmt.module}" if root else stmt.module
            for a in stmt.names:
                candidates.append(f"{root}.{a.name}" if root else a.name)
            if root:
                candidates.append(root)
            hit = set()
            for cand in candidates:
                name = cand
                while name:
                    if name in known:
                        # a submodule importing from an ancestor package
                        # (`from repro.core import api` inside
                        # repro.core.engine) resolves to the sibling
                        # submodule, not to the package __init__ — the
                        # idiomatic re-export pattern is not a cycle
                        if name not in hit and \
                                not modname.startswith(name + "."):
                            hit.add(name)
                            yield name, stmt.lineno
                        break
                    name = name.rpartition(".")[0]


def find_cycles(graph: Dict[str, Dict[str, int]]):
    """Strongly connected components with >1 node (or a self edge):
    yields (members, {module: line-of-offending-import})."""
    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    on_stack: Set[str] = set()
    stack: List[str] = []
    counter = [0]
    sccs: List[List[str]] = []

    def strongconnect(v: str):
        work = [(v, iter(sorted(graph.get(v, {}))))]
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on_stack.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on_stack.add(w)
                    work.append((w, iter(sorted(graph.get(w, {})))))
                    advanced = True
                    break
                elif w in on_stack:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                comp = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    comp.append(w)
                    if w == node:
                        break
                sccs.append(comp)

    for v in sorted(graph):
        if v not in index:
            strongconnect(v)
    for comp in sccs:
        members = set(comp)
        if len(comp) > 1 or (comp and comp[0] in graph.get(comp[0], {})):
            lines = {}
            for m in comp:
                for target, line in sorted(graph.get(m, {}).items()):
                    if target in members:
                        lines[m] = line
                        break
            yield sorted(members), lines
