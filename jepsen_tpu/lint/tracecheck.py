"""Trace discipline: host-sync and retrace hazards inside traced code.

The hot paths are compiled JAX ladders; their perf collapses silently
when a launch re-traces (a hidden compile bucket) or when traced values
leak back to the host mid-program (an implicit device sync serializing
the pipeline).  The compile-cache hit counters only report AFTER the
chip hours are burned — this analyzer flags the hazards at review time.

Mechanics (pure ``ast``, no jax import): every ``jax.jit`` /
``jax.vmap`` / ``jax.shard_map`` / ``pl.pallas_call`` site whose
target resolves to a module-local function becomes a *traced root*.
Parameters are **static** when named in ``static_argnames`` or bound to
host values via ``functools.partial``; every other parameter is
**tainted** (a tracer at trace time).  An intraprocedural taint walk —
descending into module-local callees with the call-site taint mapped
onto their parameters — then flags:

  * ``trace-host-sync`` — ``.item()`` / ``.tolist()`` /
    ``.block_until_ready()`` / ``float()``/``int()``/``bool()`` /
    ``np.*`` / ``jax.device_get`` applied to a tainted value;
  * ``trace-host-control`` — Python ``if`` / ``while`` / ``assert`` /
    ``for`` over a tainted value (each distinct host value seen here is
    a fresh trace; the fix is ``static_argnames`` for config args,
    ``lax.cond``/``jnp.where`` for data);
  * ``trace-nondeterminism`` — ``time.*`` / ``random.*`` /
    ``np.random.*`` inside traced code (baked in at trace time, stale
    ever after);
  * ``trace-implicit-dtype`` — ``jnp.zeros``/``full``/``array``/…
    without an explicit ``dtype``: the weak-type default shifts with
    operand promotion, and a shifted dtype is a new compile bucket;
  * ``trace-raw-geometry`` — a function calling a jit-runner factory
    (``async_runner`` & co.) without deriving its shapes from the
    padded-geometry helpers (``bucket_geometry``/``padded_batch``/
    ``pad_*``): every distinct raw shape is a hidden compile bucket.

Functions whose callees can't be resolved module-locally are left
alone — the analyzer under-reports rather than guessing.
"""

from __future__ import annotations

import ast

from jepsen_tpu.lint import Finding, SourceFile

RULES = (
    "trace-host-sync", "trace-host-control", "trace-nondeterminism",
    "trace-implicit-dtype", "trace-raw-geometry",
)

#: attribute reads on a traced value that yield HOST (static) values.
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size"}

#: method calls that force a device sync / host transfer.
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}

#: builtins that coerce a traced value to host.
_HOST_COERCE = {"float", "int", "bool", "complex"}

#: dotted-name prefixes whose call results are traced values.
_TRACED_ROOTS = ("jnp.", "lax.", "jax.")

#: dotted-name prefixes that are nondeterministic at trace time.
_NONDET_PREFIXES = ("time.", "random.", "np.random.", "numpy.random.")

#: jnp constructors and the positional index their dtype lands at.
_DTYPE_CTORS = {
    "jnp.zeros": 1, "jnp.ones": 1, "jnp.empty": 1, "jnp.array": 1,
    "jnp.asarray": 1, "jnp.full": 2, "jnp.arange": 3,
}

#: lax/jax combinators whose function-valued arguments are traced with
#: fully-tainted parameters.
_COMBINATORS = {
    "lax.scan", "lax.cond", "lax.while_loop", "lax.fori_loop", "lax.map",
    "lax.switch", "jax.lax.scan", "jax.lax.cond", "jax.lax.while_loop",
    "jax.lax.fori_loop", "jax.checkpoint", "jax.remat",
}

#: jit-runner factories (compiled-launch entry points) for the
#: raw-geometry audit ...
_RUNNER_FACTORIES = {
    "exact_batched_runner", "async_runner",
    "greedy_runner", "lane_shard", "_sharded_runner",
}

#: ... and the padded-geometry helpers that legitimize their shapes.
_GEOMETRY_HELPERS = {
    "bucket_geometry", "padded_batch", "pad_packed", "pad_B", "pad_resume",
}

_MAX_DEPTH = 10


_DTYPE_CALL_RE = None  # compiled lazily (module import stays trivial)


def _explicit_dtype(node: ast.AST) -> bool:
    """Whether a value expression pins its own dtype: a ``jnp.uint32(x)``
    -style constructor or an ``.astype(...)`` call."""
    import re as _re

    global _DTYPE_CALL_RE
    if _DTYPE_CALL_RE is None:
        _DTYPE_CALL_RE = _re.compile(
            r"^(jnp|np|numpy)\.(u?int\d+|float\d+|bool_?|bfloat16)$"
        )
    if isinstance(node, ast.Call):
        name = _dotted(node.func)
        if name and _DTYPE_CALL_RE.match(name):
            return True
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "astype":
            return True
    return False


def _dotted(node: ast.AST) -> str | None:
    """'jnp.zeros' for Attribute/Name chains rooted at a Name; None for
    anything dynamic (method calls on expressions)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _const_names(node: ast.AST | None) -> set[str]:
    """static_argnames as a name set ('x' or ('x', 'y'))."""
    out: set[str] = set()
    if node is None:
        return out
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        out.add(node.value)
    elif isinstance(node, (ast.Tuple, ast.List)):
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.add(el.value)
    return out


def _param_names(fn: ast.FunctionDef) -> list[str]:
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)]


class _Target:
    """A resolved trace target: the function plus which of its params
    are STATIC at trace time (static_argnames + partial-bound)."""

    def __init__(self, fn: ast.FunctionDef, static: set[str]):
        self.fn = fn
        self.static = static

    @property
    def tainted(self) -> frozenset:
        return frozenset(p for p in _param_names(self.fn)
                         if p not in self.static)


class TraceChecker:
    def __init__(self, src: SourceFile):
        self.src = src
        self.findings: list[Finding] = []
        #: module-level (and class-level) defs by bare name
        self.fns: dict[str, ast.FunctionDef] = {}
        self._collect_fns(src.tree, prefix="")
        #: (qualname, tainted) -> returns_tainted, for memoized descent
        self._memo: dict[tuple, bool] = {}
        self._in_progress: set[tuple] = set()

    # -- indexing ----------------------------------------------------------

    def _collect_fns(self, node: ast.AST, prefix: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.fns.setdefault(child.name, child)
                child._qualname = prefix + child.name  # type: ignore
            elif isinstance(child, ast.ClassDef):
                self._collect_fns(child, prefix=child.name + ".")

    def _qual(self, fn: ast.FunctionDef) -> str:
        return getattr(fn, "_qualname", fn.name)

    # -- root discovery ----------------------------------------------------

    def run(self) -> list[Finding]:
        for target in self._find_roots():
            self._analyze(target.fn, target.tainted, depth=0)
        self._audit_geometry(self._jitted_names())
        return self.findings

    def _jitted_names(self) -> set[str]:
        """Module-level names bound to jit-wrapped callables (``_run =
        jax.jit(...)`` / ``x = functools.partial(jax.jit, ...)(f)``) —
        calling one IS a compiled launch, so the geometry audit treats
        them like runner factories."""
        out: set[str] = set()
        for stmt in self.src.tree.body:
            if not isinstance(stmt, ast.Assign) \
                    or not isinstance(stmt.value, ast.Call):
                continue
            call = stmt.value
            is_jit = (_dotted(call.func) in ("jax.jit", "jit")
                      or (isinstance(call.func, ast.Call)
                          and self._jit_static(call.func) is not None))
            if is_jit:
                for tgt in stmt.targets:
                    if isinstance(tgt, ast.Name):
                        out.add(tgt.id)
        return out

    def _find_roots(self) -> list[_Target]:
        roots: list[_Target] = []
        # decorated defs
        for fn in set(self.fns.values()):
            for deco in fn.decorator_list:
                static = self._jit_static(deco)
                if static is not None:
                    roots.append(_Target(fn, static))
        # call-site wrapping: jax.jit(f, ...) / shard_map(f, ...) /
        # jax.vmap(f) / pl.pallas_call(f) anywhere in the module
        for call in ast.walk(self.src.tree):
            if not isinstance(call, ast.Call):
                continue
            name = _dotted(call.func)
            wrap = None
            if name in ("jax.jit", "jit"):
                wrap = "jit"
            elif name and (name.endswith("shard_map")
                           or name.endswith("pallas_call")):
                wrap = "shard"
            elif name in ("jax.vmap", "vmap"):
                wrap = "vmap"
            # functools.partial(jax.jit, static_argnames=...)(f)
            elif (isinstance(call.func, ast.Call)
                  and self._jit_static(call.func) is not None):
                static0 = self._jit_static(call.func)
                for t in self._resolve_targets(call.args[0] if call.args
                                               else None, call):
                    t.static |= static0
                    roots.append(t)
                continue
            if wrap is None or not call.args:
                continue
            static0 = _const_names(next(
                (k.value for k in call.keywords
                 if k.arg in ("static_argnames", "static_argnums")), None))
            for t in self._resolve_targets(call.args[0], call):
                t.static |= static0
                roots.append(t)
        # a vmap nested directly inside a jit(...) shows up twice: once
        # via the jit (with its static_argnames) and once as a bare vmap
        # root with none — keep only the maximal static sets per fn
        out: list[_Target] = []
        for t in roots:
            if any(o.fn is t.fn and o.static > t.static for o in roots):
                continue
            if any(o.fn is t.fn and o.static == t.static and o is not t
                   for o in out):
                continue
            out.append(t)
        return out

    def _jit_static(self, node: ast.AST) -> set[str] | None:
        """None unless ``node`` IS a jit wrapper (bare ``jax.jit`` or
        ``[functools.]partial(jax.jit, static_argnames=...)``); else its
        static-argname set."""
        if _dotted(node) in ("jax.jit", "jit"):
            return set()
        if isinstance(node, ast.Call):
            fname = _dotted(node.func)
            if fname in ("functools.partial", "partial") and node.args:
                if _dotted(node.args[0]) in ("jax.jit", "jit"):
                    return _const_names(next(
                        (k.value for k in node.keywords
                         if k.arg in ("static_argnames", "static_argnums")),
                        None))
        return None

    def _resolve_targets(self, node: ast.AST | None,
                         site: ast.AST) -> list[_Target]:
        """Resolve a function-valued expression to module-local defs,
        tracking partial-bound (static) parameters.  Unresolvable
        expressions resolve to nothing — under-report, never guess."""
        if node is None:
            return []
        if isinstance(node, ast.Name):
            fn = self._local_value(node, site) or self.fns.get(node.id)
            if isinstance(fn, ast.FunctionDef):
                return [_Target(fn, set())]
            if isinstance(fn, ast.AST):
                return self._resolve_targets(fn, site)
            return []
        if isinstance(node, ast.Call):
            fname = _dotted(node.func)
            if fname in ("functools.partial", "partial") and node.args:
                inner = self._resolve_targets(node.args[0], site)
                for t in inner:
                    params = _param_names(t.fn)
                    bound = set(params[: len(node.args) - 1])
                    bound |= {k.arg for k in node.keywords if k.arg}
                    t.static |= bound
                return inner
            if fname in ("jax.vmap", "vmap") or (
                    fname and (fname.endswith("shard_map")
                               or fname.endswith("pallas_call"))):
                return (self._resolve_targets(node.args[0], site)
                        if node.args else [])
        return []

    def _local_value(self, name: ast.Name, site: ast.AST) -> ast.AST | None:
        """The expression last assigned to ``name`` in the function
        enclosing ``site`` (resolves ``core = functools.partial(...)``
        bindings inside runner factories)."""
        encl = self._enclosing_fn(site)
        if encl is None:
            return None
        value = None
        best_line = -1
        for stmt in ast.walk(encl):
            # SOURCE order, not ast.walk visit order: a later top-level
            # rebinding must shadow an earlier nested one
            if isinstance(stmt, ast.Assign) \
                    and best_line < stmt.lineno < site.lineno:
                for tgt in stmt.targets:
                    if isinstance(tgt, ast.Name) and tgt.id == name.id:
                        value = stmt.value
                        best_line = stmt.lineno
        return value

    def _enclosing_fn(self, node: ast.AST) -> ast.FunctionDef | None:
        best = None
        for fn in set(self.fns.values()):
            if fn.lineno <= node.lineno <= (fn.end_lineno or fn.lineno):
                if best is None or fn.lineno > best.lineno:
                    best = fn
        return best

    # -- taint walk --------------------------------------------------------

    def _analyze(self, fn: ast.FunctionDef, tainted: frozenset,
                 depth: int) -> bool:
        """Walk ``fn`` with ``tainted`` parameter names; returns whether
        its return value is tainted.  Memoized per (fn, taint-set) so
        shared helpers report each hazard once."""
        key = (self._qual(fn), tainted)
        if key in self._memo:
            return self._memo[key]
        if key in self._in_progress or depth > _MAX_DEPTH:
            return True  # cycle/limit: assume traced, stop descending
        self._in_progress.add(key)
        env = set(tainted)
        returns = [False]
        for stmt in fn.body:
            self._stmt(stmt, env, fn, depth, returns)
        self._in_progress.discard(key)
        self._memo[key] = returns[0]
        return returns[0]

    def _taint_target(self, tgt: ast.expr, env: set) -> None:
        """Taint the names a tainted assignment actually writes: the
        root container of a subscript/attribute store, every element of
        a tuple — but never index expressions (``scratch[i] = x`` must
        not taint the host int ``i``)."""
        if isinstance(tgt, ast.Name):
            env.add(tgt.id)
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            for el in tgt.elts:
                self._taint_target(el, env)
        elif isinstance(tgt, ast.Starred):
            self._taint_target(tgt.value, env)
        elif isinstance(tgt, (ast.Subscript, ast.Attribute)):
            self._taint_target(tgt.value, env)

    def _flag(self, rule: str, node: ast.AST, fn: ast.FunctionDef,
              slug: str, message: str) -> None:
        if self.src.is_disabled(rule, node.lineno):
            return
        self.findings.append(Finding(
            rule=rule, path=self.src.rel, line=node.lineno,
            scope=self._qual(fn), slug=slug, message=message,
        ))

    def _stmt(self, stmt: ast.stmt, env: set, fn: ast.FunctionDef,
              depth: int, returns: list) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # nested defs run only when called (combinators resolve)
        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            value = stmt.value
            t = self._expr(value, env, fn, depth) if value is not None \
                else False
            targets = (stmt.targets if isinstance(stmt, ast.Assign)
                       else [stmt.target])
            for tgt in targets:
                if isinstance(stmt, ast.AugAssign):
                    t = t or self._expr(tgt, env, fn, depth)
                if t:
                    self._taint_target(tgt, env)
                else:
                    self._expr(tgt, env, fn, depth)  # subscript hazards
            return
        if isinstance(stmt, (ast.If, ast.While)):
            if self._expr(stmt.test, env, fn, depth):
                kind = "if" if isinstance(stmt, ast.If) else "while"
                names = sorted(
                    n.id for n in ast.walk(stmt.test)
                    if isinstance(n, ast.Name) and n.id in env
                )
                hint = (
                    "add it to static_argnames if it is host config, or "
                    "use lax.cond/jnp.where if it is data"
                )
                self._flag(
                    "trace-host-control", stmt, fn, f"{kind}:{','.join(names) or '?'}",
                    f"Python `{kind}` on traced value(s) "
                    f"{', '.join(names) or '<expr>'} re-traces per distinct "
                    f"host value — {hint}",
                )
            for s in (*stmt.body, *stmt.orelse):
                self._stmt(s, env, fn, depth, returns)
            return
        if isinstance(stmt, ast.For):
            it = stmt.iter
            hazard = False
            if (isinstance(it, ast.Call) and _dotted(it.func) == "range"
                    and any(self._expr(a, env, fn, depth) for a in it.args)):
                hazard = True
            elif self._expr(it, env, fn, depth):
                hazard = True
            if hazard:
                self._flag(
                    "trace-host-control", stmt, fn, "for",
                    "Python `for` over a traced value unrolls/re-traces — "
                    "use lax.scan/fori_loop, or make the bound static",
                )
                # loop targets are traced only when the iterable is —
                # `for i in range(4)` yields host ints
                for n in ast.walk(stmt.target):
                    if isinstance(n, ast.Name):
                        env.add(n.id)
            for s in (*stmt.body, *stmt.orelse):
                self._stmt(s, env, fn, depth, returns)
            return
        if isinstance(stmt, ast.Assert):
            if self._expr(stmt.test, env, fn, depth):
                self._flag(
                    "trace-host-control", stmt, fn, "assert",
                    "`assert` on a traced value forces a host sync — use "
                    "checkify or drop it from traced code",
                )
            return
        if isinstance(stmt, ast.Return):
            if stmt.value is not None and self._expr(stmt.value, env, fn,
                                                     depth):
                returns[0] = True
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._expr(item.context_expr, env, fn, depth)
            for s in stmt.body:
                self._stmt(s, env, fn, depth, returns)
            return
        if isinstance(stmt, ast.Try):
            for s in (*stmt.body, *stmt.orelse, *stmt.finalbody):
                self._stmt(s, env, fn, depth, returns)
            for h in stmt.handlers:
                for s in h.body:
                    self._stmt(s, env, fn, depth, returns)
            return
        if isinstance(stmt, ast.Expr):
            self._expr(stmt.value, env, fn, depth)
            return
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.expr):
                self._expr(child, env, fn, depth)
            elif isinstance(child, ast.stmt):
                self._stmt(child, env, fn, depth, returns)

    def _expr(self, e: ast.expr, env: set, fn: ast.FunctionDef,
              depth: int) -> bool:
        if isinstance(e, ast.Name):
            return e.id in env
        if isinstance(e, ast.Constant):
            return False
        if isinstance(e, ast.Lambda):
            return False  # bodies run via combinators, resolved there
        if isinstance(e, ast.Attribute):
            if e.attr in _STATIC_ATTRS:
                self._expr(e.value, env, fn, depth)
                return False  # .shape/.dtype of a tracer are host values
            return self._expr(e.value, env, fn, depth)
        if isinstance(e, ast.Call):
            return self._call(e, env, fn, depth)
        if isinstance(e, (ast.ListComp, ast.SetComp, ast.DictComp,
                          ast.GeneratorExp)):
            # taint the generator targets BEFORE walking the element,
            # or hazards inside the element go unseen
            out = False
            for gen in e.generators:
                if self._expr(gen.iter, env, fn, depth):
                    out = True
                    for n in ast.walk(gen.target):
                        if isinstance(n, ast.Name):
                            env.add(n.id)
                for cond in gen.ifs:
                    self._expr(cond, env, fn, depth)
            for part in ((e.key, e.value) if isinstance(e, ast.DictComp)
                         else (e.elt,)):
                out = self._expr(part, env, fn, depth) or out
            return out
        out = False
        for child in ast.iter_child_nodes(e):
            if isinstance(child, ast.expr):
                out = self._expr(child, env, fn, depth) or out
        return out

    def _call(self, call: ast.Call, env: set, fn: ast.FunctionDef,
              depth: int) -> bool:
        name = _dotted(call.func)
        arg_nodes = list(call.args) + [k.value for k in call.keywords]
        arg_taints = [self._expr(a, env, fn, depth) for a in arg_nodes]
        any_tainted = any(arg_taints)

        # method call on a traced receiver (covers bare names too:
        # `x.item()` resolves to the dotted "x.item", but x is tainted)
        if isinstance(call.func, ast.Attribute):
            recv_tainted = self._expr(call.func.value, env, fn, depth)
            if recv_tainted:
                if call.func.attr in _SYNC_METHODS:
                    self._flag(
                        "trace-host-sync", call, fn, call.func.attr,
                        f"`.{call.func.attr}()` on a traced value is a "
                        "device sync inside traced code",
                    )
                    return False
                return True

        # nondeterminism: baked in at trace time regardless of args
        if name and (name.startswith(_NONDET_PREFIXES)
                     or name in ("time", "perf_counter")):
            self._flag(
                "trace-nondeterminism", call, fn, name,
                f"`{name}()` inside traced code is evaluated once at "
                "trace time and baked into the program — hoist it to the "
                "host caller",
            )
            return False

        # host coercion / sync
        if name in _HOST_COERCE and any_tainted:
            self._flag(
                "trace-host-sync", call, fn, name,
                f"`{name}()` on a traced value blocks on the device "
                "(implicit sync) — keep it as an array op, or make the "
                "operand static",
            )
            return False
        if name == "jax.device_get" and any_tainted:
            self._flag(
                "trace-host-sync", call, fn, "device_get",
                "`jax.device_get` inside traced code syncs the device — "
                "move it to the host caller",
            )
            return False
        if (name and (name.startswith(("np.", "numpy."))
                      and not name.startswith(_NONDET_PREFIXES))
                and any_tainted):
            self._flag(
                "trace-host-sync", call, fn, name,
                f"`{name}` on a traced value forces a host transfer — "
                "use the jnp equivalent inside traced code",
            )
            return False

        # implicit dtype on jnp constructors
        if name in _DTYPE_CTORS:
            pos = _DTYPE_CTORS[name]
            has_dtype = (len(call.args) > pos
                         or any(k.arg == "dtype" for k in call.keywords))
            if not has_dtype and name in ("jnp.full", "jnp.array",
                                          "jnp.asarray"):
                # an explicitly-dtyped fill/source value carries the
                # dtype itself: jnp.full(shape, jnp.uint32(x))
                vpos = pos - 1
                if vpos < len(call.args):
                    has_dtype = _explicit_dtype(call.args[vpos])
            if not has_dtype:
                self._flag(
                    "trace-implicit-dtype", call, fn, name,
                    f"`{name}` without an explicit dtype weak-types by "
                    "promotion — a shifted operand dtype silently becomes "
                    "a new compile bucket; pass dtype=",
                )
            return True

        # combinators trace their function-valued args with full taint
        if name in _COMBINATORS:
            for a in call.args:
                for t in self._resolve_targets(a, call):
                    self._analyze(t.fn, t.tainted | frozenset(), depth + 1)
            return True

        if name and name.startswith(_TRACED_ROOTS):
            return True
        if name == "len":
            return False  # length of a traced array is static shape info

        # module-local descent: map call-site taint onto callee params
        if name and "." not in name and name in self.fns:
            callee = self.fns[name]
            params = _param_names(callee)
            callee_taint: set[str] = set()
            for i, a in enumerate(call.args):
                if i < len(params) and arg_taints[i]:
                    callee_taint.add(params[i])
            for k, kt in zip(call.keywords,
                             arg_taints[len(call.args):]):
                if k.arg and kt:
                    callee_taint.add(k.arg)
            return self._analyze(callee, frozenset(callee_taint), depth + 1)

        if isinstance(call.func, ast.expr):
            self._expr(call.func, env, fn, depth)
        return any_tainted

    # -- raw-geometry audit ------------------------------------------------

    def _audit_geometry(self, jitted_names: set[str]) -> None:
        """Every function that calls a jit-runner factory (or a module-
        level jitted callable) must also call a padded-geometry helper
        (or a local ``pad*`` helper) — a launch whose shapes come
        straight from input sizes mints one compile bucket per distinct
        size."""
        for fn in set(self.fns.values()):
            factory_calls: list[ast.Call] = []
            has_geometry = False
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                callee = _dotted(node.func)
                tail = callee.rsplit(".", 1)[-1] if callee else None
                if tail in _RUNNER_FACTORIES or tail in jitted_names:
                    factory_calls.append(node)
                if tail and (tail in _GEOMETRY_HELPERS
                             or tail.startswith("pad")):
                    has_geometry = True
            if not factory_calls or has_geometry:
                continue
            # one finding per (function, launch callee): the fix — or
            # the triage — is per launch path, not per call expression
            seen: set[str] = set()
            for call in factory_calls:
                callee = (_dotted(call.func) or "?").rsplit(".", 1)[-1]
                if callee in seen:
                    continue
                seen.add(callee)
                self._flag(
                    "trace-raw-geometry", call, fn, callee,
                    f"`{callee}` launch site in a function that never "
                    "touches the padded-geometry helpers "
                    "(bucket_geometry/padded_batch/pad_*) — raw shapes "
                    "mint a hidden compile bucket per distinct size",
                )


def check_source(src: SourceFile) -> list[Finding]:
    out = TraceChecker(src).run()
    # one root may reach a helper under several taint sets; the hazard
    # is the same source line — report it once
    seen: set[tuple] = set()
    uniq: list[Finding] = []
    for f in out:
        k = (f.rule, f.path, f.line, f.slug)
        if k not in seen:
            seen.add(k)
            uniq.append(f)
    return uniq
