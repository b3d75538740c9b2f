"""Tracer-hazard analyzer: host round-trips and Python control flow on
traced values inside jit/pjit/shard_map-staged functions.

Under ``jax.jit`` the function body runs once with abstract tracers;
anything that needs a concrete value — ``float(x)``, ``x.item()``,
``np.asarray(x)``, ``if x > 0`` — either raises a
``ConcretizationTypeError`` at trace time or (worse, for side effects like
``print``) silently runs only at trace time.  pytest on CPU catches the
loud failures; this rule catches them before any run, and catches the
silent ones pytest cannot.

Detection is a per-function taint walk: the jitted function's array
parameters (minus ``static_argnums``/``static_argnames``) seed the taint
set; assignments, arithmetic, subscripts, and calls propagate it; the
static-under-trace attributes (``.shape``/``.dtype``/``.ndim``) launder it.
Jitted functions are found by decorator (``@jax.jit``,
``@partial(jax.jit, ...)``, ``@shard_map``-style) and by same-module
wrapping calls (``f2 = jax.jit(f)``, ``jax.shard_map(f, mesh=...)``).

Interprocedural tier: calls out of a staged function to a resolvable
project helper consult the helper's dataflow summary
(:mod:`.dataflow`), so ``float(x)`` buried one or two helper frames
down still reports — at the staged call site, naming the helper line.
"""
from __future__ import annotations

import ast

from . import callgraph
from .core import Finding, Rule, register
from .dataflow import EMPTY, Hazard, OriginWalker, SummaryEngine, call_name

# Attributes that are static (Python values) even on a tracer.
_STATIC_ATTRS = {"shape", "dtype", "ndim", "weak_type", "sharding", "aval"}
# Builtins whose result is static even with a traced argument.
_SHAPE_FNS = {"len", "isinstance", "type", "id", "repr", "str", "format"}
_CAST_FNS = {"float", "int", "bool", "complex"}
_HOST_METHODS = {"item", "tolist", "numpy", "to_py"}
_NUMPY_ROOTS = {"np", "numpy", "onp"}
_NUMPY_FORCERS = {"asarray", "array", "asanyarray", "ascontiguousarray"}
_STAGING_NAMES = {"jit", "pjit", "shard_map"}


def _call_name(fn):
    """Dotted name of a call target, e.g. 'jax.jit' or 'jit'; None if the
    target is not a plain name/attribute chain."""
    parts = []
    while isinstance(fn, ast.Attribute):
        parts.append(fn.attr)
        fn = fn.value
    if isinstance(fn, ast.Name):
        parts.append(fn.id)
        return ".".join(reversed(parts))
    return None


def _is_staging(name):
    return name is not None and name.split(".")[-1] in _STAGING_NAMES


def _static_filter(call_kwargs):
    """(static_argnums, static_argnames) pulled from jit(...) keywords with
    literal values; non-literal values are ignored (best effort)."""
    nums, names = set(), set()
    for kw in call_kwargs:
        if kw.arg == "static_argnums":
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    nums.add(e.value)
        elif kw.arg == "static_argnames":
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    names.add(e.value)
    return nums, names


def _staged_functions(tree):
    """The (FunctionDef, static_argnums, static_argnames, how) of every
    function staged by jit/pjit/shard_map in this module.  Kept on the
    tree: the three tracer rules, jit-recompile and the interprocedural
    pass all ask for the same module's list (two walks of the tree each
    time, a fifth of the repo-wide scan before it was kept)."""
    found = getattr(tree, "_staged_functions", None)
    if found is None:
        found = tree._staged_functions = list(_find_staged(tree))
    return found


def _find_staged(tree):
    defs = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, node)

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    name = _call_name(dec.func)
                    if _is_staging(name):  # @shard_map(mesh=...)-style factory
                        nums, names = _static_filter(dec.keywords)
                        yield node, nums, names, name
                    elif name is not None and name.split(".")[-1] == "partial":
                        if dec.args and _is_staging(_call_name(dec.args[0])):
                            nums, names = _static_filter(dec.keywords)
                            yield node, nums, names, _call_name(dec.args[0])
                else:
                    name = _call_name(dec)
                    if _is_staging(name):
                        yield node, set(), set(), name
        elif isinstance(node, ast.Call):
            name = _call_name(node.func)
            if _is_staging(name) and node.args and isinstance(node.args[0], ast.Name):
                target = defs.get(node.args[0].id)
                if target is not None:
                    nums, names = _static_filter(node.keywords)
                    yield target, nums, names, name


class _TaintWalker(ast.NodeVisitor):
    def __init__(self, rule, ctx, fn, tainted, staged_as, engine=None):
        self.rule = rule
        self.ctx = ctx
        self.fn = fn
        self.tainted = tainted
        self.staged_as = staged_as
        self.engine = engine        # dataflow.SummaryEngine (interproc) or None
        self.findings = []

    # -- taint query -------------------------------------------------------
    def is_tainted(self, node):
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self.is_tainted(node.value)
        if isinstance(node, ast.Subscript):
            return self.is_tainted(node.value)
        if isinstance(node, ast.BinOp):
            return self.is_tainted(node.left) or self.is_tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.is_tainted(node.operand)
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None` is an identity (presence) check:
            # static under trace even when x is a tracer — the repo's
            # PRESENCE-static optional-argument idiom depends on it.
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
            return self.is_tainted(node.left) or any(
                self.is_tainted(c) for c in node.comparators)
        if isinstance(node, ast.BoolOp):
            return any(self.is_tainted(v) for v in node.values)
        if isinstance(node, ast.IfExp):
            return (self.is_tainted(node.body) or self.is_tainted(node.orelse)
                    or self.is_tainted(node.test))
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.is_tainted(e) for e in node.elts)
        if isinstance(node, ast.Starred):
            return self.is_tainted(node.value)
        if isinstance(node, ast.Call):
            name = _call_name(node.func)
            base = name.split(".")[-1] if name else None
            if base in _SHAPE_FNS:
                return False
            if isinstance(node.func, ast.Attribute) and self.is_tainted(node.func.value):
                return True
            return any(self.is_tainted(a) for a in node.args) or any(
                self.is_tainted(k.value) for k in node.keywords)
        return False

    # -- taint propagation -------------------------------------------------
    def _bind(self, target, tainted):
        if isinstance(target, ast.Name):
            if tainted:
                self.tainted.add(target.id)
            else:
                self.tainted.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, tainted)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, tainted)

    def visit_Assign(self, node):
        self.visit(node.value)
        t = self.is_tainted(node.value)
        for tgt in node.targets:
            self._bind(tgt, t)

    def visit_AugAssign(self, node):
        self.visit(node.value)
        if self.is_tainted(node.value):
            self._bind(node.target, True)

    def visit_AnnAssign(self, node):
        if node.value is not None:
            self.visit(node.value)
            self._bind(node.target, self.is_tainted(node.value))

    # -- hazards -----------------------------------------------------------
    def _flag(self, node, rule_name, msg):
        self.findings.append(Finding(self.ctx.path, node.lineno, rule_name, msg))

    def visit_Call(self, node):
        name = _call_name(node.func)
        base = name.split(".")[-1] if name else None
        arg_tainted = any(self.is_tainted(a) for a in node.args)

        if base in _CAST_FNS and name == base and arg_tainted:
            self._flag(node, "tracer-host-cast",
                       f"{base}() on a traced value inside {self.staged_as}"
                       f"-staged '{self.fn.name}' forces a host round-trip "
                       "(ConcretizationTypeError at trace time); keep it as "
                       "an array or mark the argument static")
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr in _HOST_METHODS
              and self.is_tainted(node.func.value)):
            self._flag(node, "tracer-host-cast",
                       f".{node.func.attr}() on a traced value inside "
                       f"{self.staged_as}-staged '{self.fn.name}' forces a "
                       "host round-trip; move it outside the staged function")
        elif (name is not None and "." in name
              and name.split(".")[0] in _NUMPY_ROOTS
              and base in _NUMPY_FORCERS and arg_tainted):
            self._flag(node, "tracer-host-cast",
                       f"{name}() concretizes a traced value inside "
                       f"{self.staged_as}-staged '{self.fn.name}'; use jnp")
        elif name == "print" and self.staged_as is not None:
            self._flag(node, "tracer-side-effect",
                       f"print() inside {self.staged_as}-staged "
                       f"'{self.fn.name}' runs only at trace time; use "
                       "jax.debug.print()")
        elif self.engine is not None:
            self._check_callee(node)
        self.generic_visit(node)

    def _check_callee(self, node):
        """Interprocedural step: when the callee is a project-local helper,
        instantiate its hazard summary against the taint of the actual
        arguments, so a host cast one (or two) helper frames down still
        reports — at THIS call site, naming the helper line."""
        hazards = _callee_hazards(self.engine, node, self.fn,
                                  lambda e: self.is_tainted(e))
        for fi, hz in hazards:
            self._flag(node, hz.rule,
                       f"{hz.message} in helper '{fi.name}' (line {hz.line})"
                       f" reached with a traced value from {self.staged_as}"
                       f"-staged '{self.fn.name}'")

    def visit_If(self, node):
        if self.is_tainted(node.test):
            self._flag(node, "tracer-python-branch",
                       f"Python `if` on a traced value inside {self.staged_as}"
                       f"-staged '{self.fn.name}'; use jnp.where or "
                       "jax.lax.cond")
        self.generic_visit(node)

    def visit_While(self, node):
        if self.is_tainted(node.test):
            self._flag(node, "tracer-python-branch",
                       f"Python `while` on a traced value inside "
                       f"{self.staged_as}-staged '{self.fn.name}'; use "
                       "jax.lax.while_loop")
        self.generic_visit(node)

    def visit_Assert(self, node):
        if self.is_tainted(node.test):
            self._flag(node, "tracer-python-branch",
                       f"`assert` on a traced value inside {self.staged_as}"
                       f"-staged '{self.fn.name}'; use "
                       "jax.debug.check or checkify")
        self.generic_visit(node)

    # Don't descend into nested function definitions with the same taint
    # frame's *parameters* — but closures do see outer locals, so keep the
    # shared taint set and just walk the body.
    def visit_FunctionDef(self, node):
        for stmt in node.body:
            self.visit(stmt)

    visit_AsyncFunctionDef = visit_FunctionDef


class _TracerOriginWalker(OriginWalker):
    """Origin-set mirror of _TaintWalker used to SUMMARIZE helper
    functions: same hazard classes, but each records which parameters it
    fires for, so call sites instantiate them against actual-argument
    taint.  Messages here are fragments; the reporting walker wraps them
    with the helper/staged-function context."""

    def on_call(self, node):
        name = call_name(node.func)
        base = name.split(".")[-1] if name else None
        arg_origins = EMPTY
        for a in node.args:
            arg_origins |= self.origins(a)
        if base in _CAST_FNS and name == base and arg_origins:
            self.hazards.append(Hazard(
                arg_origins, "tracer-host-cast",
                f"{base}() forces a host round-trip", node.lineno))
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr in _HOST_METHODS
              and self.origins(node.func.value)):
            self.hazards.append(Hazard(
                self.origins(node.func.value), "tracer-host-cast",
                f".{node.func.attr}() forces a host round-trip",
                node.lineno))
        elif (name is not None and "." in name
              and name.split(".")[0] in _NUMPY_ROOTS
              and base in _NUMPY_FORCERS and arg_origins):
            self.hazards.append(Hazard(
                arg_origins, "tracer-host-cast",
                f"{name}() concretizes the value", node.lineno))
        elif name == "print":
            self.hazards.append(Hazard(
                EMPTY, "tracer-side-effect",
                "print() runs only at trace time", node.lineno))
        else:
            self.instantiate_callee_hazards(node)

    def _branch(self, node, what, fix):
        o = self.origins(node.test)
        if o:
            self.hazards.append(Hazard(
                o, "tracer-python-branch",
                f"Python `{what}` on the value ({fix})", node.lineno))

    def visit_If(self, node):
        self._branch(node, "if", "use jnp.where or jax.lax.cond")
        self.generic_visit(node)

    def visit_While(self, node):
        self._branch(node, "while", "use jax.lax.while_loop")
        self.generic_visit(node)

    def visit_Assert(self, node):
        self._branch(node, "assert", "use jax.debug.check or checkify")
        self.generic_visit(node)


def _make_engine(ctx):
    """Project-shared SummaryEngine for the tracer walkers, or None when
    the scan has no resolvable package files (single-snippet tests still
    resolve same-module helpers through their own FileContext)."""
    project = ctx.project
    if project is None or not getattr(project, "files", None):
        return None
    engine = getattr(project, "_tracer_engine", None)
    if engine is None:
        cg = callgraph.for_project(project)
        if not cg.modules:
            return None
        engine = SummaryEngine(
            cg, lambda e, fi, depth: _TracerOriginWalker(e, fi, depth))
        engine._staged_ids = None
        project._tracer_engine = engine
    return engine


def _staged_node_ids(engine):
    if engine._staged_ids is None:
        ids = set()
        for mi in engine.callgraph.modules.values():
            for fn, _n, _s, _how in _staged_functions(mi.ctx.tree):
                ids.add(id(fn))
        engine._staged_ids = ids
    return engine._staged_ids


def _callee_hazards(engine, node, caller_fn, tainted_pred):
    """(FunctionInfo, Hazard) pairs live at this call site: the callee's
    summarized hazards whose origin parameters are bound to tainted
    actuals (plus unconditional ones).  Callees that are themselves
    staged are skipped — the tracer checks them directly at their own
    definition."""
    cg = engine.callgraph
    scope = cg.function_info(caller_fn)
    if scope is None:
        return []
    fi = cg.resolve_call(node.func, scope)
    if fi is None or id(fi.node) in _staged_node_ids(engine):
        return []
    summary = engine.summary(fi)
    if not summary.hazards:
        return []
    params = fi.params
    if params and params[0] == "self" and isinstance(node.func,
                                                     ast.Attribute):
        params = params[1:]
    binding = {}
    for i, a in enumerate(node.args):
        if isinstance(a, ast.Starred):
            break
        if i < len(params):
            binding[params[i]] = tainted_pred(a)
    for kw in node.keywords:
        if kw.arg is not None:
            binding[kw.arg] = tainted_pred(kw.value)
    return [(fi, hz) for hz in summary.hazards
            if not hz.origins or any(binding.get(o) for o in hz.origins)]


class _TracerRuleBase(Rule):
    """Shared machinery; three registered names so suppressions and
    `--select` can address each hazard class separately."""

    kind = "semantic"
    scope = "package"

    def check(self, ctx):
        engine = _make_engine(ctx)
        seen = set()
        for fn, static_nums, static_names, how in _staged_functions(ctx.tree):
            key = (fn.lineno, fn.name)
            if key in seen:
                continue
            seen.add(key)
            params = []
            a = fn.args
            params.extend(p.arg for p in a.posonlyargs + a.args)
            tainted = set()
            for i, p in enumerate(params):
                if i in static_nums or p in static_names:
                    continue
                tainted.add(p)
            tainted.update(p.arg for p in a.kwonlyargs
                           if p.arg not in static_names)
            tainted.discard("self")
            w = _TaintWalker(self, ctx, fn, tainted, how.split(".")[-1],
                             engine=engine)
            for stmt in fn.body:
                w.visit(stmt)
            for f in w.findings:
                if f.rule == self.name:
                    yield f


@register
class TracerHostCastRule(_TracerRuleBase):
    name = "tracer-host-cast"
    description = ("float()/int()/.item()/.tolist()/np.asarray on a traced "
                   "value inside a jit/pjit/shard_map function")


@register
class TracerPythonBranchRule(_TracerRuleBase):
    name = "tracer-python-branch"
    description = "Python if/while/assert on a traced value inside a staged function"


@register
class TracerSideEffectRule(_TracerRuleBase):
    name = "tracer-side-effect"
    description = "side-effecting call (print) inside a staged function"
