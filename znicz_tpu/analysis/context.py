"""Traced-context detection: which functions does JAX trace?

A function body runs under the tracer (so host-side Python is a hazard
there) when the function is:

* decorated with ``jax.jit`` / ``pjit`` / a ``partial(jax.jit, ...)``;
* passed by name to a transform (``jax.jit(f)``, ``jax.grad(f)``,
  ``jax.vmap``, ``jax.shard_map``, ``jax.checkpoint`` ...);
* passed by name to a ``lax`` control-flow combinator (``scan``,
  ``while_loop``, ``fori_loop``, ``cond``, ``switch``, ``map``);
* lexically nested inside any traced function (closures like a scan
  body defined inside a jitted step).

The index is built per module; the PROJECT-wide pass
(:mod:`znicz_tpu.analysis.project`) extends it across imports by
calling :meth:`TracedIndex.mark_traced` on the defining module's index
for every transform applied elsewhere (``jax.jit(workflow.step)`` in a
bench marks ``step`` traced in ``workflow``), and by chain-marking
module-level helpers reachable only from traced callers.  Static
arguments declared via ``static_argnums`` / ``static_argnames``
(literal values only) are excluded from the traced-parameter sets, so
branching on a static config flag inside a jitted function does not
fire ZNC001.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

# transform families: value args are traced callables
TRANSFORMS = {
    "jit",
    "pjit",
    "grad",
    "value_and_grad",
    "vmap",
    "pmap",
    "shard_map",
    "checkpoint",
    "remat",
    "custom_gradient",
}
# jit-like wrappers relevant to donation analysis (ZNC005)
JIT_WRAPPERS = {"jit", "pjit"}
# lax combinators: (call name) -> positional indices holding traced bodies
LAX_BODIES = {
    "scan": (0,),
    "while_loop": (0, 1),
    "fori_loop": (2,),
    "cond": (1, 2, 3),
    "switch": (1, 2, 3, 4, 5, 6, 7),
    "map": (0,),
    "associative_scan": (0,),
}
# module paths whose members count as transform/combinator homes.
# Deliberately NOT "": from-imports are already alias-resolved to full
# dotted paths, and accepting bare names would conflate builtin map()
# (or any local def named jit/scan) with the jax combinators.
_MODULE_PATHS = {
    "jax",
    "lax",
    "jax.lax",
    "functools",
    "jax.experimental",
    "jax.experimental.shard_map",
    "jax.experimental.pjit",
}


def _basename(dotted: Optional[str]) -> Optional[str]:
    """``jax.lax.scan`` -> ``scan`` when the module path is a known
    transform home.  Unrelated dotted names (``self.fn``,
    ``jax.numpy.sum``) return None so an arbitrary attribute that
    happens to be called ``scan`` is not misread."""
    if dotted is None:
        return None
    head, _, last = dotted.rpartition(".")
    return last if head in _MODULE_PATHS else None


def _literal_tuple(node: ast.AST) -> Optional[Tuple]:
    """Literal int/str or tuple/list of them -> python tuple, else None."""
    if isinstance(node, ast.Constant) and isinstance(
        node.value, (int, str)
    ):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(
                elt.value, (int, str)
            ):
                vals.append(elt.value)
            else:
                return None
        return tuple(vals)
    return None


def scope_local_names(fn) -> Set[str]:
    """Parameters plus every name the function itself binds — python
    scoping makes such a name local THROUGHOUT the function, so a load
    of it can never refer to a module-level def or variable."""
    names: Set[str] = set(_param_names(fn))
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                names.add(node.name)  # the def statement binds its name
            continue  # nested scopes bind their own names
        if isinstance(node, ast.Name) and isinstance(
            node.ctx, (ast.Store, ast.Del)
        ):
            names.add(node.id)
        stack.extend(ast.iter_child_nodes(node))
    return names


def name_is_shadowed(info, node: ast.AST, name: str) -> bool:
    """Is ``name``, read at ``node``, bound by an enclosing function
    scope (parameter, local assignment, nested def)?  A shadowed name
    can never resolve to the module-level def of the same name."""
    fn = info.enclosing_function(node)
    while fn is not None:
        if name in scope_local_names(fn):
            return True
        fn = info.enclosing_function(fn)
    return False


def name_is_param(info, node: ast.AST, name: str) -> bool:
    """Is ``name``, read at ``node``, a PARAMETER of an enclosing
    function?  ``jax.jit(step)`` inside ``def compile_it(step)`` wraps
    whatever the caller passed — never the module-level ``step`` def.
    (Weaker than :func:`name_is_shadowed` on purpose: nested-def names
    must stay resolvable for scan-body/closure patterns.)"""
    fn = info.enclosing_function(node)
    while fn is not None:
        if name in _param_names(fn):
            return True
        fn = info.enclosing_function(fn)
    return False


def unwrap_partial(info, node: ast.AST):
    """``partial(body, ...)`` -> ``(body, n_positional_bound,
    keyword_bound_names)``; anything else passes through with zero
    bindings.  The ONE owner of partial-unwrapping semantics — the
    per-module traced index and the project pass both call it, so the
    two can never diverge on what a partial binds."""
    if (
        isinstance(node, ast.Call)
        and _basename(info.resolved(node.func)) == "partial"
        and node.args
    ):
        kwnames = {kw.arg for kw in node.keywords if kw.arg}
        return node.args[0], len(node.args) - 1, kwnames
    return node, 0, set()


def _param_names(fn) -> List[str]:
    args = fn.args
    names = [a.arg for a in args.posonlyargs + args.args]
    if args.vararg:
        names.append(args.vararg.arg)
    names += [a.arg for a in args.kwonlyargs]
    if args.kwarg:
        names.append(args.kwarg.arg)
    return names


def _positional_names(fn) -> List[str]:
    args = fn.args
    return [a.arg for a in args.posonlyargs + args.args]


def _static_names_from_kwargs(fn, keywords) -> Set[str]:
    """static_argnums / static_argnames keywords -> parameter names."""
    static: Set[str] = set()
    positional = _positional_names(fn)
    for kw in keywords:
        if kw.arg not in ("static_argnums", "static_argnames"):
            continue
        vals = _literal_tuple(kw.value)
        if vals is None:
            continue
        for v in vals:
            if isinstance(v, str):
                static.add(v)
            elif isinstance(v, int) and 0 <= v < len(positional):
                static.add(positional[v])
    return static


class JitCall:
    """One resolvable jit/pjit application (decorator or call form)."""

    def __init__(self, node, fn, keywords):
        self.node = node  # the Call (or decorator) AST node to report on
        self.fn = fn  # the wrapped FunctionDef, when resolvable
        self.keywords = {kw.arg: kw.value for kw in keywords if kw.arg}

    def has_donation(self) -> bool:
        return (
            "donate_argnums" in self.keywords
            or "donate_argnames" in self.keywords
        )

    def static_names(self) -> Set[str]:
        if self.fn is None:
            return set()
        return _static_names_from_kwargs(
            self.fn,
            [
                ast.keyword(arg=k, value=v)
                for k, v in self.keywords.items()
            ],
        )


class TracedIndex:
    """Per-module index of traced functions and jit applications."""

    def __init__(self, info):
        self.info = info
        self._traced: Set[ast.AST] = set()
        # traced function -> statically-excluded parameter names
        self._static: Dict[ast.AST, Set[str]] = {}
        self.jit_calls: List[JitCall] = []
        self._defs_by_name: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(info.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._defs_by_name.setdefault(node.name, []).append(node)
        self._index()

    # -- construction ----------------------------------------------------
    def _wrapper_call(self, call: ast.Call):
        """``jax.jit`` / ``partial(jax.jit, ...)`` call -> (base, kwargs);
        base is the transform's basename, kwargs the jit kwargs."""
        name = _basename(self.info.resolved(call.func))
        if name == "partial" and call.args:
            inner = _basename(self.info.resolved(call.args[0]))
            if inner in TRANSFORMS:
                return inner, list(call.keywords)
            return None, []
        if name in TRANSFORMS:
            return name, list(call.keywords)
        return None, []

    def _mark(self, fn, static: Set[str]) -> None:
        if fn in self._traced:
            self._static[fn] |= static
            return
        self._traced.add(fn)
        self._static[fn] = set(static)
        # closures defined inside a traced body are traced too
        for node in ast.walk(fn):
            if node is not fn and isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                if node not in self._traced:
                    self._traced.add(node)
                    self._static[node] = set()

    def _visible_from(self, fn, site) -> bool:
        """Is ``fn``'s defining scope an ancestor of (or the module
        containing) ``site``?  A same-named def in a SIBLING function is
        a different object and must not be conflated."""
        enclosing = self.info.enclosing_function(fn)
        if enclosing is None:
            return True  # module-level def: visible everywhere
        cur = self.info.enclosing_function(site)
        while cur is not None:
            if cur is enclosing:
                return True
            cur = self.info.enclosing_function(cur)
        return False

    def _resolve_local(self, node, site=None) -> List[tuple]:
        """Callable AST node -> [(funcdef, partial_bound_names)],
        restricted to defs lexically visible from ``site``.

        ``partial(body, ...)`` (the repo's dominant way of handing
        configured bodies to shard_map/scan) unwraps to ``body``; the
        names the partial binds — keywords, plus the leading positional
        parameters — are trace-time CONSTANTS, so they join the static
        set rather than the traced one.
        """
        node, n_pos, kwnames = unwrap_partial(self.info, node)
        out = []
        if isinstance(node, ast.Name):
            if site is not None and name_is_param(
                self.info, site, node.id
            ):
                return []  # wraps whatever the caller passed in
            for fn in self._defs_by_name.get(node.id, []):
                if site is not None and not self._visible_from(fn, site):
                    continue
                bound = set(kwnames)
                bound.update(_positional_names(fn)[:n_pos])
                out.append((fn, bound))
        elif isinstance(node, ast.Lambda):
            out.append((node, set()))
        return out

    def _index(self) -> None:
        info = self.info
        # 1. decorator forms
        for name, defs in self._defs_by_name.items():
            for fn in defs:
                for dec in fn.decorator_list:
                    if isinstance(dec, ast.Call):
                        base, kws = self._wrapper_call(dec)
                        if base is None:
                            continue
                        static = _static_names_from_kwargs(fn, kws)
                        self._mark(fn, static)
                        if base in JIT_WRAPPERS:
                            self.jit_calls.append(JitCall(dec, fn, kws))
                    else:
                        base = _basename(info.resolved(dec))
                        if base in TRANSFORMS:
                            self._mark(fn, set())
                            if base in JIT_WRAPPERS:
                                self.jit_calls.append(JitCall(dec, fn, []))
        # 2. call forms: jax.jit(f, ...), jax.grad(f), lax.scan(body, ...)
        for node in ast.walk(info.tree):
            if not isinstance(node, ast.Call):
                continue
            base, kws = self._wrapper_call(node)
            if base is not None and node.args:
                resolved = self._resolve_local(node.args[0], node)
                for fn, bound in resolved:
                    static = set(bound)
                    if not isinstance(fn, ast.Lambda):
                        static |= _static_names_from_kwargs(fn, kws)
                    self._mark(fn, static)
                    if base in JIT_WRAPPERS and isinstance(
                        fn, (ast.FunctionDef, ast.AsyncFunctionDef)
                    ):
                        self.jit_calls.append(JitCall(node, fn, kws))
                if base in JIT_WRAPPERS and not resolved:
                    # unresolvable target (method, imported fn): keep the
                    # call so ZNC005 can still reason about kwargs
                    self.jit_calls.append(JitCall(node, None, kws))
                continue
            lax_name = _basename(info.resolved(node.func))
            body_slots = LAX_BODIES.get(lax_name or "")
            if body_slots:
                for i in body_slots:
                    if i < len(node.args):
                        for fn, bound in self._resolve_local(
                            node.args[i], node
                        ):
                            self._mark(fn, bound)

    # -- the project pass's entry point ----------------------------------
    def mark_traced(self, fn, static: Set[str]) -> None:
        """Mark ``fn`` traced with ``static`` parameter names excluded
        — the cross-module hook :mod:`znicz_tpu.analysis.project` uses
        when a transform application in ANOTHER module resolves to a
        def in this one.  Closures nested in ``fn`` are marked too,
        exactly like a same-module application."""
        self._mark(fn, static)

    # -- queries ---------------------------------------------------------
    def is_traced(self, fn) -> bool:
        return fn in self._traced

    def in_traced_code(self, node) -> bool:
        """True when the nearest enclosing function of ``node`` is traced."""
        fn = self.info.enclosing_function(node)
        return fn is not None and fn in self._traced

    def traced_param_names(self, node) -> Set[str]:
        """Union of non-static parameter names over the enclosing traced
        function chain — the names a branch condition must not consume."""
        names: Set[str] = set()
        fn = self.info.enclosing_function(node)
        while fn is not None:
            if fn in self._traced:
                names |= set(_param_names(fn)) - self._static.get(
                    fn, set()
                )
            fn = self.info.enclosing_function(fn)
        return names
