"""ZT03 — jit-recompile hazards.

A compile takes seconds to minutes (ARCHITECTURE.md warm-up note), so a
``jax.jit`` that re-traces at serving time is a production stall, not a
micro-inefficiency. Two shapes are flagged:

1. ``jax.jit(...)`` *constructed* inside a loop body, or inside a plain
   function/method (a fresh jit wrapper per call has a fresh trace
   cache: every call recompiles). Module scope is fine; so is any
   enclosing function cached with ``functools.lru_cache``/``cache`` —
   the repo's ``_compiled_programs`` factory pattern.
2. A *known-jitted* callable (bound from ``jax.jit(...)`` without
   ``static_argnums``/``static_argnames``) invoked with a varying
   Python scalar positional arg — a loop variable, or an ``int()``/
   ``float()`` coercion at the call site. Each distinct value traces a
   new program (Python scalars hash into the jit cache key by value
   when weak-typed promotion fails to canonicalize them); wrap in
   ``jnp.uint32(...)``/``jnp.asarray`` or declare the arg static.
"""

from __future__ import annotations

import ast

from zipkin_tpu.lint.core import Checker, Module, register
from zipkin_tpu.lint.taint import _root_name

_FUNC_KINDS = (ast.FunctionDef, ast.AsyncFunctionDef)
_CACHE_DECORATORS = {"lru_cache", "cache", "cached_property"}


def _is_jit_call(node: ast.AST) -> bool:
    """jax.jit(...), jit(...), or functools.partial(jax.jit, ...)."""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr == "jit" and _root_name(f) == "jax":
        return True
    if isinstance(f, ast.Name) and f.id == "jit":
        return True
    if (
        isinstance(f, ast.Attribute)
        and f.attr == "partial"
        and node.args
        and _is_jit_call(ast.Call(func=node.args[0], args=[], keywords=[]))
    ):
        return True
    return False


def _decorator_names(fn: ast.AST):
    for dec in getattr(fn, "decorator_list", ()):
        target = dec.func if isinstance(dec, ast.Call) else dec
        if isinstance(target, ast.Attribute):
            yield target.attr
        elif isinstance(target, ast.Name):
            yield target.id


def _jit_has_static(call: ast.Call) -> bool:
    return any(
        k.arg in ("static_argnums", "static_argnames") for k in call.keywords
    )


@register
class RecompileHazards(Checker):
    rule = "ZT03"
    severity = "error"
    name = "jit-recompile-hazards"
    doc = "jax.jit per call/iteration; varying scalars into jitted callables"
    hint = (
        "hoist jax.jit to module scope or an lru_cache'd factory; pass "
        "scalars as jnp arrays (jnp.uint32(x)) or declare them static"
    )

    def check(self, module: Module):
        if not module.imported_roots & {"jax", "jnp"}:
            return
        yield from self._jit_construction_sites(module)
        yield from self._scalar_args_to_jitted(module)

    # -- shape 1: where is jax.jit constructed? ---------------------------

    def _jit_construction_sites(self, module: Module):
        # decorator expressions evaluate at def time (module scope for
        # top-level defs) — @functools.partial(jax.jit, ...) is NOT a
        # per-call construction
        in_decorator = set()
        for fn in ast.walk(module.tree):
            for dec in getattr(fn, "decorator_list", ()):
                in_decorator.update(ast.walk(dec))
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Call) and _is_jit_call(node)):
                continue
            if node in in_decorator:
                continue
            loop = next(
                iter(module.enclosing(node, (ast.For, ast.While))), None
            )
            if loop is not None:
                yield self.found(
                    module,
                    node,
                    "jax.jit constructed inside a loop — every iteration "
                    "builds a wrapper with an empty trace cache",
                )
                continue
            enclosing_fns = list(module.enclosing(node, _FUNC_KINDS))
            if not enclosing_fns:
                continue  # module scope: compiled once per import
            if any(
                set(_decorator_names(fn)) & _CACHE_DECORATORS
                for fn in enclosing_fns
            ):
                continue  # the cached-factory pattern (_compiled_programs)
            yield self.found(
                module,
                node,
                f"jax.jit constructed inside {enclosing_fns[0].name}() — "
                "a fresh wrapper (and recompile) per call; hoist to "
                "module scope or cache the factory",
            )

    # -- shape 2: varying Python scalars hitting jitted callables ---------

    def _scalar_args_to_jitted(self, module: Module):
        # names bound from jax.jit(...) without static declarations, at
        # any assignment site in the module (module or function scope)
        jitted: dict = {}
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                if _is_jit_call(node.value) and not _jit_has_static(node.value):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            jitted[t.id] = node.value
        if not jitted:
            return
        for node in ast.walk(module.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in jitted
            ):
                continue
            for arg in node.args:
                reason = self._varying_scalar(module, node, arg)
                if reason:
                    yield self.found(
                        module,
                        node,
                        f"jitted callable {node.func.id}() takes a "
                        f"{reason} positionally — each distinct value "
                        "recompiles (not declared static)",
                    )
                    break

    def _varying_scalar(self, module: Module, call: ast.Call, arg: ast.AST):
        if (
            isinstance(arg, ast.Call)
            and isinstance(arg.func, ast.Name)
            and arg.func.id in ("int", "float")
        ):
            return "Python-scalar int()/float() coercion"
        if isinstance(arg, ast.Name):
            for loop in module.enclosing(call, (ast.For,)):
                t = loop.target
                names = (
                    {t.id}
                    if isinstance(t, ast.Name)
                    else {
                        el.id
                        for el in getattr(t, "elts", ())
                        if isinstance(el, ast.Name)
                    }
                )
                if arg.id in names:
                    return f"loop-varying Python scalar ({arg.id})"
        return None
