"""ZT06 — blocking sync on serving paths.

``block_until_ready()`` stalls the calling thread until every queued
device computation retires. In a benchmark that is the point
(wall-clock honesty); on a serving path it serializes the async ingest
pipeline behind the device and hands the transport's fixed round trip
to the caller. The ingest/read planes are designed to overlap host and
device work (the device queue's run-ahead, the lock-scoped
dispatch-then-pull split in state_clone) — a stray sync undoes that
silently.

Rule: any ``*.block_until_ready()`` (or ``jax.block_until_ready(x)``)
call in library code — paths under ``chipbench/`` and ``tests/`` are
exempt, as is the body of a method itself NAMED
``block_until_ready`` (that is the deliberate sync seam the exempt
callers use). Legitimate library blockers (health checks, drain seams,
warm-up) carry a scoped pragma naming why blocking is the contract.
"""

from __future__ import annotations

import ast

from zipkin_tpu.lint.core import Checker, Module, register

_FUNC_KINDS = (ast.FunctionDef, ast.AsyncFunctionDef)
_EXEMPT_PATH_PARTS = ("chipbench/", "tests/", "test_")


@register
class BlockingSync(Checker):
    rule = "ZT06"
    severity = "error"
    name = "blocking-sync"
    doc = "block_until_ready outside chipbench/tests"
    hint = (
        "let the async pipeline overlap host and device work; if "
        "blocking IS the contract (drain/health/warm-up), suppress on "
        "the def line saying so"
    )

    def check(self, module: Module):
        if any(part in module.rel for part in _EXEMPT_PATH_PARTS):
            return
        for node in ast.walk(module.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready"
            ):
                continue
            fn = next(iter(module.enclosing(node, _FUNC_KINDS)), None)
            if fn is not None and fn.name == "block_until_ready":
                continue  # the sync seam's own definition
            where = f" in {fn.name}()" if fn is not None else ""
            yield self.found(
                module,
                node,
                f"block_until_ready{where} — serving-path host stall "
                "until the device queue retires",
            )
