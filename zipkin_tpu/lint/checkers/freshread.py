"""ZT07 — searches on the fresh-read path.

A fresh dependency read runs under the aggregator lock that every POST
needs, so its device time is every sender's stall. On the chip a
vectorized binary search — a ``fori_loop`` of ~20 passes of gathers
over 2^19 stored keys — is the slow shape: two of them cost the
since-rollup delta read 0.67 s of its 0.87-1.26 s a call, where the
from-scratch resolve's one sort of the whole join union costs some
30 ms (TPU v5e, PERF.md), and four of them had cost the roll-up's
advance 2.2 s. So the fresh read resolves from scratch
(``tpu/ingest.py:fresh_link_context``), and this rule is the fence
against a search coming back: a ``fori_loop`` or ``lax.scan`` reachable
from a fresh-read entrypoint fails tier-1 (tests/test_lint_clean.py).
Sorts, prefix sums, the run-min ladder and the ancestor chase's
convergence-bounded ``while_loop`` are the shapes the chip runs fast
and are not flagged.

Mechanics: functions named in ``FRESH_READ_ENTRYPOINTS`` — wherever
they live — seed a walk over the WHOLE-PROGRAM call graph (qualified-
name resolution; conservative fallback edges descend into same-module
defs and uniquely-named imported methods, over-approximating rather
than missing a helper), so a search can no longer hide one import away.
A loop that belongs there carries a ZT07 pragma whose reason states its
bound; the pragma-with-reason mechanism (ZT00) keeps that claim
reviewable.

ISSUE 15 added a second fenced surface with the same failure shape at a
different tier: windowed sketch queries (``[lookback, endTs]`` on the
quantile/cardinality/dependency routes) answer by merging sealed
time-bucket segments (``tpu/timetier.py``) — compact host-side numpy
over O(W) segments. The tempting regression is a "helpful" fallback
that answers an uncovered window by rescanning the span archive
(``candidate_trace_ids`` / ``_disk_query`` — O(archive) wall per
query, exactly the cost the tier exists to avoid; uncovered epochs are
reported as coverage gaps instead). That walk is UNGATED on jax
imports: the windowed routing layer is pure host code and must stay
fenced even if it moves out of a jax-importing module. (The search
fence gates on the ROOT's module importing jax — the hazard is a device
loop, which a jax-free entrypoint module cannot seed.)
"""

from __future__ import annotations

import ast

from zipkin_tpu.lint.core import Checker, Module, register
from zipkin_tpu.lint.taint import _root_name

_FUNC_KINDS = (ast.FunctionDef, ast.AsyncFunctionDef)

# the query-path surface: functions that run (or build the program for)
# a FRESH read. The graph follows imports now, so seeding each module's
# own entrypoint is belt-and-braces rather than a necessity; the names
# stay because each IS an entrypoint of its tier.
FRESH_READ_ENTRYPOINTS = {
    "spmd_link_ctx",        # parallel/sharded.py: ctx-only program
    "spmd_edges_fresh",     # parallel/sharded.py: fused ctx+edges program
    "fresh_link_context",   # tpu/ingest.py: the read's resolve
}

# fixed-count carry loops: a binary search's shape (see module docstring)
SEARCH_LOOP_ATTRS = {"fori_loop", "scan"}
SEARCH_LOOP_ROOTS = {"jax", "lax"}

# windowed sketch-tier entrypoints (tpu/store.py, ISSUE 15): queries
# carrying a [lookback, endTs] range answer from merged time-bucket
# segments
WINDOWED_ENTRYPOINTS = {
    "latency_quantiles",
    "trace_cardinalities",
    "_get_dependencies",
    "_tt_window",
}

# the full-archive scan surface (tpu/store.py → tpu/archive.py):
# correct for trace retrieval, catastrophic as a windowed-sketch
# fallback — O(archive) wall per query
ARCHIVE_SCANNERS = {"candidate_trace_ids", "_disk_query"}


def _callee_name(func: ast.AST):
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


@register
class FreshReadRingSort(Checker):
    rule = "ZT07"
    severity = "error"
    name = "fresh-read-ring-sort"
    doc = (
        "fori_loop/scan (a binary search's shape) reachable from "
        "fresh-read entrypoints"
    )
    hint = (
        "resolve by one sort of the join union and contiguous work over "
        "it (linker.link_context), or suppress with a reason stating the "
        "loop's bound"
    )
    whole_program = True

    def check_program(self, program):
        fresh_roots, windowed_roots = [], []
        for module in program.modules:
            jax_gated = bool(module.imported_roots & {"jax", "jnp"})
            for fn in ast.walk(module.tree):
                if not isinstance(fn, _FUNC_KINDS):
                    continue
                qual = program.qual_of(fn)
                if qual is None:
                    continue
                # the search fence gates on the ROOT's module importing
                # jax: the hazard is a device loop, which a jax-free
                # entrypoint module cannot seed
                if fn.name in FRESH_READ_ENTRYPOINTS and jax_gated:
                    fresh_roots.append(qual)
                if fn.name in WINDOWED_ENTRYPOINTS:
                    windowed_roots.append(qual)
        for qual, (root, _d, _p) in program.reach(fresh_roots).items():
            info = program.functions[qual]
            module = program.module_for(info.module_rel)
            if module is not None:
                yield from self._scan_function(
                    module, info.node, program.functions[root].name
                )
        for qual, (root, _d, _p) in program.reach(windowed_roots).items():
            info = program.functions[qual]
            module = program.module_for(info.module_rel)
            if module is not None:
                yield from self._scan_windowed(
                    module, info.node, program.functions[root].name
                )

    def _scan_function(self, module: Module, fn: ast.AST, root: str):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _callee_name(node.func)
            if (
                isinstance(node.func, ast.Attribute)
                and name in SEARCH_LOOP_ATTRS
                and _root_name(node.func) in SEARCH_LOOP_ROOTS
            ):
                where = "" if fn.name == root else f" (via {fn.name}())"
                yield self.found(
                    module,
                    node,
                    f"{_root_name(node.func)}.{name} reachable from "
                    f"fresh-read entrypoint {root}(){where} — a search "
                    "loop holds every sender for its passes",
                )

    def _scan_windowed(self, module: Module, fn: ast.AST, root: str):
        if fn.name in ARCHIVE_SCANNERS:
            # the scanners themselves (and their internals) are the
            # trace-retrieval path — only CALLS INTO them from the
            # windowed surface are the violation
            return
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _callee_name(node.func)
            if name in ARCHIVE_SCANNERS:
                where = "" if fn.name == root else f" (via {fn.name}())"
                yield self.found(
                    module,
                    node,
                    f"archive scanner {name}() reachable from windowed "
                    f"entrypoint {root}(){where} — windowed queries must "
                    "merge sealed time-bucket segments (coverage gaps "
                    "are reported, not backfilled by archive rescans)",
                )
