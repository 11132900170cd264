"""ZT08 — flight-recorder stage discipline.

The obs tier (``zipkin_tpu/obs``) is host-side instrumentation with a
CLOSED stage catalogue (``obs.stages.STAGES``): dashboards, budgets, and
the /statusz schema key off the fixed name set, and the recorder indexes
histograms by ``STAGE_INDEX`` — an unknown name is a hot-path KeyError.
Two shapes are flagged:

1. ``record()`` reachable from device-traced code. ``obs.record`` is
   Python host code (thread-local lists, a seqlock counter): inside a
   ``jax.jit``/``shard_map`` region it would execute once at trace time
   — recording a single bogus near-zero sample, then silently never
   again — or fail outright under tracing. Traced defs are those
   decorated with (or passed to) ``jax.jit``/``shard_map``, plus
   everything they reach through the whole-program call graph's
   RESOLVED edges (lexical/self/import resolution) at cross-module
   depth. Fallback name-keyed edges are deliberately excluded from this
   walk: traced code calling ``x.m()`` on an unknown receiver must not
   smear "traced" onto every same-named host method — precision rules
   ride resolved edges, fence rules keep the over-approximation.
2. A ``record()`` stage argument that is not a string literal from the
   catalogue. Literal-only keeps every stage name greppable and lets
   this rule verify membership statically; a dynamic stage would also
   dodge the budget table. To add a stage, extend ``obs/stages.py``
   (name + budget) — see its docstring — and this rule learns it
   automatically.

Recognized record shapes: ``obs.record(...)``, ``RECORDER.record(...)``,
``obs.RECORDER.record(...)``, and a bare ``record(...)`` when the module
imports it ``from zipkin_tpu.obs import record``. ``record_relayed`` —
the no-selfspan variant the fan-out dispatcher uses for worker-measured
stages — is held to the same discipline (literal catalogue stage, host
code only), and so is ``span`` — ``record`` as a context manager that also
opens a profiler annotation: ``with obs.span("stage", key=...):``.

The windowed-telemetry and device-observatory hooks (ISSUE 9) are host
instrumentation too: ``WINDOWS.tick()`` / ``tick_if_due()`` mutate ring
state under locks, ``OBSERVATORY.wrap()`` / ``observe()`` time dispatch
walls with ``perf_counter``. Inside a traced region each would burn in a
trace-time constant or fail under tracing, so traced-reachability flags
them alongside ``record`` (roots ``WINDOWS``/``OBSERVATORY``/
``obs_device``, plus bare imports from ``zipkin_tpu.obs.windows`` /
``zipkin_tpu.obs.device``).
"""

from __future__ import annotations

import ast

from zipkin_tpu.lint.core import Checker, Module, register
from zipkin_tpu.lint.taint import _root_name
from zipkin_tpu.obs.stages import STAGES

_FUNC_KINDS = (ast.FunctionDef, ast.AsyncFunctionDef)

_RECORD_ATTRS = {"record", "record_relayed", "span"}
_RECORD_ROOTS = {"obs", "RECORDER"}
# windows/device/shadow hooks: host-only for the same reason record is;
# flagged by the traced-reach pass but exempt from stage-arg validation
# (they take no stage). The accuracy-observatory hooks (ISSUE 10) join
# the set: offer_* are bounded-deque appends and drain/rollup mutate
# shadow state under locks — a traced region would capture one
# trace-time batch forever (or fail under tracing).
_HOOK_ATTRS = {
    "tick", "tick_if_due", "observe", "wrap",
    "offer_cols", "offer_fused", "offer_spans", "drain",
    "rollup", "maybe_rollup",
    # critical-path tracer (ISSUE 11): ledger writes are seqlocked
    # shared-memory mutation + perf_counter reads, and the stitcher
    # folds under a lock — all host-only. A traced region would stamp
    # one trace-time interval forever (or fail under tracing).
    "stamp", "stamp_active", "alloc", "ack", "abandon", "release",
    "stitch", "calibrate", "set_active", "set_active_group",
    "clear_active",
    # query-plane observatory (ISSUE 12): trace arming is thread-local
    # state, the instrumented-lock wrapper measures perf_counter waits,
    # and the stitcher folds under a lock — all host-only. A traced
    # region would bake one trace-time interval (or fail under tracing).
    "begin", "finish", "relabel", "lock_label",
}
_HOOK_ROOTS = {
    "obs", "WINDOWS", "OBSERVATORY", "obs_device", "SHADOW", "ACCURACY",
    "critpath", "_critpath", "CRITPATH",
    "querytrace", "_querytrace", "QUERYTRACE",
}
_HOOK_MODULES = {
    "zipkin_tpu.obs.windows", "zipkin_tpu.obs.device",
    "zipkin_tpu.obs.shadow", "zipkin_tpu.obs.accuracy",
    "zipkin_tpu.obs.critpath", "zipkin_tpu.obs.querytrace",
}
_TRACE_NAMES = {"jit", "shard_map"}


def _is_trace_call(node: ast.AST) -> bool:
    """jax.jit(...), jit(...), shard_map(...), or a partial over one."""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in _TRACE_NAMES:
        return True
    if isinstance(f, ast.Name) and f.id in _TRACE_NAMES:
        return True
    if (
        isinstance(f, ast.Attribute)
        and f.attr == "partial"
        and node.args
        and _is_trace_call(ast.Call(func=node.args[0], args=[], keywords=[]))
    ):
        return True
    return False


@register
class ObsStageDiscipline(Checker):
    rule = "ZT08"
    severity = "error"
    name = "obs-stage-discipline"
    doc = (
        "obs.record inside device-traced code; stage args outside the "
        "closed catalogue"
    )
    hint = (
        "record stages from host code only, with a string literal from "
        "obs.stages.STAGES; to add a stage extend obs/stages.py"
    )

    whole_program = True

    def check_program(self, program):
        aliases = {}  # module rel -> (record aliases, hook aliases)
        traced_roots = []
        for module in program.modules:
            if "zipkin_tpu" not in module.imported_roots:
                continue
            bare, bare_hooks = self._bare_aliases(module)
            aliases[module.rel] = (bare, bare_hooks)
            records = [
                node
                for node in ast.walk(module.tree)
                if self._is_record_call(node, bare)
            ]
            yield from self._check_stage_args(module, records)
            if module.imported_roots & {"jax", "jnp"}:
                traced_roots.extend(
                    q for q in map(
                        program.qual_of, self._traced_defs(module)
                    ) if q
                )
        if not traced_roots:
            return
        # traced-reach rides RESOLVED edges only (module docstring)
        reached = program.reach(traced_roots, resolved_only=True)
        for qual, (root, _d, _p) in reached.items():
            info = program.functions[qual]
            module = program.module_for(info.module_rel)
            if module is None:
                continue
            if module.rel not in aliases:
                aliases[module.rel] = self._bare_aliases(module)
            bare, bare_hooks = aliases[module.rel]
            yield from self._scan_traced(
                module, info.node, program.functions[root].name,
                bare, bare_hooks,
            )

    # -- record/hook call recognition --------------------------------------

    def _bare_aliases(self, module: Module):
        """(record aliases, hook aliases) pulled in by bare imports."""
        records, hooks = set(), set()
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.ImportFrom):
                continue
            if node.module == "zipkin_tpu.obs":
                for a in node.names:
                    if a.name in _RECORD_ATTRS:
                        records.add(a.asname or a.name)
            elif node.module in _HOOK_MODULES:
                for a in node.names:
                    if a.name in _HOOK_ATTRS:
                        hooks.add(a.asname or a.name)
        return records, hooks

    def _is_record_call(self, node: ast.AST, bare: set) -> bool:
        if not isinstance(node, ast.Call):
            return False
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _RECORD_ATTRS:
            return _root_name(f) in _RECORD_ROOTS
        return isinstance(f, ast.Name) and f.id in bare

    def _is_hook_call(self, node: ast.AST, bare_hooks: set) -> bool:
        if not isinstance(node, ast.Call):
            return False
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _HOOK_ATTRS:
            return _root_name(f) in _HOOK_ROOTS
        return isinstance(f, ast.Name) and f.id in bare_hooks

    # -- shape 2: stage names come from the closed catalogue ----------------

    def _check_stage_args(self, module: Module, records):
        for call in records:
            arg = call.args[0] if call.args else None
            if arg is None:
                for kw in call.keywords:
                    if kw.arg == "stage":
                        arg = kw.value
            if arg is None:
                yield self.found(module, call, "record() call with no stage")
                continue
            if not (isinstance(arg, ast.Constant) and isinstance(arg.value, str)):
                yield self.found(
                    module,
                    call,
                    "record() stage must be a string literal — dynamic "
                    "names dodge the catalogue and the budget table",
                )
                continue
            if arg.value not in STAGES:
                yield self.found(
                    module,
                    call,
                    f"unknown stage {arg.value!r} — not in obs.stages."
                    "STAGES (histograms/budgets/statusz key off the "
                    "closed set)",
                )

    # -- shape 1: no recording inside device-traced code -------------------

    def _traced_defs(self, module: Module):
        """Defs decorated with (or passed by name to) jit/shard_map."""
        defs = {}
        for node in ast.walk(module.tree):
            if isinstance(node, _FUNC_KINDS):
                defs.setdefault(node.name, node)
        traced = []
        for fn in defs.values():
            if any(_is_trace_call(d) or _trace_target(d) for d in fn.decorator_list):
                traced.append(fn)
        for node in ast.walk(module.tree):
            if _is_trace_call(node):
                for arg in node.args:
                    tgt = defs.get(arg.id) if isinstance(arg, ast.Name) else None
                    if tgt is not None:
                        traced.append(tgt)
        return traced

    def _scan_traced(self, module, fn, root, bare, bare_hooks):
        for node in ast.walk(fn):
            if self._is_record_call(node, bare):
                where = "" if fn.name == root else f" (via {fn.name}())"
                yield self.found(
                    module,
                    node,
                    f"obs.record inside device-traced {root}(){where} "
                    "— host-side instrumentation runs once at trace "
                    "time, then never again",
                )
            elif self._is_hook_call(node, bare_hooks):
                where = "" if fn.name == root else f" (via {fn.name}())"
                yield self.found(
                    module,
                    node,
                    f"obs windows/device hook inside device-traced "
                    f"{root}(){where} — ring/registry mutation is host "
                    "code; under tracing it burns in a trace-time "
                    "constant",
                )


def _trace_target(dec: ast.AST) -> bool:
    """Bare (non-call) jit/shard_map decorator: ``@jax.jit``/``@jit``."""
    if isinstance(dec, ast.Attribute):
        return dec.attr in _TRACE_NAMES
    return isinstance(dec, ast.Name) and dec.id in _TRACE_NAMES
