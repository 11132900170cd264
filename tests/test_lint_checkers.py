"""ZT-lint checker fixtures: one positive + one negative snippet per
rule, pragma suppression (line, next-line, def-scoped, reasonless →
ZT00), baseline round-trip, and select/ignore plumbing.

Every positive fixture doubles as the "fails when its checker is
disabled" demonstration: the same snippet linted with the rule ignored
must produce nothing, so the finding provably comes from that checker.
"""

from __future__ import annotations

import textwrap
from pathlib import Path

from zipkin_tpu.lint import all_checkers, run_paths
from zipkin_tpu.lint.cli import main as lint_main


def lint(tmp_path, source, name="mod.py", **kwargs):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return run_paths([str(p)], root=tmp_path, **kwargs)


def rules(result):
    return sorted(f.rule for f in result.findings)


def assert_rule_owned(tmp_path, source, rule, name="mod.py"):
    """The finding is present — and vanishes when its checker is
    disabled (so the fixture fails if the checker is unregistered)."""
    assert rule in rules(lint(tmp_path, source, name=name))
    assert rule not in rules(
        lint(tmp_path, source, name=name, ignore={rule})
    )


# -- ZT01: host-transfer chokepoint -------------------------------------


ZT01_POSITIVE = """
    import jax
    import numpy as np

    class Agg:
        def read(self):
            return np.asarray(self.state.hist)
"""


def test_zt01_flags_device_pull_outside_chokepoint(tmp_path):
    assert_rule_owned(tmp_path, ZT01_POSITIVE, "ZT01")


def test_zt01_ignores_host_input_coercion(tmp_path):
    result = lint(
        tmp_path,
        """
        import jax
        import numpy as np

        def coerce(qs):
            return np.asarray(qs, np.float32)
        """,
    )
    assert rules(result) == []


def test_zt01_ignores_jax_device_metadata(tmp_path):
    # jax.devices() returns host-side Device handles, not device arrays
    result = lint(
        tmp_path,
        """
        import jax
        import numpy as np

        def make_mesh():
            return np.asarray(jax.devices())
        """,
    )
    assert rules(result) == []


def test_zt01_flags_item_and_float_of_device_values(tmp_path):
    result = lint(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        class Agg:
            def peek(self):
                total = jnp.sum(self.state.counters)
                return float(total), self.state.pend_pos.item()
        """,
        select={"ZT01"},
    )
    assert rules(result).count("ZT01") >= 2


# -- ZT02: multi-pull read shapes ---------------------------------------


ZT02_POSITIVE = """
    import jax
    import numpy as np

    class Agg:
        def read(self):
            a = np.asarray(self.state.hist)
            b = np.asarray(self.state.hll)
            return a, b
"""


def test_zt02_flags_two_pulls_per_method(tmp_path):
    assert_rule_owned(tmp_path, ZT02_POSITIVE, "ZT02")


def test_zt02_allows_single_packed_pull(tmp_path):
    result = lint(
        tmp_path,
        """
        import jax

        class Agg:
            def read(self):
                return self._pull(self._merge(self.state))
        """,
        select={"ZT02"},
    )
    assert rules(result) == []


# -- ZT03: jit-recompile hazards ----------------------------------------


ZT03_POSITIVE = """
    import jax

    def build(config):
        return jax.jit(lambda state: state)
"""


def test_zt03_flags_jit_factory_without_cache(tmp_path):
    assert_rule_owned(tmp_path, ZT03_POSITIVE, "ZT03")


def test_zt03_allows_lru_cached_factory(tmp_path):
    result = lint(
        tmp_path,
        """
        import functools

        import jax

        @functools.lru_cache(maxsize=None)
        def build(config):
            return jax.jit(lambda state: state)
        """,
    )
    assert rules(result) == []


def test_zt03_jit_decorator_is_not_a_construction_site(tmp_path):
    # regression: @functools.partial(jax.jit, ...) evaluates at def
    # time, not per call
    result = lint(
        tmp_path,
        """
        import functools

        import jax

        @functools.partial(jax.jit, static_argnames=("interpret",))
        def step(x, interpret=False):
            return x
        """,
    )
    assert rules(result) == []


def test_zt03_flags_jit_in_loop_and_varying_scalar(tmp_path):
    result = lint(
        tmp_path,
        """
        import jax

        step = jax.jit(lambda s, n: s)

        def replay(state, batches):
            for n in batches:
                state = step(state, n)
            return state

        def rebuild(sizes):
            fns = []
            for _ in sizes:
                fns.append(jax.jit(lambda s: s))
            return fns
        """,
        select={"ZT03"},
    )
    assert rules(result).count("ZT03") == 2


# -- ZT04: lock discipline ----------------------------------------------


ZT04_POSITIVE = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0

        def bump(self):
            with self._lock:
                self.count += 1

        def reset(self):
            self.count = 0
"""


def test_zt04_flags_lock_free_write_of_guarded_attr(tmp_path):
    assert_rule_owned(tmp_path, ZT04_POSITIVE, "ZT04")


def test_zt04_quiet_when_all_writes_guarded(tmp_path):
    result = lint(
        tmp_path,
        """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def bump(self):
                with self._lock:
                    self.count += 1

            def reset(self):
                with self._lock:
                    self.count = 0
        """,
    )
    assert rules(result) == []


def test_zt04_recognizes_instrumented_rlock(tmp_path):
    # the contention-ledger lock (obs/querytrace.py, ISSUE 12) is a
    # drop-in RLock; swapping it in must not blind the discipline check
    assert_rule_owned(
        tmp_path,
        """
        from zipkin_tpu.obs import querytrace

        class Agg:
            def __init__(self):
                self.lock = querytrace.InstrumentedRLock(name="agg")
                self.tables = {}

            def ingest(self, k, v):
                with self.lock:
                    self.tables[k] = v

            def clear(self):
                self.tables = {}
        """,
        "ZT04",
    )


def test_zt04_quiet_for_guarded_instrumented_rlock(tmp_path):
    result = lint(
        tmp_path,
        """
        from zipkin_tpu.obs import querytrace

        class Agg:
            def __init__(self):
                self.lock = querytrace.InstrumentedRLock(name="agg")
                self.tables = {}

            def ingest(self, k, v):
                with self.lock:
                    self.tables[k] = v

            def clear(self):
                with self.lock:
                    self.tables = {}
        """,
    )
    assert rules(result) == []


# -- ZT05: donation misuse ----------------------------------------------


ZT05_POSITIVE = """
    import jax

    step = jax.jit(lambda s, x: s, donate_argnums=(0,))

    def run(state, x):
        out = step(state, x)
        return out, state.sum()
"""


def test_zt05_flags_read_after_donation(tmp_path):
    assert_rule_owned(tmp_path, ZT05_POSITIVE, "ZT05")


def test_zt05_allows_rebinding_the_donated_name(tmp_path):
    result = lint(
        tmp_path,
        """
        import jax

        step = jax.jit(lambda s, x: s, donate_argnums=(0,))

        def run(state, x):
            state = step(state, x)
            return state.sum()
        """,
    )
    assert rules(result) == []


# -- ZT06: blocking sync ------------------------------------------------


ZT06_POSITIVE = """
    import jax

    def serve(agg):
        agg.block_until_ready()
"""


def test_zt06_flags_blocking_sync_in_serving_code(tmp_path):
    assert_rule_owned(tmp_path, ZT06_POSITIVE, "ZT06")


def test_zt06_exempts_benchmarks_and_tests(tmp_path):
    for name in ("chipbench/run.py", "tests/test_x.py"):
        assert rules(lint(tmp_path, ZT06_POSITIVE, name=name)) == []


# -- ZT07: fresh-read ring sorts ----------------------------------------


ZT07_POSITIVE = """
    import jax
    import jax.numpy as jnp

    def _resolve(keys):
        return jax.lax.sort(keys, num_keys=4)

    def spmd_edges_fresh(state, ts_lo, ts_hi):
        order = _resolve(state.ring_keys)
        return order
"""


def test_zt07_flags_sort_reachable_from_fresh_entrypoint(tmp_path):
    assert_rule_owned(tmp_path, ZT07_POSITIVE, "ZT07")


def test_zt07_flags_from_scratch_rebuilder_call(tmp_path):
    result = lint(
        tmp_path,
        """
        import jax
        from zipkin_tpu.ops import linker

        def fresh_link_context(config, state):
            return linker.link_context(state.ring)
        """,
    )
    assert "ZT07" in rules(result)


def test_zt07_ignores_sorts_on_the_rollup_path(tmp_path):
    # the same sort outside the fresh-read surface (rollup cadence /
    # oracle) is the design, not a violation
    result = lint(
        tmp_path,
        """
        import jax

        def advance(state, seg):
            return jax.lax.sort(state.ring_keys, num_keys=4)

        def rollup_step(config, state):
            return advance(state, config.rollup_segment)
        """,
    )
    assert rules(result) == []


def test_zt07_ignores_cumsum_on_fresh_path(tmp_path):
    # prefix sums are the delta formulation's own workhorse: O(n)
    # vectorized, not the O(n log n) comparison sort the rule fences
    result = lint(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        def delta_resolve(x, cs, seg):
            return jnp.cumsum(cs.run_starts)
        """,
    )
    assert rules(result) == []


def test_zt07_pragma_with_delta_bound_suppresses(tmp_path):
    result = lint(
        tmp_path,
        ZT07_POSITIVE.replace(
            "return jax.lax.sort(keys, num_keys=4)",
            "return jax.lax.sort(keys, num_keys=4)"
            "  # zt-lint: disable=ZT07 — sorts only the 2*seg delta lanes",
        ),
    )
    assert rules(result) == []
    assert [f.rule for f in result.suppressed] == ["ZT07"]


# -- ZT07 windowed fence: no archive scans from windowed entrypoints ----


ZT07_WINDOWED_POSITIVE = """
    class Store:
        def trace_cardinalities(self, end_ts=None, lookback=None):
            if end_ts is not None:
                return self._backfill(end_ts, lookback)
            return self._rows()

        def _backfill(self, end_ts, lookback):
            # the tempting regression: answer an uncovered window by
            # rescanning the span archive
            return self._disk_query((end_ts, lookback))
"""


def test_zt07_flags_archive_scan_from_windowed_entrypoint(tmp_path):
    # note: NO jax import in the fixture — the windowed fence is
    # ungated, because the windowed routing layer is pure host code
    assert_rule_owned(tmp_path, ZT07_WINDOWED_POSITIVE, "ZT07")


def test_zt07_archive_scan_on_trace_retrieval_path_is_clean(tmp_path):
    # the scanners themselves ARE the getTraces path — only windowed
    # entrypoints reaching them is the violation
    result = lint(
        tmp_path,
        """
        class Store:
            def get_traces_query(self, request):
                return self._disk_query(request)

            def _disk_query(self, request):
                return self.candidate_trace_ids(request)

            def candidate_trace_ids(self, request):
                return []
        """,
    )
    assert rules(result) == []


def test_zt07_windowed_segment_merge_is_clean(tmp_path):
    # the shipped shape: windowed entrypoints merge covering time-tier
    # segments through the mirror-keyed window read
    result = lint(
        tmp_path,
        """
        class Store:
            def latency_quantiles(self, qs, end_ts=None, lookback=None):
                lo_ep, hi_ep = self._tt_epochs(end_ts, lookback)
                return self._tt_window(lo_ep, hi_ep)

            def _tt_epochs(self, end_ts, lookback):
                return 0, 1

            def _tt_window(self, lo_ep, hi_ep):
                return self.timetier.window(self.agg, lo_ep, hi_ep)
        """,
    )
    assert rules(result) == []


# -- pragmas and ZT00 ----------------------------------------------------


def test_pragma_with_reason_suppresses(tmp_path):
    result = lint(
        tmp_path,
        """
        import jax

        def serve(agg):
            agg.block_until_ready()  # zt-lint: disable=ZT06 — drain contract
        """,
    )
    assert rules(result) == []
    assert [f.rule for f in result.suppressed] == ["ZT06"]


def test_own_line_pragma_governs_next_code_line(tmp_path):
    result = lint(
        tmp_path,
        """
        import jax

        def serve(agg):
            # zt-lint: disable=ZT06 — justification too long for the line
            # (continuation comments are skipped over)
            agg.block_until_ready()
        """,
    )
    assert rules(result) == []
    assert [f.rule for f in result.suppressed] == ["ZT06"]


def test_def_scoped_pragma_covers_whole_body(tmp_path):
    result = lint(
        tmp_path,
        ZT04_POSITIVE.replace(
            "def reset(self):",
            "def reset(self):  # zt-lint: disable=ZT04 — callers hold _lock",
        ),
    )
    assert rules(result) == []
    assert [f.rule for f in result.suppressed] == ["ZT04"]


def test_reasonless_pragma_is_its_own_finding(tmp_path):
    result = lint(
        tmp_path,
        """
        import jax

        def serve(agg):
            agg.block_until_ready()  # zt-lint: disable=ZT06
        """,
    )
    assert rules(result) == ["ZT00"]  # ZT06 suppressed, hygiene flagged


def test_zt00_cannot_be_ignored(tmp_path):
    source = """
        import jax

        def serve(agg):
            agg.block_until_ready()  # zt-lint: disable=ZT06
    """
    assert rules(lint(tmp_path, source, ignore={"ZT00"})) == ["ZT00"]
    assert rules(lint(tmp_path, source, select={"ZT01"})) == ["ZT00"]


def test_pragma_does_not_suppress_other_rules(tmp_path):
    result = lint(
        tmp_path,
        """
        import jax

        def serve(agg):
            agg.block_until_ready()  # zt-lint: disable=ZT01 — wrong rule
        """,
    )
    assert rules(result) == ["ZT06"]


# -- baseline + CLI ------------------------------------------------------


def test_baseline_round_trip(tmp_path, capsys, monkeypatch):
    # the CLI resolves paths relative to cwd; pytest's tmp dir name
    # contains "test_", which would trip ZT06's test-path exemption if
    # the file fell back to its absolute path
    monkeypatch.chdir(tmp_path)
    p = tmp_path / "legacy.py"
    p.write_text(textwrap.dedent(ZT06_POSITIVE))
    baseline = tmp_path / "baseline.json"
    assert lint_main([str(p), "--write-baseline", str(baseline)]) == 0
    # the accepted finding no longer fails the run...
    assert lint_main([str(p), "--baseline", str(baseline)]) == 0
    # ...but a NEW violation (distinct source line — fingerprints hash
    # the stripped line, not the line number) still does
    p.write_text(
        textwrap.dedent(ZT06_POSITIVE)
        + "\n\ndef serve2(agg2):\n    agg2.block_until_ready()\n"
    )
    assert lint_main([str(p), "--baseline", str(baseline)]) == 1


def test_cli_exit_codes_and_rule_listing(tmp_path, capsys, monkeypatch):
    monkeypatch.chdir(tmp_path)
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert lint_main([str(clean)]) == 0
    dirty = tmp_path / "dirty.py"
    dirty.write_text(textwrap.dedent(ZT06_POSITIVE))
    assert lint_main([str(dirty)]) == 1
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in all_checkers():
        assert rule in out


def test_unparsable_file_is_an_error_not_a_crash(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    result = run_paths([str(bad)], root=tmp_path)
    assert result.exit_code == 1
    assert result.errors and "bad.py" in result.errors[0]


# -- ZT08: obs stage discipline -----------------------------------------


ZT08_JIT_POSITIVE = """
    import jax
    from zipkin_tpu import obs

    @jax.jit
    def step(x):
        obs.record("pack", 0.001)
        return x
"""


def test_zt08_flags_record_inside_jitted_def(tmp_path):
    assert_rule_owned(tmp_path, ZT08_JIT_POSITIVE, "ZT08")


def test_zt08_flags_record_reachable_from_traced_code(tmp_path):
    assert_rule_owned(
        tmp_path,
        """
        import jax
        from zipkin_tpu import obs

        def _note(x):
            obs.record("pack", 0.001)
            return x

        def kernel(x):
            return _note(x)

        run = jax.jit(kernel)
        """,
        "ZT08",
    )


def test_zt08_flags_unknown_stage_name(tmp_path):
    assert_rule_owned(
        tmp_path,
        """
        from zipkin_tpu import obs

        def serve():
            obs.record("warp_drive", 0.1)
        """,
        "ZT08",
    )


def test_zt08_flags_non_literal_stage(tmp_path):
    assert_rule_owned(
        tmp_path,
        """
        from zipkin_tpu import obs

        def serve(name):
            obs.record(name, 0.1)
        """,
        "ZT08",
    )


def test_zt08_recognizes_bare_record_import(tmp_path):
    assert_rule_owned(
        tmp_path,
        """
        from zipkin_tpu.obs import record

        def serve():
            record("nope", 0.1)
        """,
        "ZT08",
    )


def test_zt08_clean_host_side_catalogue_record(tmp_path):
    result = lint(
        tmp_path,
        """
        import jax
        from zipkin_tpu import obs
        from zipkin_tpu.obs import RECORDER

        def serve(x):
            obs.record("query_fresh", 0.1)
            RECORDER.record("wal_append", 0.05)
            return x

        @jax.jit
        def kernel(x):
            return x + 1
        """,
    )
    assert rules(result) == []


def test_zt08_flags_record_relayed_unknown_stage(tmp_path):
    # the no-selfspan relay variant obeys the same closed catalogue
    assert_rule_owned(
        tmp_path,
        """
        from zipkin_tpu import obs

        def dispatch():
            obs.record_relayed("warp_drive", 0.1)
        """,
        "ZT08",
    )


def test_zt08_flags_record_relayed_inside_jitted_def(tmp_path):
    assert_rule_owned(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs import record_relayed

        @jax.jit
        def kernel(x):
            record_relayed("mp_parse", 0.1)
            return x
        """,
        "ZT08",
    )


def test_zt08_flags_windows_hook_reachable_from_traced_code(tmp_path):
    # windows ring ticks are host-side lock-holding mutation
    assert_rule_owned(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs.windows import WINDOWS

        def _note(x):
            WINDOWS.tick_if_due()
            return x

        def kernel(x):
            return _note(x)

        run = jax.jit(kernel)
        """,
        "ZT08",
    )


def test_zt08_flags_observatory_hook_inside_jitted_def(tmp_path):
    assert_rule_owned(
        tmp_path,
        """
        import jax
        from zipkin_tpu import obs
        from zipkin_tpu.obs.device import OBSERVATORY

        @jax.jit
        def kernel(x):
            OBSERVATORY.observe(kernel, (x,), {}, False)
            return x
        """,
        "ZT08",
    )


def test_zt08_clean_host_side_windows_device_hooks(tmp_path):
    # wrapping programs / ticking windows from plain host code is the
    # intended use — only traced reachability is the violation
    result = lint(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs.device import OBSERVATORY
        from zipkin_tpu.obs.windows import WINDOWS

        @jax.jit
        def kernel(x):
            return x + 1

        def build():
            fn = OBSERVATORY.wrap("spmd_step", kernel)
            WINDOWS.tick_if_due()
            return fn
        """,
    )
    assert rules(result) == []


def test_zt08_flags_querytrace_stamp_inside_jitted_def(tmp_path):
    # query-observatory stamps are thread-local host mutation: a traced
    # region would bake one trace-time interval forever
    assert_rule_owned(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs import querytrace

        @jax.jit
        def kernel(x):
            querytrace.stamp_active(querytrace.QSEG_UNPACK, 0, 1)
            return x
        """,
        "ZT08",
    )


def test_zt08_flags_querytrace_begin_reachable_from_traced_code(tmp_path):
    assert_rule_owned(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs import querytrace

        QUERYTRACE = querytrace.QueryObservatory()

        def _arm(x):
            QUERYTRACE.begin("dependencies")
            return x

        def kernel(x):
            return _arm(x)

        run = jax.jit(kernel)
        """,
        "ZT08",
    )


def test_zt08_clean_host_side_querytrace_hooks(tmp_path):
    # arming/stitching/lock-wrapping from plain host code is the
    # intended use — only traced reachability is the violation
    result = lint(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs import querytrace

        QUERYTRACE = querytrace.QueryObservatory()

        @jax.jit
        def kernel(x):
            return x + 1

        def read():
            tr = QUERYTRACE.begin("quantiles")
            try:
                return kernel(1)
            finally:
                QUERYTRACE.finish(tr)
                QUERYTRACE.stitch()
        """,
    )
    assert rules(result) == []


def test_zt08_ignores_unrelated_record_methods(tmp_path):
    # a .record attribute on some other object is not the obs recorder
    result = lint(
        tmp_path,
        """
        import zipkin_tpu

        def serve(vcr):
            vcr.record("anything", 0.1)
        """,
    )
    assert rules(result) == []


def test_zt08_flags_shadow_offer_inside_jitted_def(tmp_path):
    # accuracy-shadow taps hold a host lock and touch numpy: never from
    # traced code
    assert_rule_owned(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs.shadow import SHADOW

        @jax.jit
        def kernel(cols):
            SHADOW.offer_cols(cols)
            return cols
        """,
        "ZT08",
    )


def test_zt08_flags_shadow_drain_reachable_from_traced_code(tmp_path):
    assert_rule_owned(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs.shadow import drain

        def _fold(x):
            drain()
            return x

        def kernel(x):
            return _fold(x)

        run = jax.jit(kernel)
        """,
        "ZT08",
    )


def test_zt08_flags_accuracy_rollup_inside_shard_map(tmp_path):
    # a rollup pulls device reads + replays the linker oracle: host only
    assert_rule_owned(
        tmp_path,
        """
        import jax
        from jax.experimental.shard_map import shard_map
        from zipkin_tpu.obs.accuracy import ACCURACY

        def step(x):
            ACCURACY.maybe_rollup()
            return x

        run = shard_map(step, mesh=None, in_specs=None, out_specs=None)
        """,
        "ZT08",
    )


def test_zt08_clean_host_side_shadow_accuracy_hooks(tmp_path):
    # offering lanes / draining / rolling up from plain host code is the
    # intended use — only traced reachability is the violation
    result = lint(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs.shadow import SHADOW
        from zipkin_tpu.obs.accuracy import ACCURACY

        @jax.jit
        def kernel(x):
            return x + 1

        def dispatch(cols):
            SHADOW.offer_cols(cols)
            SHADOW.drain()
            ACCURACY.maybe_rollup()
            return kernel(cols)
        """,
    )
    assert rules(result) == []


def test_zt08_ignores_shadow_named_attribute_elsewhere(tmp_path):
    # self.shadow.offer_cols on an arbitrary object is not the module
    # hook — only the SHADOW/ACCURACY roots are recognized
    result = lint(
        tmp_path,
        """
        import jax

        @jax.jit
        def kernel(self, x):
            self.shadow.offer_cols(x)
            return x
        """,
    )
    assert rules(result) == []


def test_zt08_flags_critpath_stamp_inside_jitted_def(tmp_path):
    # interval-ledger writes are seqlocked shm mutation + perf_counter
    # reads: a traced region would stamp one trace-time interval forever
    assert_rule_owned(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs import critpath

        @jax.jit
        def kernel(x):
            critpath.stamp_active(critpath.SEG_DEVICE_FEED, 0, 1)
            return x
        """,
        "ZT08",
    )


def test_zt08_flags_critpath_stitch_reachable_from_traced_code(tmp_path):
    # the stitcher folds slots under a lock and mutates aggregate state
    assert_rule_owned(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs.critpath import stitch

        def _fold(x):
            stitch()
            return x

        def kernel(x):
            return _fold(x)

        run = jax.jit(kernel)
        """,
        "ZT08",
    )


def test_zt08_clean_host_side_critpath_hooks(tmp_path):
    # stamping from the dispatcher / stitching on the ticker is the
    # intended use — only traced reachability is the violation
    result = lint(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs import critpath

        @jax.jit
        def kernel(x):
            return x + 1

        def dispatch(ledger, slot, pid):
            critpath.set_active(ledger, slot, pid)
            critpath.stamp_active(critpath.SEG_WAL_APPEND, 0, 1)
            critpath.clear_active()
            ledger.ack(slot, pid)
            return kernel(slot)
        """,
    )
    assert rules(result) == []


# -- ZT09: dispatch-critical loops ---------------------------------------


ZT09_POSITIVE = """
    def _handle(self, msg):  # zt-dispatch-critical: single dispatch core
        for row in msg:
            self.apply(row)
"""


def test_zt09_flags_loop_in_marked_function(tmp_path):
    assert_rule_owned(tmp_path, ZT09_POSITIVE, "ZT09")


def test_zt09_flags_comprehension_and_multiline_header(tmp_path):
    # the marker may trail the closing paren of a multi-line signature
    # (the columnar.remap_fused shape); comprehensions count as loops
    result = lint(
        tmp_path,
        """
        def remap(
            fused, svc_map
        ):  # zt-dispatch-critical: per-span id remap on the dispatch core
            return [svc_map[s] for s in fused]
        """,
    )
    assert rules(result) == ["ZT09"]


def test_zt09_ignores_unmarked_functions(tmp_path):
    result = lint(
        tmp_path,
        """
        def worker_parse(payload):
            return [s for s in payload]

        def also_loops(rows):
            for r in rows:
                yield r
        """,
    )
    assert rules(result) == []


def test_zt09_pragma_on_enclosing_statement_suppresses(tmp_path):
    # comprehension findings anchor at the enclosing STATEMENT line, so
    # a justified pragma above the statement suppresses (the mp_ingest
    # vocab-journal shape: trip count is per new string, not per span)
    result = lint(
        tmp_path,
        """
        def _handle(self, new):  # zt-dispatch-critical: dispatch core
            # zt-lint: disable=ZT09 — per NEWLY INTERNED string, bounded
            # by vocab capacity, not per span
            self.map = extend(
                self.map, [self.intern(s) for s in new]
            )
        """,
    )
    assert rules(result) == []
    assert len(result.suppressed) == 1


def test_zt09_marker_without_reason_is_flagged(tmp_path):
    assert_rule_owned(
        tmp_path,
        """
        def _flush(self):  # zt-dispatch-critical
            pass
        """,
        "ZT09",
    )


def test_zt09_critpath_ledger_writer_shape(tmp_path):
    # the interval-ledger writers are marked zt-dispatch-critical and
    # must stay loop-free: a handful of word stores per stamp. The
    # marked-with-loop variant trips; the straight-line variant (the
    # shipped critpath.stamp shape) lints clean.
    assert_rule_owned(
        tmp_path,
        """
        def stamp(self, slot, code, t0, t1):  # zt-dispatch-critical: ledger write
            for w in (code, t0, t1):
                self.a[slot] = w
        """,
        "ZT09",
    )
    result = lint(
        tmp_path,
        """
        def stamp(self, slot, code, t0, t1):  # zt-dispatch-critical: seqlocked word stores, no loops
            self.a[slot] += 1
            self.a[slot + 1] = code
            self.a[slot + 2] = t0
            self.a[slot + 3] = t1
            self.a[slot] += 1
        """,
    )
    assert rules(result) == []


# -- ZT10: mirror-served reads stay off the aggregator lock -------------


ZT10_POSITIVE = """
    class Store:
        def serve_overview(self):  # zt-mirror-served: lock-free snapshot read
            with self.agg.lock:
                return dict(self._snap.values)
"""


def test_zt10_flags_lock_hold_in_marked_function(tmp_path):
    assert_rule_owned(tmp_path, ZT10_POSITIVE, "ZT10")


def test_zt10_flags_explicit_acquire_and_lock_takers(tmp_path):
    # both the raw .lock.acquire() spelling and a call into a known
    # lock-taking helper (_cached_read re-enters the aggregator lock)
    result = lint(
        tmp_path,
        """
        class Store:
            def serve(self, key):  # zt-mirror-served: seqlock snapshot copy
                self.agg.lock.acquire()
                try:
                    return self._cached_read(key, lambda: None)
                finally:
                    self.agg.lock.release()
        """,
    )
    assert rules(result) == ["ZT10", "ZT10"]


def test_zt10_follows_local_helper_calls(tmp_path):
    # ZT07-style reachability: the lock hold hides one hop down in a
    # same-module helper — the historical regression shape ("just call
    # the existing read method from the serve path")
    assert_rule_owned(
        tmp_path,
        """
        class Store:
            def serve(self, key):  # zt-mirror-served: published epoch only
                return self._probe(key)

            def _probe(self, key):
                with self.agg.lock:
                    return self._snap.get(key)
        """,
        "ZT10",
    )


def test_zt10_ignores_unmarked_and_private_locks(tmp_path):
    # unmarked functions may lock freely (that IS the fresh path), and
    # a marked function's private coordination locks (_demand_lock,
    # _lock, ...) are legal — only the bare .lock spelling is the
    # aggregator lock by convention
    result = lint(
        tmp_path,
        """
        class Store:
            def fresh_read(self, key):
                with self.agg.lock:
                    return self.agg.quantiles((0.5,))

            def register(self, key, fn):  # zt-mirror-served: demand registry only
                with self._demand_lock:
                    self._demand[key] = fn
        """,
    )
    assert rules(result) == []


def test_zt10_flags_tt_read_from_mirror_served(tmp_path):
    # ISSUE 15: the unsealed-bucket device pull (tt_read) flushes then
    # reads under the aggregator lock — a windowed serve must come off
    # the published ttq: WindowAnswer, not recompute per request
    assert_rule_owned(
        tmp_path,
        """
        class Store:
            def serve_window(self, lo_ep, hi_ep):  # zt-mirror-served: published ttq: answer only
                return self._merge(lo_ep, hi_ep)

            def _merge(self, lo_ep, hi_ep):
                return self.agg.tt_read(lo_ep, hi_ep)
        """,
        "ZT10",
    )


def test_zt10_marker_without_reason_is_flagged(tmp_path):
    assert_rule_owned(
        tmp_path,
        """
        def serve(key):  # zt-mirror-served
            return key
        """,
        "ZT10",
    )


def test_zt10_pragma_with_reason_suppresses(tmp_path):
    # the standard escape hatch still applies — a justified pragma on
    # the offending line keeps the audit trail without failing the gate
    result = lint(
        tmp_path,
        """
        class Store:
            def serve(self, key):  # zt-mirror-served: snapshot read
                # zt-lint: disable=ZT10 — boot-only fallback before the
                # first epoch is published; never runs post-boot
                with self.agg.lock:
                    return self.agg.cardinalities()
        """,
    )
    assert rules(result) == []
    assert len(result.suppressed) >= 1


def test_zt10_shipped_serve_shape_is_clean(tmp_path):
    # the shipped tpu/mirror.py serve shape: seqlock generation spin,
    # one reference copy, demand-refresh via GIL-atomic item write
    result = lint(
        tmp_path,
        """
        class ReadMirror:
            def serve(self, key, bound_ms):  # zt-mirror-served: seqlock spin + reference copy
                snap = self.snapshot()
                if snap is None:
                    return None
                ent = self._demand.get(key)
                if ent is not None:
                    ent[1] = self.publishes
                return snap.values.get(key)

            def snapshot(self):  # zt-mirror-served: torn-generation retry loop
                for _ in range(1000):
                    g0 = self.gen
                    if g0 & 1:
                        continue
                    snap = self._snap
                    if self.gen == g0:
                        return snap
                return self._snap
        """,
    )
    assert rules(result) == []


def test_zt08_flags_set_active_group_inside_jitted_def(tmp_path):
    # the coalesced-flush hook arms a thread-local with a slot GROUP —
    # host-only mutation, same fence as set_active (ISSUE 16)
    assert_rule_owned(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs import critpath

        @jax.jit
        def kernel(x):
            critpath.set_active_group(None, [(0, 1)])
            return x
        """,
        "ZT08",
    )


def test_zt08_clean_host_side_group_hooks(tmp_path):
    # arming the group on the dispatcher before a coalesced device step
    # is the intended use (mp_ingest._flush_group)
    result = lint(
        tmp_path,
        """
        import jax
        from zipkin_tpu.obs import critpath

        @jax.jit
        def kernel(x):
            return x + 1

        def flush_group(ledger, pairs):
            critpath.set_active_group(ledger, pairs)
            critpath.stamp_active(critpath.SEG_COALESCE, 0, 1)
            critpath.clear_active()
            return kernel(len(pairs))
        """,
    )
    assert rules(result) == []


def test_zt09_coalesce_gather_shape(tmp_path):
    # the ring-drain/coalesce functions (concat_remap, _flush_group,
    # _pump) are zt-dispatch-critical: their loops are per CHUNK of a
    # bounded coalesced group — pragma'd they lint clean, bare they trip
    assert_rule_owned(
        tmp_path,
        """
        def concat_remap(parts, out):  # zt-dispatch-critical: the coalesce gather
            off = 0
            for fused, svc_map, key_map in parts:
                out[off] = fused
                off += 1
            return off
        """,
        "ZT09",
    )
    result = lint(
        tmp_path,
        """
        def concat_remap(parts, out):  # zt-dispatch-critical: the coalesce gather
            off = 0
            # zt-lint: disable=ZT09 — bounded by coalesce_max CHUNKS;
            # each iteration is whole-image vectorized
            for fused, svc_map, key_map in parts:
                out[off] = fused
                off += 1
            return off
        """,
    )
    assert rules(result) == []
    assert len(result.suppressed) == 1


# -- multi-file helper (the interprocedural rules need >1 module) --------


def lint_tree(tmp_path, files, **kwargs):
    """Write a dict of {rel path: source} and lint the whole tree —
    the shape the whole-program rules (ZT11–ZT13, cross-module ZT07/
    ZT08) are exercised in."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return run_paths([str(tmp_path)], root=tmp_path, **kwargs)


# -- ZT11: shm seqlock discipline ----------------------------------------


ZT11_TORN = """
    import numpy as np

    _S_GEN = 0
    _S_TS0 = 1

    class Ring:
        def publish(self, hdr, ts):
            hdr[_S_TS0] = ts
"""


def test_zt11_flags_unstamped_protected_write(tmp_path):
    # the injected torn-write shape: a protected slot-header word
    # stored with NO generation stamp anywhere in the writer
    result = lint(tmp_path, ZT11_TORN, name="zipkin_tpu/tpu/ring.py")
    assert rules(result) == ["ZT11"]
    assert_rule_owned(
        tmp_path, ZT11_TORN, "ZT11", name="zipkin_tpu/tpu/ring.py"
    )


def test_zt11_clean_bracketed_write(tmp_path):
    result = lint(
        tmp_path,
        """
        import numpy as np

        _S_GEN = 0
        _S_TS0 = 1

        class Ring:
            def publish(self, hdr, ts):
                hdr[_S_GEN] += 1
                hdr[_S_TS0] = ts
                hdr[_S_GEN] += 1
        """,
        name="zipkin_tpu/tpu/ring.py",
    )
    assert rules(result) == []


def test_zt11_flags_write_outside_bracket(tmp_path):
    result = lint(
        tmp_path,
        """
        import numpy as np

        _S_GEN = 0
        _S_TS0 = 1
        _S_DUR = 2

        class Ring:
            def publish(self, hdr, ts, dur):
                hdr[_S_GEN] += 1
                hdr[_S_TS0] = ts
                hdr[_S_GEN] += 1
                hdr[_S_DUR] = dur
        """,
        name="zipkin_tpu/tpu/ring.py",
    )
    assert rules(result) == ["ZT11"]
    assert "outside" in result.findings[0].message


def test_zt11_flags_single_gen_read_reader(tmp_path):
    # a gen-aware reader that reads the generation ONCE copied a
    # possibly-torn payload and never noticed
    result = lint(
        tmp_path,
        """
        import numpy as np

        _S_GEN = 0
        _S_TS0 = 1

        class Ring:
            def peek(self, hdr):
                g = hdr[_S_GEN]
                return hdr[_S_TS0]
        """,
        name="zipkin_tpu/tpu/ring.py",
    )
    assert rules(result) == ["ZT11"]


def test_zt11_clean_retry_reader_and_other_modules(tmp_path):
    # the retry idiom (read gen, copy, re-read gen) is the sanctioned
    # reader; and the same torn write OUTSIDE a registered region is
    # not ZT11's business
    result = lint(
        tmp_path,
        """
        import numpy as np

        _S_GEN = 0
        _S_TS0 = 1

        class Ring:
            def peek(self, hdr):
                g0 = hdr[_S_GEN]
                v = hdr[_S_TS0]
                g1 = hdr[_S_GEN]
                return v if g0 == g1 else None
        """,
        name="zipkin_tpu/tpu/ring.py",
    )
    assert rules(result) == []
    assert rules(lint(tmp_path, ZT11_TORN, name="other/mod.py")) == []


def test_zt11_cross_function_bracket_via_callers(tmp_path):
    # the ring's try_claim/publish split: the writer stamps ZERO times
    # but every in-graph caller brackets the call — the graph proof
    # replaces a pragma
    result = lint(
        tmp_path,
        """
        import numpy as np

        _S_GEN = 0
        _S_TS0 = 1

        class Ring:
            def _fill(self, hdr, ts):
                hdr[_S_TS0] = ts

            def publish(self, hdr, ts):
                hdr[_S_GEN] += 1
                self._fill(hdr, ts)
                hdr[_S_GEN] += 1
        """,
        name="zipkin_tpu/tpu/ring.py",
    )
    assert rules(result) == []


# -- ZT12: durability commit chokepoints ---------------------------------


ZT12_BARE_RENAME = """
    import os

    def commit(path, blob):
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
"""


def test_zt12_flags_fsyncless_rename(tmp_path):
    # the injected shape: tmp-write + rename with no fsync on either
    # side — exactly ZT12's finding (pre- and post-rename halves)
    result = lint(tmp_path, ZT12_BARE_RENAME, name="zipkin_tpu/tpu/wal.py")
    assert set(rules(result)) == {"ZT12"}
    assert_rule_owned(
        tmp_path, ZT12_BARE_RENAME, "ZT12", name="zipkin_tpu/tpu/wal.py"
    )


def test_zt12_clean_full_commit_chain(tmp_path):
    result = lint(
        tmp_path,
        """
        import os

        def _fsync_dir(d):
            fd = os.open(d, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)

        def commit(path, blob):
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(blob)
                os.fsync(f.fileno())
            os.replace(tmp, path)
            _fsync_dir(".")
        """,
        name="zipkin_tpu/tpu/snapshot.py",
    )
    assert rules(result) == []


def test_zt12_caller_fsync_split_is_clean(tmp_path):
    # the Wal._file_for/append split: the opener never fsyncs, but
    # every in-graph caller does — the graph accepts the split
    result = lint(
        tmp_path,
        """
        import os

        def _file_for(path):
            return open(path, "ab")

        def append(path, data):
            fh = _file_for(path)
            fh.write(data)
            os.fsync(fh.fileno())
        """,
        name="zipkin_tpu/tpu/wal.py",
    )
    assert rules(result) == []


def test_zt12_flags_open_when_a_caller_skips_fsync(tmp_path):
    result = lint(
        tmp_path,
        """
        import os

        def _file_for(path):
            return open(path, "ab")

        def append(path, data):
            _file_for(path).write(data)
        """,
        name="zipkin_tpu/tpu/wal.py",
    )
    assert rules(result) == ["ZT12"]


def test_zt12_scoped_to_durability_modules(tmp_path):
    # the same bare rename outside wal/snapshot/timetier/archive is
    # not a restore-readable file — other modules stay out of scope
    assert rules(
        lint(tmp_path, ZT12_BARE_RENAME, name="zipkin_tpu/server/app.py")
    ) == []


# -- ZT13: reader isolation at full cross-module depth -------------------


ZT13_TWO_DEEP = {
    "app/serve.py": """
        from app import mid

        def snapshot():  # zt-mirror-served: epoch-pinned read surface
            return mid.resolve()
    """,
    "app/mid.py": """
        def resolve():
            return _read()

        def _read():
            with AGG.lock:
                return 1
    """,
}


def test_zt13_flags_cross_module_acquire_two_calls_deep(tmp_path):
    # the injected shape: reader entrypoint → helper module → second
    # helper that takes the aggregator lock — exactly ZT13's finding
    result = lint_tree(tmp_path, ZT13_TWO_DEEP)
    assert rules(result) == ["ZT13"]
    assert "snapshot" in result.findings[0].message
    assert "via" in result.findings[0].message
    clean = lint_tree(tmp_path, ZT13_TWO_DEEP, ignore={"ZT13"})
    assert rules(clean) == []


def test_zt13_same_module_sink_is_zt10s_jurisdiction(tmp_path):
    # one bug, one rule: a lock acquire in the ROOT's own module is
    # ZT10's finding and ZT13 stays silent
    result = lint_tree(
        tmp_path,
        {
            "app/serve.py": """
                def snapshot():  # zt-mirror-served: epoch-pinned read
                    return _read()

                def _read():
                    with AGG.lock:
                        return 1
            """,
        },
    )
    assert rules(result) == ["ZT10"]


def test_zt13_reader_process_marker_roots_the_walk(tmp_path):
    files = dict(ZT13_TWO_DEEP)
    files["app/serve.py"] = """
        from app import mid

        def reader_main():  # zt-reader-process: mmap-only query worker (ROADMAP item 3)
            return mid.resolve()
    """
    result = lint_tree(tmp_path, files)
    assert rules(result) == ["ZT13"]
    assert "reader_main" in result.findings[0].message


def test_zt13_reader_marker_without_reason_is_flagged(tmp_path):
    result = lint_tree(
        tmp_path,
        {
            "app/serve.py": """
                def reader_main():  # zt-reader-process
                    return 1
            """,
        },
    )
    assert rules(result) == ["ZT13"]
    assert "reason" in result.findings[0].message


def test_zt13_flags_renamed_instrumented_rlock_attr(tmp_path):
    # renaming the aggregator lock does not launder the acquire: any
    # attr assigned from InstrumentedRLock anywhere in the program is
    # a ZT13 sink
    result = lint_tree(
        tmp_path,
        {
            "app/agg.py": """
                from zipkin_tpu.obs import querytrace

                class Agg:
                    def __init__(self):
                        self._mu = querytrace.InstrumentedRLock(name="agg")
            """,
            "app/serve.py": """
                from app import mid

                def snapshot():  # zt-mirror-served: epoch-pinned read
                    return mid.resolve()
            """,
            "app/mid.py": """
                def resolve():
                    AGG._mu.acquire()
                    return 1
            """,
        },
    )
    assert rules(result) == ["ZT13"]


def test_zt13_clean_lock_free_serve_chain(tmp_path):
    result = lint_tree(
        tmp_path,
        {
            "app/serve.py": """
                from app import mid

                def snapshot():  # zt-mirror-served: epoch-pinned read
                    return mid.resolve()
            """,
            "app/mid.py": """
                def resolve():
                    return dict(EPOCH.view)
            """,
        },
    )
    assert rules(result) == []


# ISSUE 19: the serving-tier shape — a reader-process entrypoint that
# attaches the shm segment and serves through a view module. The whole
# point of the process split is that NO path from the reader reaches
# the aggregator lock; ZT13 is the static proof.

ZT13_READER_ATTACH = {
    "serving/reader.py": """
        from serving import segment, view

        def run_reader(params, idx, port):  # zt-reader-process: attaches the segment and serves
            seg = segment.attach(params)
            return view.serve(seg)
    """,
    "serving/segment.py": """
        def attach(params):
            return params
    """,
    "serving/view.py": """
        def serve(seg):
            return _rows(seg)

        def _rows(seg):
            return dict(seg.payload)
    """,
}


def test_zt13_flags_lock_reached_through_shm_attach_path(tmp_path):
    # the regression the marker exists to catch: a "stateless" reader
    # whose view helper quietly reaches back into the ingest process's
    # aggregator lock two modules below the attach call
    files = dict(ZT13_READER_ATTACH)
    files["serving/view.py"] = """
        def serve(seg):
            return _rows(seg)

        def _rows(seg):
            with seg.store.agg.lock:
                return dict(seg.payload)
    """
    result = lint_tree(tmp_path, files)
    assert rules(result) == ["ZT13"]
    assert "run_reader" in result.findings[0].message
    assert "via" in result.findings[0].message


def test_zt13_clean_reader_attach_chain_passes(tmp_path):
    # the shipped shape: attach → view → shaped rows, no lock anywhere
    # on any path from the marked entrypoint
    result = lint_tree(tmp_path, ZT13_READER_ATTACH)
    assert rules(result) == []


# -- the PR 15 collision class stays dead (graph-backed resolution) ------


def test_same_named_nested_locals_do_not_collide(tmp_path):
    # the exact PR 15 shape: _disk_query's nested `fetch` vs another
    # function's nested `fetch` that takes the lock — the name-keyed
    # walk conflated them (forcing a rename); lexical resolution keeps
    # each scope's `fetch` its own
    result = lint(
        tmp_path,
        """
        def serve():  # zt-mirror-served: epoch-pinned read
            def fetch(k):
                return k
            return fetch(1)

        def other():
            def fetch(k):
                with AGG.lock:
                    return k
            return fetch(1)
        """,
    )
    assert rules(result) == []


def test_same_named_methods_on_different_classes_do_not_collide(tmp_path):
    result = lint(
        tmp_path,
        """
        class Mirror:
            def serve(self):  # zt-mirror-served: epoch-pinned read
                return self.fetch(1)

            def fetch(self, k):
                return k

        class Agg:
            def fetch(self, k):
                with self.lock:
                    return k
        """,
    )
    assert rules(result) == []

# -- ZT14: tenant-admission coverage for ingest boundaries ---------------


ZT14_COVERED = {
    "app/http.py": """
        from app import coll

        def ingest(body):  # zt-ingest-boundary: HTTP spans POST
            return coll.accept(body)
    """,
    "app/coll.py": """
        def accept(body):
            # zt-tenant-admission: tenant budget before parse/dispatch
            return len(body)
    """,
}


def test_zt14_clean_when_boundary_reaches_chokepoint(tmp_path):
    result = lint_tree(tmp_path, ZT14_COVERED)
    assert rules(result) == []


def test_zt14_flags_boundary_that_bypasses_admission(tmp_path):
    # the quiet-bypass shape: a second transport hands bytes straight
    # to the fan-out tier without ever traversing admission
    files = dict(ZT14_COVERED)
    files["app/udp.py"] = """
        from app import fanout

        def ingest_udp(body):  # zt-ingest-boundary: UDP spans datagram
            return fanout.submit(body)
    """
    files["app/fanout.py"] = """
        def submit(body):
            return len(body)
    """
    result = lint_tree(tmp_path, files)
    assert rules(result) == ["ZT14"]
    assert "ingest_udp" in result.findings[0].message
    clean = lint_tree(tmp_path, files, ignore={"ZT14"})
    assert rules(clean) == []


def test_zt14_follows_to_thread_callable_reference(tmp_path):
    # the real boundary shape: the handler hops threads by REFERENCE
    # (asyncio.to_thread(self.collector.accept, ...)) — a Call-edge-only
    # walk would break the chain here and false-positive the boundary
    result = lint_tree(
        tmp_path,
        {
            "app/http.py": """
                import asyncio

                class Server:
                    async def ingest(self, body):  # zt-ingest-boundary: HTTP spans POST
                        await asyncio.to_thread(self.collector.accept, body)
            """,
            "app/coll.py": """
                class Collector:
                    def accept(self, body):
                        # zt-tenant-admission: tenant budget before dispatch
                        return len(body)
            """,
        },
    )
    assert rules(result) == []


def test_zt14_marker_without_reason_is_flagged(tmp_path):
    result = lint_tree(
        tmp_path,
        {
            "app/http.py": """
                def ingest(body):  # zt-ingest-boundary
                    return accept(body)

                def accept(body):
                    # zt-tenant-admission: tenant budget before dispatch
                    return len(body)
            """,
        },
    )
    assert rules(result) == ["ZT14"]
    assert "reason" in result.findings[0].message


def test_zt14_no_chokepoint_at_all_flags_every_boundary(tmp_path):
    result = lint_tree(
        tmp_path,
        {
            "app/http.py": """
                def ingest(body):  # zt-ingest-boundary: HTTP spans POST
                    return len(body)
            """,
        },
    )
    assert rules(result) == ["ZT14"]
    assert "no zt-tenant-admission chokepoint" in result.findings[0].message


def test_zt14_real_tree_boundaries_are_covered():
    # the live wiring, not a fixture: both wire entrypoints (HTTP
    # _ingest, gRPC report) must reach a marked admission chokepoint in
    # the repo's own call graph — this is the gate the satellite ships
    repo = Path(__file__).resolve().parents[1]
    result = run_paths([str(repo / "zipkin_tpu")], root=repo)
    assert "ZT14" not in rules(result)
