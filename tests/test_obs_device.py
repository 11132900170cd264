"""Device-program observatory (ISSUE 9): runtime recompile detection
via jit cache-size deltas, first-compile cost/memory analysis, and the
integration seam that wraps every spmd_* program at build time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.fixtures import lots_of_spans
from zipkin_tpu.obs.device import (
    OBSERVATORY,
    DeviceObservatory,
    hbm_stats,
)
from zipkin_tpu.tpu.state import AggConfig
from zipkin_tpu.tpu.store import TpuStorage


def toy_program():
    @jax.jit
    def double(x):
        return x * 2

    return double


# -- recompile detection -------------------------------------------------


def test_observatory_catches_induced_recompile():
    obs = DeviceObservatory(enabled=True, analysis=False)
    fn = obs.wrap("toy_double", toy_program())
    fn(jnp.zeros(4, jnp.float32))          # first compile
    fn(jnp.ones(4, jnp.float32))           # cache hit: same signature
    fn(jnp.zeros(8, jnp.float32))          # shape change -> recompile
    st = fn.program_stats
    assert st.calls == 3
    assert st.compiles == 2
    assert st.recompiles == 1
    assert st.compile_wall_s > 0
    assert st.max_call_s >= st.last_compile_s
    totals = obs.totals()
    assert totals["programs"] == 1
    assert totals["recompiles"] == 1


def test_steady_state_shows_zero_recompiles():
    obs = DeviceObservatory(enabled=True, analysis=False)
    fn = obs.wrap("toy_double", toy_program())
    fn(jnp.zeros(16, jnp.float32))  # warmup
    obs.reset_counters()
    for i in range(5):
        fn(jnp.full(16, i, jnp.float32))
    st = fn.program_stats
    assert st.calls == 5
    assert st.compiles == 0  # no shape churn after warmup
    assert obs.totals()["recompiles"] == 0


def test_analysis_captured_at_first_compile():
    obs = DeviceObservatory(enabled=True, analysis=True)
    fn = obs.wrap("toy_double", toy_program())
    fn(jnp.zeros(4, jnp.float32))
    st = fn.program_stats
    assert st.cost is not None
    assert st.cost["flops"] >= 0
    assert st.memory is not None
    assert st.memory["outputBytes"] > 0
    d = fn.program_stats.as_dict()
    assert "cost" in d and "memory" in d
    # analysis runs through the AOT path: no dispatch-cache pollution
    assert st.compiles == 1


def test_disabled_observatory_is_transparent():
    obs = DeviceObservatory(enabled=False)
    fn = obs.wrap("toy_double", toy_program())
    out = fn(jnp.zeros(4, jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), np.zeros(4))
    assert fn.program_stats.calls == 0
    assert obs.totals()["calls"] == 0


def test_wrapper_preserves_lower_and_wrapped():
    obs = DeviceObservatory(enabled=True, analysis=False)
    inner = toy_program()
    fn = obs.wrap("toy_double", inner)
    assert fn.__wrapped__ is inner
    # test_chip_compile AOT-compiles programs directly via .lower()
    compiled = fn.lower(jnp.zeros(4, jnp.float32)).compile()
    assert compiled is not None


def test_programs_merge_multiple_builds_of_one_name():
    obs = DeviceObservatory(enabled=True, analysis=False)
    a = obs.wrap("toy_double", toy_program())
    b = obs.wrap("toy_double", toy_program())
    a(jnp.zeros(4, jnp.float32))
    b(jnp.zeros(4, jnp.float32))
    merged = obs.programs()["toy_double"]
    assert merged["builds"] == 2
    assert merged["calls"] == 2
    assert merged["compiles"] == 2


# -- status / gauges -----------------------------------------------------


def test_status_shape_and_transfer_gauges():
    obs = DeviceObservatory(enabled=True, analysis=False)
    body = obs.status()
    assert body["enabled"] is True
    assert set(body["totals"]) == {"programs", "calls", "compiles",
                                   "recompiles", "analysisFailures"}
    assert isinstance(body["hbm"], dict)  # {} on CPU backends
    assert body["transfers"]["count"] >= 0
    assert body["transfers"]["bytes"] >= 0


def test_hbm_stats_empty_on_cpu():
    # CPU devices expose no memory_stats(); the gauge degrades to {}
    assert hbm_stats() == {}


# -- integration: the sharded build wraps every program ------------------


def test_store_programs_report_through_observatory():
    was = OBSERVATORY.enabled
    OBSERVATORY.set_enabled(True)
    try:
        store = TpuStorage(
            config=AggConfig(max_services=128, max_keys=512,
                             hll_precision=10, digest_centroids=32,
                             ring_capacity=1 << 14),
            pad_to_multiple=256,
        )
        spans = lots_of_spans(300, seed=7)
        store.accept(spans).execute()
        progs = OBSERVATORY.programs()
        spmd = {n for n in progs if n.startswith("spmd_")}
        assert "spmd_init" in spmd or "spmd_step" in spmd
        counters = store.ingest_counters()
        assert counters["deviceProgramCalls"] > 0
        assert counters["deviceCompiles"] > 0
        assert "deviceRecompiles" in counters
        assert counters["hostTransferBytes"] >= 0
    finally:
        OBSERVATORY.set_enabled(was)
