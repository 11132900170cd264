"""Tenant-isolated admission, budgets, and flood containment (ISSUE 18).

The property under test is CONTAINMENT: one flooding tenant is driven
to per-tenant B2/B3 admission while every other tenant — and the global
brownout ladder — stays at B0. The satellites ride along: bounded
per-tenant key spaces (admission LRU, retained-spans budget table,
tenant-prefixed mirror demand keys), tenant-scoped fault injection, the
per-tenant SLO grammar, and the ``{tenant=}`` prometheus families.
"""

from __future__ import annotations

import pytest

from zipkin_tpu import faults, native
from zipkin_tpu.runtime.overload import B0, B3, CLASS_ERROR, OverloadController
from zipkin_tpu.runtime.tenant import (
    CURRENT_TENANT,
    DEFAULT_TENANT,
    TenantAdmission,
    normalize_tenant,
    tenant_slug,
)
from zipkin_tpu.sampling.controller import TenantBudgetTable


class Clock:
    """Injectable monotonic clock: refill math becomes deterministic."""

    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    faults.disarm()


# -- identity ------------------------------------------------------------


class TestNormalizeTenant:
    def test_valid_ids_pass_through(self):
        for raw in ("acme", "team-a", "a.b_c-9", "X" * 64):
            assert normalize_tenant(raw) == raw

    def test_missing_and_hostile_collapse_to_default(self):
        hostile = [
            None, "", "   ", "a" * 65, 'ten"ant', "ten{ant}", "a/b",
            "a b", "t\nx", "café", "\x00",
        ]
        for raw in hostile:
            assert normalize_tenant(raw) == DEFAULT_TENANT

    def test_whitespace_stripped(self):
        assert normalize_tenant("  acme  ") == "acme"

    def test_slug_is_counter_safe(self):
        assert tenant_slug("team-a.eu") == "team_a_eu"
        assert tenant_slug("simple") == "simple"


# -- TenantAdmission ------------------------------------------------------


class TestTenantAdmission:
    def test_accounting_only_always_admits(self):
        clk = Clock()
        ta = TenantAdmission(bytes_per_s=0.0, clock=clk)
        for _ in range(50):
            ok, retry = ta.admit("a", 10_000)
            assert ok and retry == 0.0
        c = ta.counters()
        assert c["tenantOffered_a"] == 50
        assert c["tenantAdmitted_a"] == 50
        assert c["tenantShedTotal"] == 0

    def test_bucket_shed_with_per_tenant_retry(self):
        clk = Clock()
        ta = TenantAdmission(bytes_per_s=100.0, burst_s=1.0, clock=clk)
        ok, retry = ta.admit("a", 60)
        assert ok and retry == 0.0
        ok, retry = ta.admit("a", 60)  # 40 tokens left < 60
        assert not ok
        # deficit 20B at 100B/s, level-2 scaling: 0.2 * 3 = 0.6s
        assert retry == pytest.approx(0.6)
        assert ta.level_of("a") == 2
        # a fresh tenant's bucket is untouched by a's shed
        ok, _ = ta.admit("b", 60)
        assert ok and ta.level_of("b") == 0

    def test_error_class_lifeline_below_level3(self):
        clk = Clock()
        ta = TenantAdmission(bytes_per_s=100.0, burst_s=1.0, clock=clk)
        assert ta.admit("a", 100)[0]        # drain the bucket
        assert not ta.admit("a", 50)[0]     # bulk: shed, level 2
        assert ta.admit("a", 50, cls="error")[0]  # lifeline rides through

    def test_flood_escalates_to_essential_only(self):
        clk = Clock()
        ta = TenantAdmission(
            bytes_per_s=100.0, burst_s=1.0, flood_ratio=2.0, clock=clk,
        )
        # 16x the budget offered in one tick: pressure EMA (alpha .5)
        # lands at 8 >= 2*flood_ratio -> straight to level 3
        for _ in range(16):
            ta.admit("flood", 100)
        ta.tick(1.0)
        assert ta.level_of("flood") == 3
        # refill, then: bulk is still shed AT level 3, error admitted
        clk.advance(5.0)
        assert not ta.admit("flood", 10)[0]
        assert ta.admit("flood", 10, cls="error")[0]
        # a quiet tenant ticked alongside stays at level 0
        ta.admit("quiet", 10)
        assert ta.level_of("quiet") == 0

    def test_exit_hysteresis_steps_down_one_level_per_dwell(self):
        clk = Clock()
        ta = TenantAdmission(
            bytes_per_s=100.0, burst_s=1.0, flood_ratio=2.0,
            dwell_ticks=1, clock=clk,
        )
        for _ in range(16):
            ta.admit("f", 100)
        ta.tick(1.0)
        assert ta.level_of("f") == 3
        levels = []
        for _ in range(6):  # calm: no offers, bucket refills each tick
            clk.advance(2.0)
            ta.tick(1.0)
            levels.append(ta.level_of("f"))
        # pressure halves each calm tick (8,4,2,1,.5...): two sub-1.0
        # calm ticks walk 3 -> 2 -> 0, never a direct 3 -> 0 jump
        assert levels[-1] == 0
        assert 2 in levels
        assert ta.level_of("f") == 0

    def test_lru_bounded_and_default_never_evicted(self):
        clk = Clock()
        ta = TenantAdmission(bytes_per_s=0.0, max_tenants=4, clock=clk)
        ta.admit(DEFAULT_TENANT, 1)
        for i in range(10):
            ta.admit(f"hostile-{i}", 1)
        c = ta.counters()
        assert c["tenantTableSize"] <= 4
        assert c["tenantEvictions"] >= 7
        assert DEFAULT_TENANT in ta.status()["tenants"]

    def test_retry_for_unknown_tenant_is_floor(self):
        ta = TenantAdmission(bytes_per_s=100.0, clock=Clock())
        assert ta.retry_after_s("never-seen") == 0.05

    def test_retained_budget_gates_next_admission(self):
        clk = Clock()
        table = TenantBudgetTable(
            spans_per_s=10.0, burst_s=1.0, clock=clk,
        )
        ta = TenantAdmission(
            bytes_per_s=10_000.0, burst_s=1.0, clock=clk,
            retained_table=table,
        )
        assert ta.admit("a", 100)[0]
        ta.note_retained("a", 50)   # 5x the burst: bucket deep in debt
        assert table.over_budget("a")
        ok, retry = ta.admit("a", 100)  # plenty of byte-tokens left
        assert not ok and retry > 0.0
        assert ta.status()["tenants"]["a"]["retainedShed"] == 1
        assert ta.status()["tenants"]["a"]["retainedSpans"] == 50
        # error class still rides through retention debt
        assert ta.admit("a", 100, cls="error")[0]

    def test_status_shape_for_statusz(self):
        ta = TenantAdmission(bytes_per_s=100.0, clock=Clock())
        ta.admit("a", 10)
        st = ta.status()
        assert st["enabled"] and st["budgetBytesPerS"] == 100.0
        row = st["tenants"]["a"]
        for key in ("level", "pressure", "offered", "admitted", "shed",
                    "retainedSpans", "retainedShed", "tokens"):
            assert key in row


# -- TenantBudgetTable (sampling tier) -------------------------------------


class TestTenantBudgetTable:
    def test_disabled_tallies_without_enforcing(self):
        t = TenantBudgetTable(spans_per_s=0.0, clock=Clock())
        assert t.charge("a", 1_000_000)
        assert not t.over_budget("a")
        assert t.counters()["tenantRetainedTotal"] == 1_000_000

    def test_debt_then_refill(self):
        clk = Clock()
        t = TenantBudgetTable(spans_per_s=10.0, burst_s=1.0, clock=clk)
        assert t.charge("a", 5)          # 5 tokens left
        assert not t.charge("a", 10)     # -5: in debt
        assert t.over_budget("a")
        clk.advance(1.0)                 # +10 spans refill
        assert not t.over_budget("a")

    def test_over_budget_never_creates_rows(self):
        t = TenantBudgetTable(spans_per_s=10.0, clock=Clock())
        assert not t.over_budget("ghost")
        assert t.counters()["tenantBudgetTableSize"] == 0

    def test_lru_bounded_and_default_kept(self):
        t = TenantBudgetTable(
            spans_per_s=10.0, max_tenants=3, clock=Clock(),
        )
        t.charge("default", 1)
        for i in range(10):
            t.charge(f"hostile-{i}", 1)
        c = t.counters()
        assert c["tenantBudgetTableSize"] <= 3
        assert c["tenantBudgetEvictions"] >= 8
        assert t.retained("default") == 1


# -- containment through the overload controller ---------------------------


class TestOverloadContainment:
    def _controller(self, clk):
        ctl = OverloadController(clock=clk)
        ctl.tenant_admission = TenantAdmission(
            bytes_per_s=100.0, burst_s=1.0, clock=clk,
        )
        return ctl

    def test_flooding_tenant_sheds_alone_global_stays_b0(self):
        clk = Clock()
        ctl = self._controller(clk)
        payload = b"x" * 60
        v = ctl.admit(payload, tenant="B")
        assert v.admitted and v.scope == "none"
        v = ctl.admit(payload, tenant="B")  # B's bucket is dry
        assert not v.admitted
        assert v.scope == "tenant" and v.tenant == "B"
        assert v.retry_after_s > 0.0
        # A and C are untouched by B's shed
        for t in ("A", "C"):
            v = ctl.admit(payload, tenant=t)
            assert v.admitted and v.scope == "none"
        assert ctl.evaluate({"critpathQueueSaturation": 0.0}) == B0
        c = ctl.counters()
        assert c["overloadLevel"] == B0
        assert c["overloadShedTenant"] == 1
        assert c["tenantShed_B"] == 1
        assert c["tenantLevel_B"] == 2
        assert c["tenantLevel_A"] == 0 and c["tenantLevel_C"] == 0

    def test_global_shed_reports_global_scope(self):
        clk = Clock()
        ctl = OverloadController(clock=clk)  # no tenant table
        for _ in range(12):
            if ctl.evaluate({"critpathQueueSaturation": 0.9}) >= B3:
                break
        assert ctl.level == B3
        v = ctl.admit(b"x" * 10, tenant="A")
        assert not v.admitted and v.scope == "global"
        assert v.retry_after_s > 0.0
        # essential class survives global B3, attributed to its tenant
        v = ctl.admit(b"", tenant="A", value_class=CLASS_ERROR)
        assert v.admitted and v.tenant == "A"

    def test_missing_tenant_lands_on_default(self):
        ctl = self._controller(Clock())
        v = ctl.admit(b"x")
        assert v.tenant == DEFAULT_TENANT and v.admitted

    def test_retry_guidance_is_tenant_scoped(self):
        clk = Clock()
        ctl = self._controller(clk)
        ctl.admit(b"x" * 100, tenant="B")
        assert not ctl.admit(b"x" * 100, tenant="B").admitted
        # tenant route: B's own refill horizon, not the global backoff
        assert ctl.retry_after_s("B") > 0.0
        assert ctl.retry_after_s(None) >= 0.0


# -- tenant-scoped fault injection -----------------------------------------


class TestTenantScopedFaults:
    def test_only_the_named_tenant_fires(self):
        faults.arm_resource(
            "feed.latency", nth=1, count=1, latency_ms=1.0, tenant="B",
        )
        for _ in range(5):
            faults.resource_point("feed.latency", tenant="A")
        assert faults.is_resource_armed("feed.latency")  # A never consumed it
        faults.resource_point("feed.latency", tenant="B")
        assert not faults.is_resource_armed("feed.latency")

    def test_nonmatching_tenants_do_not_consume_nth(self):
        faults.arm_resource(
            "feed.latency", nth=2, count=1, latency_ms=1.0, tenant="B",
        )
        for _ in range(5):
            faults.resource_point("feed.latency", tenant="A")
        faults.resource_point("feed.latency", tenant="B")  # 1st traversal
        assert faults.is_resource_armed("feed.latency")
        faults.resource_point("feed.latency", tenant="B")  # 2nd: fires
        assert not faults.is_resource_armed("feed.latency")

    def test_contextvar_fallback_attribution(self):
        faults.arm_resource(
            "feed.latency", nth=1, count=1, latency_ms=1.0, tenant="B",
        )
        tok = CURRENT_TENANT.set("B")
        try:
            faults.resource_point("feed.latency")  # ambient tenant
        finally:
            CURRENT_TENANT.reset(tok)
        assert not faults.is_resource_armed("feed.latency")

    def test_env_grammar_parses_tenant_scope(self, monkeypatch):
        for var in (faults.ENV_VAR, faults.ENV_CORRUPT):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setenv(
            faults.ENV_RESOURCE, "feed.latency:2:3:tenant=acme",
        )
        monkeypatch.setenv(faults.ENV_RESOURCE_LATENCY, "1")
        faults._arm_from_env()
        spec = faults._resource_armed["feed.latency"]
        assert spec == [2, 3, 0.001, "acme"]


# -- bounded tenant-prefixed mirror demand keys (satellite 3) ---------------


class _Agg:
    write_version = 0


class TestMirrorTenantKeys:
    def _mirror(self, max_keys):
        from zipkin_tpu.tpu.mirror import ReadMirror

        agg = _Agg()
        return ReadMirror(lambda: agg, enabled=True, max_keys=max_keys)

    def test_tenant_keys_overflow_at_cap(self):
        m = self._mirror(max_keys=2)
        assert m.register("ttq:tenant=A:p99", lambda: 1)
        assert m.register("ttq:tenant=B:p99", lambda: 2)
        assert not m.register("ttq:tenant=C:p99", lambda: 3)
        c = m.counters()
        assert c["mirrorDemandKeys"] == 2
        assert c["mirrorDemandOverflow"] == 1
        # an existing key refreshes instead of overflowing
        assert m.register("ttq:tenant=A:p99", lambda: 1)

    def test_tenant_keys_expire_by_publish_ttl(self):
        m = self._mirror(max_keys=8)
        assert m.register("ttq:tenant=A:p99", lambda: 1)
        for _ in range(m.DEMAND_TTL_PUBLISHES + 2):
            assert m.publish(force=True)
        assert m.counters()["mirrorDemandKeys"] == 0
        # expiry freed the slot: a re-register succeeds, no overflow
        assert m.register("ttq:tenant=A:p99", lambda: 1)
        assert m.counters()["mirrorDemandOverflow"] == 0


# -- per-tenant SLO grammar -------------------------------------------------


class TestTenantSlo:
    def test_tenant_specs_bind_to_slugged_counters(self):
        from zipkin_tpu.obs.slo import tenant_specs

        (spec,) = tenant_specs("team-a")
        assert spec.name == "tenant_team_a_shed_ratio"
        assert spec.bad == "tenantShed_team_a"
        assert spec.total == "tenantOffered_team_a"
        assert spec.kind == "ratio"

    def test_add_spec_is_idempotent(self):
        from zipkin_tpu.obs.recorder import StageRecorder
        from zipkin_tpu.obs.slo import SloWatchdog, tenant_specs
        from zipkin_tpu.obs.windows import WindowedTelemetry

        w = WindowedTelemetry(StageRecorder(), dict)
        dog = SloWatchdog(w, subscribe=False)
        n = len(dog.specs)
        (spec,) = tenant_specs("acme")
        dog.add_spec(spec)
        dog.add_spec(spec)
        assert len(dog.specs) == n + 1


# -- {tenant=} prometheus families -----------------------------------------


class TestPromTenantFamilies:
    def test_families_are_labelled_and_format_valid(self):
        from zipkin_tpu.server.app import _prom_tenants

        clk = Clock()
        ctl = OverloadController(clock=clk)
        ctl.tenant_admission = TenantAdmission(
            bytes_per_s=100.0, burst_s=1.0, clock=clk,
        )
        ctl.admit(b"x" * 60, tenant="acme")
        ctl.admit(b"x" * 60, tenant="acme")  # shed
        lines = _prom_tenants(ctl.status())
        text = "\n".join(lines)
        assert 'zipkin_tpu_tenant_level{tenant="acme"} 2' in text
        assert 'zipkin_tpu_tenant_shed_total{tenant="acme"} 1' in text
        assert 'zipkin_tpu_tenant_offered_total{tenant="acme"} 2' in text
        assert "# TYPE zipkin_tpu_tenant_table_size gauge" in text
        # format sanity: every sample line follows HELP/TYPE for its
        # family and parses as name{labels} value
        seen_fams = set()
        for line in lines:
            if line.startswith("# HELP "):
                seen_fams.add(line.split()[2])
            elif not line.startswith("#"):
                fam = line.split("{")[0].split(" ")[0]
                assert fam in seen_fams
                float(line.rsplit(" ", 1)[1])

    def test_empty_status_renders_nothing(self):
        from zipkin_tpu.server.app import _prom_tenants

        assert _prom_tenants(None) == []
        assert _prom_tenants({"tenants": None}) == []


# -- the scoped shed at the real boundaries ---------------------------------


class TestBoundaryScopedShed:
    def test_http_429_names_the_tenant_and_spares_the_others(self):
        from tests.test_overload import bulk_payload, run_server
        from zipkin_tpu.runtime.tenant import TENANT_HEADER
        from zipkin_tpu.server.config import ServerConfig

        body = bulk_payload(1, per=8)

        async def scenario(client, server):
            async def post(tenant):
                return await client.post(
                    "/api/v2/spans", data=body,
                    headers={"Content-Type": "application/json",
                             TENANT_HEADER: tenant})

            assert (await post("B")).status == 202
            shed = await post("B")
            assert shed.status == 429
            assert shed.headers["X-Shed-Scope"] == "tenant"
            assert shed.headers["X-Shed-Tenant"] == "B"
            assert int(shed.headers["Retry-After"]) >= 1
            assert int(shed.headers["X-Retry-After-Ms"]) > 0
            for other in ("A", "C"):
                assert (await post(other)).status == 202
            counters = server._overload.counters()
            assert counters["overloadLevel"] == B0
            assert counters["overloadTransitions"] == 0
            statusz = await (await client.get("/api/v2/tpu/statusz")).json()
            tenants = statusz["overload"]["tenants"]["tenants"]
            assert tenants["B"]["level"] >= 2
            assert tenants["A"]["level"] == 0
            prom = await (await client.get("/prometheus")).text()
            assert 'zipkin_tpu_tenant_level{tenant="B"} 2' in prom

        # a burst of one and a half payloads: B's second POST sheds
        run_server(scenario, config=ServerConfig(
            tenant_ingest_bytes_per_s=1.5 * len(body),
            tenant_ingest_burst_s=1.0,
        ))

    def test_grpc_trailers_name_scope_and_tenant(self):
        import types

        from zipkin_tpu.server.grpc import _SpanServiceHandler
        from zipkin_tpu.tpu.mp_ingest import IngestBackpressure

        handler = _SpanServiceHandler(
            types.SimpleNamespace(overload=OverloadController()))
        trailers = dict(handler._retry_trailers(IngestBackpressure(
            "over budget", scope="tenant", tenant="B", retry_after_s=0.25)))
        assert trailers["shed-scope"] == "tenant"
        assert trailers["shed-tenant"] == "B"
        assert trailers["retry-delay"] == "0.250s"
        assert trailers["retry-delay-ms"] == "250"


# -- tenant attribution through the MP fan-out tier -------------------------


@pytest.mark.skipif(not native.available(), reason="native codec unavailable")
class TestMpIngestTenantThreading:
    def test_submit_tenant_reaches_ack_accounting_and_sink(self):
        from tests.test_mp_ingest import make_store, payloads
        from zipkin_tpu.tpu.mp_ingest import MultiProcessIngester

        store = make_store(shards=2)
        ing = MultiProcessIngester(store, workers=2)
        sink_calls = []
        ing.tenant_sink = lambda tenant, n: sink_calls.append((tenant, n))
        try:
            ps = payloads(n_payloads=2, spans_each=256)
            ing.submit(ps[0], tenant="acme")
            ing.submit(ps[1])  # legacy: no tenant header
            ing.drain()
            table = ing.stats()["mpTenantTable"]
        finally:
            ing.close()
        assert table["acme"]["payloads"] == 1
        assert table["acme"]["spans"] == 256
        assert table["default"]["payloads"] == 1
        acked = {t: n for t, n in sink_calls}
        assert acked.get("acme") == 256
        assert acked.get("default") == 256
