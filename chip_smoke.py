#!/usr/bin/env python3
"""chip_smoke.py: the served path, once, on the chip, against the host oracles.

Starts ``python -m zipkin_tpu.server --storage tpu`` as a child at the
default ``AggConfig`` (fast ingest, two parse workers, WAL with fsync),
posts seeded spans over HTTP (JSON v2 and proto3), asks the six query
types and compares every answer with the exact in-repo oracles
(``storage/memory.py``, ``internal/dependency_linker.py``), which run in
this process. The device is whatever the SERVER reports on
``/api/v2/tpu/statusz``; the run fails unless that is a TPU.

This process never imports JAX: the chip belongs to the server child,
and the ``spawn``ed parse workers re-import ``__main__``. Keep every
import that could reach JAX out of this file.

    python chip_smoke.py             # one chip, 262,144 + 8,192 spans
    python chip_smoke.py --chips 4   # the four-chip phase only

Each POST is applied (``mpInflight`` 0) before the next is sent. The
270,336 spans wrap the 2^18-lane retention ring by 8,192 lanes, and link
counts are exact only while a span's tree neighbours are still in the
ring when it is rolled up or read (``tpu/ingest.py:rollup_step``). With
several POSTs in flight the two parse workers finish in either order and
the dispatcher coalesces whatever is ready, so which lanes were rolled
and overwritten varied from run to run, and about every second run had
one edge off by one (PERF.md section 6, PR 22). In arrival order the
answer is exact for any seed, so that is what this check holds it to.

Earlier output lines are JSON objects worth keeping (cold-run set-up
times, not performance numbers); the LAST line is the verdict:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# every wait is sized for a cold compile on the chip (minutes), and the
# whole run for the driver's 1200 s limit
WAIT_S = 900.0
RUN_LIMIT_S = 1100
POST_SPANS = 65_536  # the largest device batch; the shape every record used
SERVICES = 40
SPAN_NAMES = 120

# sketch error bounds, taken from the store's own tests:
#   tests/test_tpu_store.py::test_digest_quantiles_tighter_tail (p50, n>=50)
#   tests/test_timetier.py accuracyWindowedDigestP99RelErr     (p99, n>=100)
#   tests/test_tpu_store.py::test_cardinality_parity (global, per service)
P50_RTOL, P50_MIN_N = 0.15, 50
P99_RTOL, P99_MIN_N = 0.25, 100
CARD_GLOBAL_RTOL, CARD_SVC_RTOL, CARD_SVC_MIN_N = 0.10, 0.15, 100


class SmokeFailure(Exception):
    """A phase could not run to its end (transport, timeout, status)."""


def emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """The child server, its process group and its stderr file."""

    def __init__(self, chips: int, workdir: str) -> None:
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.stderr_path = os.path.join(workdir, "server.stderr")
        env = dict(os.environ)  # as it is: no JAX_PLATFORMS of our own
        env.update(
            TPU_FAST_INGEST="1", TPU_MP_WORKERS="2", TPU_WAL_FSYNC="1",
        )
        if chips > 1:
            env["TPU_DEVICES"] = str(chips)
        self._stderr = open(self.stderr_path, "wb")
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", "zipkin_tpu.server",
                "--storage", "tpu", "--port", str(self.port),
                "--resume-dir", os.path.join(workdir, "resume"),
            ],
            cwd=HERE, env=env, stdout=self._stderr, stderr=self._stderr,
            # own process group: the spawn workers and their resource
            # tracker die with it, whatever state the server is in
            start_new_session=True,
        )

    def alive(self) -> bool:
        return self.proc.poll() is None

    def request(self, method: str, path: str, body: bytes = None,
                ctype: str = None, timeout: float = WAIT_S):
        """One HTTP exchange -> (status, body bytes). HTTP error statuses
        are returned, not raised; transport errors raise."""
        req = urllib.request.Request(self.base + path, data=body, method=method)
        if ctype:
            req.add_header("Content-Type", ctype)
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, resp.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def get_json(self, path: str, **params):
        if params:
            path += "?" + urllib.parse.urlencode(params)
        status, body = self.request("GET", path)
        if status != 200:
            raise SmokeFailure(f"GET {path} -> {status}: {body[:300]!r}")
        return json.loads(body)

    def wait_health(self) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < WAIT_S:
            if not self.alive():
                raise SmokeFailure(
                    f"server exited with code {self.proc.returncode} "
                    "before /health answered"
                )
            try:
                status, _ = self.request("GET", "/health", timeout=5.0)
                if status == 200:
                    return time.monotonic() - t0
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.5)
        raise SmokeFailure(f"/health did not answer within {WAIT_S:.0f}s")

    def post_spans(self, body: bytes, ctype: str) -> tuple:
        """POST until 202; a 429 is retried (the client contract), any
        other status fails. Returns (seconds, 429s seen)."""
        t0 = time.monotonic()
        rejected = 0
        while time.monotonic() - t0 < WAIT_S:
            status, text = self.request("POST", "/api/v2/spans", body, ctype)
            if status == 202:
                return time.monotonic() - t0, rejected
            if status != 429:
                raise SmokeFailure(
                    f"POST /api/v2/spans -> {status}: {text[:300]!r}")
            rejected += 1
            time.sleep(min(0.005 * rejected, 0.25))
        raise SmokeFailure(f"POST still 429 after {WAIT_S:.0f}s")

    def wait_drained(self, want_spans: int) -> float:
        """After an ack: the MP tier has applied ``want_spans`` in all."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < WAIT_S:
            if not self.alive():
                raise SmokeFailure("server died while draining")
            m = self.get_json("/metrics")
            if (
                m.get("gauge.zipkin_tpu.mpInflight") == 0
                and m.get("gauge.zipkin_tpu.mpAccepted", 0) >= want_spans
            ):
                return time.monotonic() - t0
            time.sleep(0.25)
        raise SmokeFailure(f"MP tier not drained within {WAIT_S:.0f}s")

    def stop(self) -> None:
        """SIGTERM the server (drain + snapshot), then SIGKILL what is
        left of its group: no process of ours outlives the run."""
        if self.alive():
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=45)
            except subprocess.TimeoutExpired:
                pass
        try:
            # start_new_session made the server its group's leader
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        self._stderr.close()

    def stderr_tail(self, lines: int = 40) -> str:
        try:
            with open(self.stderr_path, "rb") as f:
                return b"\n".join(f.read().splitlines()[-lines:]).decode(
                    "utf-8", "replace")
        except OSError as e:
            return f"<no server stderr: {e}>"


def _link_map(links) -> dict:
    return {
        (l["parent"], l["child"]): (
            int(l.get("callCount", 0)), int(l.get("errorCount", 0)))
        for l in links
    }


def _rel(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-12)


def compare_links(server, oracle, window, fail) -> None:
    from zipkin_tpu.model import json_v2

    want = _link_map(
        json_v2.link_to_dict(l)
        for l in oracle.get_dependencies(
            window["endTs"], window["lookback"]).execute()
    )
    got = _link_map(server.get_json(
        "/api/v2/dependencies", staleness_ms=0, **window))
    if got != want:
        diff = [
            (k, got.get(k), want.get(k))
            for k in sorted(set(got) | set(want)) if got.get(k) != want.get(k)
        ]
        fail(f"dependency links differ in {len(diff)} of {len(want)} "
             f"edges, e.g. {diff[:3]}")


def compare_cardinalities(server, spans, window, fail) -> None:
    got = server.get_json(
        "/api/v2/tpu/cardinalities", staleness_ms=0, **window)
    by_svc = {}
    for s in spans:
        by_svc.setdefault(s.local_service_name, set()).add(s.trace_id)
    true_global = len(set().union(*by_svc.values()))
    if _rel(got.get("_global", 0), true_global) >= CARD_GLOBAL_RTOL:
        fail(f"global cardinality {got.get('_global')} vs {true_global}")
    for svc, tids in by_svc.items():
        if len(tids) >= CARD_SVC_MIN_N and (
            _rel(got.get(svc, 0), len(tids)) >= CARD_SVC_RTOL
        ):
            fail(f"cardinality of {svc}: {got.get(svc)} vs {len(tids)}")


def compare_percentiles(server, spans, window, fail) -> None:
    import statistics

    rows = server.get_json(
        "/api/v2/tpu/percentiles", q="0.5,0.99", staleness_ms=0, **window)
    by_key = {}
    for s in spans:
        if s.duration is not None:
            by_key.setdefault(
                (s.local_service_name, s.name), []).append(s.duration)
    got_keys = {(r["serviceName"], r["spanName"]) for r in rows}
    if got_keys != set(by_key):
        fail(f"percentile rows cover {len(got_keys)} keys, "
             f"oracle has {len(by_key)}")
    checked = 0
    for r in rows:
        durs = sorted(by_key.get((r["serviceName"], r["spanName"]), ()))
        if r["count"] != len(durs):
            fail(f"count of {r['serviceName']}/{r['spanName']}: "
                 f"{r['count']} vs {len(durs)}")
            continue
        # inclusive method == numpy's default linear interpolation
        cuts = statistics.quantiles(durs, n=100, method="inclusive") \
            if len(durs) >= 2 else None
        for q, cut, rtol, min_n in (
            ("0.5", 49, P50_RTOL, P50_MIN_N), ("0.99", 98, P99_RTOL, P99_MIN_N),
        ):
            if len(durs) < min_n:
                continue
            checked += 1
            if _rel(r["quantiles"][q], cuts[cut]) > rtol:
                fail(f"p{q} of {r['serviceName']}/{r['spanName']}: "
                     f"{r['quantiles'][q]} vs exact {cuts[cut]} (n={len(durs)})")
    if not checked:
        fail("no percentile row had enough samples to check")


def compare_traces(server, oracle, spans, window, fail) -> None:
    """The fast path archives a 1-in-64 sample of traces: whatever comes
    back must equal the oracle's trace of the same id, span for span."""
    from zipkin_tpu.model import json_v2

    svc = spans[0].local_service_name
    traces = server.get_json(
        "/api/v2/traces", serviceName=svc, limit=10, **window)
    if not traces:
        fail(f"/api/v2/traces?serviceName={svc} returned no trace")
    for t in traces:
        tid = t[0]["traceId"]
        want = sorted(
            (json_v2.span_to_dict(s)
             for s in oracle.get_trace(tid).execute()),
            key=lambda d: d["id"],
        )
        if sorted(t, key=lambda d: d["id"]) != want:
            fail(f"trace {tid} differs from the oracle's "
                 f"({len(t)} vs {len(want)} spans)")
        if not any(
            (s.get("localEndpoint") or {}).get("serviceName") == svc
            for s in t
        ):
            fail(f"trace {tid} has no span of service {svc}")


class Workload:
    """Seeded spans, their wire payloads and the exact oracle over them."""

    def __init__(self, args) -> None:
        # host oracles: pure Python, no JAX (tests/test_chip_bringup.py
        # keeps them so)
        from tests.fixtures import lots_of_spans
        from zipkin_tpu.model import json_v2, proto3
        from zipkin_tpu.storage.memory import InMemoryStorage

        four = args.chips == 4
        n_json = min(args.spans, POST_SPANS) if four else args.spans
        n_proto = 0 if four else max(1, args.spans // 32)
        self.spans = spans = lots_of_spans(
            n_json + n_proto, seed=args.seed,
            services=SERVICES, span_names=SPAN_NAMES,
        )
        per_post = n_json if four else max(1, n_json // 4)
        # (body, content type, spans in it)
        cuts = [(i, min(i + per_post, n_json))
                for i in range(0, n_json, per_post)]
        self.payloads = [
            (json_v2.encode_span_list(spans[lo:hi]), "application/json",
             hi - lo)
            for lo, hi in cuts
        ]
        if n_proto:
            self.payloads.append((
                proto3.encode_span_list(spans[n_json:]),
                "application/x-protobuf", n_proto,
            ))
        self.oracle = InMemoryStorage(max_span_count=len(spans) + 1)
        self.oracle.accept(spans).execute()


def run(args, load: Workload, server: Server, failures: list) -> dict:
    fail = failures.append
    four = args.chips == 4
    spans, payloads, oracle = load.spans, load.payloads, load.oracle

    emit({"phase": "boot", "coldSetupSecondsToHealth":
          round(server.wait_health(), 3)})

    # one POST at a time, each applied before the next (module docstring)
    post_s, apply_s, rejected, sent = [], [], 0, 0
    for body, ctype, n in payloads:
        dt, r = server.post_spans(body, ctype)
        post_s.append(round(dt, 3))
        rejected += r
        sent += n
        apply_s.append(round(server.wait_drained(sent), 3))
    counters = server.get_json("/api/v2/tpu/counters")
    emit({
        "phase": "ingest", "spansSent": sent, "posts": len(payloads),
        "coldSetupSecondsFirstPost": post_s[0], "postSeconds": post_s,
        "coldSetupSecondsApply": apply_s,
        "coldSetupSecondsDrain": round(sum(apply_s), 3), "http429": rejected,
        # one device step per POST, none coalesced, is what the link
        # comparison below relies on
        "deviceBatches": counters.get("batches"),
        "mpCoalescedBatches": counters.get("mpCoalescedBatches"),
        "ctxAdvances": counters.get("ctxAdvances"),
    })

    metrics = server.get_json("/metrics")
    stored = metrics.get("counter.zipkin_collector.spans.http")
    if stored != len(spans):
        fail(f"/metrics counts {stored} spans, {len(spans)} were sent")
    if metrics.get("gauge.zipkin_tpu.mpAccepted") != len(spans):
        fail(f"mpAccepted {metrics.get('gauge.zipkin_tpu.mpAccepted')} "
             f"!= {len(spans)} sent")

    end_ts = max(s.timestamp for s in spans) // 1000 + 60_000
    window = {"endTs": end_ts, "lookback": 3_600_000}

    t0 = time.monotonic()
    compare_links(server, oracle, window, fail)
    emit({"phase": "query", "coldSetupSecondsFirstDependencyRead":
          round(time.monotonic() - t0, 3)})
    compare_cardinalities(server, spans, window, fail)
    if not four:
        want_svcs = sorted({s.local_service_name for s in spans}
                           | {s.remote_service_name for s in spans})
        got_svcs = server.get_json("/api/v2/services")
        if sorted(got_svcs) != want_svcs:
            fail(f"services differ: {len(got_svcs)} vs {len(want_svcs)}")
        svc = spans[0].local_service_name
        want_names = sorted(oracle.get_span_names(svc).execute())
        got_names = server.get_json("/api/v2/spans", serviceName=svc)
        if sorted(got_names) != want_names:
            fail(f"span names of {svc} differ: "
                 f"{len(got_names)} vs {len(want_names)}")
        compare_percentiles(server, spans, window, fail)
        compare_traces(server, oracle, spans, window, fail)

    statusz = server.get_json("/api/v2/tpu/statusz")
    device = statusz.get("device", {})
    hbm = device.get("hbm", {})
    per_device = hbm.get("perDevice", [])
    if device.get("count") != args.chips:
        fail(f"server's mesh has {device.get('count')} devices, "
             f"want {args.chips}")
    if four and not (
        len(per_device) == 4 and all(d.get("bytesInUse", 0) > 0
                                     for d in per_device)
    ):
        fail(f"state is not on all four devices: {per_device}")
    if device.get("totals", {}).get("analysisFailures"):
        fail(f"device observatory analysis failed "
             f"{device['totals']['analysisFailures']} times")
    cache_dir = device.get("compileCacheDir")
    emit({
        "phase": "statusz", "deviceTotals": device.get("totals"),
        # per program: compiles, their wall, and the observatory's
        # second (cost-analysis) compile: cold-run set-up walls, ms
        "compiled": {
            name: [p["compiles"], p["compileWallMs"],
                   p.get("analysisWallMs")]
            for name, p in device.get("programs", {}).items()
            if p.get("compiles")
        },
        "hbmBytesInUse": hbm.get("bytesInUse"), "hbmPerDevice": per_device,
        "compileCacheDir": cache_dir,
        "compileCacheEntries":
            len(os.listdir(cache_dir))
            if cache_dir and os.path.isdir(cache_dir) else 0,
        "nativeParserBuilt": bool(glob.glob(os.path.join(
            HERE, "zipkin_tpu", "native", "build", "span_json-*.so"))),
    })
    if device.get("platform") != "tpu":
        fail(f"the server's device platform is {device.get('platform')!r}, "
             "not 'tpu'")
    return {
        "platform": device.get("platform"),
        "kind": device.get("deviceKind"),
        "count": device.get("count"),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--spans", type=int, default=4 * POST_SPANS,
        help="rehearsal only: JSON spans to send (default 262144)")
    args = ap.parse_args()

    def on_signal(signum, _frame):
        raise SmokeFailure(f"stopped by signal {signum} "
                           f"(run limit {RUN_LIMIT_S}s)")

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGALRM, on_signal)
    signal.alarm(RUN_LIMIT_S)

    # built before the server starts, so the waits below time the
    # server alone; it also fails here, with nothing started, where the
    # repo is not around this file
    load = Workload(args)
    failures: list = []
    device = {"platform": None, "kind": None, "count": None}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        server = Server(args.chips, workdir)
        try:
            device = run(args, load, server, failures)
        except (SmokeFailure, OSError) as e:
            # OSError: a refused or timed-out connection (URLError,
            # TimeoutError) — the server is gone or wedged
            failures.append(f"{type(e).__name__}: {e}")
        finally:
            signal.alarm(0)
            server.stop()
            if failures:
                print("---- server stderr (tail) ----", file=sys.stderr)
                print(server.stderr_tail(), file=sys.stderr)
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    sys.stderr.flush()
    # the verdict line, key order as documented (emit() sorts keys)
    print(json.dumps({"ok": not failures, "device": device}), flush=True)
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
