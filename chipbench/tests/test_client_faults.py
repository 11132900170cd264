"""What the client and ``run.py`` do when a request is slow or fails, against
a stub HTTP server in this process: no JAX, no server of the program. The stub
answers ``/health``, the applied counter, ``statusz`` and every read with an
empty object, and does with ``POST /api/v2/spans`` what the case tells it:
holds it, resets the connection, or answers another status. The client's
timeouts are the module's constants, set small here."""

import http.server
import json
import socket
import struct
import sys
import threading
import time

import pytest

import client as client_mod
import measures
import run as run_mod
from launcher import Http

OP_S, SETUP_S = 0.3, 3.0  # the window's timeout and the set-up's, shrunk
POST_SPANS = 64

CONFIG = {
    "fleet": {"services": 4, "span_names": 6, "trace_depth": [1, 3],
              "duration_pareto_alpha": 1.2, "error_share": 0.02},
    # a roll-up every 4 POSTs, a flush every 4: set-up is 10 POSTs in all
    "agg": {"ring_capacity": 8 * POST_SPANS, "digest_buffer": 4 * POST_SPANS},
    "applied_counter": {"path": "/metrics",
                        "key": "counter.zipkin_collector.spans.http"},
    "device_sync": "/api/v2/tpu/percentiles?staleness_ms=0&q=0.5,{nonce}",
    "guarantees": {"settle_ms": 0},
}


def workload(connections: int) -> dict:
    return {"config": "stub", "fill_spans": 8 * POST_SPANS,
            "posts": {"spans": POST_SPANS, "templates": 2, "loop": "closed",
                      "connections": connections}}


class Stub:
    """``acts`` maps (phase, index of the POST within the phase) to what the
    stub does with it: ``("hold", seconds)``, ``("reset",)`` or ``("status",
    code)``. The phase is ``fill`` until the client has taken its snapshot
    before the window (the one reader of ``/api/v2/tpu/counters``)."""

    def __init__(self, acts: dict = None, fail_get: str = None) -> None:
        self.acts, self.fail_get = acts or {}, fail_get
        self.lock = threading.Lock()
        self.phase, self.n = "fill", {"fill": 0, "window": 0}
        self.applied = 0
        self.posts = []  # (phase, index, the client's port), as received
        stub = self

        class Handler(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args) -> None:
                pass

            def answer(self, status: int, obj) -> None:
                body = json.dumps(obj).encode()
                self.send_response(status)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self) -> None:
                try:
                    status, obj = stub.get(self.path)
                    self.answer(status, obj)
                except OSError:
                    self.close_connection = True

            def do_POST(self) -> None:
                self.rfile.read(int(self.headers["Content-Length"]))
                with stub.lock:
                    phase = stub.phase
                    index = stub.n[phase]
                    stub.n[phase] += 1
                    stub.posts.append((phase, index, self.client_address[1]))
                act = stub.acts.get((phase, index), ("status", 202))
                if act[0] == "reset":
                    self.connection.setsockopt(
                        socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
                    self.close_connection = True
                    self.connection.close()
                    return
                if act[0] == "hold":
                    time.sleep(act[1])
                status = act[1] if act[0] == "status" else 202
                try:
                    self.answer(status, {})
                except OSError:  # the client gave the request up
                    self.close_connection = True
                    return
                if status == 202:
                    with stub.lock:
                        stub.applied += POST_SPANS

        class Quiet(http.server.ThreadingHTTPServer):
            daemon_threads = True

            def handle_error(self, request, client_address) -> None:
                pass  # a connection that this stub reset itself

        self.httpd = Quiet(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        self.thread = threading.Thread(target=self.httpd.serve_forever,
                                       kwargs={"poll_interval": 0.02})
        self.thread.start()

    def get(self, path: str):
        if self.fail_get and path.startswith(self.fail_get):
            return 500, {"error": "the stub was told to"}
        if path == "/metrics":
            return 200, {CONFIG["applied_counter"]["key"]: self.applied}
        if path == "/api/v2/tpu/counters":
            with self.lock:
                self.phase = "window"
        if path == "/api/v2/tpu/statusz":
            # one program, compiled once by the first POST for 250 s
            done = int(self.applied > 0)
            return 200, {
                "stages": {}, "overload": {"readMode": "normal"},
                "device": {
                    "platform": "stub", "deviceKind": "stub", "count": 1,
                    "totals": {"compiles": done}, "hbm": {"perDevice": []},
                    "programs": {"spmd_step": {
                        "calls": self.applied // POST_SPANS, "compiles": done,
                        "compileWallMs": 250000.0 * done}}}}
        return 200, {}

    def close(self) -> None:
        self.httpd.shutdown()
        self.thread.join(timeout=5)
        self.httpd.server_close()


SCENARIOS = {
    # name: (stub's acts, connections, window seconds)
    "setup_hold": ({("fill", 2): ("hold", 3 * OP_S)}, 2, 0.3),
    "window_hold": ({("window", 0): ("hold", 2 * OP_S)}, 1, 1.0),
    "window_reset": ({("window", 1): ("reset",)}, 1, 0.5),
}
RUNS = {}


@pytest.fixture
def small_timeouts(monkeypatch):
    monkeypatch.setattr(client_mod, "OP_TIMEOUT_S", OP_S)
    monkeypatch.setattr(client_mod, "SETUP_TIMEOUT_S", SETUP_S)
    monkeypatch.setattr(client_mod, "DRAIN_LIMIT_S", 2.0)


def spec_for(stub: Stub, connections: int, seconds: float, out: str) -> dict:
    return {"port": stub.port, "seed": 2147483777, "seconds": seconds,
            "config": CONFIG, "workload": workload(connections), "out": out}


def scenario(name: str, monkeypatch) -> dict:
    """One run of the client against the stub, made once and looked at by
    several cases: the client's record, the stub's, and every exception that
    ended a thread."""
    if name not in RUNS:
        acts, connections, seconds = SCENARIOS[name]
        stub, died = Stub(acts), []
        monkeypatch.setattr(threading, "excepthook", died.append)
        try:
            c = client_mod.Client(spec_for(stub, connections, seconds, ""))
            result = c.run()
        finally:
            stub.close()
        RUNS[name] = {"result": result, "posts": stub.posts, "died": died,
                      "taken": c.next_n}
    return RUNS[name]


def window_sends(run: dict) -> list:
    return [s for s in run["result"]["sends"] if s["phase"] == "window"]


def check_setup_post_outlasts_the_windows_timeout(mp):
    run = scenario("setup_hold", mp)
    fill = [s for s in run["result"]["sends"] if s["phase"] == "fill"]
    assert len(fill) == 10 and all(s["status"] == 202 for s in fill)
    assert max(s["acked"] - s["due"] for s in fill) >= 3 * OP_S
    assert window_sends(run), "set-up went on to the window"


def check_setup_reports_what_it_waited_for(mp):
    run = scenario("setup_hold", mp)
    notes = run_mod.setup_waits({}, run["result"])
    assert notes["setup_compiles"] == 1
    assert notes["setup_compile_s"] == 250.0
    assert 3 * OP_S <= notes["setup_post_max_s"] < SETUP_S


def check_window_timeout_is_recorded_by_name(mp):
    first = window_sends(scenario("window_hold", mp))[0]
    assert first["status"].startswith("TimeoutError"), first
    assert first["acked"] is None


def check_next_batch_on_a_fresh_connection(mp, name, at):
    run = scenario(name, mp)
    sends = window_sends(run)
    assert sends[at]["status"] != 202 and sends[at + 1]["status"] == 202
    ports = [port for phase, _, port in run["posts"] if phase == "window"]
    assert ports[at + 1] != ports[at]  # one sender: the same one, reconnected
    assert len(set(ports[at + 1:])) == 1  # and it keeps the new connection


def check_reset_is_recorded_by_name(mp):
    hit = window_sends(scenario("window_reset", mp))[1]
    assert isinstance(hit["status"], str) and hit["status"].split(":")[0] in (
        "ConnectionResetError", "RemoteDisconnected", "BrokenPipeError"), hit


def check_no_thread_ended_by_an_exception(mp):
    for name in SCENARIOS:
        assert scenario(name, mp)["died"] == [], name


def check_attempted_and_failed_count_every_batch_taken(mp):
    for name, n_failed in (("window_hold", 1), ("window_reset", 1),
                           ("setup_hold", 0)):
        run = scenario(name, mp)
        result = run["result"]
        assert [s["n"] for s in result["sends"]] == list(range(run["taken"]))
        _, ops = measures.end_to_end(result, 1.0)
        assert ops["attempted"] == run["taken"] - 10 == len(window_sends(run))
        assert ops["failed"] == n_failed == sum(
            s["status"] != 202 for s in window_sends(run))


def client_main(stub: Stub, tmp_path, monkeypatch, capfd):
    # one connection: batch numbers and the stub's count of POSTs agree
    spec = spec_for(stub, 1, 0.3, str(tmp_path / "client.json"))
    (tmp_path / "spec.json").write_text(json.dumps(spec))
    monkeypatch.setattr(sys, "argv", ["client.py", str(tmp_path / "spec.json")])
    died = []
    monkeypatch.setattr(threading, "excepthook", died.append)
    try:
        rc = client_mod.main()
    finally:
        stub.close()
    out, err = capfd.readouterr()
    assert died == []
    return rc, out, err


def check_refused_setup_batch_ends_the_client_with_one_line(mp, tmp_path, capfd):
    rc, out, err = client_main(Stub({("fill", 3): ("status", 500)}),
                               tmp_path, mp, capfd)
    assert rc == 1
    assert err.count("\n") == 1 and "Traceback" not in err
    assert err.startswith("chipbench-client: setup: 1 batches refused, "
                          "the first: {'n': 3, 'status': 500")
    said = json.loads(out.splitlines()[-1].split(" ", 1)[1])
    assert said["failed_in"] == "setup" and "'n': 3" in said["reason"]


def check_setup_post_past_its_own_timeout_ends_the_client(mp, tmp_path, capfd):
    mp.setattr(client_mod, "SETUP_TIMEOUT_S", 2 * OP_S)
    rc, _, err = client_main(Stub({("fill", 0): ("hold", 4 * OP_S)}),
                             tmp_path, mp, capfd)
    assert rc == 1 and err.count("\n") == 1
    assert err.startswith("chipbench-client: setup: ") and "TimeoutError" in err


def run_py(stub: Stub, monkeypatch, capfd):
    """``run.py`` past its boot of the program's server: the stub stands in
    for it, the client is the real one in a process of its own."""
    http = Http(stub.port)

    class StubServer:
        def __init__(self, *args, **kwargs) -> None:
            self.port, self.http = stub.port, http

        def alive(self) -> bool:
            return True

        def wait_health(self) -> float:
            return 0.0

        def signal(self, signum: int) -> None:
            pass

        def stop(self) -> None:
            http.close()

        def stderr_tail(self, lines: int = 40) -> str:
            return "<the stub writes none>"

    bench, cell, _, _ = run_mod.load_cell("inproc.saturate")
    monkeypatch.setattr(run_mod, "load_cell",
                        lambda name: (bench, cell, CONFIG, workload(2)))
    monkeypatch.setattr(run_mod, "Server", StubServer)
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "inproc.saturate", "--seed", "5",
        "--seconds", "0.3", "--trace", "0", "--rehearse"])
    try:
        rc = run_mod.main()
    finally:
        stub.close()
    out, err = capfd.readouterr()
    return rc, out, err


def check_run_py_ends_a_failed_run_with_the_phase(mp, capfd, stub, phase, word):
    rc, out, err = run_py(stub, mp, capfd)
    assert rc == 1 and out.strip() == ""
    last = json.loads(err.splitlines()[-1])
    assert list(last) == ["failed_in", "reason"]
    assert last["failed_in"] == phase and phase in run_mod.PHASES
    assert word in last["reason"]
    assert "Traceback" not in err
    assert "---- server stderr (tail) ----" in err


CASES = {
    "a set-up POST held three times the window's timeout is acknowledged":
        lambda mp, tmp, cap: check_setup_post_outlasts_the_windows_timeout(mp),
    "the set-up's notes: compiles, their walls, the longest POST":
        lambda mp, tmp, cap: check_setup_reports_what_it_waited_for(mp),
    "a window POST held past its timeout is in sends under the exception's name":
        lambda mp, tmp, cap: check_window_timeout_is_recorded_by_name(mp),
    "after a timeout the same sender gets its next 202 on a fresh connection":
        lambda mp, tmp, cap: check_next_batch_on_a_fresh_connection(
            mp, "window_hold", 0),
    "a connection reset mid-POST is in sends under the exception's name":
        lambda mp, tmp, cap: check_reset_is_recorded_by_name(mp),
    "after a reset the same sender gets its next 202 on a fresh connection":
        lambda mp, tmp, cap: check_next_batch_on_a_fresh_connection(
            mp, "window_reset", 1),
    "no thread ends by an exception":
        lambda mp, tmp, cap: check_no_thread_ended_by_an_exception(mp),
    "attempted is every batch number taken, failed those not acknowledged":
        lambda mp, tmp, cap: check_attempted_and_failed_count_every_batch_taken(mp),
    "a refused set-up batch ends the client non-zero with the one line":
        check_refused_setup_batch_ends_the_client_with_one_line,
    "a set-up POST past the set-up's own timeout ends the client likewise":
        check_setup_post_past_its_own_timeout_ends_the_client,
    "run.py: a failed set-up ends standard error with the JSON line":
        lambda mp, tmp, cap: check_run_py_ends_a_failed_run_with_the_phase(
            mp, cap, Stub({("fill", 1): ("status", 503)}), "setup", "503"),
    "run.py: a failed fetch is named as that phase":
        lambda mp, tmp, cap: check_run_py_ends_a_failed_run_with_the_phase(
            mp, cap, Stub(fail_get="/api/v2/dependencies"), "fetch", "500"),
}


@pytest.mark.parametrize("case", list(CASES), ids=lambda c: c.replace(" ", "_"))
def test_slow_and_failed_requests(case, small_timeouts, monkeypatch, tmp_path,
                                  capfd):
    CASES[case](monkeypatch, tmp_path, capfd)
