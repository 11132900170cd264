"""The server child: boot, probe, signal, tear down. No JAX in this process.

Copied from ``chip_smoke.py`` (PR 22), which stays as it is: the documented
start (``python -m zipkin_tpu.server --storage tpu --port P --resume-dir D``),
its own process group so that the spawn workers die with it, every wait sized
for a cold compile on the chip. Differences: the environment and extra
arguments come from the cell's configuration file, ``--trace 1`` starts
``serve_traced.py`` instead (the same server ``main()`` under a profiler), and
teardown kills the group at once: a run needs no shutdown snapshot, and the
snapshot would write the whole device state to disk in every run.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.parse

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WAIT_S = 900.0  # a cold compile on the chip takes minutes


# what a request can raise: a timeout and a reset are OSErrors, a connection
# left half-way through a request or answered with garbage an HTTPException
TRANSPORT_ERRORS = (OSError, http.client.HTTPException)
STALE_ERRORS = (http.client.RemoteDisconnected, BrokenPipeError,
                ConnectionResetError, http.client.CannotSendRequest)


class RunFailure(Exception):
    """A phase could not run to its end (transport, timeout, status)."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Http:
    """One keep-alive connection. Statuses are returned, not raised."""

    def __init__(self, port: int, timeout: float = WAIT_S) -> None:
        self.port, self.timeout = port, timeout
        self.conn = None

    def request(self, method: str, path: str, body: bytes = None,
                headers: dict = None):
        """-> (status, body bytes, response headers dict). Whatever exception
        comes out, the connection is closed first: a request that was sent and
        not answered leaves it unusable (``CannotSendRequest``), and the next
        request has to start on a fresh one."""
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=self.timeout)
            try:
                self.conn.request(method, path, body=body, headers=headers or {})
                resp = self.conn.getresponse()
                data = resp.read()
                return resp.status, data, resp.headers
            except TRANSPORT_ERRORS as e:
                self.close()
                # a keep-alive connection the server closed: a GET goes once
                # more on a fresh one; a POST may have been taken, and a
                # second copy would be spans the reference does not know
                if attempt or method != "GET" or not isinstance(e, STALE_ERRORS):
                    raise

    def get_json(self, path: str, **params):
        if params:
            path += "?" + urllib.parse.urlencode(params)
        status, body, _ = self.request("GET", path)
        if status != 200:
            raise RunFailure(f"GET {path} -> {status}: {body[:300]!r}")
        return json.loads(body)

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


class Server:
    """The child server, its process group and its stderr file."""

    def __init__(self, config: dict, workdir: str, traced: bool,
                 trace_seconds: float = 4.0) -> None:
        self.port = free_port()
        self.stderr_path = os.path.join(workdir, "server.stderr")
        env = dict(os.environ)  # as it is: no JAX_PLATFORMS of our own
        env.update(config["server"]["env"])
        if traced:
            head = [sys.executable, os.path.join(HERE, "serve_traced.py"),
                    "--trace-dir", os.path.join(workdir, "trace"),
                    "--trace-seconds", str(trace_seconds)]
        else:
            head = [sys.executable, "-m", "zipkin_tpu.server"]
        self._stderr = open(self.stderr_path, "wb")
        self.proc = subprocess.Popen(
            head + list(config["server"]["args"]) + [
                "--port", str(self.port),
                "--resume-dir", os.path.join(workdir, "resume"),
            ],
            cwd=ROOT, env=env, stdout=self._stderr, stderr=self._stderr,
            start_new_session=True,
        )
        self.http = Http(self.port)

    def alive(self) -> bool:
        return self.proc.poll() is None

    def wait_health(self) -> float:
        t0 = time.monotonic()
        probe = Http(self.port, timeout=5.0)
        while time.monotonic() - t0 < WAIT_S:
            if not self.alive():
                raise RunFailure(
                    f"server exited with code {self.proc.returncode} "
                    "before /health answered")
            try:
                status, _, _ = probe.request("GET", "/health")
                if status == 200:
                    probe.close()
                    return time.monotonic() - t0
            except TRANSPORT_ERRORS:
                pass
            time.sleep(0.25)
        raise RunFailure(f"/health did not answer within {WAIT_S:.0f}s")

    def signal(self, signum: int) -> None:
        self.proc.send_signal(signum)

    def stop(self) -> None:
        """Kill the whole group and wait: no process of ours outlives a run.
        Safe to call twice."""
        if self._stderr.closed:
            return
        self.http.close()
        try:
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
