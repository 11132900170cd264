"""The load client: a process of its own that offers one cell's traffic.

Started by ``run.py`` with a spec file; never imports JAX or the program.
Phases: build the templates from the seed; warm the device step at the cell's
own POST size and fill the ring once; print ``WINDOW <monotonic>`` and offer
the cell's traffic for ``seconds``; stop sending, wait until the server has
applied every acknowledged span and the device has run every step; write
every batch with its times to the result file. All clocks are this process's ``time.monotonic()``, which on
Linux is one clock for every process of the machine, so ``run.py`` can
compare them with its own.

An operation is one batch. Its time runs from when it was first due to its
202, 429s retried after the server's ``X-Retry-After-Ms`` included. A POST
that the transport fails (a timeout, a reset) is never sent again, since a
second copy would be spans the reference does not know: it is recorded with
the exception as its ``status``, counts as failed, and its sender goes on to
its next batch on a fresh connection. The window's POSTs wait ``OP_TIMEOUT_S``
for their answer, the set-up's ``SETUP_TIMEOUT_S``: a fresh server compiles
its read programs one after the other under the lock that a POST needs, for
minutes (PERF.md section 6). A run that cannot go on ends with one line on
standard error, ``chipbench-client: <phase>: <reason>``, the same on standard
output as ``FAILED <json>`` for ``run.py``, and exit code 1. The one
loop kind so far is ``closed``: ``connections`` senders, each sending its next
batch on the 202, which finds the pace where the configuration's 202 means
applied. A workload file that asks for another kind is refused: the PR that
proves such a cell adds the loop.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import gen  # noqa: E402
from launcher import TRANSPORT_ERRORS, Http, RunFailure  # noqa: E402

DRAIN_LIMIT_S = 90.0  # an answer that comes late is late, not wrong
OP_TIMEOUT_S = 120.0  # a POST of the window: part of what an operation is
SETUP_TIMEOUT_S = 900.0  # a POST of the set-up: as long as its reads wait
WARM_ROUNDS = 6  # looks at the program table; a good warm-up takes 3


class Client:
    def __init__(self, spec: dict) -> None:
        self.spec = spec
        self.port = spec["port"]
        self.cfg = spec["config"]
        self.wl = spec["workload"]
        self.posts = self.wl["posts"]
        if self.posts["loop"] != "closed":
            raise ValueError(f"loop kind {self.posts['loop']!r}: only 'closed' "
                             "is built so far (chipbench/README.md)")
        self.traffic = gen.Traffic(spec["seed"], self.cfg["fleet"], self.posts)
        self.post_spans = self.traffic.post_spans
        self.lock = threading.Lock()
        self.next_n = 0
        self.sends = []  # dicts, one per batch
        self.phase = "boot"  # then "setup", "window", "drain": run.py's names
        self.marks = {}  # the set-up's parts, each by the moment it ended
        self.publish_waits = []  # one entry for each ``after_publish``

    # ---- writes ----------------------------------------------------------

    def take_n(self) -> int:
        with self.lock:
            n = self.next_n
            self.next_n += 1
            return n

    def send_batch(self, http: Http, scratch: dict, n: int, due: float,
                   give_up_at: float, phase: str) -> dict:
        body = self.traffic.body(n, scratch)
        rec = {"n": n, "template": self.traffic.template_of(n), "due": due,
               "phase": phase, "retries": 0, "status": None, "acked": None}
        while True:
            try:
                status, text, headers = http.request(
                    "POST", "/api/v2/spans", body,
                    {"Content-Type": "application/json"})
            except TRANSPORT_ERRORS as e:
                # never sent again; ``http`` has closed its connection
                rec["status"] = f"{type(e).__name__}: {e}"
                break
            now = time.monotonic()
            if status == 202:
                rec["status"], rec["acked"] = 202, now
                break
            if status != 429 or now > give_up_at:
                rec["status"] = status
                rec["error"] = text[:200].decode("utf-8", "replace")
                break
            rec["retries"] += 1
            wait_ms = headers.get("X-Retry-After-Ms")
            time.sleep(float(wait_ms) / 1000.0 if wait_ms
                       else min(0.005 * rec["retries"], 0.25))
        rec["ended"] = time.monotonic()  # with its answer or without
        with self.lock:
            self.sends.append(rec)
        return rec

    def closed_loop(self, timeout_s: float, count: int = None,
                    until: float = None, give_up_at: float = None,
                    phase: str = "window") -> None:
        """``connections`` senders, each sending its next batch on the 202 and
        waiting ``timeout_s`` for it. Ends after ``count`` batches in all, or
        at ``until``. A sender outlives a batch that failed; one that an
        exception ends all the same fails the run, since the loop would go on
        with fewer connections than the cell states."""
        left = [count]
        ended = []

        def worker() -> None:
            http, scratch = Http(self.port, timeout_s), {}
            try:
                while True:
                    with self.lock:
                        if count is not None:
                            if left[0] <= 0:
                                break
                            left[0] -= 1
                    if until is not None and time.monotonic() >= until:
                        break
                    self.send_batch(
                        http, scratch, self.take_n(), time.monotonic(),
                        give_up_at or time.monotonic() + timeout_s, phase)
            except Exception as e:  # the thread's boundary: reported below
                ended.append(f"{type(e).__name__}: {e}")
            finally:
                http.close()

        threads = [threading.Thread(target=worker)
                   for _ in range(int(self.posts["connections"]))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if ended:
            raise RunFailure(f"{len(ended)} of {len(threads)} senders ended "
                             f"by an exception, the first: {ended[0]}")

    # ---- the applied-span counter ------------------------------------------

    def wait_applied(self, want: int, limit_s: float) -> float:
        """Until the server's applied-span counter has reached ``want``."""
        http = Http(self.port, OP_TIMEOUT_S)
        c = self.cfg["applied_counter"]
        t0 = time.monotonic()
        while time.monotonic() - t0 < limit_s:
            if int(http.get_json(c["path"]).get(c["key"], 0)) >= want:
                http.close()
                return time.monotonic()
            time.sleep(0.02)
        http.close()
        return float("nan")

    def device_sync(self, t_counted: float, timeout_s: float) -> float:
        """When the device has run every step fed to it. The applied-span
        counter moves when a step is handed to the device's queue, and the
        host runs seconds ahead of the device (PERF.md section 6), so the
        window's work is done only when a read that has to follow the queued
        steps comes back: the configuration's ``device_sync``, a fresh read
        under a key nobody asked for (``{nonce}``), so that no cache and no
        mirror answers it. -> the later of the two moments."""
        path = self.cfg.get("device_sync")
        if not path or t_counted != t_counted:
            return t_counted
        http = Http(self.port, timeout_s)
        nonce = f"0.9{1000 + (self.spec['seed'] * 7919 + self.next_n) % 9000}"
        http.get_json(path.replace("{nonce}", nonce))
        http.close()
        return time.monotonic()

    def snapshot(self) -> dict:
        """Stage table, counters and the program table, for the per-layer
        readers' deltas and the count of compiles inside the window."""
        http = Http(self.port, OP_TIMEOUT_S)
        statusz = http.get_json("/api/v2/tpu/statusz")
        programs = statusz.get("device", {}).get("programs", {})
        out = {"t": time.monotonic(), "stages": statusz.get("stages", {}),
               "device_totals": statusz.get("device", {}).get("totals", {}),
               "program_calls": {n: [p.get("calls", 0), p.get("compiles", 0)]
                                 for n, p in programs.items()},
               "program_compile_ms": {n: p.get("compileWallMs", 0.0)
                                      for n, p in programs.items()},
               "counters": http.get_json("/api/v2/tpu/counters")}
        http.close()
        return out

    # ---- set-up: every step program of this POST size, then the ring ------

    def program_calls(self, http: Http) -> dict:
        progs = http.get_json("/api/v2/tpu/statusz")["device"]["programs"]
        return {name: p.get("calls", 0) for name, p in progs.items()}

    def post_applied(self, count: int) -> None:
        """``count`` batches at the cell's own pace (its loop, its
        connections: what the window can reach, set-up can), one step each,
        then wait until all are applied."""
        if count > 0:
            self.closed_loop(SETUP_TIMEOUT_S, count=count, phase="fill")
        bad = [s for s in self.sends if s["status"] != 202]
        if bad:
            first = {k: bad[0][k] for k in ("n", "status", "retries")}
            raise RunFailure(f"{len(bad)} batches refused, the first: {first}")
        want = self.next_n * self.post_spans
        applied = self.wait_applied(want, SETUP_TIMEOUT_S)
        if applied != applied:
            raise RunFailure(f"the applied counter did not reach {want} spans "
                             f"in {SETUP_TIMEOUT_S:.0f} s")

    def since_rollup(self) -> int:
        """Batches since the half-ring was last rolled up, from this client's
        own count: it has sent every span the server holds, all batches are
        of one size, and the roll-up is due with the first batch that would
        take the count past ``ring_capacity / 2``."""
        per_roll = self.cfg["agg"]["ring_capacity"] // 2 // self.post_spans
        return (self.next_n - 1) % per_roll + 1 if self.next_n else 0

    def publisher(self, http: Http) -> dict:
        """The read mirror's publisher by its own counters. Its ticker looks
        once a period and then does one of three things: it publishes
        (something was written since its last epoch), skips (nothing was) or
        backs off (paced behind a publish that took long)."""
        c = http.get_json("/api/v2/tpu/counters")
        return {k: c.get(k, 0) for k in ("mirrorPublishes",
                                         "mirrorPublishSkips")}

    def after_publish(self, http: Http) -> None:
        """Until the publisher has looked once more and published or skipped:
        its next look is then a period away, and the burst that is to fill
        the digest buffer has that long before a publish empties it. A skip
        serves as well as a publish: nothing was written since the last
        epoch, so no publish comes before the next write, and waiting for one
        (as this did until PR 35, by the calls of ``publish_program``) could
        only run into its limit: 15 s, whenever the publish that the last
        batch made due had run before the wait began. A back-off is waited
        out: it lasts as long as the last publish did. A server that has
        never published (no mirror) is not waited for."""
        t0 = time.monotonic()
        seen = self.publisher(http)
        looked = False
        while (seen["mirrorPublishes"] and not looked
               and time.monotonic() - t0 < 15.0):
            time.sleep(0.05)
            looked = self.publisher(http) != seen
        self.publish_waits.append(
            {"s": time.monotonic() - t0, "looked": looked})

    def floor(self) -> int:
        """Batches every set-up sends at least: what a warm-up with one
        spoiled round of the costlier kind sends (none where the
        configuration gives no hints). ``pin_phase`` pads to it, so that the
        window opens on the same number of batches whichever way the warm-up
        went."""
        if not (self.cfg.get("warm") or {}).get("step_programs"):
            return 0
        agg = self.cfg["agg"]
        per_roll = agg["ring_capacity"] // 2 // self.post_spans
        per_flush = agg["digest_buffer"] // self.post_spans
        return 2 * (per_roll + min(per_roll, per_flush)) + per_flush + 2

    def burst(self, http: Http, count: int) -> bool:
        """``count`` batches that are to fill the digest buffer. -> whether a
        publish emptied it on the way: such a round is spoiled, not idle."""
        before = self.publisher(http)["mirrorPublishes"]
        self.post_applied(count)
        return self.publisher(http)["mirrorPublishes"] > before

    def warm_steps(self):
        """Drive the write path at the cell's own POST size so that no variant
        of the device step compiles (or is read from the compile cache, which
        also takes seconds) inside the window.
        -> (rounds, hinted programs never reached, rounds a publish spoiled).

        Whatever the program, this sends one batch and then goes on to the
        ring fill, which alone takes the step through two roll-ups. Where the
        configuration's ``warm`` block gives hints, it steers first. Today's
        program folds due maintenance into the step, so the step comes in
        four variants: plain, with the digest buffer's flush, with the
        half-ring roll-up, with both. Which a batch takes depends on lanes
        since the last roll-up (this client's own count) and lanes since the
        last flush (a fresh percentile read, ``flush_by``, zeroes it; so does
        every publish of the read mirror, which is why the burst that is to
        fill the buffer starts right after the publisher has looked).
        ``step_programs`` names the variants as ``statusz`` lists them, only
        so that the loop knows when to stop: a round that reached nothing new
        and that no publish spoiled ends the loop for lack of progress (the
        hints name nothing of this program), and nothing fails. A
        configuration for a program with one step shape leaves ``warm`` out."""
        warm = self.cfg.get("warm") or {}
        http = Http(self.port, SETUP_TIMEOUT_S)
        self.post_applied(1)
        wanted = warm.get("step_programs") or []
        if not wanted:
            http.close()
            return 0, [], 0
        agg = self.cfg["agg"]
        per_roll = agg["ring_capacity"] // 2 // self.post_spans
        per_flush = agg["digest_buffer"] // self.post_spans
        both_burst = min(per_roll, per_flush)
        missing, rounds, spoiled, spoiled_last = list(wanted), 0, 0, False
        for rounds in range(1, WARM_ROUNDS + 1):
            calls = self.program_calls(http)
            reached = [p for p in missing if calls.get(p)]
            missing = [p for p in missing if p not in reached]
            if not missing or (rounds > 1 and not reached
                               and not spoiled_last):
                break  # all reached, or the hints name nothing of this program
            spoiled_last = False
            to_edge = per_roll - self.since_rollup()  # batches that still fit
            if any(p.endswith("rollup") for p in missing):
                # to the edge, flush by a read, one more: the roll-up alone.
                # Both counts now run together, and a burst of per_roll more
                # ends in flush-and-roll-up, unless a publish flushes in
                # between: so start right after the publisher has looked
                self.post_applied(to_edge)
                both = any("flush" in p for p in missing)
                if both:
                    self.after_publish(http)
                http.get_json(warm["flush_by"])
                self.post_applied(1)
                if both:
                    spoiled_last = self.burst(http, both_burst)
            else:
                # the flush alone: zero its count away from the edge
                if to_edge in (0, per_roll):
                    self.post_applied(1)
                self.after_publish(http)
                http.get_json(warm["flush_by"])
                spoiled_last = self.burst(http, per_flush + 1)
            spoiled += spoiled_last
        else:
            calls = self.program_calls(http)  # what the last round reached
            missing = [p for p in missing if not calls.get(p)]
        http.close()
        return rounds, missing, spoiled

    def pin_phase(self) -> int:
        """Leave the maintenance in one known phase before the window opens,
        whatever the warm-up needed: ``floor()`` batches sent or, past
        that, the next count that is half a roll-up period since the last
        roll-up; and, where the configuration says how, nothing pending
        since the last flush. Runs that started in different phases settled
        into rates 5-10% apart (PERF.md section 6). -> batches sent."""
        per_roll = self.cfg["agg"]["ring_capacity"] // 2 // self.post_spans
        http = Http(self.port, SETUP_TIMEOUT_S)
        pad = max(0, self.floor() - self.next_n)
        pad += (per_roll // 2 - self.since_rollup() - pad) % per_roll
        self.post_applied(pad)
        flush_by = (self.cfg.get("warm") or {}).get("flush_by")
        if flush_by:
            http.get_json(flush_by)
        http.close()
        return pad

    # ---- the run -----------------------------------------------------------

    def wait_health(self) -> None:
        """The client starts beside the server and builds its templates
        while the server boots; then it waits for it."""
        probe = Http(self.port, 5.0)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 900.0:
            try:
                if probe.request("GET", "/health")[0] == 200:
                    probe.close()
                    return
            except TRANSPORT_ERRORS:
                pass
            time.sleep(0.25)
        raise RunFailure("/health did not answer")

    def run(self) -> dict:
        seconds = float(self.spec["seconds"])
        self.wait_health()
        time.sleep(float(self.spec.get("late_s", 0.0)))
        self.marks["health"] = time.monotonic()
        self.phase = "setup"
        # set-up: every step variant at the cell's own POST size, then the
        # rest of one ring's worth, so the window runs with eviction
        warm_rounds, warm_missing, warm_spoiled = self.warm_steps()
        self.marks["warm"] = time.monotonic()
        fill = -(-int(self.wl["fill_spans"]) // self.post_spans)
        self.post_applied(max(0, fill - self.next_n))
        self.marks["fill"] = time.monotonic()
        self.pin_phase()
        self.marks["pin"] = time.monotonic()
        # the window starts with the device's queue empty, and the read that
        # will close it has run (and compiled) once
        self.device_sync(time.monotonic(), SETUP_TIMEOUT_S)
        before = self.snapshot()
        t0 = time.monotonic()
        self.phase = "window"
        print(f"WINDOW {t0!r}", flush=True)
        give_up_at = t0 + seconds + DRAIN_LIMIT_S
        self.closed_loop(OP_TIMEOUT_S, until=t0 + seconds,
                         give_up_at=give_up_at)
        t_close = time.monotonic()
        self.phase = "drain"
        print(f"CLOSED {t_close!r}", flush=True)
        acked = sum(1 for s in self.sends if s["status"] == 202)
        t_drained = self.wait_applied(acked * self.post_spans,
                                      max(1.0, give_up_at - time.monotonic()))
        t_drained = self.device_sync(t_drained, OP_TIMEOUT_S)
        after = self.snapshot()
        print(f"DRAINED {t_drained!r}", flush=True)
        return {
            "t0": t0, "t_close": t_close, "t_drained": t_drained,
            "seconds": seconds, "post_spans": self.post_spans,
            "sends": sorted(self.sends, key=lambda s: s["n"]),
            "before": before, "after": after,
            "drain_limit_s": DRAIN_LIMIT_S,
            "warm_rounds": warm_rounds, "warm_missing": warm_missing,
            "warm_spoiled": warm_spoiled,
            "marks": self.marks, "publish_waits": self.publish_waits,
        }


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    client = Client(spec)
    try:
        out = client.run()
    except (RunFailure, *TRANSPORT_ERRORS) as e:
        reason = str(e) if isinstance(e, RunFailure) \
            else f"{type(e).__name__}: {e}"
        print("FAILED " + json.dumps(
            {"failed_in": client.phase, "reason": reason}), flush=True)
        print(f"chipbench-client: {client.phase}: {reason}", file=sys.stderr,
              flush=True)
        return 1
    tmp = spec["out"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, spec["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
