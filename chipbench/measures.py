"""From the client's record of a run to the end-to-end metrics.

Every metric is over all operations of the window, on the client's clock. An
operation that failed misses every limit: in a percentile it counts with the
time to the end of the drain limit, and it is in ``failed``.
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import numpy as np


def pctl(values: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=float), q))


def setup_parts(result: dict, t_start: float) -> dict:
    """Where the set-up's seconds went, for ``notes``: each part by the
    client's marks (``time.monotonic()``, one clock for every process of the
    machine), the first from ``run.py``'s own start. ``sync_s`` is the closing
    ``device_sync`` with the snapshot that follows it, so the parts sum to
    ``setup_s``; ``after_publish_s`` is the share of ``warm_s`` spent waiting
    for the read mirror's publisher."""
    marks = result["marks"]
    edges = [t_start, marks["health"], marks["warm"], marks["fill"],
             marks["pin"], result["t0"]]
    names = ("boot_s", "warm_s", "fill_s", "pin_s", "sync_s")
    parts = {n: b - a for n, a, b in zip(names, edges, edges[1:])}
    parts["after_publish_s"] = sum(w["s"] for w in result["publish_waits"])
    parts["after_publish_missed"] = sum(
        1 for w in result["publish_waits"] if not w["looked"])
    parts["setup_batches"] = sum(
        1 for s in result["sends"] if s["phase"] != "window")
    return parts


def sub_window(result: dict, n_s: float) -> dict:
    """The end-to-end numbers of the window's first ``n_s`` seconds, from the
    record of a longer run: one round of long runs then prices every shorter
    window with no further chip time. The batches first due before
    ``t0 + n_s`` are the ones a window of that length sends; it closes when
    the last of them has its answer, and it drains for as long as the whole
    run did (the 202 means applied where this is used, so the drain is the
    device's queue and the closing read, whatever the length). No batch is
    left out or cut short: one that failed counts as in ``end_to_end``. At
    ``n_s`` = the run's own length and beyond, these are the run's numbers."""
    if n_s < result["seconds"]:
        edge = result["t0"] + n_s
        sends = [s for s in result["sends"]
                 if s["phase"] != "window" or s["due"] < edge]
        t_close = max(s.get("ended") or s["acked"] or s["due"]
                      for s in sends if s["phase"] == "window")
        result = dict(result, seconds=n_s, sends=sends, t_close=t_close,
                      t_drained=t_close + result["t_drained"]
                      - result["t_close"])
    e2e, _ = end_to_end(result, None)
    return {k: v for k, v in e2e.items() if k != "setup_s"}


def stalls(result: dict, over_ms: float = 300.0) -> list:
    """When the senders stood still, for ``notes``: the window's acks longer
    than ``over_ms``, those that ended within a quarter of a second of each
    other taken as one stall. -> [[seconds into the window at which it
    ended, acks in it, the longest in ms], ...]: the cadence of whatever
    holds the aggregator lock for long (a dependency read under it stalls
    every connection at once)."""
    out = []
    for s in sorted((s for s in result["sends"]
                     if s["phase"] == "window" and s["acked"] is not None
                     and (s["acked"] - s["due"]) * 1000.0 > over_ms),
                    key=lambda s: s["acked"]):
        at, ms = s["acked"] - result["t0"], (s["acked"] - s["due"]) * 1000.0
        if out and at - out[-1][0] < 0.25:
            out[-1] = [at, out[-1][1] + 1, max(out[-1][2], ms)]
        else:
            out.append([at, 1, ms])
    return out


def end_to_end(result: dict, setup_s: float) -> Tuple[dict, dict]:
    """-> (metric name -> value, counts of operations)."""
    t0, t_close = result["t0"], result["t_close"]
    t_drained = result["t_drained"]
    drained = not (t_drained is None or math.isnan(t_drained))
    give_up = t_close + result["drain_limit_s"]
    t_end = t_drained if drained else give_up
    window = [s for s in result["sends"] if s["phase"] == "window"]
    # a batch counts as applied when it got its 202 and the server's
    # applied-span counter reached every acknowledged span by the drain limit
    ok = [s["status"] == 202 and drained for s in window]
    ack_ms = [((s["acked"] if s["status"] == 202 else give_up) - s["due"])
              * 1000.0 for s in window]
    e2e = {"setup_s": setup_s}
    if window:
        e2e["ingest_spans_per_s"] = sum(ok) * result["post_spans"] / (t_end - t0)
        e2e["ack_p95_ms"] = pctl(ack_ms, 95)
        e2e["ack_p99_ms"] = pctl(ack_ms, 99)
    before, after = result["before"], result["after"]
    ops = {
        "attempted": len(window), "failed": len(window) - sum(ok),
        "http_429": sum(s["retries"] for s in window),
        # drain: from the last 202 until the device had run every step
        "drain_s": t_end - t_close, "window_s": t_close - t0,
        "ack_p50_ms": pctl(ack_ms, 50) if ack_ms else None,
        "ack_max_ms": max(ack_ms) if ack_ms else None,
        "acks_over_1s": sum(1 for a in ack_ms if a > 1000.0),
        "stalls": [[round(at, 3), n, round(ms, 1)]
                   for at, n, ms in stalls(result)[:24]],
        "warm_rounds": result.get("warm_rounds"),
        "warm_missing": result.get("warm_missing"),
        "warm_spoiled": result.get("warm_spoiled"),
        # nothing may compile inside the window: the server's own count
        "compiles_in_window": (
            after["device_totals"].get("compiles", 0)
            - before["device_totals"].get("compiles", 0)),
        "programs_in_window": {
            n: c[0] - before["program_calls"].get(n, [0, 0])[0]
            for n, c in after["program_calls"].items()
            if c[0] - before["program_calls"].get(n, [0, 0])[0]},
    }
    return e2e, ops


if __name__ == "__main__":
    # python3 chipbench/measures.py <seconds>[,<seconds>...] <record>...
    # prices shorter windows from the records that ``run.py --record`` kept
    import json
    import sys

    for path in sys.argv[2:]:
        with open(path) as f:
            rec = json.load(f)
        for n_s in map(float, sys.argv[1].split(",")):
            cut = sub_window(rec, n_s)
            print(path, n_s, cut["ingest_spans_per_s"], cut["ack_p95_ms"],
                  [s[0] for s in stalls(rec) if s[0] < n_s + 1.0])
