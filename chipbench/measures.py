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
        "warm_rounds": result.get("warm_rounds"),
        "warm_missing": result.get("warm_missing"),
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
