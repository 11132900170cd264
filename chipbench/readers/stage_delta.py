"""Host-clock stage time from the server's stage table (statusz), as the
change over the window: sum of ``sumUs`` of ``stages`` over ``per``:
``count`` (the first stage's own count), ``spans`` (spans applied in the
window) or ``window`` (seconds of window plus drain, giving a share).

A stage that was recorded in the window and took no time reads 0.0: a publish
that found the device's queue empty drained for 0 ms, and that is a reading.
Nothing comes back only where the recorder saw nothing of the first stage
(its count did not grow): a metric left out of the line reads as a metric
done away with (PR 34's ``publish_drain_ms``)."""


def read(ctx, params):
    before, after = ctx["result"]["before"], ctx["result"]["after"]
    d_us, d_n = 0.0, 0
    for i, stage in enumerate(params["stages"]):
        a, b = after["stages"].get(stage), before["stages"].get(stage)
        if a is None or b is None:
            return None
        d_us += a["sumUs"] - b["sumUs"]
        if i == 0:
            d_n = a["count"] - b["count"]
    if d_n <= 0:
        return None  # the recorder saw nothing of this stage
    per = params["per"]
    if per == "count":
        den = d_n
    elif per == "spans":
        den = (after["counters"]["spans"] - before["counters"]["spans"])
    elif per == "window":
        den = ctx["window_s"] * 1e6
    else:
        raise ValueError(per)
    if den <= 0:
        return None
    return max(d_us, 0.0) / den * params.get("scale", 1.0)
