"""A ratio of counter changes over the window. ``num`` and ``den`` are lists
of ``[group, key]`` summed, where group is ``counters``
(/api/v2/tpu/counters) or ``stage_count`` (a stage's count in statusz)."""


def _delta(ctx, group, key):
    before, after = ctx["result"]["before"], ctx["result"]["after"]
    if group == "stage_count":
        a, b = after["stages"].get(key), before["stages"].get(key)
        return None if a is None or b is None else a["count"] - b["count"]
    a, b = after[group].get(key), before[group].get(key)
    return None if a is None or b is None else a - b


def read(ctx, params):
    sums = []
    for side in ("num", "den"):
        parts = [_delta(ctx, g, k) for g, k in params[side]]
        if any(p is None for p in parts):
            return None
        sums.append(sum(parts))
    if sums[1] <= 0:
        return None
    return sums[0] / sums[1] * params.get("scale", 1.0)
