"""The step's share of the HBM roofline over the traced slice: the least
time the chip could take for the spans applied in the slice (roofline.py,
peaks.json), over the device time of the step programs in it. In %."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import roofline  # noqa: E402
from readers.xplane_programs import matching  # noqa: E402


def read(ctx, params):
    if not ctx.get("xplane") or not ctx.get("peaks"):
        return None
    _, seconds = matching(ctx, params["patterns"])
    # spans applied in the slice: the run's mean rate (the server's own
    # counter between the two snapshots) times the slice's length
    lo, hi = ctx["xplane"]["slice"]
    before, after = ctx["result"]["before"], ctx["result"]["after"]
    spans = ((after["counters"]["spans"] - before["counters"]["spans"])
             * (hi - lo) / (after["t"] - before["t"]))
    if not seconds or spans <= 0:
        return None
    least = roofline.min_seconds(spans, ctx["config"]["agg"], ctx["peaks"])
    return 100.0 * least / seconds
