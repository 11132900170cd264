"""1 - union of device-op intervals over the traced slice, in %."""


def read(ctx, params):
    x = ctx.get("xplane")
    if not x or not x["window_s"] or not x["busy_s"]:
        return None
    return 100.0 * (1.0 - x["busy_s"] / x["window_s"])
