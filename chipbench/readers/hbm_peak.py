"""Peak HBM in use on the fullest device, as the server reports it, in GB."""


def read(ctx, params):
    peak = ctx["device"].get("memory_peak_bytes")
    if not ctx.get("on_chip") or not peak:
        return None
    return peak / 1e9
