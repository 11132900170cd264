"""Device time of the programs whose name matches one of ``patterns``
(fnmatch), from the traced slice, in milliseconds per run of such a program."""

import fnmatch


def matching(ctx, patterns):
    """-> (runs, device seconds) of the matching programs in the slice."""
    runs, seconds = 0, 0.0
    for name, p in ctx["xplane"]["programs"].items():
        if any(fnmatch.fnmatch(name, pat) for pat in patterns):
            runs += p["count"]
            seconds += p["seconds"]
    return runs, seconds


def read(ctx, params):
    if not ctx.get("xplane"):
        return None
    runs, seconds = matching(ctx, params["patterns"])
    if not runs:
        return None
    return seconds * 1000.0 / runs
