"""429s over POSTs sent in the window, from the client's own count."""


def read(ctx, params):
    window = [s for s in ctx["result"]["sends"] if s["phase"] == "window"]
    posts = sum(1 + s["retries"] for s in window)
    if not posts:
        return None
    return 100.0 * sum(s["retries"] for s in window) / posts
