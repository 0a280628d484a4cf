"""Mean store query-plane time per batch in the window: the ``query.wall``
histogram (fold, fan-out, partials, merge, brute fallback)."""


def read(ctx):
    h = ctx["delta"].get("hists", {}).get("query.wall")
    if ctx["kind"] != "query" or not h or not h["count"]:
        return None
    return h["sum_ns"] / 1e9 / h["count"] * 1e3
