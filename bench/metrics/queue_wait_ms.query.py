"""Mean admission-queue wait of the window's queries: the stream front's
``stream.queue_wait`` histogram, submit to batch dispatch."""


def read(ctx):
    h = ctx["delta"].get("hists", {}).get("stream.queue_wait")
    if ctx["kind"] != "query" or not h or not h["count"]:
        return None
    return h["sum_ns"] / 1e9 / h["count"] * 1e3
