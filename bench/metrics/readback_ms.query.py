"""Mean device-to-host read time per batch in the window: the
``query.readback`` histogram's seconds (the fold's band hashes, the query
words, and each shard's ids, scores and candidate flags, each read waiting
out the device work ahead of it) over the batches of ``query.wall``."""


def read(ctx):
    hists = ctx["delta"].get("hists", {})
    h, wall = hists.get("query.readback"), hists.get("query.wall")
    if ctx["kind"] != "query" or not h or not wall or not wall["count"]:
        return None
    return h["sum_ns"] / 1e9 / wall["count"] * 1e3
