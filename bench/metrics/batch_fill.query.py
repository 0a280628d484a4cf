"""Queries per dispatched batch in the window: the ``stream.queries``
counter over the stream's batch count."""


def read(ctx):
    n = ctx["delta"].get("counters", {}).get("stream.queries")
    if ctx["kind"] != "query" or not n or not ctx["n_batches"]:
        return None
    return n / ctx["n_batches"]
