"""Share of the window spent in the host table insert: the ``ingest.scatter``
histogram's seconds over the window's."""


def read(ctx):
    h = ctx["delta"].get("hists", {}).get("ingest.scatter")
    if ctx["kind"] != "ingest" or not h or not ctx["window_s"]:
        return None
    return h["sum_ns"] / 1e9 / ctx["window_s"] * 100.0
