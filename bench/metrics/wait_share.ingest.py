"""Share of the window the ingest pipeline spent waiting for signed batches
to reach the host: the ``ingest.wait`` histogram's seconds over the
window's."""


def read(ctx):
    h = ctx["delta"].get("hists", {}).get("ingest.wait")
    if ctx["kind"] != "ingest" or not h or not ctx["window_s"]:
        return None
    return h["sum_ns"] / 1e9 / ctx["window_s"] * 100.0
