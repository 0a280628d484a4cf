"""Mean time per batch of the store plane's host legs between the fold and
the device probe: the ``query.operands`` (probe operands and their upload)
and ``query.spill`` (spilled-key lookup) histograms' seconds over the
batches of ``query.wall``."""


def read(ctx):
    hists = ctx["delta"].get("hists", {})
    legs = [hists[n] for n in ("query.operands", "query.spill") if n in hists]
    wall = hists.get("query.wall")
    if ctx["kind"] != "query" or not legs or not wall or not wall["count"]:
        return None
    return sum(h["sum_ns"] for h in legs) / 1e9 / wall["count"] * 1e3
