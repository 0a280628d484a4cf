"""Documents signed, packed and inserted in the window over the window's
seconds."""


def read(ctx):
    if ctx["kind"] != "ingest" or not ctx["window_s"]:
        return None
    return ctx["docs_in_window"] / ctx["window_s"]
