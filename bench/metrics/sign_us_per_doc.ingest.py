"""Device time of the sparse signing kernel (the ``jit_cminhash_sparse_pallas``
programs in the trace) per document signed in the traced window."""

from bench import trace


def read(ctx):
    red = ctx["trace"]
    if ctx["kind"] != "ingest" or not red or not ctx["rows_signed"]:
        return None
    t = trace.program_seconds(red, "jit_cminhash_sparse")
    if not t:
        return None
    return t / ctx["rows_signed"] * 1e6
