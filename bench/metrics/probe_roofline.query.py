"""The LSH probe's share of its HBM roofline in the traced window.

Bytes are the least any probe must move per (query row, band) entry: one
40-byte logical record (2 key words + 8 postings at bucket width 8; the
device's (8, 128) tiling stores it in 64), the entry's 20-byte operand row,
and its candidate row written back.  Over the device time of the
``jit_lsh_probe_jnp`` programs in the trace, at the chip's published HBM
bandwidth.  Memory-bound: the probe does no arithmetic worth a compute
bound."""

from bench import trace


def probe_bytes(entries: int, bucket_width: int) -> int:
    record = 4 * (2 + bucket_width)
    return entries * (record + 4 * 5 + 4 * bucket_width)


def read(ctx):
    red, peaks = ctx["trace"], ctx["peaks"]
    if ctx["kind"] != "query" or not red or not peaks:
        return None
    t = trace.program_seconds(red, "jit_lsh_probe_jnp")
    if not t or not ctx["rows_queried"]:
        return None
    need = probe_bytes(ctx["rows_queried"] * ctx["n_bands"],
                       ctx["bucket_width"])
    return need / peaks["hbm_bw"] / t * 100.0
