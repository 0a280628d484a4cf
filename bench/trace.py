"""Profiler trace -> device busy and idle time, per-op device time, and idle
gaps named by what the host was doing.

``record`` wraps a window in ``jax.profiler`` tracing (Python function
tracing off: it would slow the host it measures) and returns the
``.xplane.pb`` it wrote.  ``load`` reads that file with nothing but JAX
into flat event lists; ``reduce`` turns them into numbers:

* device ops: the events of each device plane's ``XLA Ops`` line, named
  ``<program>/<op>``, the program being the op's ``hlo_module`` stat (or
  the ``XLA Modules`` event that encloses it);
* busy: the union of all device-op intervals inside the window, averaged
  over the devices that ran any op; idle share = 1 - busy / window;
* per-program device time: the union of that program's op intervals, so
  an op nested in another (a while loop's body) is not counted twice;
* idle gaps: each stretch of the window in which no device op ran, named
  by the ``bench.*`` host annotation (``jax.profiler.TraceAnnotation``)
  that covers most of it — the innermost on a tie — or ``host.other``.

The window is the span of the ``bench.window`` annotation.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import os
import re
import shutil
import tempfile
from dataclasses import dataclass, field

WINDOW = "bench.window"
PREFIX = "bench."


@dataclass
class Events:
    # (device plane, program, op, start_ns, end_ns)
    device: list = field(default_factory=list)
    # (host line, name, start_ns, end_ns)
    host: list = field(default_factory=list)


@contextlib.contextmanager
def record(enabled: bool = True):
    """Trace the body; yields a dict whose ``path`` is set to the
    ``.xplane.pb`` once the body has ended.  The directory lives under the
    process's temporary directory and is removed by ``cleanup``."""
    out: dict = {"path": None, "dir": None}
    if not enabled:
        yield out
        return
    import jax
    out["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out["dir"], profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(out["dir"], "plugins", "profile",
                                          "*", "*.xplane.pb")))
    out["path"] = found[-1] if found else None


def cleanup(rec: dict) -> None:
    if rec.get("dir"):
        shutil.rmtree(rec["dir"], ignore_errors=True)


_HLO = re.compile(r"^%?(\S+) = (\(?[a-z0-9]+\[[0-9,]*\])")


def op_name(text: str) -> str:
    """``%fusion.6 = s32[512,10]{0,1:T(8,128)} fusion(...)`` -> ``fusion.6
    s32[512,10]``: the op and its result shape, without layout or body."""
    m = _HLO.match(text)
    return f"{m.group(1)} {m.group(2).lstrip('(')}" if m else text[:80]


def load(path: str) -> Events:
    """Flat event lists from an ``.xplane.pb`` (or ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    ev = Events()
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:"):
            mods = [(e.start_ns, e.start_ns + e.duration_ns,
                     e.name.split("(")[0])
                    for e in (lines["XLA Modules"].events
                              if "XLA Modules" in lines else [])]
            ops = lines.get("XLA Ops")
            for e in (ops.events if ops is not None else []):
                s, t = e.start_ns, e.start_ns + e.duration_ns
                prog = dict(e.stats).get("hlo_module")
                if prog is None:
                    prog = next((m for a, b, m in mods if a <= s and t <= b),
                                "?")
                ev.device.append((plane.name, prog, op_name(e.name), s, t))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(PREFIX):
                        ev.host.append((ln.name, e.name, e.start_ns,
                                        e.start_ns + e.duration_ns))
    return ev


def _union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, t in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(t, hi)) for s, t in iv if t > lo and s < hi]


def _length(iv) -> float:
    return sum(t - s for s, t in iv)


def window_of(ev: Events) -> tuple[float, float]:
    spans = [(s, t) for _, name, s, t in ev.host if name == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW} annotation in the trace")
    return min(s for s, _ in spans), max(t for _, t in spans)


def _label(gap, annotations) -> str:
    best, key = "host.other", (0.0, 0.0)
    for name, s, t in annotations:
        ov = min(t, gap[1]) - max(s, gap[0])
        if ov > 0 and (ov, -(t - s)) > key:
            best, key = name, (ov, -(t - s))
    return best


def reduce(ev: Events, top: int = 10) -> dict:
    """The trace's numbers: ``window_s``, ``busy_s`` (per device, averaged
    over devices that ran ops), ``program_s`` (union time per program),
    ``device_ops`` and ``idle_gaps`` (the ``top`` largest, seconds)."""
    lo, hi = window_of(ev)
    planes = sorted({p for p, *_ in ev.device})
    busy, gaps = [], []
    for p in planes:
        iv = _union(_clip([(s, t) for q, _, _, s, t in ev.device if q == p],
                          lo, hi))
        busy.append(_length(iv))
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    progs: dict[str, list] = {}
    ops: dict[str, float] = {}
    for _, prog, op, s, t in ev.device:
        c = _clip([(s, t)], lo, hi)
        if c:
            progs.setdefault(prog, []).append(c[0])
            ops[f"{prog}/{op}"] = ops.get(f"{prog}/{op}", 0.0) + _length(c)
    ann = [(name, s, t) for _, name, s, t in ev.host if name != WINDOW]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    n_dev = max(len(planes), 1)
    return {
        "devices": len(planes),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "program_s": {k: _length(_union(v)) / n_dev / 1e9
                      for k, v in progs.items()},
        "device_ops": sorted(([k, v / n_dev / 1e9] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_label(g, ann), (g[1] - g[0]) / 1e9]
                      for g in gaps[:top]],
    }


def program_seconds(red: dict, prefix: str) -> float | None:
    """Device time of the programs whose name starts with ``prefix``
    (``jit_lsh_probe_jnp``, ...); None when no such program ran."""
    got = [v for k, v in red["program_s"].items() if k.startswith(prefix)]
    return sum(got) if got else None
