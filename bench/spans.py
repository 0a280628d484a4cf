"""Device idle time put down to the program's own spans.

``bench/trace.py`` names each idle gap by the benchmark's ``bench.*``
annotations, which wrap the service from outside.  This module reads the
program's own spans from the same ``.xplane.pb``: the host events named in
the catalogue ``repro.obs.trace.SPANS`` (each a ``Timer`` of the served
query and ingest paths, written as a profiler annotation).  Every
device-idle stretch of the window goes to the innermost program span open
at that instant (the shortest, as spans on one thread nest), or to
``outside`` when none is:

* ``idle_by_span``: device-idle seconds of the window under each span, plus
  ``outside``; they sum to the window's idle time (averaged over the devices
  that ran ops, as ``trace.reduce``'s ``busy_s`` is);
* ``long_idle_gaps``: each idle gap of at least ``min_gap_s``, as
  ``[span holding most of it, seconds, start in seconds from the window's]``.

A program without the catalogue (or a trace without its spans) leaves every
idle second ``outside``.
"""

from __future__ import annotations

import gzip
import heapq

from . import trace as btrace

OUTSIDE = "outside"


def catalogue() -> frozenset:
    """The program's span names; empty where the program has none."""
    try:
        from repro.obs.trace import SPANS
    except ImportError:
        return frozenset()
    return frozenset(SPANS)


def load(path: str, names=None) -> list[tuple[str, str, int, int]]:
    """``(host line, name, start_ns, end_ns)`` of every host event of an
    ``.xplane.pb`` (or ``.xplane.pb.gz``) whose name is in ``names``
    (default: the program's catalogue)."""
    from jax.profiler import ProfileData
    names = catalogue() if names is None else frozenset(names)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name in names:
                    out.append((ln.name, e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def _idle(busy: list, lo: float, hi: float) -> list[tuple[float, float]]:
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def _split(idle: list, spans: list) -> list[dict]:
    """Per idle interval, ``{span name: ns}`` of the innermost span open
    over each part of it (``OUTSIDE`` where none is).  One sweep over every
    edge, the open spans kept in a heap keyed by length."""
    edges = sorted({x for ab in idle for x in ab}
                   | {x for s, t, _ in spans for x in (s, t)})
    order = sorted(range(len(spans)), key=lambda i: spans[i][0])
    out: list[dict] = [{} for _ in idle]
    heap: list = []
    k = g = 0
    for a, b in zip(edges, edges[1:]):
        while k < len(order) and spans[order[k]][0] <= a:
            s, t, _ = spans[order[k]]
            heapq.heappush(heap, (t - s, -s, order[k]))
            k += 1
        while heap and spans[heap[0][2]][1] <= a:
            heapq.heappop(heap)
        while g < len(idle) and idle[g][1] <= a:
            g += 1
        if g < len(idle) and idle[g][0] <= a:
            name = spans[heap[0][2]][2] if heap else OUTSIDE
            out[g][name] = out[g].get(name, 0) + (b - a)
    return out


def attribute(ev: btrace.Events, spans: list, min_gap_s: float = 0.05
              ) -> dict:
    """``idle_by_span`` and ``long_idle_gaps`` of the ``bench.window`` of
    ``ev`` (from ``trace.load``), given the program's ``spans`` (from
    ``load``)."""
    lo, hi = btrace.window_of(ev)
    planes = sorted({p for p, *_ in ev.device})
    sp = [(max(s, lo), min(t, hi), name) for _, name, s, t in spans
          if t > lo and s < hi]
    by_span: dict[str, float] = {OUTSIDE: 0.0}
    gaps = []
    for p in planes:
        busy = btrace._union(btrace._clip(
            [(s, t) for q, _, _, s, t in ev.device if q == p], lo, hi))
        idle = _idle(busy, lo, hi)
        for (a, b), parts in zip(idle, _split(idle, sp)):
            for name, ns in parts.items():
                by_span[name] = by_span.get(name, 0.0) + ns
            if b - a >= min_gap_s * 1e9:
                gaps.append([max(parts, key=parts.get), (b - a) / 1e9,
                             (a - lo) / 1e9])
    n_dev = max(len(planes), 1)
    gaps.sort(key=lambda g: -g[1])
    return {"idle_by_span": {k: v / n_dev / 1e9 for k, v in sorted(
                by_span.items(), key=lambda kv: -kv[1])},
            "long_idle_gaps": gaps}
