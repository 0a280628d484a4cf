"""The benchmark's cells cut to a size a CPU test run can hold."""

from __future__ import annotations

import os

from bench import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cell(name: str) -> manifest.Cell:
    c = manifest.cell(ROOT, name)
    c.config = dict(c.config, index_docs=2048, build_batch=512)
    if c.traffic["kind"] == "ingest":
        c.traffic = dict(c.traffic, batch=256, fresh_docs=4096,
                         n_slots=8192, check_queries=128, check_batch=64)
    else:
        c.traffic = dict(c.traffic, phases=[{"rate_qps": 200, "ms": 1000}],
                         max_batch=8)
    return c
