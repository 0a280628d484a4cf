"""``correct`` comes out false for the control and for each fault a cell
can have, with the timed path broken underneath a whole run on the CPU.

The control is the program's own lower-precision path: 8-bit stored codes
(``b=8``) where the configuration states exact 32-bit codes.  The chip runs
of the control at the cells' own sizes are recorded in PERF.md."""

import time

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny


def run(name, **kw):
    return harness.run_cell(tiny.cell(name), seed=2 ** 31 + 9, seconds=1.0,
                            traced=False, t_start=time.perf_counter(),
                            log=lambda msg: None, **kw)


def test_sound_run_is_correct():
    out = run("search-k256.stream")
    assert out["correct"] is True and out["failed"] == 0


def test_control_lower_precision_codes_is_not_correct():
    out = run("search-k256.stream", b=8)
    assert out["correct"] is False
    assert out["compared"]["wrong_answers"]["value"] > 0


def _altered_answer(svc):
    query = svc._query

    def altered(signed, top_k):
        ids, scores = query(signed, top_k)
        ids = np.array(ids)
        ids[0, 0] += 1                 # one id of each batch, where produced
        return ids, scores
    svc._query = altered


def _half_batch(svc):
    query = svc._query

    def half(signed, top_k):
        h = len(signed) // 2           # the rest of the batch left out
        ids = np.full((len(signed), top_k), -1, np.int64)
        scores = np.zeros((len(signed), top_k), np.float32)
        if h:
            ids[:h], scores[:h] = query(signed[:h], top_k)
        return ids, scores
    svc._query = half


def _state_unchanged(svc):
    svc._scatter = lambda signed: None


def _half_insert(svc):
    scatter = svc._scatter
    svc._scatter = lambda signed: scatter(np.asarray(signed)[
        : len(signed) // 2])


@pytest.mark.parametrize("name,fault", [
    ("search-k256.stream", _altered_answer),
    ("search-k256.stream", _half_batch),
    ("dedup-k128.ingest", _state_unchanged),
    ("dedup-k128.ingest", _half_insert),
])
def test_fault_is_not_correct(name, fault):
    out = run(name, hook=fault)
    assert out["correct"] is False and out["failed"] > 0
