"""Transport plane: real tcp shard workers vs the in-process plane.

The acceptance contract: a tcp-backed ``ShardedSketchStore`` (worker
processes on localhost, framed wire protocol) answers **bit-identically**
to the in-process plane — and to a single ``SketchStore`` — on the same
items, for S in {1, 2, 4}, including the brute-force-fallback rows.  Plus
failure semantics: a killed worker surfaces as a client-side exception
within the fan-out timeout (never a hang), worker-side errors propagate
with their message, and snapshots round-trip both directions (tcp save ->
inproc load, inproc save -> worker snapshot boot).

These tests spawn real processes; each spawn re-imports jax, so they are
grouped to spend as few worker boots as possible.
"""

import time

import numpy as np
import pytest

from repro.store import ShardedSketchStore, SketchStore, StoreConfig
from repro.transport import (TransportError, WorkerError, connect_sharded,
                             shutdown_plane, spawn_workers)

K, NB, R = 64, 16, 4
SHARD_COUNTS = [1, 2, 4]


def _corpus(n=120, k=K, seed=0, dup_pairs=3):
    rng = np.random.default_rng(seed)
    sigs = rng.integers(0, 1 << 16, (n, k), dtype=np.int32)
    for t in range(dup_pairs):          # planted exact duplicates
        sigs[n - 1 - t] = sigs[t]
    return sigs


def _queries(sigs, n_strangers=2, seed=1):
    """Indexed rows + strangers that hit no bucket anywhere (forcing the
    global brute-force-fallback leg over the wire)."""
    rng = np.random.default_rng(seed)
    strangers = rng.integers(1 << 20, 1 << 24,
                             (n_strangers, sigs.shape[1]), dtype=np.int32)
    return np.concatenate([sigs[:10], strangers])


def _shutdown(store, handles):
    assert shutdown_plane(store, handles, join_timeout=15)
    for h in handles:
        assert not h.alive, f"worker {h.shard} survived graceful shutdown"


@pytest.mark.parametrize("s", SHARD_COUNTS)
def test_tcp_plane_bit_identical(s, tmp_path):
    """tcp == inproc == single store: ids, scores, fallback rows, stats —
    plus a snapshot written over the wire reloads in-process exactly."""
    sigs = _corpus(seed=s)
    q = _queries(sigs, seed=s + 1)
    cfg = StoreConfig(k=K, n_bands=NB, rows_per_band=R)
    single = SketchStore(cfg)
    single.add(sigs)
    inproc = ShardedSketchStore(cfg, s)
    inproc.add(sigs)
    handles = spawn_workers(cfg, s)
    try:
        tcp = connect_sharded([h.address for h in handles], cfg, timeout=60)
        gids = tcp.add(sigs)
        assert np.array_equal(gids, np.arange(len(sigs)))
        for top_k in (1, 5):
            want_ids, want_scores = single.query(q, top_k=top_k)
            in_ids, in_scores = inproc.query(q, top_k=top_k)
            got_ids, got_scores = tcp.query(q, top_k=top_k)
            assert np.array_equal(want_ids, in_ids)
            assert np.array_equal(want_ids, got_ids)
            assert np.array_equal(want_scores, in_scores)
            assert np.array_equal(want_scores, got_scores)
        assert np.array_equal(tcp.shard_sizes(), inproc.shard_sizes())
        assert tcp.n_spilled == inproc.n_spilled
        # workers resolve probe_impl="auto" against THEIR backend at boot
        # and report the choice in STATS (a mixed CPU/accelerator fleet
        # serves one plane, each worker on its best probe path)
        for sh in tcp.shards:
            assert sh.stats()["probe_impl"] in ("numpy", "jnp", "pallas")
            assert sh.stats()["query_impl"] in ("jnp", "pallas", "host")
            # ...on the JAX platform the coordinator pinned at spawn
            assert sh.stats()["platform"] == "cpu"
        # wall-time split is populated for the artifact row
        assert set(tcp.last_timings) == \
            {"fold_s", "broadcast_s", "partial_s", "merge_s"}
        # snapshot written worker-side, reloaded in-process: same answers
        snap = str(tmp_path / "plane")
        tcp.save(snap)
        re = ShardedSketchStore.load(snap)
        want = single.query(q, top_k=4)
        got = re.query(q, top_k=4)
        assert np.array_equal(want[0], got[0])
        assert np.array_equal(want[1], got[1])
        _shutdown(tcp, handles)
    finally:
        for h in handles:
            h.terminate()


def test_worker_fails_at_boot_on_wrong_platform(monkeypatch):
    """A worker that cannot get the JAX platform it was spawned for dies
    before reporting an address — it never serves from some other backend
    in silence."""
    from repro.transport import server
    monkeypatch.setattr(server, "WORKER_PLATFORM", "no_such_platform")
    cfg = StoreConfig(k=K, n_bands=NB, rows_per_band=R)
    with pytest.raises(RuntimeError, match="shard worker 0"):
        spawn_workers(cfg, 1, start_timeout=60)


def test_tcp_packed_path_and_snapshot_boot(tmp_path):
    """Fused packed ingest/query over the wire, then workers booted FROM an
    inproc snapshot answer identically (the resharding/boot workflow)."""
    import jax.numpy as jnp

    from repro.kernels import ops

    sigs = _corpus(seed=9)
    cfg = StoreConfig(k=K, n_bands=NB, rows_per_band=R)
    words = np.asarray(ops.pack_codes(jnp.asarray(sigs), 32))
    qw = np.asarray(ops.pack_codes(jnp.asarray(_queries(sigs, seed=10)), 32))
    single = SketchStore(cfg)
    single.add_packed(words)
    want = single.query_packed(qw, top_k=6)

    inproc = ShardedSketchStore(cfg, 2, partition="hash")
    inproc.add_packed(words)
    snap = str(tmp_path / "plane")
    inproc.save(snap)

    handles = spawn_workers(None, 2, snapshot_dir=snap)
    try:
        # forgetting snapshot_dir must be rejected, not answer with
        # shard-local ids: the coordinator's (empty) gid maps don't match
        # the workers' stores
        with pytest.raises(WorkerError, match="gid map"):
            connect_sharded([h.address for h in handles], cfg, timeout=60)
        tcp = connect_sharded([h.address for h in handles],
                              snapshot_dir=snap, timeout=60)
        assert tcp.n_items == inproc.n_items
        assert tcp.partition == "hash"
        got = tcp.query_packed(qw, top_k=6)
        assert np.array_equal(want[0], got[0])
        assert np.array_equal(want[1], got[1])
        # the booted plane keeps ingesting: gids continue in arrival order
        more = _corpus(n=30, seed=11, dup_pairs=0)
        w_more = np.asarray(ops.pack_codes(jnp.asarray(more), 32))
        assert np.array_equal(tcp.add_packed(w_more),
                              np.arange(len(sigs), len(sigs) + 30))
        single.add_packed(w_more)
        inproc.add_packed(w_more)
        want2 = single.query_packed(qw, top_k=6)
        got2 = tcp.query_packed(qw, top_k=6)
        in2 = inproc.query_packed(qw, top_k=6)
        assert np.array_equal(want2[0], got2[0])
        assert np.array_equal(want2[1], got2[1])
        assert np.array_equal(want2[0], in2[0])
        _shutdown(tcp, handles)
    finally:
        for h in handles:
            h.terminate()


def test_killed_worker_raises_within_timeout():
    """A dead worker is a client-side exception, never a hang — both on the
    fan-out path and on the blocking request path."""
    sigs = _corpus(n=60, dup_pairs=0)
    cfg = StoreConfig(k=K, n_bands=NB, rows_per_band=R)
    handles = spawn_workers(cfg, 2)
    try:
        tcp = connect_sharded([h.address for h in handles], cfg, timeout=5)
        tcp.add(sigs)
        tcp.query(sigs[:4], top_k=3)           # plane is healthy first
        handles[1].proc.kill()                 # SIGKILL: no goodbye frame
        handles[1].proc.join(10)
        t0 = time.monotonic()
        with pytest.raises(TransportError):
            tcp.query(sigs[:4], top_k=3)
        assert time.monotonic() - t0 < 30
        t0 = time.monotonic()
        with pytest.raises(TransportError):
            tcp.add(sigs)                      # blocking path fails too
        assert time.monotonic() - t0 < 30
    finally:
        for h in handles:
            h.terminate()


def test_killed_worker_mid_add_poisons_plane():
    """A worker killed under the ADD fan-out raises within the deadline AND
    poisons the plane: the surviving shard may have indexed its slice, so a
    retry would re-issue the same gids and double-index — the plane must
    refuse further writes and reads instead (mirrors the query-side kill
    test, which stays read-only and does NOT poison)."""
    sigs = _corpus(n=60, dup_pairs=0)
    cfg = StoreConfig(k=K, n_bands=NB, rows_per_band=R)
    handles = spawn_workers(cfg, 2)
    try:
        tcp = connect_sharded([h.address for h in handles], cfg, timeout=5)
        tcp.add(sigs)                          # plane is healthy first
        handles[0].proc.kill()                 # SIGKILL: no goodbye frame
        handles[0].proc.join(10)
        t0 = time.monotonic()
        with pytest.raises(TransportError):
            tcp.add(sigs)                      # fan-out write hits the corpse
        assert time.monotonic() - t0 < 30
        with pytest.raises(RuntimeError, match="inconsistent"):
            tcp.add(sigs)                      # retry must not double-index
        with pytest.raises(RuntimeError, match="inconsistent"):
            tcp.query(sigs[:4], top_k=3)
    finally:
        for h in handles:
            h.terminate()


def test_failed_query_fanout_does_not_poison_writes():
    """Queries are read-only: a fan-out that dies mid-QUERY must not mark
    the plane inconsistent — the surviving plane still refuses nothing
    (the degraded query itself raises, as always)."""
    sigs = _corpus(n=40, dup_pairs=0)
    cfg = StoreConfig(k=K, n_bands=NB, rows_per_band=R)
    handles = spawn_workers(cfg, 1)
    try:
        tcp = connect_sharded([h.address for h in handles], cfg, timeout=5)
        tcp.add(sigs)
        handles[0].proc.kill()
        handles[0].proc.join(10)
        with pytest.raises(TransportError):
            tcp.query(sigs[:4], top_k=3)
        assert tcp._failed is None             # reads never poison
    finally:
        for h in handles:
            h.terminate()


def test_stale_reply_discarded():
    """A reply left over from an abandoned request (its seq never matches)
    is skipped — the connection pairs each request with its own reply."""
    import socket
    import threading

    from repro.transport.client import ShardConnection
    from repro.transport.wire import (Message, MsgType, recv_message,
                                      send_message)

    lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)

    def serve():
        conn, _ = lsock.accept()
        with conn:
            msg = recv_message(conn)
            send_message(conn, Message(MsgType.OK, {"n": 99}, seq=0xDEAD))
            send_message(conn, Message(MsgType.OK, {"n": 7}, seq=msg.seq))

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    try:
        c = ShardConnection(lsock.getsockname(), timeout=10)
        assert int(c.request(Message(MsgType.STATS, {}))["n"]) == 7
        c.close()
        t.join(10)
    finally:
        lsock.close()


def _fake_worker(handler):
    """A scripted TCP shard 'worker' for protocol-level failure tests:
    runs ``handler(conn)`` for one accepted connection on a daemon thread.
    Returns (listener socket, thread)."""
    import socket
    import threading

    lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)

    def serve():
        conn, _ = lsock.accept()
        with conn:
            handler(conn)

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    return lsock, t


def test_one_shard_error_does_not_brick_the_group():
    """An ERROR reply from one shard raises WorkerError — and the fan-out
    group abandons the round cleanly, so the next query works instead of
    tripping the one-outstanding-request guard."""
    from repro.transport.client import (FanoutGroup, RemoteShard,
                                        ShardConnection)
    from repro.transport.wire import (Message, MsgType, recv_message,
                                      send_message)

    def ok_partial(conn, rounds=2):
        for _ in range(rounds):
            msg = recv_message(conn)
            q = msg["qwords"].shape[0]
            send_message(conn, Message(MsgType.PARTIAL, {
                "ids": np.full((q, 3), -1, np.int64),
                "scores": np.full((q, 3), -np.inf, np.float32),
                "has": np.zeros(q, bool)}, seq=msg.seq))

    def error_then_ok(conn):
        msg = recv_message(conn)
        send_message(conn, Message(MsgType.ERROR, {"error": "boom"},
                                   seq=msg.seq))
        ok_partial(conn, rounds=1)

    l0, t0 = _fake_worker(error_then_ok)
    l1, t1 = _fake_worker(lambda c: ok_partial(c, rounds=2))
    try:
        conns = [ShardConnection(l0.getsockname(), timeout=10),
                 ShardConnection(l1.getsockname(), timeout=10)]
        group = FanoutGroup(conns, timeout=10)
        shards = [RemoteShard(c, group) for c in conns]
        hashes = np.zeros((2, NB), np.uint64)
        qw = np.zeros((2, K), np.uint32)
        pend = [sh.start_query(hashes, qw, 3, "sig") for sh in shards]
        with pytest.raises(WorkerError, match="boom"):
            for p in pend:
                p.result()
        # the plane is still queryable: a fresh round completes on both
        pend = [sh.start_query(hashes, qw, 3, "sig") for sh in shards]
        for p in pend:
            part = p.result()
            assert part.ids.shape == (2, 3)
        for c in conns:
            c.close()
    finally:
        l0.close()
        l1.close()


def test_midframe_timeout_poisons_connection():
    """A reply cut mid-frame by a timeout cannot be re-synced by seq
    pairing — the connection must refuse further use, not misparse."""
    import time as _time

    from repro.transport.client import ShardConnection
    from repro.transport.wire import Message, MsgType, message_bytes, \
        recv_message

    def half_reply(conn):
        msg = recv_message(conn)
        frame = message_bytes(Message(MsgType.OK, {"n": 1}, seq=msg.seq))
        conn.sendall(frame[: len(frame) - 4])      # cut mid-frame
        _time.sleep(3)                             # past the client timeout

    lsock, _ = _fake_worker(half_reply)
    try:
        c = ShardConnection(lsock.getsockname(), timeout=1)
        with pytest.raises(TransportError):
            c.request(Message(MsgType.STATS, {}))
        assert c.broken
        with pytest.raises(WorkerError, match="unusable"):
            c.request(Message(MsgType.STATS, {}))
    finally:
        lsock.close()


def test_worker_survives_client_hangup_mid_reply():
    """A client that disconnects before reading a (large) reply must not
    kill the worker: it returns to accept and serves the next client."""
    import socket

    from repro.transport.wire import Message, MsgType, send_message

    cfg = StoreConfig(k=K, n_bands=NB, rows_per_band=R)
    handles = spawn_workers(cfg, 1)
    try:
        # raw client: request a ~1.2 MB brute partial, vanish immediately
        rude = socket.create_connection(handles[0].address, timeout=30)
        send_message(rude, Message(
            MsgType.BRUTE,
            {"qwords": np.zeros((2000, K), np.uint32), "top_k": 50}, seq=1))
        rude.close()
        # the worker must still be there for a well-behaved coordinator
        tcp = connect_sharded([handles[0].address], cfg, timeout=60)
        sigs = _corpus(n=30, dup_pairs=0)
        tcp.add(sigs)
        ids, _ = tcp.query(sigs[:3], top_k=2)
        assert np.array_equal(ids[:, 0], np.arange(3))
        assert handles[0].alive
        _shutdown(tcp, handles)
    finally:
        for h in handles:
            h.terminate()


def test_hedged_reads_bit_identical_and_win():
    """With one shard sleeping on most of its reads, hedged twin reads must
    (a) fire, (b) win some races, and (c) never change a single bit of any
    answer — the losing leg's late reply is discarded by seq, not merged."""
    from repro.transport import HedgePolicy

    sigs = _corpus(seed=21)
    q = _queries(sigs, seed=22)
    cfg = StoreConfig(k=K, n_bands=NB, rows_per_band=R)
    single = SketchStore(cfg)
    single.add(sigs)
    want = single.query(q, top_k=5)
    handles = spawn_workers(cfg, 2, slow_shards={1: (0.8, 0.03)})
    try:
        tcp = connect_sharded([h.address for h in handles], cfg, timeout=60,
                              hedge=HedgePolicy(delay_s=0.005))
        tcp.add(sigs)
        for _ in range(15):
            got = tcp.query(q, top_k=5)
            assert np.array_equal(want[0], got[0])
            assert np.array_equal(want[1], got[1])
        g = tcp.shards[0].group
        assert g.n_hedges > 0, "slow shard never triggered a hedge"
        assert g.n_hedge_wins > 0, "no hedge ever beat a 30 ms stall"
        _shutdown(tcp, handles)
    finally:
        for h in handles:
            h.terminate()


def test_hedge_delay_derives_from_peer_skew():
    """The adaptive delay for a shard comes from its PEERS' reply-skew
    histograms, never its own: a stalling shard's own percentiles are
    inflated by rounds queued behind each stall, and a self-derived delay
    would grow past the stall and veto the very hedge that should cut it.
    (``FanoutGroup``'s ctor never touches sockets, so plain objects stand
    in for connections.)"""
    from repro.transport import HedgePolicy
    from repro.transport.client import FanoutGroup

    slow, fast1, fast2 = object(), object(), object()
    g = FanoutGroup([slow, fast1, fast2], hedge=HedgePolicy(),
                    hedge_conns={slow: object(), fast1: object(),
                                 fast2: object()})
    for _ in range(40):                 # peers land ~2 ms after the fastest
        g._lat_h[fast1].observe(0.002)
        g._lat_h[fast2].observe(0.002)
        g._lat_h[slow].observe(0.5)     # the slow shard skews 500 ms
    g._msgs = {slow: object(), fast1: object()}   # hedgeable this round
    d = g._hedge_delay(slow)
    assert d is not None and d < 0.05, \
        f"slow shard's own history leaked into its delay (got {d})"
    # the healthy shard's delay sees the slow peer's fat tail — that only
    # makes its hedges rarer, never wrong
    assert g._hedge_delay(fast1) is not None
    assert g._hedge_delay(fast2) is None          # not hedgeable this round
    # a single-connection group has no peers, hence no skew signal: the
    # adaptive mode never hedges it (a fixed delay_s still would)
    lone = FanoutGroup([slow], hedge=HedgePolicy(),
                       hedge_conns={slow: object()})
    lone._msgs = {slow: object()}
    for _ in range(40):
        lone._lat_h[slow].observe(0.002)
    assert lone._hedge_delay(slow) is None


def test_writes_never_hedge():
    """ADD is not idempotent: even with an immediate hedge delay, only the
    read path (QUERY/BRUTE) may re-issue on the twin connection."""
    from repro.transport import HedgePolicy

    sigs = _corpus(n=80, dup_pairs=0)
    cfg = StoreConfig(k=K, n_bands=NB, rows_per_band=R)
    handles = spawn_workers(cfg, 2, slow_shards={0: (1.0, 0.02)})
    try:
        tcp = connect_sharded([h.address for h in handles], cfg, timeout=60,
                              hedge=HedgePolicy(delay_s=0.0))
        g = tcp.shards[0].group
        tcp.add(sigs)
        tcp.add(_corpus(n=40, seed=5, dup_pairs=0))
        assert g.n_hedges == 0, "a write was hedged"
        tcp.query(sigs[:4], top_k=3)           # every read stalls 20 ms:
        assert g.n_hedges > 0                  # delay-0 hedges must fire
        _shutdown(tcp, handles)
    finally:
        for h in handles:
            h.terminate()


def test_query_timeout_error_names_the_knob():
    """A fan-out deadline on the query path tells the operator WHICH
    deadline expired (``query_timeout_s``), not just that one did."""
    sigs = _corpus(n=60, dup_pairs=0)
    cfg = StoreConfig(k=K, n_bands=NB, rows_per_band=R)
    handles = spawn_workers(cfg, 1, slow_shards={0: (1.0, 2.0)})
    try:
        tcp = connect_sharded([h.address for h in handles], cfg, timeout=0.5)
        tcp.add(sigs)                          # writes are never slowed
        with pytest.raises(TransportError, match="query_timeout_s"):
            tcp.query(sigs[:2], top_k=3)
    finally:
        for h in handles:
            h.terminate()


def test_worker_error_propagates_with_message():
    """A worker-side exception comes back as WorkerError carrying the
    worker's own message, and the worker keeps serving afterwards."""
    cfg = StoreConfig(k=K, n_bands=NB, rows_per_band=R)
    handles = spawn_workers(cfg, 1)
    try:
        tcp = connect_sharded([h.address for h in handles], cfg, timeout=60)
        with pytest.raises(WorkerError, match="expected"):
            tcp.add(np.zeros((2, K + 1), np.int32))     # wrong K
        sigs = _corpus(n=40, dup_pairs=0)
        tcp.add(sigs)                          # connection still healthy
        ids, _ = tcp.query(sigs[:3], top_k=2)
        assert np.array_equal(ids[:, 0], np.arange(3))
        _shutdown(tcp, handles)
    finally:
        for h in handles:
            h.terminate()
