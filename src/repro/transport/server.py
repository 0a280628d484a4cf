"""Shard worker process: one ``SketchStore`` behind a framed TCP socket.

A worker is the remote half of the ``ShardBackend`` split: it owns exactly
the state an ``InProcessShard`` owns (one ``SketchStore``) and serves the
same operations over the wire protocol — ADD batches, the QUERY hash
broadcast (candidates + ``partial_topk_packed``), the BRUTE fallback leg,
STATS, SNAPSHOT, and a graceful SHUTDOWN.  All ranking code is the store's
own; the worker adds no scoring logic, which is what keeps tcp answers
bit-identical to the in-process plane.

Workers are ``multiprocessing``-spawnable (the entry point takes only
picklable arguments) and boot either empty from a ``StoreConfig`` or from a
per-shard snapshot written by ``ShardedSketchStore.save``.  The bound
address travels back to the parent over a one-shot pipe so workers can bind
port 0 and never race over port numbers.

Connections are served one thread each, so a coordinator may hold more than
one connection to the same worker — which is what makes hedged queries
(``client.HedgePolicy``) work: a hedge re-issue on the second connection is
accepted and answered even while the primary connection is stalled.  The
``SketchStore`` itself is not thread-safe, so actual request *handling* is
serialized behind one worker-wide lock; the concurrency buys bypass of
head-of-line stalls that happen outside the store (socket backlog, a
dropped reply, the injected-slowness sleep below), which is exactly the
class of stall hedging targets.

Failure semantics: a handler exception is caught and answered with an ERROR
frame (the connection stays up); a protocol-level decode failure (bad
checksum, truncated frame) also gets an ERROR frame but then drops the
connection, since the stream can no longer be trusted to be in sync.  EOF
from the client returns the worker to ``accept`` — a coordinator can
reconnect.  Only SHUTDOWN (acked first) exits the process.

One process per chip: a coordinator that serves from an accelerator holds
that chip, and a worker that tried to claim it too would fail on the
runtime's lock or quietly land on the CPU.  So a worker's JAX platform is
never left to chance: ``spawn_workers`` pins ``WORKER_PLATFORM`` (the
host CPU) in each child's environment and JAX config before anything
compiles, the worker reports it in STATS next to
``probe_impl``/``query_impl``, and a worker that did not get the platform
it was asked for fails at boot.

``spawn_workers(slow_shards=...)`` injects probabilistic latency into a
worker's QUERY/BRUTE handling (a pre-handle sleep) — the reproducible
"one slow shard" scenario the hedging benchmarks and CI smoke use to
demonstrate tail-latency cuts without relying on a noisy host.
"""

from __future__ import annotations

import json
import multiprocessing
import os
import random
import select
import socket
import threading
import time
import traceback

import jax
import numpy as np

from repro.launch.compile_cache import setup_compile_cache
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.store.sharded import shard_snapshot_path
from repro.store.store import SketchStore, StoreConfig

from . import wire
from .faults import KILL_EXIT_CODE, FaultPlan
from .wire import Message, MsgType

# the JAX platform spawned workers run on: the host CPU, since the
# coordinator process holds the accelerator (one process per chip)
WORKER_PLATFORM = "cpu"

GATE_LIMIT_ENV = "REPRO_GATE_LIMIT"
DEFAULT_GATE_LIMIT = 64

# overload control gates READS only: an OVERLOADED write leg would surface
# as a failed scatter round — poisoning the unreplicated plane and downing
# the lane on a replicated one — so writes keep their existing backpressure
# (the bounded ingest pipeline + the poison taxonomy) and the gate protects
# the latency-sensitive read path, where shedding is cheap and clean
_GATED_TYPES = (MsgType.QUERY, MsgType.BRUTE)


class AdmissionGate:
    """Bounded-inflight admission for a worker's read path.

    ``limit`` caps requests admitted concurrently (executing + waiting on
    the exec lock across all connection threads).  At the cap the worker
    answers ``OVERLOADED`` instead of queueing — the queue that would have
    formed here is unbounded memory and head-of-line latency with no one
    left to read the answer; an explicit reject is retryable within the
    caller's budget and deadline.
    """

    def __init__(self, limit: int):
        self.limit = int(limit)
        self._n = 0
        self._lock = threading.Lock()
        reg = obs_metrics.default()
        self._depth_g = reg.gauge("worker.admission.depth")
        reg.gauge("worker.admission.limit").set(self.limit)
        self.n_overloaded = reg.counter("worker.overloaded")
        self.n_expired = reg.counter("worker.expired")

    @property
    def depth(self) -> int:
        return self._n

    def try_enter(self) -> bool:
        with self._lock:
            if self._n >= self.limit:
                return False
            self._n += 1
            self._depth_g.set(self._n)
            return True

    def leave(self) -> None:
        with self._lock:
            self._n -= 1
            self._depth_g.set(self._n)


def _overloaded_reply(reason: str, retry_after_us: int,
                      gate: "AdmissionGate | None") -> Message:
    f = {"reason": reason, "retry_after_us": int(retry_after_us)}
    if gate is not None:
        f["gate_depth"] = gate.depth
        f["gate_limit"] = gate.limit
    return Message(MsgType.OVERLOADED, f)


def _handle(store: SketchStore, msg: Message,
            shard: int = -1, replica: int = 0,
            gate: "AdmissionGate | None" = None) -> tuple[Message, bool]:
    """One request -> (reply, keep_serving)."""
    f = msg.fields
    if msg.type == MsgType.ADD:
        # a failed ADD must report whether it mutated the store: the
        # coordinator keeps a retry safe only when the batch provably did
        # not land (otherwise it poisons the plane instead of duplicating)
        before = (store.size, store.table.n_items)
        try:
            if "rows" in f:
                n = len(store.add(np.asarray(f["rows"], np.int32)))
            elif "words" in f:
                n = len(store.add_packed(np.asarray(f["words"], np.uint32)))
            else:
                raise wire.ProtocolError("ADD needs 'rows' or 'words'")
        except Exception as e:
            if (store.size, store.table.n_items) != before:
                e.add_dirty = True
            raise
        return Message(MsgType.OK, {"n": n}), True
    if msg.type == MsgType.QUERY:
        hashes = wire.join_u64(f["hash_lo"], f["hash_hi"])
        # the store routes to the fused device pipeline or the legacy host
        # walk per its query_impl knob — bit-identical either way
        part = store.partial_topk_packed_hashed(
            hashes, np.asarray(f["qwords"], np.uint32), int(f["top_k"]),
            mode=f["mode"])
        return Message(MsgType.PARTIAL,
                       {"ids": part.ids, "scores": part.scores,
                        "has": part.has_candidates}), True
    if msg.type == MsgType.BRUTE:
        part = store.planner.brute_partial_packed(
            np.asarray(f["qwords"], np.uint32), int(f["top_k"]))
        return Message(MsgType.PARTIAL,
                       {"ids": part.ids, "scores": part.scores,
                        "has": part.has_candidates}), True
    if msg.type == MsgType.STATS:
        # ``obs`` is this worker's full registry snapshot (store/table/
        # kernel instrumentation plus the worker.* transport metrics) as a
        # JSON string — the coordinator merges these across shards with
        # ``obs.metrics.merge_snapshots`` exactly like ``merge_topk``
        return Message(MsgType.OK, {"size": store.size,
                                    "n_spilled": store.n_spilled,
                                    "n_rebuilds": store.n_rebuilds,
                                    "probe_impl": store.probe_impl,
                                    "query_impl": store.query_impl,
                                    "platform": jax.default_backend(),
                                    "pid": os.getpid(),
                                    "shard": int(shard),
                                    "replica": int(replica),
                                    "gate_limit": gate.limit if gate else -1,
                                    "gate_depth": gate.depth if gate else 0,
                                    "n_overloaded":
                                        gate.n_overloaded.value if gate else 0,
                                    "n_expired":
                                        gate.n_expired.value if gate else 0,
                                    "obs": json.dumps(
                                        obs_metrics.default().snapshot())
                                    }), True
    if msg.type == MsgType.DIGEST:
        # signature-buffer content digest — the resync parity check a
        # respawned replica must pass against a live peer before rejoining
        return Message(MsgType.OK, store.digest()), True
    if msg.type == MsgType.SNAPSHOT:
        store.save(f["path"])
        return Message(MsgType.OK, {}), True
    if msg.type == MsgType.SHUTDOWN:
        return Message(MsgType.OK, {}), False
    raise wire.ProtocolError(f"unexpected message type {msg.type!r}")


def _serve_conn(store: SketchStore, conn: socket.socket,
                shard: int = -1, *,
                exec_lock: threading.Lock | None = None,
                slow: tuple[float, float] | None = None,
                replica: int = 0,
                gate: AdmissionGate | None = None,
                faults: FaultPlan | None = None) -> bool:
    """Serve one coordinator connection.  Returns False when SHUTDOWN.

    ``exec_lock`` serializes handler execution across this worker's
    connection threads (the store is single-threaded code).  ``slow`` is
    ``(prob, sleep_s)`` injected latency: each QUERY/BRUTE independently
    sleeps ``sleep_s`` with probability ``prob`` *before* taking the lock,
    so a hedged re-issue of the same request gets a fresh draw and can
    overtake a sleeping primary.

    ``gate`` bounds read inflight (reject with OVERLOADED at the cap);
    expired-deadline reads are dropped before computing.  ``faults`` is
    the worker's deterministic fault schedule, consulted pre-handle —
    a plan ``kill`` dies before mutating the store, a ``drop`` closes the
    connection without a reply, a ``truncate`` sends a half frame (the
    peer sees a corrupt stream, not a clean hangup).
    """
    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    if exec_lock is None:
        exec_lock = threading.Lock()
    rng = random.Random()
    reg = obs_metrics.default()
    tracer = obs_trace.default()
    bytes_in = reg.counter("worker.bytes_in")
    bytes_out = reg.counter("worker.bytes_out")
    errors = reg.counter("worker.errors")
    wire_errors = reg.counter("worker.wire_errors")
    backlog = reg.counter("worker.backlog")
    faults_fired = reg.counter("worker.faults_fired")
    handle_h = {t: reg.histogram(f"worker.handle.{t.name.lower()}")
                for t in MsgType}
    while True:
        try:
            msg = wire.recv_message(conn, meter=bytes_in.inc)
        except wire.ConnectionClosed:
            return True                          # client went away: re-accept
        except wire.WireError as e:              # stream out of sync: drop it
            wire_errors.inc()
            try:
                wire.send_message(conn, Message(
                    MsgType.ERROR, {"error": f"{type(e).__name__}: {e}"}),
                    meter=bytes_out.inc)
            except OSError:
                pass
            return True
        if faults is not None:
            for ev in faults.on_message(msg.type.name.lower()):
                faults_fired.inc()
                if ev.kind == "delay":
                    FaultPlan.sleep(ev)
                elif ev.kind == "drop":
                    return True                  # EOF mid-round, no reply
                elif ev.kind == "truncate":
                    frame = wire.message_bytes(Message(
                        MsgType.ERROR, {"error": "injected truncation"},
                        seq=msg.seq))
                    try:                         # half a frame, then hangup
                        conn.sendall(frame[:max(wire.HEADER_SIZE + 1,
                                                len(frame) // 2)])
                    except OSError:
                        pass
                    return True
                elif ev.kind == "kill":
                    # fired-event log already fsynced by on_message; die
                    # before handling so the store never half-mutates
                    os._exit(KILL_EXIT_CODE)
        # a request carrying trace fields joins the coordinator's trace:
        # the worker's legs nest under the span whose id rode the frame
        ctx = None
        if wire.TRACE_ID_FIELD in msg.fields:
            ctx = obs_trace.TraceCtx(int(msg.fields[wire.TRACE_ID_FIELD]),
                                     int(msg.fields[wire.TRACE_PARENT_FIELD]))
        admitted = False
        if gate is not None and msg.type in _GATED_TYPES:
            dl = msg.fields.get(wire.DEADLINE_FIELD)
            if dl is not None and time.time() * 1e6 > int(dl):
                # caller's deadline already passed: computing the answer
                # is pure waste — drop before scoring, tell the caller why
                gate.n_expired.inc()
                reply = _overloaded_reply("expired", 0, gate)
                reply.seq = msg.seq
                try:
                    wire.send_message(conn, reply, meter=bytes_out.inc)
                except OSError:
                    return True
                continue
            if not gate.try_enter():
                gate.n_overloaded.inc()
                # back off roughly one queue drain: mean read handle time
                # x current depth (2ms floor when the worker is cold)
                h = handle_h[MsgType.QUERY]
                per = h.mean if h.count else 2e-3
                reply = _overloaded_reply(
                    "admission", int(max(per, 2e-3) * gate.depth * 1e6),
                    gate)
                reply.seq = msg.seq
                try:
                    wire.send_message(conn, reply, meter=bytes_out.inc)
                except OSError:
                    return True
                continue
            admitted = True
        if slow is not None and msg.type in (MsgType.QUERY, MsgType.BRUTE) \
                and rng.random() < slow[0]:
            time.sleep(slow[1])
        t0 = time.perf_counter()
        try:
            # with no ctx (and the worker tracer's sample rate of 0) this
            # returns the shared no-op span — untraced requests pay nothing
            with tracer.span(f"worker.{msg.type.name.lower()}", parent=ctx):
                with exec_lock:
                    reply, keep = _handle(store, msg, shard, replica, gate)
        except Exception as e:                   # worker-side op failure
            errors.inc()
            reply, keep = Message(MsgType.ERROR, {
                "error": f"{type(e).__name__}: {e}",
                "dirty": int(getattr(e, "add_dirty", False)),
                "traceback": traceback.format_exc(limit=8)}), True
        finally:
            if admitted:
                gate.leave()
        handle_h[msg.type].observe(time.perf_counter() - t0)
        if ctx is not None:
            spans = tracer.drain()
            if spans:               # reply carries this worker's spans home
                reply.fields[wire.TRACE_SPANS_FIELD] = json.dumps(spans)
        reply.seq = msg.seq                      # pair reply to its request
        try:
            wire.send_message(conn, reply, meter=bytes_out.inc)
        except OSError:
            return keep    # client vanished before reading: back to accept
        if not keep:
            return False
        # queue-depth proxy for a serial connection: another request
        # already readable the moment we finish one means the coordinator
        # is ahead of us — each such observation is one backlogged request
        try:
            if select.select([conn], [], [], 0)[0]:
                backlog.inc()
        except OSError:
            pass


def run_worker(ready_conn, cfg: StoreConfig | None, snapshot: str | None,
               probe_impl: str, host: str, port: int,
               shard: int = -1, query_impl: str = "auto",
               slow: tuple[float, float] | None = None,
               replica: int = 0, gate_limit: int | None = None,
               fault_spec: str | None = None,
               platform: str = WORKER_PLATFORM) -> None:
    """Worker entry point (spawn target — all arguments picklable).

    Boots a ``SketchStore`` (empty from ``cfg``, or from ``snapshot``),
    binds ``(host, port)`` (port 0 = ephemeral), reports the bound address
    through ``ready_conn``, and serves until SHUTDOWN.  Each accepted
    connection gets its own serving thread (see ``_serve_conn`` for the
    locking discipline); ``slow`` injects probabilistic read latency.

    ``probe_impl="auto"`` and ``query_impl="auto"`` are resolved HERE,
    against this worker's own jax backend — not the coordinator's — so a
    mixed CPU/accelerator fleet serves one plane with each worker on its
    best path (Pallas on its accelerator hosts, compiled-jnp / the numpy
    walk on CPU hosts).  The resolved backends are reported in STATS
    (``probe_impl`` / ``query_impl``).

    ``gate_limit`` bounds admitted read inflight (``REPRO_GATE_LIMIT`` env
    overrides when None; default ``DEFAULT_GATE_LIMIT``; <= 0 keeps the
    gate but admits nothing — the always-shed worker the overload tests
    use).  ``fault_spec`` is a ``FaultPlan.encode()`` JSON schedule
    (``REPRO_FAULTS`` env keyed ``"<shard>.<replica>"`` when None).

    ``platform`` is pinned before the first compile (``_pin_platform``):
    a worker that cannot get it raises here, before reporting an address.
    """
    _pin_platform(platform)
    setup_compile_cache()
    lane = f"{shard}.{replica}"
    if fault_spec is not None:
        faults = FaultPlan.decode(fault_spec, lane=lane)
    else:
        faults = FaultPlan.from_env(lane)
    if gate_limit is None:
        gate_limit = int(os.environ.get(GATE_LIMIT_ENV, DEFAULT_GATE_LIMIT))
    # the worker gets its own tracer labelled with its shard index, so a
    # stitched trace says which process each span ran in; sample rate stays
    # 0 — worker spans only open under a wire-propagated parent, inheriting
    # the coordinator's sampling decision
    proc = f"shard{shard}" if shard >= 0 else f"worker-pid{os.getpid()}"
    if shard >= 0 and replica > 0:       # R-way lanes get distinct proc tags
        proc = f"shard{shard}r{replica}"
    obs_trace.set_default(obs_trace.Tracer(proc=proc))
    if probe_impl == "auto":
        from repro.kernels.dispatch import select_probe_impl
        probe_impl = select_probe_impl()
    if query_impl == "auto":
        from repro.kernels.dispatch import select_query_impl
        query_impl = select_query_impl()
    if snapshot is not None:
        store = SketchStore.load(snapshot)
        store.probe_impl = probe_impl
        store.query_impl = query_impl
    else:
        if cfg is None:
            raise ValueError("worker needs a StoreConfig or a snapshot")
        store = SketchStore(cfg, probe_impl=probe_impl,
                            query_impl=query_impl)
    lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        lsock.bind((host, port))
        lsock.listen(8)
        ready_conn.send(lsock.getsockname())
        ready_conn.close()
        stop = threading.Event()
        exec_lock = threading.Lock()
        gate = AdmissionGate(gate_limit)

        def _serve(conn: socket.socket) -> None:
            try:
                with conn:
                    if not _serve_conn(store, conn, shard,
                                       exec_lock=exec_lock, slow=slow,
                                       replica=replica, gate=gate,
                                       faults=faults):
                        stop.set()
            except ConnectionResetError:
                # normal for a hedge twin: the coordinator closes it with an
                # unread stale reply still buffered, which surfaces as RST
                pass
            except Exception:
                # a crashed serving thread must not take the worker down:
                # the coordinator sees the dropped connection and reacts
                # (mark_broken / TransportError); other connections live on
                traceback.print_exc()

        threads: list[threading.Thread] = []
        lsock.settimeout(0.25)       # bounded accept so SHUTDOWN is noticed
        while not stop.is_set():
            try:
                conn, _ = lsock.accept()
            except socket.timeout:
                continue
            t = threading.Thread(target=_serve, args=(conn,), daemon=True,
                                 name=f"serve-shard{shard}")
            t.start()
            threads.append(t)
        for t in threads:
            t.join(5)
    finally:
        lsock.close()


def _pin_platform(platform: str) -> None:
    """Run this process's JAX on ``platform`` or fail: set in the
    environment (inherited by anything this worker starts) and in JAX's
    config (the environment was read when jax was imported), then checked
    against the backend JAX actually brings up."""
    os.environ["JAX_PLATFORMS"] = platform
    jax.config.update("jax_platforms", platform)
    got = jax.default_backend()
    if got != platform:
        raise RuntimeError(f"shard worker asked for JAX platform "
                           f"{platform!r} but got {got!r}")


class WorkerHandle:
    """A spawned shard worker: its process and its bound address."""

    def __init__(self, proc, address: tuple[str, int], shard: int,
                 replica: int = 0):
        self.proc = proc
        self.address = address
        self.shard = shard
        self.replica = replica

    @property
    def alive(self) -> bool:
        return self.proc.is_alive()

    def join(self, timeout: float | None = None) -> None:
        self.proc.join(timeout)

    def terminate(self) -> None:
        """Hard stop (the graceful path is a client-side SHUTDOWN)."""
        if self.proc.is_alive():
            self.proc.terminate()
        self.proc.join(5)

    def __repr__(self) -> str:
        state = "alive" if self.alive else "dead"
        return f"WorkerHandle(shard={self.shard}, replica={self.replica}, " \
               f"addr={self.address[0]}:{self.address[1]}, {state})"


def spawn_workers(cfg: StoreConfig | None, n_workers: int, *,
                  snapshot_dir: str | None = None, probe_impl: str = "auto",
                  query_impl: str = "auto", host: str = "127.0.0.1",
                  start_timeout: float = 120.0,
                  slow_shards: dict[int, tuple[float, float]] | None = None,
                  shards: list[int] | None = None,
                  replicas: list[int] | None = None,
                  gate_limit: int | None = None,
                  faults: dict[int, "FaultPlan | str"] | None = None,
                  ) -> list[WorkerHandle]:
    """Spawn ``n_workers`` shard workers on localhost; returns their handles.

    Workers start in parallel (the dominant cost is each spawn re-importing
    jax) and each reports its ephemeral port back before this returns.  With
    ``snapshot_dir``, worker ``i`` boots from ``shard_{shards[i]}.npz``
    inside it (the ``ShardedSketchStore.save`` layout) instead of empty from
    ``cfg``.

    ``shards``/``replicas`` give each worker its explicit (shard, replica)
    assignment — a replicated plane spawns R workers per shard index
    (``repro.replica``).  The default is the classic unreplicated layout:
    worker ``i`` IS shard ``i``, replica 0.

    ``slow_shards`` maps WORKER index -> ``(prob, sleep_s)`` injected read
    latency (the hedging benchmarks' reproducible slow-shard scenario; for
    the default layout worker index == shard index).

    ``gate_limit`` sets every worker's read admission cap (None = env /
    default).  ``faults`` maps WORKER index -> ``FaultPlan`` (or its
    ``encode()`` JSON) — the deterministic chaos schedule; workers with no
    entry also pick up ``REPRO_FAULTS`` env keyed by lane.

    Every worker is pinned to ``WORKER_PLATFORM`` (module docstring: one
    process per chip).
    """
    if shards is None:
        shards = list(range(n_workers))
    if replicas is None:
        replicas = [0] * n_workers
    if len(shards) != n_workers or len(replicas) != n_workers:
        raise ValueError("shards/replicas must have one entry per worker")
    ctx = multiprocessing.get_context("spawn")
    started = []
    try:
        for i in range(n_workers):
            snap = shard_snapshot_path(snapshot_dir, shards[i]) \
                if snapshot_dir is not None else None
            parent, child = ctx.Pipe(duplex=False)
            plan = faults.get(i) if faults else None
            if isinstance(plan, FaultPlan):
                plan = plan.encode()
            proc = ctx.Process(
                target=run_worker,
                args=(child, cfg, snap, probe_impl, host, 0, shards[i],
                      query_impl,
                      slow_shards.get(i) if slow_shards else None,
                      replicas[i], gate_limit, plan, WORKER_PLATFORM),
                daemon=True, name=f"shard-worker-{shards[i]}r{replicas[i]}")
            proc.start()
            child.close()
            started.append((proc, parent, i))
        handles = []
        for proc, parent, i in started:
            if not parent.poll(start_timeout):
                if not proc.is_alive():
                    raise RuntimeError(
                        f"shard worker {i} exited (code {proc.exitcode}) "
                        "before reporting its address")
                raise TimeoutError(
                    f"shard worker {i} did not report its address within "
                    f"{start_timeout:.0f}s")
            try:
                handles.append(WorkerHandle(proc, tuple(parent.recv()),
                                            shards[i], replicas[i]))
            except EOFError as e:
                proc.join(5)
                raise RuntimeError(
                    f"shard worker {i} died during startup "
                    f"(exitcode {proc.exitcode})") from e
            parent.close()
        return handles
    except Exception:
        for proc, _, _ in started:
            if proc.is_alive():
                proc.terminate()
        raise
