"""The shared replication engine: admission, QoS, fault isolation.

One :class:`ReplicationHub` owns one
:class:`~..backend.tpu_backend.DigestPipeline` (or a mesh-sharded hash
engine over it) and multiplexes every registered session onto it:

* **Edge state, shared engine.**  Each session keeps its own queues,
  window accounting, and stats in a :class:`_SessionState`; the device
  path sees only coalesced batches.  Completions carry the session's
  state in their tag and route back without any shared-path lookup.
* **Admission control.**  ``register()`` rejects with a structured
  :class:`HubBusy` (never unbounded queue growth) once the session
  count or the global parked-bytes budget is exhausted.
* **Per-session backpressure windows.**  ``submit()`` blocks the
  *calling session's* thread while that session's parked work (queued +
  in-pipeline + undelivered completions) exceeds its window — a slow
  consumer stalls only its own window; the dispatcher never runs user
  callbacks, so it can never be parked by one.
* **Parked bytes are charged bytes.**  A blob parks as the pieces it
  arrived in — views of the receive slabs, copied for the first time by
  the dispatcher's pack — and a view pins its whole slab.  An entry is
  charged its payload and the slab bytes its views pin besides
  (:meth:`HubSession.submit_parts`); the window, admission and the shed
  policy all read the charged figure.
* **Weighted-fair batching.**  Each cross-session batch is composed
  round-robin with per-session quotas proportional to ``weight``, then
  greedily filled (work-conserving): a heavy session cannot monopolize
  a dispatch, an idle one costs nothing.
* **Load shedding.**  When global parked bytes exceed the budget — or
  the recent ``hub.dispatch.latency`` p99 crosses ``latency_shed_s``
  while parked bytes are past half budget — the heaviest offender (max
  per-session parked bytes) is shed: its queued work is dropped, its
  in-flight completions are discarded on arrival, its waiters wake
  into :class:`SessionShed`, and a ``hub.shed`` event names it.  The
  other sessions never notice.

Locking discipline (enforced by the ``hub-isolation`` datlint rule):
**no lock is ever held across a device dispatch** — batches are
composed under ``self._lock``, dispatched outside it — and per-session
state is only ever reached through the session-keyed accessor
(:meth:`ReplicationHub._session_state`) or a handle captured from it.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Optional

from ..obs.events import DeferredEmitQueue as _DeferredEmitQueue
from ..obs.events import emit as _emit
from ..obs.metrics import (
    OBS as _OBS,
    REGISTRY as _REGISTRY,
    counter as _counter,
    gauge as _gauge,
    histogram as _histogram,
)
from ..session.decoder import _M_DEC_BLOB_COPIED
from ..utils.payload import PayloadParts, buffer_address
from ..utils.trace import span

__all__ = [
    "ReplicationHub",
    "HubSession",
    "HubBusy",
    "HubError",
    "SessionShed",
]

# hub telemetry (OBSERVABILITY.md `hub.*` catalog)
_M_SESSIONS = _gauge("hub.sessions")
_M_PARKED = _gauge("hub.parked.bytes")
# high-water mark of hub.parked.bytes while lit; against
# hub.parked.budget_bytes (the collector's) it is how near the hub came
# to closing admission (half) and to shedding (the whole)
_M_PARKED_PEAK = _gauge("hub.parked.peak_bytes")
_M_ADMITTED = _counter("hub.admitted")
_M_REJECTED = _counter("hub.rejected")
_M_SHED = _counter("hub.shed")
_M_BATCHES = _counter("hub.dispatch.batches")
_M_ITEMS = _counter("hub.dispatch.items")
_M_BYTES = _counter("hub.dispatch.bytes")
_M_DROPPED = _counter("hub.completions.dropped")
_H_LATENCY = _histogram("hub.dispatch.latency")
# the dispatcher's condition wait, lit: busy (hub.dispatch.latency)
# plus wait can be held against wall time.  Waits are counted, never
# bracketed by a stage span (OBSERVABILITY.md)
_H_WAIT = _histogram("hub.dispatch.wait_s")
# distinct sessions a composed batch drew from (a count, not seconds)
_H_SESSIONS = _histogram(
    "hub.dispatch.sessions", buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 1024))

# dispatcher/waiter guarded-fallback period: wakeups are event-driven
# (condition notifies); the bound only matters if one is ever lost
_WAKE_FALLBACK = 0.05


class HubBusy(RuntimeError):
    """Structured admission rejection: the hub is at capacity.

    Carries the decision's inputs so a caller (the sidecar's accept
    loop, a future RPC layer) can answer with a meaningful retry hint
    instead of letting queues grow: ``sessions``/``max_sessions`` and
    ``parked_bytes``/``parked_budget`` at rejection time.
    """

    def __init__(self, message: str, *, sessions: int, max_sessions: int,
                 parked_bytes: int, parked_budget: int):
        super().__init__(message)
        self.sessions = sessions
        self.max_sessions = max_sessions
        self.parked_bytes = parked_bytes
        self.parked_budget = parked_budget


class SessionShed(RuntimeError):
    """This session was shed by the hub's overload policy.  ``reason``
    is the policy arm (``parked-budget`` / ``dispatch-latency``);
    ``parked_bytes`` is what the session held when shed."""

    def __init__(self, key: str, reason: str, parked_bytes: int):
        super().__init__(
            f"session {key!r} shed by hub ({reason}, "
            f"{parked_bytes} parked bytes)")
        self.key = key
        self.reason = reason
        self.parked_bytes = parked_bytes


class HubError(RuntimeError):
    """The shared engine itself failed (dispatcher died / hub closed);
    every session observes the same structured error."""


class _SessionState:
    """Per-session edge state.  Mutated ONLY under the hub lock, reached
    ONLY through the hub's session-keyed accessor or a handle captured
    from it (the hub-isolation contract)."""

    __slots__ = (
        "key", "weight", "cv", "q", "q_items", "q_bytes",
        "out_items", "out_bytes", "comp", "comp_items", "comp_bytes",
        "submitted", "submitted_bytes", "delivered", "delivered_bytes",
        "dispatches", "shed", "shed_parked", "gone", "flush_goal",
        "nowait", "marks",
    )

    def __init__(self, key: str, weight: float, lock: threading.Lock,
                 nowait: bool = False):
        self.key = key
        self.weight = weight
        self.nowait = nowait
        self.cv = threading.Condition(lock)
        # (kind, item, cb, tag, nbytes): nbytes is what the entry is
        # CHARGED — its payload, and the slab spare its views pin
        self.q: deque = deque()
        # lit: [submit time, items still queued] per submitted run,
        # oldest first — the batch fill clock's view of this queue
        self.marks: deque = deque()
        self.q_items = 0
        self.q_bytes = 0
        self.out_items = 0        # in the shared pipeline
        self.out_bytes = 0
        self.comp: deque = deque()  # (cb, tag, digest, nbytes)
        self.comp_items = 0
        self.comp_bytes = 0
        self.submitted = 0
        self.submitted_bytes = 0
        self.delivered = 0
        self.delivered_bytes = 0
        self.dispatches = 0       # batches this session contributed to
        self.shed: Optional[str] = None
        self.shed_parked = 0      # parked bytes at shed time (the verdict)
        self.gone = False
        self.flush_goal: Optional[int] = None

    @property
    def parked_bytes(self) -> int:
        return self.q_bytes + self.out_bytes + self.comp_bytes

    @property
    def parked_items(self) -> int:
        return self.q_items + self.out_items + self.comp_items


# HubSession's slab account before its first view: (slab, address,
# size, offset past the newest view)
_NO_SLAB = (None, 0, 0, 0)


class HubSession:
    """A session's handle on the hub — and a drop-in ``pipeline`` for
    :class:`~..backend.tpu_backend.TpuDecoder` / ``TpuEncoder``: the
    same ``submit`` / ``submit_parts`` / ``submit_stream`` / ``flush``
    surface as :class:`~..backend.tpu_backend.DigestPipeline`, with the
    work coalesced across sessions behind it.  A payload parks as it
    was handed over — ``bytes``, or the pieces it arrived in — and the
    dispatcher passes it to the shared pipeline untouched.  Completions
    are delivered on the session's OWN thread (inside
    ``submit``/``flush``), in submit order, so a callback that blocks —
    the sidecar's reply backpressure — parks only this session."""

    def __init__(self, hub: "ReplicationHub", state: _SessionState):
        self._hub = hub
        self._state = state
        # submit_parts' account of the slab its newest parked view lies
        # in (the submitting thread's alone): the slab, its address and
        # size, and the offset just past that view.  What lies beyond is
        # not known to be spare until the views move on
        self._slab = _NO_SLAB

    @property
    def key(self) -> str:
        return self._state.key

    @property
    def shed_reason(self) -> Optional[str]:
        return self._state.shed

    def submit(self, payload, on_digest: Callable, tag=None) -> None:
        self._hub._submit_run(
            self._state,
            (("payload", payload, on_digest, tag, len(payload)),),
            len(payload))

    def submit_parts(self, parts, on_digest: Callable, tag=None) -> None:
        """Park one payload that arrived in pieces — ``bytes`` objects
        or views of receive slabs, in order — without joining them: the
        dispatcher's pack is the first place its bytes are copied, and
        the pieces are dropped there.

        A view pins its whole slab, so the entry is charged what its
        views pin besides the payload: the slab bytes in front of each
        view that no parked view of this session covers (headers, other
        frames), and, when the views move on to another slab, what was
        left of the last one behind its newest view.  A session then
        pins at most what it is charged and the slabs at its two ends —
        the tail of the newest, unknown until the views move on, and
        the head of the oldest, where a delivered entry's views lay.

        A payload whose views would pin more spare than they carry
        (an attachment among change frames) is joined here instead: one
        copy, counted in ``decoder.blob.copied.bytes``, and no slab
        held for it."""
        carried = left = gaps = 0
        slab, at, size, end = self._slab
        for part in parts:
            n = len(part)
            carried += n
            if n and type(part) is memoryview:
                a = buffer_address(part)
                if part.obj is not slab or a < at + end:
                    # another slab (or the same memory written anew)
                    left += size - end
                    slab = part.obj
                    at, size, end = (buffer_address(slab),
                                     memoryview(slab).nbytes, 0)
                gaps += a - at - end
                end = a - at + n
        if gaps > carried:
            item = b"".join(parts)
            if _OBS.on:
                _M_DEC_BLOB_COPIED.inc(carried)
            charged = carried  # no view parks: the account stays put
        else:
            if len(parts) == 1:
                item = parts[0]  # whole already: bytes, or one view
            else:
                item = PayloadParts(parts) if parts else b""
            self._slab = (slab, at, size, end)
            charged = carried + left + gaps
        self._hub._submit_run(
            self._state, (("payload", item, on_digest, tag, charged),),
            charged)

    def submit_many(self, payloads, on_digest: Callable,
                    tag_base: int = 0) -> None:
        """Bulk submit: one window check and ONE lock round-trip for a
        whole run (tags ``tag_base..tag_base+n-1``) — the bulk decoder
        feeds thousands of change payloads per wire chunk, and a lock
        acquisition per payload was ~3x the whole submit cost.  The
        window is enforced at run granularity (a run is admitted whole
        once there is any room — same policy as the oversized single
        item)."""
        entries = []
        total = 0
        for k, p in enumerate(payloads):
            n = len(p)
            entries.append(("payload", p, on_digest, tag_base + k, n))
            total += n
        if entries:
            self._hub._submit_run(self._state, entries, total)

    def submit_stream(self, stream, on_digest: Callable, tag=None) -> None:
        nbytes = int(getattr(stream, "length", 0))
        self._hub._submit_run(
            self._state, (("stream", stream, on_digest, tag, nbytes),),
            nbytes)

    def flush(self) -> None:
        self._hub._flush_session(self._state)

    # -- nowait surface (the event-driven edge, ISSUE 17) -------------------

    def poll(self) -> int:
        """One non-blocking completion turn: pop whatever digests have
        routed back and deliver them (in submit order, on THIS thread —
        the edge loop's), never waiting.  Returns the count delivered;
        raises :class:`SessionShed` / :class:`HubError` exactly like
        ``submit`` when the hub's overload policy hit this session."""
        return self._hub._poll_session(self._state)

    @property
    def has_completions(self) -> bool:
        """Lock-free: are completions waiting for :meth:`poll`?  A
        plain GIL-atomic attribute read (at worst one update stale) so
        the edge loop can skip the hub lock for idle sessions."""
        return self._state.comp_items > 0

    def window_room(self) -> bool:
        """Lock-free mirror of the submit window check — the SAME
        predicate ``_submit_run_inner`` gates on, read without the
        lock.  The edge loop gates READS on this: a full window stops
        the session's socket from being drained, so the kernel buffer
        (then the peer's TCP window) absorbs the overload — the
        identical ladder, enforced by backpressure instead of a
        blocked thread."""
        st, hub = self._state, self._hub
        return st.parked_items < hub.window_items and (
            st.parked_bytes < hub.window_bytes or st.parked_items == 0)

    @property
    def drained(self) -> bool:
        """Lock-free: nothing parked (queued, in-pipeline, or
        undelivered) — the edge's flush-before-finalize barrier
        predicate."""
        return self._state.parked_items == 0

    def close(self) -> None:
        """Unregister; queued work is dropped, in-flight completions are
        discarded on arrival.  Idempotent."""
        self._slab = _NO_SLAB
        self._hub._unregister(self._state)

    def stats(self) -> dict:
        return self._hub._session_stats(self._state)

    def __enter__(self) -> "HubSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _mesh_hash_begin_factory(n_devices: Optional[int] = None):
    """The cross-session mesh engine: the served batch engine with the
    coalesced batch's rows laid over the device mesh
    (:func:`..parallel.mesh.sharded_hash_engine`: batch-dim
    ``NamedSharding``, every chip hashing its shard, no exchange).
    ``n_devices=None`` takes the largest power of two of the visible
    devices (the mesh layer's constraint).

    Returns ``(devices, hash_begin)``, or None only where the routing
    layer observes a host platform
    (:func:`..utils.routing.host_reason`) — the pipeline's host engine
    then serves, as for any hub.  With a device in play, a mesh that
    was asked for and cannot be built raises: fewer than two devices,
    or a pinned count the mesh layer refuses."""
    from ..utils.routing import prefer_host

    if prefer_host("DAT_DEVICE_HASH"):
        return None
    import jax  # noqa: PLC0415

    from ..parallel import mesh as pmesh  # noqa: PLC0415

    n = n_devices
    if n is None:
        n = len(jax.devices())
        while n & (n - 1):
            n -= 1
    if n < 2:
        raise ValueError(
            f"hub mesh needs at least two devices; "
            f"{len(jax.devices())} visible, {n_devices or 'auto'} asked")
    m = pmesh.make_mesh(n)
    if _OBS.on:
        from ..obs.device import note_engine as _note_engine

        _note_engine("digest.hash", "device-batch-mesh", devices=n)
    return n, pmesh.sharded_hash_engine(m)


class ReplicationHub:
    """See module docstring.  One hub per process/daemon; sessions come
    and go via :meth:`register` / :meth:`HubSession.close`.

    ``mesh="auto"`` shards cross-session batches over every local device
    (the host engine serves where the routing layer observes a CPU
    platform; a device backend with fewer than two chips raises); an
    int pins the device count; ``None`` (default) keeps the
    single-device engine.
    """

    def __init__(
        self,
        pipeline=None,
        *,
        hash_batch: Optional[Callable] = None,
        mesh=None,
        max_sessions: int = 1024,
        parked_budget: int = 256 << 20,
        window_items: int = 4096,
        window_bytes: int = 32 << 20,
        max_batch: int = 1024,
        max_batch_bytes: int = 1 << 30,
        linger_s: float = 0.002,
        latency_shed_s: Optional[float] = None,
    ):
        # devices the cross-session batch is sharded over (0 = the
        # single-device or host engine)
        self.mesh_devices = 0
        if pipeline is None:
            from ..backend.tpu_backend import DigestPipeline

            hash_begin = None
            if mesh is not None and hash_batch is None:
                meshed = _mesh_hash_begin_factory(
                    None if mesh == "auto" else int(mesh))
                if meshed is not None:
                    self.mesh_devices, hash_begin = meshed
            # the hub owns batching: the inner pipeline's item cap is
            # effectively ours (we dispatch explicitly per composed
            # batch), its inflight bound stays the readback pipeline
            pipeline = DigestPipeline(
                hash_batch=hash_batch, hash_begin=hash_begin,
                max_batch=max_batch, max_batch_bytes=max_batch_bytes)
        self._pipeline = pipeline
        self.max_sessions = int(max_sessions)
        self.parked_budget = int(parked_budget)
        self.window_items = int(window_items)
        self.window_bytes = int(window_bytes)
        self._max_batch = int(max_batch)
        self._max_batch_bytes = int(max_batch_bytes)
        self._linger_s = float(linger_s)
        self.latency_shed_s = latency_shed_s

        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._sessions: dict[str, _SessionState] = {}
        # shed events queued under the lock, emitted by
        # _drain_shed_events once the holder releases (the event sink
        # can block; blocking under the hub lock convoys every session)
        self._shed_events = _DeferredEmitQueue("hub.shed", self._lock)
        # the concurrency pass enforces these (ANALYSIS.md):
        # datlint: guarded-by(self._lock): self._sessions
        self._next_id = 0
        self._rr = 0
        self._q_items = 0            # global queued (not yet in pipeline)
        self._q_bytes = 0
        self._parked_bytes = 0       # global queued+outstanding+undelivered
        self._oldest_ts: Optional[float] = None
        # lit: oldest submit time among the items of the batch last
        # composed (None: no marked item in it); dispatcher-thread-local
        self._batch_oldest: Optional[float] = None
        self._routed: list = []     # dispatcher-thread-local (see _route)
        # recent dispatch-turn latencies (dispatcher-thread-local ring):
        # the latency shed arm triggers on this window's p99, not on one
        # isolated slow turn (a first-bucket compile must not shed)
        self._lat_ring: deque = deque(maxlen=64)
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._failed: Optional[BaseException] = None
        # bind the collector ONCE: close() unregisters owner-checked by
        # identity, so an old hub draining past a successor's startup
        # cannot delete the successor's live collector
        self._collector_fn = self._collect
        _REGISTRY.register_collector("hub", self._collector_fn)

    # -- registration / admission -------------------------------------------

    def register(self, key: Optional[str] = None,
                 weight: float = 1.0, *,
                 nowait: bool = False) -> HubSession:
        """Admit one session.  Raises :class:`HubBusy` (structured) when
        the session count or parked-bytes budget is exhausted — bounded
        state instead of queue growth is the overload contract.

        ``nowait=True`` registers an event-driven session (the edge
        loop's, ISSUE 17): ``submit``/``flush`` never block and never
        deliver inline — completions are drained by
        :meth:`HubSession.poll` and the window is enforced by the
        caller gating reads on :meth:`HubSession.window_room` (the
        same predicate, applied as backpressure instead of a blocked
        thread).  Admission and shed policy are identical."""
        if weight <= 0:
            raise ValueError("session weight must be > 0")
        if key is not None and (not key or any(
                c in key for c in "{},=\"\n\r")):
            # keys ride telemetry label sets ({session=KEY}) and JSON
            # stats breakdowns: structural characters would corrupt the
            # exposition for EVERY session, so refuse at the boundary
            raise ValueError(
                f"session key {key!r} must be non-empty and contain "
                'none of {},=" or newlines')
        busy = None
        with self._lock:
            self._check_alive_locked()
            if key is None:
                key = f"s{self._next_id}"
            self._next_id += 1
            if key in self._sessions:
                raise ValueError(f"session key {key!r} already registered")
            # admission closes at HALF the shed budget: new sessions
            # are refused while the hub still has headroom to serve the
            # ones it already admitted — rejecting a newcomer is cheap,
            # shedding a live session is not, so the former guards the
            # latter (ROBUSTNESS.md overload behavior)
            if len(self._sessions) >= self.max_sessions or \
                    self._parked_bytes >= self.parked_budget // 2:
                # built under the lock (consistent counts), emitted and
                # raised OUTSIDE it: the event sink can block, and
                # blocking under the hub lock convoys every session
                # (blocking-under-lock contract, ANALYSIS.md)
                busy = HubBusy(
                    f"hub at capacity ({len(self._sessions)}/"
                    f"{self.max_sessions} sessions, "
                    f"{self._parked_bytes}/{self.parked_budget} parked "
                    f"bytes)",
                    sessions=len(self._sessions),
                    max_sessions=self.max_sessions,
                    parked_bytes=self._parked_bytes,
                    parked_budget=self.parked_budget,
                )
            else:
                st = _SessionState(key, float(weight), self._lock,
                                   nowait=nowait)
                self._sessions[key] = st
                sessions_now = len(self._sessions)
                if _OBS.on:
                    # gauge set under the lock: a concurrent
                    # unregister's set would otherwise interleave out
                    # of order and latch a stale session count
                    _M_SESSIONS.set(sessions_now)
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._dispatch_loop, name="hub-dispatch",
                        daemon=True)
                    self._thread.start()
        if busy is not None:
            if _OBS.on:
                _M_REJECTED.inc()
                _emit("hub.reject", key=key, sessions=busy.sessions,
                      max_sessions=self.max_sessions,
                      parked_bytes=busy.parked_bytes,
                      parked_budget=self.parked_budget)
            raise busy
        if _OBS.on:
            _M_ADMITTED.inc()
            _emit("hub.admit", key=key, weight=float(weight),
                  sessions=sessions_now)
        return HubSession(self, st)

    def _session_state(self, key: str) -> _SessionState:
        """THE session-keyed accessor (hub-isolation contract): every
        key-addressed reach into per-session state goes through here."""
        return self._sessions[key]

    def _unregister(self, st: _SessionState) -> None:
        done_stats = None
        with self._lock:
            if st.gone:
                return
            st.gone = True
            self._drop_parked_locked(st)
            if self._sessions.get(st.key) is st:
                del self._sessions[st.key]
            st.cv.notify_all()
            self._work.notify_all()
            if _OBS.on:
                _M_SESSIONS.set(len(self._sessions))
                _M_PARKED.set(self._parked_bytes)
                done_stats = self._session_stats_locked(st)
        if done_stats is not None:
            _emit("hub.session.done", key=st.key, shed=st.shed,
                  **{k: v for k, v in done_stats.items()
                     if k in ("submitted", "delivered", "submitted_bytes",
                              "dispatches")})

    def _drop_queued_locked(self, st: _SessionState) -> None:
        """Nothing will dispatch what ``st`` has queued: the entries,
        and the slabs their views pin, leave the parked set now."""
        self._q_items -= st.q_items
        self._q_bytes -= st.q_bytes
        self._parked_bytes -= st.q_bytes
        st.q.clear()
        st.marks.clear()
        st.q_items = st.q_bytes = 0

    def _drop_parked_locked(self, st: _SessionState) -> None:
        """A session that stopped listening (closed or shed): queued
        entries and undelivered completions leave the parked set now;
        in-pipeline bytes leave as their (discarded) completions route
        back."""
        self._drop_queued_locked(st)
        self._parked_bytes -= st.comp_bytes
        st.comp.clear()
        st.comp_items = st.comp_bytes = 0

    # -- session-side paths (run on the session's own thread) ---------------

    def _submit_run(self, st: _SessionState, entries, run_bytes: int) -> None:
        """Admit a run of entries (possibly one) into the session's
        queue — ONE lock round-trip per run, window-checked at run
        granularity.  Blocks (delivering ready completions meanwhile)
        while the session's window is full."""
        n = len(entries)
        try:
            self._submit_run_inner(st, entries, run_bytes, n)
        finally:
            # emit any shed this submit triggered (possibly our own
            # SessionShed unwinding) with the lock released
            self._drain_shed_events()

    def _submit_run_inner(self, st: _SessionState, entries,
                          run_bytes: int, n: int) -> None:
        if st.nowait:
            # event-driven session: never wait, never deliver inline.
            # The caller (the edge loop) gated reads on window_room()
            # before decoding these entries, so overshoot is bounded by
            # one read turn's decode product — the same run-granularity
            # admission the blocking path applies to an oversized run.
            # Accounting, shed policy, and liveness checks are the
            # blocking path's verbatim.
            with self._lock:
                self._check_session_alive_locked(st)
                st.q.extend(entries)
                st.q_items += n
                st.q_bytes += run_bytes
                st.submitted += n
                st.submitted_bytes += run_bytes
                was_idle = self._q_items == 0
                self._q_items += n
                self._q_bytes += run_bytes
                self._parked_bytes += run_bytes
                if self._oldest_ts is None:
                    self._oldest_ts = time.monotonic()
                if _OBS.on:
                    self._lit_parked_grew_locked()
                    st.marks.append([time.monotonic(), n])
                self._maybe_shed_locked()
                self._check_session_alive_locked(st)
                if was_idle or self._q_items >= self._max_batch:
                    self._work.notify_all()
            return
        while True:
            with self._lock:
                self._check_session_alive_locked(st)
                ready = self._pop_completions_locked(st)
                if not ready:
                    # window: parked work (queued + in-pipeline +
                    # undelivered) bounds this session; a run (or an
                    # oversized single item) is admitted whole once
                    # there is any room, rather than deadlocking an
                    # empty window
                    if st.parked_items < self.window_items and (
                            st.parked_bytes < self.window_bytes
                            or st.parked_items == 0):
                        st.q.extend(entries)
                        st.q_items += n
                        st.q_bytes += run_bytes
                        st.submitted += n
                        st.submitted_bytes += run_bytes
                        was_idle = self._q_items == 0
                        self._q_items += n
                        self._q_bytes += run_bytes
                        self._parked_bytes += run_bytes
                        if self._oldest_ts is None:
                            self._oldest_ts = time.monotonic()
                        if _OBS.on:
                            self._lit_parked_grew_locked()
                            st.marks.append([time.monotonic(), n])
                        self._maybe_shed_locked()
                        self._check_session_alive_locked(st)
                        # wake the dispatcher only on the transitions it
                        # acts on (first work after idle, batch full) —
                        # a notify per submit was pure GIL churn
                        if was_idle or self._q_items >= self._max_batch:
                            self._work.notify_all()
                        return
                    st.cv.wait(_WAKE_FALLBACK)
                    continue
            self._deliver(st, ready)

    def _lit_parked_grew_locked(self) -> None:
        """Lit only: the two submit paths are where parked bytes grow,
        so they are where the high-water mark can move."""
        parked = self._parked_bytes
        _M_PARKED.set(parked)
        if parked > _M_PARKED_PEAK.value:
            _M_PARKED_PEAK.set(parked)

    def _flush_session(self, st: _SessionState) -> None:
        """Block until every item this session submitted *before this
        call* has had its digest delivered — the per-session
        flush-before-finalize barrier on the shared engine."""
        with self._lock:
            self._check_session_alive_locked(st)
            st.flush_goal = st.submitted
            self._work.notify_all()
        if st.nowait:
            # event-driven session: the flush BARRIER moves to the
            # caller (the edge defers enc.finalize until the session is
            # drained); setting the goal above is what matters — the
            # dispatcher now drains the readback pipeline promptly so
            # completions land without waiting for the next batch
            return
        try:
            while True:
                with self._lock:
                    ready = self._pop_completions_locked(st)
                    if not ready:
                        self._check_session_alive_locked(st)
                        if st.delivered >= (st.flush_goal or 0):
                            return
                        st.cv.wait(_WAKE_FALLBACK)
                        continue
                self._deliver(st, ready)
        finally:
            with self._lock:
                st.flush_goal = None

    def _poll_session(self, st: _SessionState) -> int:
        """One non-blocking completion turn for a nowait session (see
        :meth:`HubSession.poll`): pop under the lock, deliver outside
        it — the thread delivering is the edge loop's, so a slow
        digest consumer parks only its own session's turn."""
        with self._lock:
            ready = self._pop_completions_locked(st)
            if not ready:
                # surface shed/closure HERE (the poll path is the nowait
                # session's only recurring hub call when the wire is idle)
                self._check_session_alive_locked(st)
                return 0
        self._deliver(st, ready)
        return len(ready)

    def _pop_completions_locked(self, st: _SessionState) -> list:
        if not st.comp:
            return []
        ready = list(st.comp)
        st.comp.clear()
        st.comp_items = 0
        freed = st.comp_bytes
        st.comp_bytes = 0
        # delivery accounting happens at pop time, in bulk: the popping
        # thread IS the delivering thread (the session's own), so the
        # counter can never run ahead of an observable delivery by more
        # than that thread's own call stack
        st.delivered += len(ready)
        st.delivered_bytes += freed
        self._parked_bytes -= freed
        if _OBS.on:
            _M_PARKED.set(self._parked_bytes)
        return ready

    @staticmethod
    def _deliver(st: _SessionState, ready: list) -> None:
        # user callbacks run here, on the session's own thread, with no
        # hub lock held: a blocking consumer parks only itself
        for cb, tag, digest, nbytes in ready:
            if tag is None:
                cb(digest)
            else:
                cb(tag, digest)
        if _OBS.on:
            # the run's digests into decoder.digests, one increment
            from ..backend.tpu_backend import fold_digest_tallies

            fold_digest_tallies()

    def _check_alive_locked(self) -> None:
        if self._failed is not None:
            raise HubError(
                f"hub dispatcher failed: {self._failed!r}") from self._failed
        if self._closed:
            raise HubError("hub is closed")

    def _check_session_alive_locked(self, st: _SessionState) -> None:
        self._check_alive_locked()
        if st.shed is not None:
            raise SessionShed(st.key, st.shed, st.shed_parked)
        if st.gone:
            raise HubError(f"session {st.key!r} is closed")

    # -- the dispatcher (the only thread that touches the pipeline) ---------

    def _dispatch_loop(self) -> None:
        try:
            while True:
                with self._lock:
                    while not (self._closed or self._failed
                               or self._turn_ready_locked()):
                        self._idle_wait_locked()
                    if self._closed or self._failed:
                        return
                # the turn's own lock hold, apart from the wait's: the
                # stage span must not bracket the wait, and must close
                # (a ring record, maybe a sink write) with no lock held
                with span("hub.compose"):
                    with self._lock:
                        batch = self._compose_locked()
                        engine_flush = self._flush_needed_locked()
                    if batch and _OBS.on:
                        _H_SESSIONS.observe(len({id(e[0]) for e in batch}))
                t0 = time.monotonic()
                items = len(batch)
                turn_bytes = self._hand_over(batch) if items else 0
                with self._lock:
                    drain_idle = (self._q_items == 0
                                  and self._pipeline.inflight > 0)
                if engine_flush or drain_idle:
                    # queue is dry (or a session is at its finalize
                    # barrier): drain the readback pipeline so windows
                    # free and flush barriers release promptly
                    self._pipeline.flush()
                if self._routed:
                    with span("hub.distribute", items=len(self._routed)):
                        self._distribute_routed()
                if items or engine_flush:
                    latency = time.monotonic() - t0
                    self._lat_ring.append(latency)
                    if _OBS.on:
                        _H_LATENCY.observe(latency)
                        if items:
                            _M_BATCHES.inc()
                            _M_ITEMS.inc(items)
                            _M_BYTES.inc(turn_bytes)
                    ordered = sorted(self._lat_ring)
                    p99 = ordered[min(len(ordered) - 1,
                                      int(0.99 * len(ordered)))]
                    with self._lock:
                        self._maybe_shed_locked(latency_p99=p99)
                self._drain_shed_events()  # per-turn catch-all
        except BaseException as exc:  # noqa: BLE001 — fanned out below
            # emit BEFORE taking the lock: the event sink can block,
            # and the waiters notified below contend on this lock
            _emit("hub.error", error=f"{type(exc).__name__}: {exc}")
            with self._lock:
                self._failed = exc
                for key in list(self._sessions):
                    self._session_state(key).cv.notify_all()
                self._work.notify_all()

    def _hand_over(self, batch: list) -> int:
        """A composed batch into the pipeline, and its dispatch; returns
        the bytes the batch was charged.  The entries are consumed: a
        payload parked as views of receive slabs is copied for the
        first time by the pipeline's pack, and nothing of the hub's —
        not the batch, not this frame — refers to it after that."""
        if self._batch_oldest is not None:
            # lit: the batch's fill clock started in the sessions'
            # queues, not at the pipeline's door
            mark = getattr(self._pipeline, "mark_fill", None)
            if mark is not None:
                mark(self._batch_oldest)
        turn_bytes = 0
        with span("hub.submit", items=len(batch)):
            for entry_st, kind, item, cb, tag, nbytes in batch:
                routed = (entry_st, cb, tag, nbytes)
                if kind == "payload":
                    self._pipeline.submit(item, self._route, routed)
                else:
                    self._pipeline.submit_stream(item, self._route, routed)
                turn_bytes += nbytes
        batch.clear()
        self._pipeline.dispatch()
        return turn_bytes

    def _idle_wait_locked(self) -> None:
        """One bounded wait for work.  Lit, its seconds are counted
        (``hub.dispatch.wait_s``) — a wait is never a stage span."""
        t0 = time.monotonic() if _OBS.on else None
        self._work.wait(self._wait_s_locked())
        if t0 is not None and _OBS.on:  # still lit: a gate that went
            # dark during the wait gets no late observation
            _H_WAIT.observe(time.monotonic() - t0)

    def _turn_ready_locked(self) -> bool:
        if self._flush_needed_locked():
            return True
        if self._q_items == 0:
            return self._pipeline.inflight > 0
        if self._q_items >= self._max_batch or \
                self._q_bytes >= self._max_batch_bytes:
            return True
        return (self._oldest_ts is not None
                and time.monotonic() - self._oldest_ts >= self._linger_s)

    def _wait_s_locked(self) -> float:
        if self._oldest_ts is not None:
            remaining = self._linger_s - (time.monotonic() - self._oldest_ts)
            if remaining > 0:
                return min(_WAKE_FALLBACK, remaining)
        return _WAKE_FALLBACK

    def _flush_needed_locked(self) -> bool:
        for st in self._sessions.values():
            # a shed session's goal can never be met (its queue was
            # dropped); its own thread is about to observe SessionShed
            # and clear the goal — don't spin the engine on it
            if st.flush_goal is not None and st.shed is None and \
                    st.delivered + st.comp_items < st.flush_goal:
                return True
        return False

    def _compose_locked(self) -> list:
        """Weighted-fair cross-session batch: one quota pass
        proportional to session weight, then a greedy work-conserving
        fill.  Moves accounting queued -> outstanding; the caller
        dispatches OUTSIDE the lock."""
        order = [st for st in self._sessions.values()
                 if st.q_items and st.shed is None]
        if not order:
            return []
        start = self._rr % len(order)
        order = order[start:] + order[:start]
        self._rr += 1
        total_w = sum(st.weight for st in order)
        items_left = self._max_batch
        bytes_left = self._max_batch_bytes
        batch: list = []
        self._batch_oldest = None

        def take(st: _SessionState, limit: int) -> int:
            nonlocal items_left, bytes_left
            n = 0
            while n < limit and items_left and st.q:
                nbytes = st.q[0][4]
                if st.q[0][0] == "payload" and nbytes > bytes_left \
                        and batch:
                    break  # oversized item waits for its own batch
                kind, item, cb, tag, nbytes = st.q.popleft()
                st.q_items -= 1
                st.q_bytes -= nbytes
                st.out_items += 1
                st.out_bytes += nbytes
                self._q_items -= 1
                self._q_bytes -= nbytes
                batch.append((st, kind, item, cb, tag, nbytes))
                items_left -= 1
                if kind == "payload":
                    bytes_left -= nbytes
                n += 1
            if n and st.marks:
                self._take_marks(st, n)
            return n

        for st in order:  # quota pass: weight-proportional shares
            quota = max(1, int(self._max_batch * st.weight / total_w))
            if take(st, quota):
                st.dispatches += 1
        for st in order:  # greedy fill: unused budget is not wasted
            if items_left <= 0 or bytes_left <= 0:
                break
            take(st, items_left)
        self._oldest_ts = time.monotonic() if self._q_items else None
        return batch

    def _take_marks(self, st: _SessionState, n: int) -> None:
        """Lit leftovers only (marks exist only for runs submitted
        lit): ``n`` items left ``st``'s queue for the batch being
        composed; pop their run marks, keeping the oldest submit time
        seen for the batch's fill clock."""
        marks = st.marks
        while n > 0 and marks:
            mark = marks[0]
            if self._batch_oldest is None or mark[0] < self._batch_oldest:
                self._batch_oldest = mark[0]
            if mark[1] <= n:
                n -= mark[1]
                marks.popleft()
            else:
                mark[1] -= n
                n = 0

    def _route(self, routed, digest: bytes) -> None:
        """Pipeline completion -> the dispatcher-local buffer.  ONLY the
        dispatcher thread runs pipeline calls, so this append needs no
        lock; :meth:`_distribute_routed` moves the buffer into the
        per-session completion queues in one locked pass per turn —
        one lock round-trip for a whole batch instead of one per item."""
        self._routed.append((routed, digest))

    def _distribute_routed(self) -> None:
        routed, self._routed = self._routed, []
        if not routed:
            return
        dropped = 0
        with self._lock:
            touched = set()
            for (st, cb, tag, nbytes), digest in routed:
                st.out_items -= 1
                st.out_bytes -= nbytes
                if st.gone or st.shed is not None:
                    # the session is no longer listening: its bytes
                    # leave the parked set here (queued/comp already did)
                    self._parked_bytes -= nbytes
                    dropped += 1
                else:
                    st.comp.append((cb, tag, digest, nbytes))
                    st.comp_items += 1
                    st.comp_bytes += nbytes
                touched.add(st)
            for st in touched:
                st.cv.notify_all()
            if dropped and _OBS.on:
                _M_DROPPED.inc(dropped)
                _M_PARKED.set(self._parked_bytes)

    # -- overload policy ----------------------------------------------------

    def _maybe_shed_locked(self,
                           latency_p99: Optional[float] = None) -> None:
        over_budget = self._parked_bytes > self.parked_budget
        slow = (latency_p99 is not None
                and self.latency_shed_s is not None
                and latency_p99 > self.latency_shed_s
                and self._parked_bytes > self.parked_budget // 2)
        if not (over_budget or slow):
            return
        reason = "parked-budget" if over_budget else "dispatch-latency"
        live = [st for st in self._sessions.values() if st.shed is None]
        if not live:
            return
        victim = max(live, key=lambda st: st.parked_bytes)
        self._shed_locked(victim, reason)

    def _shed_locked(self, st: _SessionState, reason: str) -> None:
        held = st.parked_bytes
        st.shed = reason
        st.shed_parked = held
        self._drop_parked_locked(st)
        st.cv.notify_all()
        if _OBS.on:
            _M_SHED.inc()
            _M_PARKED.set(self._parked_bytes)
        # the EVENT is deferred: queued here (fields captured while
        # consistent), emitted by _drain_shed_events after release
        self._shed_events.queue_locked(
            key=st.key, reason=reason, parked_bytes=held,
            sessions=len(self._sessions))

    def _drain_shed_events(self) -> None:
        """Emit queued shed events with the hub lock RELEASED.  Called
        by the submit path and once per dispatcher turn."""
        self._shed_events.flush()

    # -- snapshots / lifecycle ----------------------------------------------

    def _session_stats_locked(self, st: _SessionState) -> dict:
        return {
            "parked_bytes": st.parked_bytes,
            "submitted": st.submitted,
            "submitted_bytes": st.submitted_bytes,
            "delivered": st.delivered,
            "dispatches": st.dispatches,
            "shed": st.shed,
        }

    def _session_stats(self, st: _SessionState) -> dict:
        with self._lock:
            return self._session_stats_locked(st)

    def sessions_snapshot(self) -> dict:
        """{key: per-session stats} for every live session — the
        ``sessions`` breakdown the sidecar's ``--stats-fd`` lines carry
        in hub mode (and the chaos oracle cross-checks)."""
        with self._lock:
            return {key: self._session_stats_locked(self._session_state(key))
                    for key in self._sessions}

    def snapshot(self) -> dict:
        # the pump route resolves OUTSIDE the lock (env read + cached
        # library check, but blocking-under-lock stays trivially clean)
        from ..session.pump import effective_pump_route

        pump_route = effective_pump_route()
        with self._lock:
            return {
                "sessions": len(self._sessions),
                "parked_bytes": self._parked_bytes,
                "queued_items": self._q_items,
                # which byte mover feeds the sessions multiplexed here
                # (ISSUE 14): hub aggregate scaling is only legible next
                # to the wire route that produced it
                "pump_route": pump_route,
                "failed": (None if self._failed is None
                           else f"{type(self._failed).__name__}: "
                                f"{self._failed}"),
            }

    def admission_state(self) -> dict:
        """Lock-free admission view for ``/healthz`` (ISSUE 11): plain
        attribute reads only — GIL-atomic, at worst one update stale,
        by design.  A health probe must never block behind the hub
        lock: a wedged dispatcher holding it would turn the liveness
        check itself into a hang, inverting its purpose.  The datlint
        healthz check keeps the handler side of this contract honest;
        this method is the hub's matching half."""
        sessions = len(self._sessions)
        parked = self._parked_bytes
        return {
            "open": (not self._closed and self._failed is None
                     and sessions < self.max_sessions
                     and parked < self.parked_budget // 2),
            "sessions": sessions,
            "max_sessions": self.max_sessions,
            "parked_bytes": parked,
            "parked_budget": self.parked_budget,
            "failed": self._failed is not None,
        }

    def _collect(self) -> dict:
        """Registry snapshot collector: labeled per-session entries for
        sessions currently alive (bounded cardinality by construction —
        dead sessions simply stop appearing)."""
        counters: dict = {}
        gauges: dict = {}
        with self._lock:
            gauges["hub.sessions"] = float(len(self._sessions))
            gauges["hub.parked.budget_bytes"] = float(self.parked_budget)
            if self.mesh_devices:
                gauges["hub.mesh.devices"] = float(self.mesh_devices)
            for key in self._sessions:
                st = self._session_state(key)
                label = f"{{session={key}}}"
                gauges["hub.session.parked_bytes" + label] = \
                    float(st.parked_bytes)
                counters["hub.session.submitted" + label] = st.submitted
                counters["hub.session.delivered" + label] = st.delivered
                counters["hub.session.dispatches" + label] = st.dispatches
        return {"counters": counters, "gauges": gauges}

    def close(self) -> None:
        """Stop the dispatcher and release the collector.  Sessions
        still registered observe :class:`HubError` on their next call;
        callers should drain/close sessions first."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            for key in list(self._sessions):
                self._session_state(key).cv.notify_all()
            self._work.notify_all()
            thread = self._thread
        if thread is not None:
            thread.join(timeout=5)
        with self._lock:
            # the dispatcher is gone; completions already routed stay
            # for their sessions' last poll
            for key in list(self._sessions):
                self._drop_queued_locked(self._session_state(key))
            if _OBS.on:
                _M_PARKED.set(self._parked_bytes)
        _REGISTRY.unregister_collector("hub", self._collector_fn)

    def __enter__(self) -> "ReplicationHub":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
