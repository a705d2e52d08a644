"""Async request router on the authenticated p2p/events control plane.

Topology: a serving gang of :class:`ServeWorker`\\ s (ranks ``0..S-1``),
each owning a set of models (the ``placement`` map ``{model: rank}``), plus
any number of :class:`RouterClient`\\ s on ranks ``>= S``. Every frame is a
point-to-point :class:`~harp_tpu.parallel.p2p.P2PTransport` send — two
processes touch each message, no gang-wide call anywhere on the request
path (the reference's SyncClient/Server residual, now carrying traffic).

Fan-out: a client submits to the model's owner directly when it knows the
placement; a request landing on a non-owning worker is FORWARDED to the
owner (one extra hop), with the original client's ``reply_to`` intact — the
reply still travels owner→client directly. Workers learn client reply
addresses from the request frames (``P2PTransport.add_peer``), so clients
never pre-register.

Shutdown (the PR 7 atexit-close contract extended to serve hooks):
``begin_drain`` flips the worker to rejecting new requests with a clean
"shutting-down" reply while the in-flight micro-batches drain;
``close`` = drain + batcher stop + reader-thread join + transport close.
Live workers and clients register in a module-level set closed at
interpreter exit, so an abandoned serving gang never leaves orphan threads
or listening sockets behind.
"""

from __future__ import annotations

import atexit
import itertools
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from harp_tpu.parallel.events import EventQueue
from harp_tpu.parallel.p2p import P2PTransport
from harp_tpu.serve import protocol
from harp_tpu.serve.batcher import DEFAULT_MAX_WAIT_S, MicroBatcher
from harp_tpu.telemetry import spans

_LIVE: "set" = set()          # live workers + clients, closed at exit
_live_lock = threading.Lock()
_atexit_installed = False


def _register_live(obj) -> None:
    global _atexit_installed
    with _live_lock:
        _LIVE.add(obj)
        if not _atexit_installed:
            atexit.register(_close_at_exit)
            _atexit_installed = True


def _unregister_live(obj) -> None:
    with _live_lock:
        _LIVE.discard(obj)


def _close_at_exit() -> None:
    # same contract as telemetry.step_log's atexit flush: a process exiting
    # mid-serve must drain in-flight batches and release sockets/threads
    import logging

    with _live_lock:
        live = list(_LIVE)
    for obj in live:
        try:
            obj.close()
        except Exception:
            # one wedged worker (drain timeout, dead socket) must not skip
            # closing the REST of the live set at interpreter exit — each
            # object gets its close attempt, failures are logged
            logging.getLogger("harp_tpu.serve").exception(
                "atexit close failed for %r", obj)


class ServeWorker:
    """One serving gang member: transport + per-model micro-batchers.

    Fleet surface (ISSUE 14): the placement map is MUTABLE — a
    :mod:`~harp_tpu.serve.fleet` supervisor pushes versioned
    ``serve.placement`` frames after a re-placement and this worker applies
    them (:meth:`apply_placement`); clients pull the current map with
    ``serve.placement_get``. ``cache`` installs a hot-key reply cache
    (:class:`~harp_tpu.serve.cache.TopKReplyCache`) consulted before the
    batcher; ``fault_exit`` selects how the serving chaos grammar
    (``HARP_FAULT=kill@request=N``…) executes on this worker — a
    subprocess worker dies ``os._exit`` (classifiable by the supervisor),
    an in-process worker dies abruptly through :meth:`die`.
    """

    def __init__(self, session, rank: int, endpoints: Dict[str, object],
                 placement: Dict[str, int], *,
                 peers: Optional[Dict[int, Tuple[str, int]]] = None,
                 secret: Optional[bytes] = None, host: str = "127.0.0.1",
                 max_wait_s: float = DEFAULT_MAX_WAIT_S,
                 max_wait_overrides: Optional[Dict[str, float]] = None,
                 metrics=None,
                 slo=None, metrics_port: Optional[int] = None,
                 cache=None, fault_exit: bool = False,
                 aot_store=None,
                 aot_model_hashes: Optional[Dict[str, str]] = None,
                 compile_cache_dir: Optional[str] = None,
                 on_control: Optional[Callable[[dict], None]] = None,
                 max_queue: Optional[int] = None,
                 brownout_min_priority: int = 0):
        if metrics is None:
            from harp_tpu.utils.metrics import DEFAULT as metrics
        self.session = session
        self.rank = rank
        self.placement = dict(placement)
        self.endpoints = dict(endpoints)
        # AOT cold start (ISSUE 15): the persistent compilation cache is
        # wired first (whatever still compiles below loads from it), then
        # every endpoint PREPARES FROM ARTIFACTS — fresh store hits are
        # installed as the resident dispatch (trace_counts stays 0 for
        # those buckets, asserted by the endpoint) and warmed; misses are
        # compiled AND warmed now, so an aot-enabled worker never serves
        # a cold bucket either way. All of this happens before the
        # receive thread starts — for a fleet subprocess that means
        # before rendezvous: an elastic replacement never recompiles
        # under traffic.
        from harp_tpu.aot.cache import enable_compile_cache

        enable_compile_cache(compile_cache_dir)
        self.aot_loaded: Dict[str, list] = {}
        if aot_store is not None:
            from harp_tpu.aot import serve_artifacts
            from harp_tpu.aot.store import ArtifactStore

            if isinstance(aot_store, str):
                aot_store = ArtifactStore(aot_store, metrics=metrics)
            hashes = aot_model_hashes or {}
            for name, ep in self.endpoints.items():
                loaded = serve_artifacts.load_endpoint(
                    aot_store, ep, model_hash=hashes.get(name),
                    warm=True, warm_missing=True)
                self.aot_loaded[name] = loaded
                metrics.count(f"serve.aot_loaded_buckets.{name}",
                              len(loaded))
        # gang ranks are reserved: a reply_to rank colliding with a serving
        # worker must never overwrite the forwarding route to that worker.
        # placement/_worker_ranks/placement_version mutate together under
        # _placement_lock (receive thread applies pushed frames, the fleet
        # supervisor may apply directly from its own thread)
        self._worker_ranks = set(self.placement.values()) | {rank}
        self._placement_lock = threading.Lock()
        self.placement_version = 0
        self.cache = cache
        self._fault_exit = bool(fault_exit)
        self.on_control = on_control
        # receive-thread-only counter driving the serving fault grammar
        # (request=N trigger points); no lock — single-writer, single-reader
        self._requests_seen = 0
        self.metrics = metrics
        # the serving-plane observability hooks (both optional): an
        # SLOWatchdog fed one (age, ok) sample per reply, and a per-worker
        # pull exporter (metrics_port=0 binds an ephemeral port — read it
        # back from worker.exporter.port)
        self.slo = slo
        self.max_wait_s = max_wait_s
        self._secret = secret        # the fleet respawns a dead worker's
        #                              twin with the same transport auth
        self.exporter = None
        if metrics_port is not None:
            from harp_tpu.telemetry.exporter import MetricsExporter

            self.exporter = MetricsExporter(metrics, port=metrics_port,
                                            rank=rank)
        self.queue = EventQueue()
        self.transport = P2PTransport(self.queue, rank=rank,
                                      peers=peers if peers is not None
                                      else {},
                                      secret=secret, host=host)
        # per-model coalescing deadlines (ISSUE 15 satellite): a model's
        # override beats the worker-wide default — two models on one
        # worker can run different latency/batching trades (the
        # suggest_max_wait_s helper derives a value from the span table)
        overrides = max_wait_overrides or {}
        self.max_wait_overrides = {str(m): float(v)
                                   for m, v in overrides.items()}
        # admission control (ISSUE 16): max_queue bounds every batcher's
        # backlog (over-bound submits are shed with a retryable overloaded
        # reply); brownout rides the SLO watchdog's burning state — while
        # the error budget burns, sub-brownout_min_priority traffic is
        # shed even from a within-bounds queue. Hot-key cache hits are
        # served in _handle BEFORE admission, so they survive brownout.
        self.max_queue = max_queue
        self.brownout_min_priority = brownout_min_priority
        self.batchers: Dict[str, MicroBatcher] = {
            name: self._make_batcher(name, ep)
            for name, ep in self.endpoints.items()}
        # drain flag crosses threads (begin_drain on the caller's thread,
        # checked in the receive loop): an Event, not a bare bool — the
        # JL301 class the concurrency lint exists for. close() races
        # itself too (module-level atexit sweep vs an owner thread's
        # close), so its idempotence check-then-act runs under a lock
        self._draining = threading.Event()
        self._close_lock = threading.Lock()
        self._closed = False
        # set ONLY by die(): the fleet monitor keys recovery on this, so
        # a cleanly close()d worker (shutdown, atexit sweep) is never
        # mistaken for a corpse and resurrected
        self.died = False
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"harp-serve-worker-{rank}")
        self._thread.start()
        _register_live(self)

    @property
    def address(self) -> Tuple[str, int]:
        return self.transport.address

    # -- receive loop -------------------------------------------------------

    def _loop(self) -> None:
        while not self._stop.is_set():
            ev = self.queue.wait(timeout=0.05)
            if ev is None:
                continue
            payload = ev.payload
            kind = payload.get("kind") if isinstance(payload, dict) else None
            if kind == protocol.PLACEMENT:
                try:
                    self.apply_placement(payload.get("placement") or {},
                                         payload.get("peers") or {},
                                         payload.get("version", 0))
                except (TypeError, ValueError, AttributeError, IndexError,
                        KeyError):
                    # version-skewed frame shapes (non-dict placement,
                    # short address tuples) must cost one dropped frame,
                    # never the receive thread
                    self.metrics.count("serve.malformed_placements")
                continue
            if kind == protocol.PLACEMENT_GET:
                self._answer_placement_get(payload)
                continue
            if kind == protocol.CONTROL:
                if self.on_control is not None:
                    try:
                        self.on_control(payload)
                    except Exception:
                        # an operator frame must never cost the receive
                        # loop — same lifeline rule as request handling
                        import logging

                        logging.getLogger("harp_tpu.serve").exception(
                            "control frame handler failed")
                        self.metrics.count("serve.control_errors")
                continue
            if kind != protocol.REQUEST:
                self.metrics.count("serve.non_request_events")
                continue
            try:
                self._handle(payload)
            except Exception:
                # the receive thread is the worker's lifeline: a malformed
                # frame (missing id, unhashable model — anything the typed
                # guards below did not anticipate) costs that one frame,
                # logged and counted, never the loop
                import logging

                logging.getLogger("harp_tpu.serve").exception(
                    "dropping unhandlable request frame")
                self.metrics.count("serve.malformed_requests")

    def _handle(self, msg: dict) -> None:
        self.metrics.count("serve.requests")
        spans.stamp(msg, spans.RECV)
        # the serving chaos grammar (HARP_FAULT=kill|vanish|slow@request=N):
        # a scripted death/straggle lands HERE, on the receive path with
        # requests in flight — the scenario the recovery machinery exists
        # for. Subprocess workers exit with the classification code;
        # in-process workers die abruptly via die().
        from harp_tpu.parallel import faults

        self._requests_seen += 1
        hook = None if self._fault_exit else self.die
        faults.serve_fire(self._requests_seen, rank=self.rank,
                          on_kill=hook, on_vanish=hook)
        if self._closed:
            return                   # the fault just killed this worker
        if self._draining.is_set():
            self._reply(msg, ok=False, error=protocol.ERR_SHUTTING_DOWN)
            return
        model = msg.get("model")
        ep = self.endpoints.get(model)
        if self.cache is not None and msg.get("op") == protocol.OP_TOPK:
            # hot-key fast path: a fresh same-epoch reply skips the route
            # + coalesce + dispatch stack — and on a NON-owner router
            # (ep is None) even the forward hop: a shared cache's
            # latest-known epoch for the model stands in for the owner's
            # version, which is what makes the hot rows effectively
            # replicated at every router (the version key still makes a
            # post-refresh stale hit impossible — see serve/cache.py)
            if ep is not None and getattr(ep, "op", None) == \
                    protocol.OP_TOPK:
                version = getattr(ep, "version", None)
                hit = self.cache.get(model, msg.get("data"), version,
                                     quant=getattr(ep, "quant", None))
            elif ep is None:
                hit_v = self.cache.get_latest(model, msg.get("data"))
                hit, version = hit_v if hit_v is not None else (None,
                                                               None)
            else:
                hit = None
            if hit is not None:
                self._reply(msg, ok=True, result=hit, version=version)
                return
        with self._placement_lock:
            owner = self.placement.get(model, self.rank)
        if owner != self.rank:
            # fan out to the owning worker; reply_to stays the client's, so
            # the answer travels owner -> client directly
            try:
                spans.stamp(msg, spans.FORWARD)
                self.transport.send(owner, msg)
                self.metrics.count("serve.forwarded")
            except (KeyError, ConnectionError) as e:
                # a TRANSIENT routing state (owner died mid-window, stale
                # map): the prefixed error is retryable — the client
                # re-syncs placement and resubmits
                self._reply(msg, ok=False,
                            error=f"{protocol.ERR_FORWARD}: to worker "
                                  f"{owner}: {e}")
            return
        batcher = self.batchers.get(model)
        if batcher is None:
            self._reply(msg, ok=False,
                        error=f"{protocol.ERR_UNKNOWN_MODEL}: {model!r} "
                              f"(this worker serves "
                              f"{sorted(self.endpoints)})")
            return
        if not batcher.submit(msg):
            self._reply(msg, ok=False, error=protocol.ERR_SHUTTING_DOWN)

    # -- fleet control plane (mutable placement) ---------------------------

    def apply_placement(self, placement: Dict[str, int],
                        peers: Dict[int, Tuple[str, int]],
                        version: int) -> bool:
        """Adopt a NEWER versioned placement map + peer addresses (pushed
        by the fleet supervisor after a re-placement, or received as a
        ``serve.placement`` frame). A stale or same-version frame is a
        no-op — reordered pushes can never roll routing back. Returns
        whether the map was applied."""
        # normalize BOTH fields before touching any state: a frame that
        # is malformed anywhere (version skew) must apply NOTHING — a
        # torn half-applied map is worse than a dropped frame
        version = int(version)
        placement = {str(m): int(r) for m, r in placement.items()}
        peers = {int(r): (a[0], int(a[1])) for r, a in peers.items()}
        with self._placement_lock:
            if version <= self.placement_version:
                return False
            self.placement = placement
            self._worker_ranks = set(placement.values()) | {self.rank}
            self.placement_version = version
        for r, addr in peers.items():
            if r != self.rank:
                self.transport.add_peer(r, addr)
        self.metrics.count("serve.placement_updates")
        return True

    def placement_frame(self) -> dict:
        """The current versioned placement as a pushable frame — peer
        addresses are whatever this worker can dial (its own address
        included), which is exactly what a client needs to re-route."""
        known = self.transport.peers()
        with self._placement_lock:
            placement = dict(self.placement)
            version = self.placement_version
            ranks = set(self.placement.values())
        peers = {r: known[r] for r in ranks if r in known}
        peers[self.rank] = self.address
        return protocol.make_placement(placement, peers, version)

    def _answer_placement_get(self, msg: dict) -> None:
        try:
            rank, rhost, rport = msg["reply_to"]
            rank, rport = int(rank), int(rport)
        except (KeyError, TypeError, ValueError):
            self.metrics.count("serve.unroutable_replies")
            return
        with self._placement_lock:
            collision = rank in self._worker_ranks
        if collision:
            self.metrics.count("serve.reply_rank_collisions")
            return
        self.transport.add_peer(rank, (rhost, rport))
        try:
            self.transport.send(rank, self.placement_frame())
        except (OSError, TypeError):
            self.metrics.count("serve.lost_replies")

    # -- elastic endpoint set (ISSUE 16 autoscaler moves) -------------------

    def _brownout(self) -> bool:
        """The batchers' brownout arm: True while the SLO watchdog reports
        its error budget burning (no watchdog = never brown out)."""
        slo = self.slo
        if slo is None:
            return False
        is_burning = getattr(slo, "is_burning", None)
        return bool(is_burning()) if is_burning is not None \
            else bool(getattr(slo, "burning", False))

    def _make_batcher(self, name: str, ep) -> MicroBatcher:
        return MicroBatcher(
            ep, self._make_reply_fn(), metrics=self.metrics,
            max_wait_s=self.max_wait_overrides.get(name, self.max_wait_s),
            max_queue=self.max_queue, brownout_fn=self._brownout,
            brownout_min_priority=self.brownout_min_priority)

    def add_endpoint(self, name: str, ep) -> None:
        """Install a model endpoint LIVE (the autoscaler's scale-up /
        scale-down move target): a fresh batcher starts serving it the
        moment this returns. The fleet pushes the re-pointed placement
        separately — until then requests for ``name`` still route to the
        old owner and get forwarded here once the map lands."""
        name = str(name)
        # the model maps are read by the receive loop while the fleet
        # installs from its own thread — mutate under the same lock the
        # placement state rides
        with self._placement_lock:
            if name in self.batchers:
                raise ValueError(f"endpoint {name!r} already installed on "
                                 f"rank {self.rank}")
            self.endpoints[name] = ep
            self.batchers[name] = self._make_batcher(name, ep)
        self.metrics.count("serve.endpoints_added")

    def remove_endpoint(self, name: str, timeout: float = 30.0):
        """Drain and uninstall one model endpoint (the donor side of a
        scale move). Call AFTER the placement re-pointing the model away
        from this rank has been pushed — accepted requests drain through
        the batcher, later arrivals forward to the new owner off the
        updated map. Returns the endpoint object (the fleet re-homes it)
        or None when this rank never served it."""
        name = str(name)
        # unhook under the placement lock; the (blocking) drain runs after
        with self._placement_lock:
            batcher = self.batchers.pop(name, None)
            ep = self.endpoints.pop(name, None)
        if batcher is not None:
            batcher.drain_and_stop(timeout)
            self.metrics.count("serve.endpoints_removed")
        return ep

    # -- reply path ---------------------------------------------------------

    def _make_reply_fn(self) -> Callable:
        def reply(msg, ok, result=None, error=None, batch=None, bucket=None,
                  version=None, retry_after_s=None):
            if (ok and self.cache is not None
                    and msg.get("op") == protocol.OP_TOPK):
                # fill AT the reply boundary: the result was computed under
                # exactly `version` (snapshotted with the dispatch state)
                # and under the serving endpoint's quant mode — both join
                # the key, and the stored result stays UNencoded so one
                # entry serves old (f32) and new (accept_enc) clients
                ep = self.endpoints.get(msg.get("model"))
                self.cache.put(msg.get("model"), msg.get("data"), version,
                               result, quant=getattr(ep, "quant", None))
            self._reply(msg, ok=ok, result=result, error=error, batch=batch,
                        bucket=bucket, version=version,
                        retry_after_s=retry_after_s)
        return reply

    def _reply(self, msg: dict, ok: bool, result=None, error=None,
               batch=None, bucket=None, version=None,
               retry_after_s=None) -> None:
        if ok and result is not None:
            # compact reply wire (ISSUE 17): encode the score payload iff
            # THIS requester advertised it decodes the format — encoding
            # at the single reply exit covers the dispatch path and the
            # hot-key cache fast path alike, and a request without
            # accept_enc (every pre-r17 client) gets plain f32 forever
            enc = protocol.choose_enc(msg.get("accept_enc"))
            if enc is not None:
                result = protocol.encode_result(result, enc)
                if isinstance(result, dict) and "scores_enc" in result:
                    self.metrics.count(f"serve.reply_encoded.{enc}")
        if self.slo is not None:
            # one (age, ok) sample per reply: age = now − the client's
            # submit wall, i.e. end-to-end minus the reply hop — the
            # server-side view of the SLO, available for EVERY request
            # (sampled or not), errors included (they burn the budget)
            ts = msg.get("ts")
            if isinstance(ts, (int, float)):
                self.slo.observe(time.time() - ts, ok=ok)
        try:
            rank, rhost, rport = msg["reply_to"]
            rank, rport = int(rank), int(rport)
        except (KeyError, TypeError, ValueError):
            # malformed reply_to (wrong arity, non-numeric rank/port): the
            # reply is unroutable, the serving thread must not die for it
            self.metrics.count("serve.unroutable_replies")
            return
        with self._placement_lock:
            collision = rank in self._worker_ranks
        if collision:
            # a client claiming a serving worker's rank would hijack the
            # gang's forwarding route if we add_peer'd it — drop the reply
            # (the client is misconfigured; local_gang mints client ranks
            # past the gang) and count the collision loudly
            self.metrics.count("serve.reply_rank_collisions")
            return
        self.transport.add_peer(rank, (rhost, rport))
        reply = protocol.make_reply(
            msg, ok=ok, result=result, error=error,
            served_by=self.rank, batch=batch, bucket=bucket,
            version=version, retry_after_s=retry_after_s)
        tr = msg.get(spans.TRACE_KEY)
        if tr is not None:
            # the accumulated trace rides the reply home: the CLIENT holds
            # the complete span (including this reply hop) and records it
            spans.stamp_trace(tr, spans.REPLY_SEND)
            reply[spans.TRACE_KEY] = tr
        try:
            self.transport.send(rank, reply)
        except (OSError, TypeError):
            # client gone (closed/crashed between send and reply — OSError
            # covers ConnectionError and gaierror) or a reply_to host of a
            # nonsense type reaching the socket layer: count, keep serving
            # — at-most-once is the transport's contract
            self.metrics.count("serve.lost_replies")

    # -- shutdown (atexit-close contract) -----------------------------------

    def begin_drain(self) -> None:
        """Stop ACCEPTING: from now on new requests get a clean
        "shutting-down" reply while already-accepted batches finish."""
        self._draining.set()

    def die(self) -> None:
        """ABRUPT death — the in-process stand-in for ``os._exit`` that
        the serving chaos grammar (``kill@request=N``) uses when the
        worker shares the test process: the transport is torn down NOW,
        accepted-but-unserved requests are dropped unanswered (their
        clients time out and retry — exactly what a real process death
        does to them), nothing drains, nothing replies shutting-down.
        The thread/socket bookkeeping still runs so the corpse leaks no
        OS resources into the rest of the suite. Idempotent with close().
        """
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
            self.died = True
        self._stop.set()
        # kill the transport FIRST: replies of any still-running dispatch
        # must hit a dead socket, like a real crash mid-batch
        self.transport.close()
        for b in self.batchers.values():
            b.kill()
        if threading.current_thread() is not self._thread:
            # the chaos hook fires ON the receive thread (a worker killing
            # itself mid-request) — that thread exits via the _stop flag
            self._thread.join(5.0)
        if self.exporter is not None:
            self.exporter.close()
        _unregister_live(self)

    def close(self, timeout: float = 30.0) -> None:
        """Drain in-flight micro-batches, stop threads, close the
        transport. Idempotent. A drain timeout (wedged dispatch) still
        releases the receive thread, socket, and live-set registration
        before the TimeoutError propagates — close never leaves the worker
        half-open and unretryable."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        self.begin_drain()
        drain_errors = []
        try:
            # EVERY batcher gets its drain attempt — one wedged model must
            # not leave another's accepted requests unanswered and its
            # thread spinning against the soon-closed transport
            for name, b in self.batchers.items():
                try:
                    b.drain_and_stop(timeout)
                except TimeoutError as e:
                    drain_errors.append(f"{name}: {e}")
        finally:
            self._stop.set()
            self._thread.join(timeout)
            self.transport.close()
            if self.exporter is not None:
                self.exporter.close()
            _unregister_live(self)
        if drain_errors:
            raise TimeoutError("; ".join(drain_errors))

    def __enter__(self) -> "ServeWorker":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class _PendingReply:
    """A reply future: set by the client's receive thread."""

    __slots__ = ("_event", "reply", "_discard")

    def __init__(self, discard=None):
        self._event = threading.Event()
        self.reply: Optional[dict] = None
        self._discard = discard

    def _set(self, reply: dict) -> None:
        self.reply = reply
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        """The reply's ``result`` payload; raises
        :class:`~harp_tpu.serve.protocol.ServeError` on a server-reported
        error and ``TimeoutError`` when no reply arrives (peer gone or
        frame lost — the transport is at-most-once, so treat a timeout as
        'retry or fail', not 'bug'). A timed-out entry is dropped from the
        client's waiting map — a resident client accumulating lost replies
        must not grow that map without bound."""
        if not self._event.wait(timeout):
            if self._discard is not None:
                self._discard()
            raise TimeoutError("no reply within timeout")
        if not self.reply["ok"]:
            err = protocol.ServeError(self.reply.get("error") or "unknown")
            # the raw reply rides on the exception: the retry layer reads
            # retry_after_s off a shed reply without re-parsing the string
            err.reply = self.reply
            raise err
        # idempotent: an encoded scores_enc payload (this client asked for
        # it via accept_enc) decodes back to f32 scores; every other reply
        # shape passes through untouched
        return protocol.decode_result(self.reply["result"])


class RouterClient:
    """Client-side endpoint: submits point queries, matches replies by id."""

    def __init__(self, rank: int, peers: Dict[int, Tuple[str, int]],
                 placement: Dict[str, int], *,
                 secret: Optional[bytes] = None, host: str = "127.0.0.1",
                 metrics=None, trace_sample: Optional[int] = None,
                 span_metrics=None, breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 1.0,
                 accept_enc: Optional[Tuple[str, ...]] = None):
        if metrics is None:
            from harp_tpu.utils.metrics import DEFAULT as metrics
        self.rank = rank
        self.placement = dict(placement)
        self.metrics = metrics
        # compact replies (ISSUE 17): the encodings this client advertises
        # on every request (None = the pre-r17 plain-f32 contract). Replies
        # decode transparently in the future's result() either way.
        self.accept_enc = tuple(accept_enc) if accept_enc else None
        # request tracing (telemetry.spans): sample every Nth submit; None
        # reads HARP_TRACE_REQUESTS (0/unset = off). span_metrics is where
        # the per-stage timers land — defaults to this client's registry,
        # overridable so load generators can keep per-client registries
        # (reservoirs are lock-guarded; the override is isolation, not a
        # race workaround)
        self.trace_sample = (spans.env_sample_interval()
                             if trace_sample is None else int(trace_sample))
        self.span_metrics = span_metrics if span_metrics is not None \
            else metrics
        self._default_dest = min(peers) if peers else 0
        self.queue = EventQueue()
        self.transport = P2PTransport(self.queue, rank=rank,
                                      peers=dict(peers), secret=secret,
                                      host=host)
        # rid -> (dest rank, pending): the dest rides along so in-flight
        # requests to a rank that just died/moved can be failed FAST
        self._waiting: Dict[str, Tuple[int, _PendingReply]] = {}
        self._lock = threading.Lock()
        # fleet state (ISSUE 14): the placement map is mutable (versioned
        # pushes / placement_get pulls), and ranks observed dead are
        # marked so submits to them FAIL FAST instead of paying a reply
        # timeout. All guarded by _lock; sync_placement waiters ride the
        # condition (notified per received placement frame).
        self.placement_version = 0
        self._dead_ranks: set = set()
        self._placement_seen = 0
        self._placement_cv = threading.Condition(self._lock)
        # per-rank circuit breaker (ISSUE 16): K consecutive transport
        # failures OPEN the circuit — submits to that rank fail fast
        # without dialing — until breaker_cooldown_s elapses, then ONE
        # half-open probe is let through; its success closes the circuit,
        # its failure re-opens (and re-arms the cooldown). State lives in
        # {rank: {"fails", "state", "opened_at"}} under _lock; a placement
        # frame re-announcing a rank resets its breaker (the supervisor
        # vouches for the address).
        self.breaker_threshold = max(1, int(breaker_threshold))
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        self._breaker: Dict[int, dict] = {}
        self._ids = itertools.count()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._recv_loop, daemon=True,
            name=f"harp-serve-client-{rank}")
        self._thread.start()
        # same atexit-sweep-vs-owner close race as ServeWorker: the
        # idempotence check-then-act must be atomic
        self._close_lock = threading.Lock()
        self._closed = False
        _register_live(self)

    def _recv_loop(self) -> None:
        while not self._stop.is_set():
            ev = self.queue.wait(timeout=0.05)
            if ev is None:
                continue
            payload = ev.payload
            if not isinstance(payload, dict):
                continue
            if payload.get("kind") == protocol.PLACEMENT:
                try:
                    self.apply_placement(payload.get("placement") or {},
                                         payload.get("peers") or {},
                                         payload.get("version", 0))
                except (TypeError, ValueError, AttributeError, IndexError,
                        KeyError):
                    # same contract as the worker loop: a skewed frame is
                    # one dropped frame, never the client's lifeline
                    self.metrics.count("serve.malformed_placements")
                continue
            if payload.get("kind") != protocol.REPLY:
                continue
            tr = payload.get(spans.TRACE_KEY)
            if tr is not None:
                spans.stamp_trace(tr, spans.REPLY_RECV)
            with self._lock:
                entry = self._waiting.pop(payload.get("id"), None)
            if entry is not None:
                entry[1]._set(payload)
            else:
                # a reply whose id is not waiting: late (its future timed
                # out and was discarded) or a netdup'd duplicate (the
                # first copy already popped the slot). Dropping is CORRECT
                # — ids are minted from an ever-increasing counter, never
                # reused, so an orphan can never be delivered into a later
                # request's future — but it must be visible, not silent
                self.metrics.count("serve.client.orphan_replies")
            if tr is not None:
                self._finish_span(tr)

    def _finish_span(self, tr: dict) -> None:
        """Reconstruct + record one returned span. The receive thread is
        the client's lifeline: a malformed trace (a stamp tuple mangled in
        transit) costs that one span, counted, never the loop."""
        try:
            bd = spans.breakdown(tr)
            if bd is None:
                self.metrics.count("serve.spans_incomplete")
                return
            spans.observe_span(bd, self.span_metrics)
            spans.record_span(bd)
        except (KeyError, TypeError, ValueError, IndexError):
            self.metrics.count("serve.spans_malformed")

    # -- fleet surface (ISSUE 14) -------------------------------------------

    def apply_placement(self, placement: Dict[str, int],
                        peers: Dict[int, Tuple[str, int]],
                        version: int) -> bool:
        """Adopt a versioned placement map + worker addresses (a pushed
        ``serve.placement`` frame, a ``placement_get`` answer, or the
        fleet supervisor calling in directly). Addresses are ALWAYS
        refreshed (add_peer drops a stale pooled connection on change);
        the map itself only moves forward — a stale frame cannot roll
        routing back. A rank the frame re-announces is alive again: its
        dead mark clears (a replaced worker rejoins at the same rank,
        new address). Returns whether the MAP was applied."""
        # normalize the whole frame BEFORE mutating anything (same
        # no-torn-application rule as the worker side)
        version = int(version)
        placement = {str(m): int(r) for m, r in placement.items()}
        peers = {int(r): (a[0], int(a[1])) for r, a in peers.items()}
        old = self.transport.peers()
        moved = [r for r, addr in peers.items()
                 if r in old and old[r] != addr]
        for r, addr in peers.items():
            self.transport.add_peer(r, addr)
        for r in moved:
            # a rank re-announced at a NEW address was replaced: whatever
            # was in flight to the old incarnation can never be answered
            # (at-most-once transport) — fail it now, the retry layer
            # resubmits against the replacement
            self._fail_inflight(r, f"rank {r} was replaced at {peers[r]}")
        with self._placement_cv:
            self._placement_seen += 1
            applied = version > self.placement_version
            if applied:
                self.placement = placement
                self.placement_version = version
            # a frame re-announcing a rank's address means the sender
            # believes it is alive — clear its dead mark even when the
            # MAP is same-version (a transient send failure must not
            # brick a healthy rank for this client until some unrelated
            # recovery bumps the version; if the rank really is dead the
            # next submit re-marks it in ~one failed connect)
            self._dead_ranks -= set(peers)
            # same vouching resets the circuit breaker: the supervisor
            # re-announcing an address means it believes the rank dials
            for r in peers:
                self._breaker.pop(r, None)
            self._placement_cv.notify_all()
        if applied:
            self.metrics.count("serve.placement_updates")
        return applied

    def mark_dead(self, rank: int) -> None:
        """Record a rank as dead: submits routed to it now FAIL FAST
        (ConnectionError at submit, no reply timeout paid) until a
        placement frame re-announces the rank. The retry layer marks a
        rank on send failure; the fleet supervisor may mark it the moment
        the death is detected."""
        with self._lock:
            self._dead_ranks.add(int(rank))
        self.metrics.count("serve.client_dead_marks")
        self._fail_inflight(int(rank), f"rank {rank} marked dead")

    def _fail_inflight(self, rank: int, reason: str) -> None:
        """Fail every in-flight future addressed to ``rank`` with a
        synthetic retryable dead-rank reply — the tentpole's 'in-flight
        requests to the dead rank are failed fast and retried, never
        hung': the at-most-once transport guarantees no real reply can
        arrive once the rank is dead or replaced."""
        with self._lock:
            victims = [(rid, p) for rid, (dest, p)
                       in self._waiting.items() if dest == rank]
            for rid, _p in victims:
                del self._waiting[rid]
        for rid, p in victims:
            p._set({"kind": protocol.REPLY, "id": rid, "ok": False,
                    "result": None, "served_by": None, "batch": None,
                    "bucket": None, "version": None,
                    "error": f"{protocol.ERR_DEAD_RANK}: {reason}"})
        if victims:
            self.metrics.count("serve.client_inflight_failed_fast",
                               len(victims))

    # -- circuit breaker (ISSUE 16) -----------------------------------------

    def breaker_state(self, rank: int) -> str:
        """``"closed"`` | ``"open"`` | ``"half-open"`` for tests/ops."""
        with self._lock:
            st = self._breaker.get(int(rank))
            return st["state"] if st is not None else "closed"

    def _breaker_admit(self, rank: int) -> None:
        """Gate one submit through rank's breaker: raises ConnectionError
        (fail fast, nothing dialed) while the circuit is open; after the
        cooldown the FIRST caller becomes the half-open probe and exactly
        one request goes through until its outcome lands."""
        with self._lock:
            st = self._breaker.get(rank)
            if st is None or st["state"] == "closed":
                return
            if st["state"] == "open" and (time.monotonic() - st["opened_at"]
                                          >= self.breaker_cooldown_s):
                st["state"] = "half-open"   # this caller is the probe
                return
        self.metrics.count("serve.client.breaker_fastfail")
        raise ConnectionError(
            f"circuit open for rank {rank} "
            f"({self.breaker_threshold} consecutive transport failures; "
            f"probe in {self.breaker_cooldown_s}s)")

    def _breaker_success(self, rank: int) -> None:
        with self._lock:
            st = self._breaker.pop(rank, None)
            was_open = st is not None and st["state"] != "closed"
        if was_open:
            self.metrics.count("serve.client.breaker_closed")

    def _breaker_failure(self, rank: int) -> None:
        with self._lock:
            st = self._breaker.setdefault(
                rank, {"fails": 0, "state": "closed", "opened_at": 0.0})
            st["fails"] += 1
            opening = (st["state"] == "half-open"       # failed probe
                       or (st["state"] == "closed"
                           and st["fails"] >= self.breaker_threshold))
            if opening:
                st["state"] = "open"
                st["opened_at"] = time.monotonic()
        if opening:
            self.metrics.count("serve.client.breaker_open")

    def sync_placement(self, timeout: float = 5.0) -> bool:
        """Pull the current placement from the surviving workers: send
        ``placement_get`` to every known non-dead worker rank and wait for
        any placement frame to arrive (newer maps apply, a same-version
        answer still satisfies the wait — the caller asked 'what is the
        map now', not 'give me a newer one'). Returns False when nobody
        answered within ``timeout``."""
        with self._lock:
            targets = sorted(
                (set(self.placement.values())
                 | set(self.transport.peers()))
                - self._dead_ranks - {self.rank})
            seen0 = self._placement_seen
        frame = protocol.make_placement_get(
            (self.rank,) + tuple(self.transport.address))
        sent = False
        for t in targets:
            try:
                self.transport.send(t, frame)
                sent = True
            except KeyError:
                continue             # no address for t — nothing to dial
            except ConnectionError:
                self.mark_dead(t)
        if not sent:
            return False
        deadline = time.monotonic() + timeout
        with self._placement_cv:
            while self._placement_seen == seen0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._placement_cv.wait(remaining)
        return True

    def request_retry(self, op: str, model: str, data, *,
                      timeout: float = 30.0, attempts: int = 5,
                      backoff_s: float = 0.05,
                      backoff_factor: float = 2.0,
                      backoff_max_s: float = 2.0, jitter: float = 0.5,
                      sync_timeout: float = 5.0, priority: int = 0,
                      retry_after_cap_s: float = 5.0,
                      sleep: Callable[[float], None] = time.sleep):
        """Synchronous point query with the fleet's retry contract
        (ISSUE 14): bounded ``attempts``, exponential backoff with
        multiplicative jitter between them, and a placement re-sync after
        every failure so the retry lands on wherever the model lives NOW.

        Failure handling per attempt:

        * owner marked dead / send fails → FAIL FAST (no reply timeout
          paid), the rank is marked dead, placement re-synced, retried;
        * reply timeout (worker died holding the request, or a frame was
          lost — the transport is at-most-once) → pending entry discarded
          (the waiting map stays bounded), placement re-synced, retried;
        * a clean ``shutting-down`` reply (worker draining mid-swap) →
          re-synced and retried;
        * an ``overloaded`` shed (ISSUE 16) → retried WITHOUT a placement
          re-sync (the map did not change — the queue is just full), and
          the backoff honors the reply's ``retry_after_s`` (the server's
          own drain estimate, capped at ``retry_after_cap_s`` so a
          corrupt frame cannot stall the client) when it exceeds the
          exponential schedule;
        * any other server-reported error (unknown model, dispatch error,
          deadline) is PERMANENT for this request and raises immediately —
          retrying a malformed query cannot help.

        Raises the last retryable error once the budget is spent."""
        import random

        last: Optional[Exception] = None
        retry_after: Optional[float] = None
        attempts = max(1, attempts)
        for attempt in range(attempts):
            def resync():
                # pointless (and up to sync_timeout of blocking) after
                # the last attempt — there is no retry left to use it
                if attempt + 1 < attempts:
                    self.sync_placement(sync_timeout)
            if attempt:
                delay = min(backoff_s * backoff_factor ** (attempt - 1),
                            backoff_max_s)
                delay *= 1.0 + jitter * random.random()
                if retry_after is not None:
                    delay = max(delay, retry_after)
                    retry_after = None
                self.metrics.count("serve.client_retries")
                sleep(delay)
            with self._lock:
                dest = self.placement.get(model, self._default_dest)
                dead = dest in self._dead_ranks
            if dead:
                self.metrics.count("serve.client_fastfail")
                last = ConnectionError(
                    f"owner rank {dest} of {model!r} is marked dead")
                resync()
                continue
            try:
                pending = self.submit(op, model, data, dest=dest,
                                      priority=priority)
            except ConnectionError as e:
                # the send itself failed — the fast-fail leg: nobody
                # waited a reply timeout to learn the rank is gone
                last = e
                self.mark_dead(dest)
                self.metrics.count("serve.client_fastfail")
                resync()
                continue
            except KeyError as e:
                last = e             # no address yet — sync will fetch it
                resync()
                continue
            try:
                return pending.result(timeout)
            except TimeoutError as e:
                # result() already discarded the pending entry — the
                # waiting map cannot grow through retries
                last = e
                self.metrics.count("serve.client_reply_timeouts")
                resync()
                continue
            except protocol.ServeError as e:
                # shutting-down (draining mid-swap), dead-rank (an
                # in-flight future failed fast by a placement update),
                # forward-failed (a worker's stale map hit the dead
                # owner), and overloaded (admission shed) are the
                # transient server states — everything else is permanent
                # for this request
                msg = str(e)
                if msg.startswith(protocol.ERR_OVERLOADED):
                    last = e
                    self.metrics.count("serve.client_overloaded")
                    ra = (getattr(e, "reply", None) or {}).get(
                        "retry_after_s")
                    if isinstance(ra, (int, float)) and ra > 0:
                        retry_after = min(float(ra), retry_after_cap_s)
                    continue         # no resync: the map didn't change
                if protocol.ERR_SHUTTING_DOWN not in msg \
                        and not msg.startswith(protocol.ERR_DEAD_RANK) \
                        and not msg.startswith(protocol.ERR_FORWARD):
                    raise
                last = e
                resync()
                continue
        assert last is not None
        raise last

    # -- submit/request -----------------------------------------------------

    def submit(self, op: str, model: str, data, *,
               deadline_ts: Optional[float] = None,
               dest: Optional[int] = None,
               priority: int = 0) -> _PendingReply:
        """Asynchronously submit one point query; returns the reply future.
        ``dest`` overrides the placement-derived owner (tests exercise the
        forwarding leg this way). A ``dest`` marked dead or behind an open
        circuit breaker fails fast with ConnectionError — no socket
        timeout, no reply wait. ``priority`` >= the worker's brownout
        floor survives load shedding while the SLO budget burns."""
        if self._closed:
            raise ConnectionError("client is closed")
        n = next(self._ids)
        rid = f"{self.rank}-{n}"
        with self._lock:
            if dest is None:
                dest = self.placement.get(model, self._default_dest)
            if dest in self._dead_ranks:
                dead = True
            else:
                dead = False
        if dead:
            self.metrics.count("serve.client_fastfail")
            raise ConnectionError(f"rank {dest} is marked dead — awaiting "
                                  f"a placement update that revives it")
        self._breaker_admit(dest)
        msg = protocol.make_request(
            rid, op, model, data,
            reply_to=(self.rank,) + tuple(self.transport.address),
            deadline_ts=deadline_ts, priority=priority,
            accept_enc=self.accept_enc)
        if self.trace_sample and n % self.trace_sample == 0:
            spans.start_trace(msg, op=op, model=model)

        def discard(rid=rid):
            with self._lock:
                self._waiting.pop(rid, None)

        pending = _PendingReply(discard=discard)
        with self._lock:
            self._waiting[rid] = (dest, pending)
        try:
            self.transport.send(dest, msg)
        except ConnectionError:
            with self._lock:
                self._waiting.pop(rid, None)
            self._breaker_failure(dest)
            raise
        except KeyError:
            with self._lock:
                self._waiting.pop(rid, None)
            raise
        self._breaker_success(dest)
        return pending

    def request(self, op: str, model: str, data, *, timeout: float = 30.0,
                dest: Optional[int] = None, priority: int = 0):
        """Synchronous point query (submit + wait)."""
        return self.submit(op, model, data, dest=dest,
                           priority=priority).result(timeout)

    def close(self) -> None:
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        self._stop.set()
        self._thread.join(5.0)
        self.transport.close()
        _unregister_live(self)

    def __enter__(self) -> "RouterClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def local_gang(session, worker_endpoints: List[Dict[str, object]], *,
               secret: Optional[bytes] = b"harp-serve-local",
               max_wait_s: float = DEFAULT_MAX_WAIT_S,
               max_wait_overrides: Optional[Dict[str, float]] = None,
               metrics=None,
               slo_p99_s: Optional[float] = None,
               slo_kw: Optional[dict] = None,
               metrics_port: Optional[int] = None,
               trace_sample: Optional[int] = None,
               cache=None, aot_dir: Optional[str] = None,
               compile_cache_dir: Optional[str] = None,
               max_queue: Optional[int] = None,
               brownout_min_priority: int = 0,
               client_rank_base: Optional[int] = None,
               accept_enc: Optional[Tuple[str, ...]] = None
               ) -> Tuple[List[ServeWorker], Callable[..., RouterClient]]:
    """An in-process serving gang on loopback (the tier-1/bench topology;
    multi-host gangs pass explicit peer maps or KV rendezvous instead).

    ``worker_endpoints[r]`` is worker ``r``'s ``{model: endpoint}`` map; the
    placement is derived from it. Returns the workers plus a factory that
    mints connected clients on fresh ranks. All transports authenticate
    with ``secret`` and bind loopback only.

    Observability plane (all optional): ``slo_p99_s`` installs one
    :class:`~harp_tpu.telemetry.watchdog.SLOWatchdog` per worker at that
    p99 target (``slo_kw`` forwards window/budget/telemetry_dir);
    ``metrics_port`` starts a per-worker pull exporter (0 = ephemeral
    ports, >0 = ``port + rank`` so same-host workers never collide);
    ``trace_sample`` makes every minted client trace every Nth request
    (None = the HARP_TRACE_REQUESTS default); ``cache`` installs ONE
    shared hot-key reply cache (serve/cache.py) across the gang's workers
    — the in-process fleet's "replicate the hot keys at every router"
    configuration.

    Overload plane (ISSUE 16): ``max_queue``/``brownout_min_priority``
    forward to every worker's admission control. ``client_rank_base``
    sets where minted client ranks start — the default (gang size) is
    fine for a FIXED gang, but a fleet that scales UP mints new worker
    ranks past the gang too; pass a high base (e.g. the process fleet's
    1000) so a scaled-up worker's rank can never collide with a client's
    and trip the reply-rank-collision guard.

    ``accept_enc`` (ISSUE 17): score encodings every minted client
    advertises (e.g. ``("f16",)``) — compact replies, decoded
    transparently; None keeps the plain-f32 reply wire.
    """
    from harp_tpu.telemetry.watchdog import SLOWatchdog

    placement = {name: r for r, eps in enumerate(worker_endpoints)
                 for name in eps}
    workers = [ServeWorker(session, r, eps, placement, peers={},
                           secret=secret, max_wait_s=max_wait_s,
                           max_wait_overrides=max_wait_overrides,
                           aot_store=aot_dir,
                           compile_cache_dir=compile_cache_dir,
                           metrics=metrics, cache=cache,
                           max_queue=max_queue,
                           brownout_min_priority=brownout_min_priority,
                           slo=(SLOWatchdog(slo_p99_s, rank=r,
                                            metrics=metrics,
                                            **(slo_kw or {}))
                                if slo_p99_s else None),
                           metrics_port=(None if metrics_port is None
                                         else (metrics_port + r
                                               if metrics_port else 0)))
               for r, eps in enumerate(worker_endpoints)]
    for w in workers:
        for v in workers:
            if v.rank != w.rank:
                w.transport.add_peer(v.rank, v.address)
    next_rank = itertools.count(len(workers) if client_rank_base is None
                                else int(client_rank_base))

    def make_client(metrics_override=None,
                    span_metrics=None) -> RouterClient:
        return RouterClient(next(next_rank),
                            {w.rank: w.address for w in workers},
                            placement, secret=secret,
                            metrics=(metrics_override if metrics_override
                                     is not None else metrics),
                            trace_sample=trace_sample,
                            span_metrics=span_metrics,
                            accept_enc=accept_enc)

    return workers, make_client
