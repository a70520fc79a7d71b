"""Production serving gateway: streaming HTTP frontend over the scheduler.

The network layer the continuous-batching stack was missing: after PR 2/3
the :class:`~deepspeed_tpu.inference.scheduler.DecodeScheduler` could only
be driven in-process. This module is the DeepSpeed-MII/vLLM-serving-class
frontend, built on **stdlib only** (``asyncio`` + hand-rolled HTTP/1.1 —
no aiohttp/fastapi in the image, and none needed):

- **HTTP surface** (OpenAI-compatible where it can be, given the engine
  speaks token ids, not text): ``POST /v1/completions`` with ``"stream":
  true`` SSE token streaming (``data: {chunk}\\n\\n`` ... ``data: [DONE]``),
  ``GET /healthz`` (process liveness), ``GET /readyz`` (serving readiness —
  flips 503 during drain), ``GET /v1/metrics`` (JSON gateway stats + the
  telemetry sink's :meth:`snapshot`; Prometheus text exposition under
  ``Accept: text/plain``/``openmetrics`` or ``?format=prometheus``, so
  standard scrapers work), ``GET /v1/slo`` (the SLO engine's objective/
  burn-rate state), ``GET /v1/debug/flight`` (force a flight-recorder
  dump). Prompts are token-id lists (or whitespace-separated decimal ids in
  a string); completions carry both ``token_ids`` and a space-joined
  decimal ``text``.

- **Request tracing**: an inbound W3C ``traceparent`` or ``x-request-id``
  header names the request (minted otherwise, echoed back as
  ``x-request-id``); with telemetry + request tracing on, every request
  records a span tree (queued -> admitted -> prefix probe -> prefill
  chunks -> decode -> complete/cancel/expire) on its own Perfetto track,
  flow-linked to the scheduler's shared per-iteration spans
  (``telemetry/tracing.py``).

- **SLOs + flight recorder**: the ``telemetry.slo`` config section (or the
  default serving slate — TTFT/queue-wait/ITL p95, shed+expiry rate) is
  evaluated from the pump loop with multi-window burn rates; a burn-rate
  trip, a backend step failure, or an unexpected post-warmup XLA recompile
  dumps the telemetry flight recorder's ring of surrounding iterations to
  a timestamped file.

- **Admission control**: a bounded per-tenant fair queue
  (:class:`~deepspeed_tpu.serving.fair_queue.FairQueue`). Past
  ``max_queue_depth`` requests shed with **429** and a ``Retry-After``
  derived from live state (queue depth x EMA service time / slots) instead
  of queueing unboundedly; during drain/not-ready they shed with **503**.
  Every request carries a deadline (``request_timeout_s``, body
  ``timeout_s`` override): expiry — and client disconnect, observed as EOF
  on the connection — propagates ``handle.cancel()`` into the scheduler so
  the KV slot frees mid-decode instead of finishing a dead request.

- **Per-tenant weighted fair queuing**: deficit round-robin over
  ``(tenant, priority)`` flows sits BETWEEN the HTTP layer and scheduler
  admission — the scheduler's own FIFO is kept nearly empty so the DRR
  order decides who gets the next free slot, and one heavy tenant cannot
  starve the pool (see ``fair_queue.py``).

- **Graceful lifecycle**: ``begin_drain()`` (wired to SIGTERM by the
  ``python -m deepspeed_tpu.serving`` entrypoint) flips readiness, stops
  admitting (503 + Retry-After), finishes every already-admitted request,
  flushes telemetry, and exits; ``drain_timeout_s`` bounds the grace.

- **Replica fleet** (``continuous_batching.replicas`` > 1): N scheduler
  replicas — independent slot pools, ONE weight tree and ONE compiled
  program set — behind this one gateway (``serving/replica.py``). The DRR
  pop is placed prefix-sticky (prompts sharing a cached prefix follow the
  replica that owns it) or least-loaded (occupancy x per-replica service
  EMA); ``POST /v1/replicas/<i>/drain|resume`` and per-replica health keep
  one sick replica from sinking the fleet. ``GET /v1/replicas`` lists
  states.

- **Elastic fleet control plane** (``continuous_batching.autoscaler``):
  a :class:`~deepspeed_tpu.serving.controller.FleetController` ticked from
  the replica-0 pump reads one consolidated signal snapshot per interval
  (SLO burn rates, queue wait, phase saturation, MFU/HBM/host-gap/goodput)
  and drives three cooldown-guarded actuators — grow/shrink the replica
  fleet over the SHARED compiled-program set, flip prefill/decode roles as
  the traffic mix drifts, and a brownout ladder that evicts then preempts
  low-tier work (503 + brownout Retry-After; optionally parking decode
  state for resume through the migration transport). ``GET/POST
  /v1/autoscaler`` exposes decisions and runtime enable/dry-run.

Threading model: the asyncio event loop owns sockets and parsing; one
**pump thread per replica** owns ALL of that replica's scheduler
interaction (submit/step/cancel — each scheduler stays single-threaded).
Admission (fair-queue pop + placement) and terminal accounting serialize on
the dispatch/finish locks. Tokens cross from a pump to the responses a
LANDING at a time: the scheduler's ``on_token`` hook appends each token to
the batch of the thread that runs it (:class:`_Landing`: a pump's own,
nothing shared between pumps), and when the scheduler says the landing is
delivered (``DecodeScheduler.on_landing``, still under ``sched/deliver``,
before the pump assembles the next sync) the batch goes to the event loop
in ONE ``loop.call_soon_threadsafe``, whose callback puts every row's item
on its response's ``asyncio.Queue``. An SSE event is one such item: a row's
tokens of one landing, 1 to ``steps_per_sync`` of them in order (one where
``steps_per_sync`` is 1, at a request's last partial landing, or behind a
final chunk whose first token came alone), one JSON document and one socket
write. So a streaming client receives chunks of up to ``steps_per_sync``
tokens as each host sync lands (TTFB = queue wait + prefill + first sync,
not request completion), and any other event for a request (``done``,
``cancelled``, ``failed``, ``handoff``) first hands on what the posting
thread's batch holds: nothing overtakes a token.

Telemetry (PR-1 sink): histograms ``gateway/queue_wait_ms``,
``gateway/ttfb_ms``; gauges ``gateway/queue_depth``,
``gateway/active_requests``; counters ``gateway/requests``,
``gateway/completed``, ``gateway/tokens``, ``gateway/shed_429``,
``gateway/shed_503``, ``gateway/deadline_expired``,
``gateway/disconnects``, ``gateway/tenant/<tenant>/tokens``.
"""

import asyncio
import copy
import json
import threading
import time

import numpy as np

from ..inference.config import GatewayConfig
from ..telemetry import (DEFAULT_SERVING_OBJECTIVES, RequestTrace, SLOEngine,
                         extract_trace_context)
from ..telemetry import prometheus as prom
from ..utils import compile_cache
from ..utils.logging import logger
from . import capacity_math
from .controller import FleetController, FleetSignals
from .fair_queue import FairQueue, QueueFull
from .replica import ReplicaSet

_JSON = "application/json"
# the token events between two whose send stands under a ``gateway/send``
# profiler annotation, less one (a mask: one send in 64)
SEND_SPAN_EVERY = 63


def _round_up(x, m):
    return (x + m - 1) // m * m


class _GatewayRequest:
    """One admitted-or-queued completion request: the handoff record between
    the HTTP handler (event loop) and the scheduler pump thread."""

    __slots__ = ("rid", "prompt", "max_new_tokens", "eos_token_id", "do_sample",
                 "temperature", "top_k", "top_p", "seed", "tenant", "priority",
                 "cost", "deadline", "stream", "events", "handle",
                 "cancel_requested", "cancel_reason", "finished", "enq_ts",
                 "admit_ts", "n_tokens", "trace", "trace_id", "replica",
                 "adapter_id", "return_logits", "resume", "unread")

    def __init__(self, rid, prompt, *, max_new_tokens, eos_token_id, do_sample,
                 temperature, top_k, top_p, seed, tenant, priority, deadline,
                 stream, trace=None, trace_id=None, adapter_id=None,
                 return_logits=False, resume=None):
        self.rid = rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_token_id = eos_token_id
        self.do_sample = do_sample
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.seed = seed
        self.tenant = tenant
        self.priority = priority
        self.cost = len(prompt) + max_new_tokens  # DRR work estimate
        self.deadline = deadline
        self.stream = stream
        self.events = asyncio.Queue()
        self.handle = None
        self.cancel_requested = False
        self.cancel_reason = None
        self.finished = False
        self.enq_ts = time.monotonic()
        self.admit_ts = None
        self.n_tokens = 0
        self.trace = trace          # RequestTrace (None when tracing is off)
        self.trace_id = trace_id    # request identity echoed as x-request-id
        self.replica = None         # serving replica this request landed on
        self.unread = False         # its handler went away: events go unread
        self.adapter_id = adapter_id  # model variant (multi-LoRA serving)
        # unary responses can carry per-step logits (the multihost
        # bit-identity surface: logits must round-trip process boundaries)
        self.return_logits = return_logits
        # cross-process migration resume: the handoff descriptor a router
        # POSTed after a prefill worker handed this request off (None for
        # ordinary arrivals — resume requests bypass the fair queue and go
        # straight to the fleet's migration admission)
        self.resume = resume


class _Landing(threading.local):
    """The tokens a scheduler delivered on THIS thread that the event loop
    has not been handed yet. A thread's own (every pump thread, and any
    other thread that steps a scheduler, finds a fresh one the first time
    it looks), so no two pumps ever share a batch and none takes a lock.
    ``rows``: request -> [its tokens of the landing in order, the finish
    reason with the last]. ``gap``: the account of the pump that runs on this
    thread (None with the sink off, and on a thread that is no pump)."""

    def __init__(self):
        self.rows = {}
        self.gap = None


class Gateway:
    """Serving gateway over one :class:`InferenceEngine`'s scheduler.

    ``Gateway(engine).start_background()`` binds the HTTP server (port 0 =
    ephemeral; the bound port lands on :attr:`port`) and starts the pump
    thread; ``begin_drain()`` initiates graceful shutdown and
    ``wait_drained()`` blocks until every admitted request finished and the
    server closed. ``run()`` is the blocking form the module entrypoint
    uses. ``config`` defaults to the engine config's ``gateway`` section;
    keyword overrides replace individual fields.
    """

    def __init__(self, engine, config=None, **overrides):
        if config is None:
            config = getattr(engine._config, "gateway", None)
        if not isinstance(config, GatewayConfig):
            config = GatewayConfig(dict(config or {}))
        if overrides:
            # never mutate the caller's (usually the ENGINE's) config object
            # in place: a later Gateway(engine) would silently inherit this
            # instance's overrides
            config = copy.deepcopy(config)
        for key, val in overrides.items():
            if not hasattr(config, key):
                raise ValueError(f"unknown GatewayConfig override {key!r}")
            setattr(config, key, val)
        self.engine = engine
        self.config = config
        self.telemetry = engine.telemetry
        # multi-replica serving (continuous_batching.replicas): N scheduler
        # replicas behind one dispatch policy (serving/replica.py), sharing
        # one weight tree and ONE compiled program set. Replica 0 is the
        # engine's singleton scheduler, so `self.scheduler` keeps meaning
        # what it always did for the single-replica gateway.
        self.replicas = ReplicaSet.build(engine)
        self.scheduler = self.replicas.primary
        # disaggregated serving: a finished handoff wakes parked decode
        # pumps immediately instead of waiting out the poll interval
        self.replicas.on_migration_ready = self._wake_all
        self._fair = FairQueue(max_depth=config.max_queue_depth,
                               quantum=config.quantum_tokens,
                               tenant_weights=config.tenant_weights,
                               priority_weights=config.priority_weights)
        self.stats = {"requests": 0, "completed": 0, "tokens": 0, "shed_429": 0,
                      "shed_503": 0, "deadline_expired": 0, "disconnects": 0,
                      "rejected": 0, "brownout_shed": 0, "brownout_evicted": 0,
                      "brownout_preempted": 0, "brownout_parked": 0,
                      "replicas_added": 0, "replicas_retired": 0,
                      # multi-host serving: requests handed off to another
                      # process (prefill side) / adopted from one (decode)
                      "handoffs_out": 0, "resumed_in": 0}
        self.host = config.host
        self.port = None  # bound port (after start)
        self.ready = False
        self.draining = False
        self._rid = 0
        self._rid_lock = threading.Lock()
        self._tenant_labels = set()          # tenants with their own counter
        self._wake = threading.Event()       # pump wakeup
        self._active = set()                 # admitted, unfinished _GatewayRequests
        self._ema_service_s = None           # EMA of request wall time
        # pump-side locks: dispatch (fair-queue pop + replica placement must
        # be one atomic decision across the per-replica pump threads) and
        # finish (terminal accounting is exactly-once even when a cancel
        # settling on one pump races the final token on another)
        self._dispatch_lock = threading.Lock()
        self._finish_lock = threading.Lock()
        self._loop = None
        self._server = None
        self._open_streams = 0               # responses still being written
        self._pump_thread = None
        self._pump_threads = []
        self._loop_thread = None
        self._done_evt = threading.Event()   # fully drained + server closed
        self._force_stop = False
        # SLO engine over the shared sink: the telemetry config's 'slo'
        # section (or the default serving objective slate) evaluated from
        # the pump loop; burn-rate trips dump the flight recorder
        self.slo = None
        if self.telemetry.enabled:
            self.slo = SLOEngine(self.telemetry,
                                 getattr(self.telemetry, "slo_config", None),
                                 defaults=DEFAULT_SERVING_OBJECTIVES)
            if not self.slo.enabled:
                self.slo = None
            else:
                self.slo.on_alert.append(
                    lambda state: self.telemetry.dump_flight(
                        f"slo_burn_{state['name']}", state))
        # unexpected-recompile watch: once the gateway has completed a
        # request the scheduler's program set is considered warm; later
        # growth is an anomaly worth a flight dump
        self._compile_baseline = None
        # operator flight-dump request (SIGUSR1): the signal handler only
        # stores the reason — dump_flight takes sink locks and a handler
        # interrupting a flush on the same thread would self-deadlock on
        # the non-reentrant io lock; the pump thread performs the dump
        self._flight_request = None
        # on-demand XLA profiling (POST /v1/debug/profile): duration-bounded
        # captures written next to the flight dumps; one per process — a
        # second request while one is in flight gets 409
        self.profiler = None
        if self.telemetry.enabled:
            from ..telemetry.profiler import XlaProfiler
            self.profiler = XlaProfiler(self.telemetry.output_path)
        # elastic fleet control plane (serving/controller.py): the replica-0
        # pump ticks it with one consolidated FleetSignals snapshot per
        # interval; the four actuators below close the loop onto the
        # ReplicaSet / FairQueue / cancel machinery the stack already has.
        # Constructed even when disabled so POST /v1/autoscaler can turn it
        # on at runtime (rollout: start dry_run, watch decisions, enable).
        cb_cfg = getattr(engine._config, "continuous_batching", None)
        as_cfg = getattr(cb_cfg, "autoscaler", None)
        self.autoscaler = None
        if as_cfg is not None:
            self.autoscaler = FleetController(as_cfg, telemetry=self.telemetry)
            self.autoscaler.scale_up_fn = self._scale_up
            self.autoscaler.scale_down_fn = self._scale_down
            self.autoscaler.rebalance_fn = self._rebalance
            self.autoscaler.brownout_fn = self._set_brownout
        # a replica added at runtime needs its own pump thread: the set
        # fires this from whichever thread ran add_replica
        self.replicas.on_replica_added = self._spawn_pump
        self._brownout_bar = None   # weight bar arrivals shed under (None=off)
        self._park_pending = set()  # greqs awaiting park-out on their owning pump
        self._gap_mark = None       # (busy_s, wait_s) of the fleet's pumps at the last snapshot
        # what each pump's scheduler delivered since its last hand-over to
        # the event loop (one batch a thread)
        self._landing = _Landing()
        # the event loop's delivery (telemetry/capacity.py: Delivery), only
        # with the sink on: the loop thread adds what it wrote, the pumps'
        # trackers count the events they posted (a landing's at once, where
        # its batch is handed over: ``_flush_landing``), and the primary
        # pump's account reads both at every landing. None with the sink
        # off: the per-event path then tests this and does nothing else
        self._delivery = None
        if self.telemetry.enabled:
            from ..telemetry.capacity import Delivery
            self._delivery = Delivery()
        # multi-host serving (serving/router.py): the WorkerAgent attaches a
        # NetPrefixStore here so /v1/store/fetch can serve this shard's KV
        # bytes to remote restores; None on single-process gateways
        self.net_store = None
        # POST /v1/debug/flush_radix: replica idxs whose pump must evict the
        # whole radix trie through the tier next turn (multihost tests force
        # cross-host demotion with it)
        self._flush_radix_pending = set()

    # ------------------------------------------------------------------ lifecycle
    def start_background(self, timeout=120.0):
        """Start the server + pump on background threads; returns once the
        port is bound and the gateway is ready (raises on startup failure)."""
        ready = threading.Event()
        fail = []

        def runner():
            try:
                asyncio.run(self._serve(ready.set))
            except Exception as e:  # noqa: BLE001 — surface to the caller
                fail.append(e)
                ready.set()
            finally:
                self._done_evt.set()

        self._loop_thread = threading.Thread(target=runner, daemon=True,
                                             name="gateway-server")
        self._loop_thread.start()
        if not ready.wait(timeout):
            raise TimeoutError("gateway failed to bind within startup timeout")
        if fail:
            raise fail[0]
        return self

    def run(self):
        """Blocking serve-until-drained (the ``python -m`` entrypoint path).
        Returns 0 after a clean drain. Interruptible: signal handlers run on
        the main thread while this waits."""
        self.start_background()
        logger.info(f"gateway listening on {self.host}:{self.port}")
        print(json.dumps({"event": "GATEWAY_READY", "host": self.host,
                          "port": self.port}), flush=True)
        while not self._done_evt.wait(0.2):
            pass
        return 0

    def begin_drain(self):
        """Graceful shutdown trigger (SIGTERM handler / test hook; any
        thread): flip readiness, stop admitting, let the pump finish every
        admitted request, then close the server and flush telemetry."""
        if self.draining:
            return
        self.draining = True
        self.ready = False
        logger.info("gateway: drain initiated (no new admissions)")
        # lift any brownout: parked decode state must resume (and finish)
        # for the drain to complete, and the door is closed anyway
        self._brownout_bar = None
        self._park_pending.clear()
        self.replicas.release_parked()
        # drain grace bound: past it, in-flight requests fail fast instead
        # of holding the process open forever
        timer = threading.Timer(float(self.config.drain_timeout_s), self._force)
        timer.daemon = True
        timer.start()
        self._wake.set()

    def _force(self):
        if not self._done_evt.is_set():
            logger.warning("gateway: drain timeout exceeded; forcing stop")
            self._force_stop = True
            self._wake.set()

    def request_flight_dump(self, reason):
        """Async-signal-safe flight-dump request (a plain attribute store):
        the pump thread performs the actual dump on its next turn. This is
        what the ``SIGUSR1`` handler calls — a handler that invoked
        ``dump_flight`` directly could interrupt a flush on its own thread
        and deadlock on the sink's io lock."""
        self._flight_request = str(reason)
        self._wake.set()

    def wait_drained(self, timeout=None):
        """Block until drain completes (all admitted requests finished, the
        server closed). Returns False on timeout."""
        return self._done_evt.wait(timeout)

    def close(self, timeout=None):
        """begin_drain + wait_drained, for tests/benches."""
        self.begin_drain()
        done = self.wait_drained(timeout if timeout is not None
                                 else self.config.drain_timeout_s + 30)
        if self.profiler is not None:
            self.profiler.stop()  # a capture must not outlive the gateway
        return done

    async def _serve(self, ready_cb):
        self._loop = asyncio.get_running_loop()
        self._server = await asyncio.start_server(self._handle_conn, self.host,
                                                  self.config.port)
        self.port = self._server.sockets[0].getsockname()[1]
        # one pump thread PER REPLICA: each owns all calls into its own
        # scheduler (the single-threaded-scheduler contract, N times over);
        # admission and terminal accounting serialize on the dispatch/finish
        # locks. On a pod each pump drives its own device group; on one host
        # the threads interleave through the shared backend.
        self._pump_threads = []
        for rep in self.replicas:
            self._spawn_pump(rep)
        self._pump_thread = self._pump_threads[0]  # single-replica back-compat
        self.ready = True
        ready_cb()
        # pump exit == fully drained (each pump only returns when draining
        # and all admitted work finished, or on force-stop)
        while any(t.is_alive() for t in self._pump_threads):
            await asyncio.sleep(0.05)
        # let in-flight response writers flush their final events
        deadline = time.monotonic() + 10.0
        while self._open_streams > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        self._server.close()
        await self._server.wait_closed()
        try:
            self.telemetry.flush()
        except Exception:  # noqa: BLE001 — a sink failure must not fail drain
            pass
        logger.info("gateway: drained and closed")

    # ------------------------------------------------------------------ pump threads
    def _wake_all(self):
        """Transfer-thread-safe pump wakeup (migration-ready callback)."""
        self._wake.set()

    def _spawn_pump(self, rep):
        """Start (or restart) the pump thread that owns ``rep``'s scheduler.
        Called at startup for the initial fleet and from ``add_replica`` —
        on the on_replica_added hook — for elastic growth; a retired index
        being re-used gets a FRESH thread (the old one exited at retire)."""
        t = threading.Thread(target=self._pump, args=(rep, ), daemon=True,
                             name=f"gateway-pump-{rep.idx}")
        self._pump_threads.append(t)
        t.start()
        return t

    def _pump(self, rep):
        """One replica's pump: admit from the fair queue in DRR order
        (dispatch-locked — placement is a fleet-wide decision), step THIS
        replica's decode loop, enforce deadlines and cancellations. Exits
        only when draining and every admitted request has finished.

        Replica 0's pump additionally owns the fleet-wide side duties (SLO
        evaluation, operator flight dumps, recompile watch) so they run
        exactly once per turn regardless of fleet size."""
        sched = rep.scheduler
        primary = rep.idx == 0
        # the pump's own two spans: profiler annotations only (an idle pump
        # turns 50 times a second; the sink records none of them), heard by
        # the scheduler's account of the pump's time (None with the sink off)
        span, gap = self.telemetry.span, sched._gap
        if gap is not None and self._delivery is not None:
            # the account also covers the host's two threads: this pump's
            # processor clock, and through the primary's the event loop's
            from ..telemetry.capacity import thread_cpu_clock
            loop = self._loop_thread if primary else None
            gap.bind_threads(thread_cpu_clock(threading.get_ident()), self._delivery,
                             thread_cpu_clock(loop.ident) if loop is not None else None,
                             primary=primary)
            self._landing.gap = gap   # this thread's batches count as its posts
        # the scheduler says when a landing's tokens are all through their
        # hooks: its batch crosses to the event loop then, not a step later
        sched.on_landing = self._flush_landing
        while not self._force_stop:
            with span("gateway/admit", record=False, observer=gap), self._dispatch_lock:
                self._enforce_cancellations()
                self._admit()
            try:
                # disaggregated serving: claim parked prefill->decode
                # handoffs for THIS replica (cancelled ones settle on any
                # pump; a decode pump that adopts one becomes non-idle and
                # steps below). Inside the SAME guard as step(): a restore
                # failing on device must degrade to sick-replica shedding,
                # not kill this daemon thread and strand its requests
                self.replicas.admit_migrations(rep)
                if self._park_pending:
                    # brownout park-for-resume: only the owning pump may
                    # call migrate_out on its scheduler
                    self._park_owned(rep)
                if rep.idx in self._flush_radix_pending:
                    # debug-forced demotion: only this pump may touch its
                    # scheduler's radix trie
                    self._flush_radix(rep)
                if not rep.idle() and not rep.sick:
                    rep.step()
            except Exception:  # noqa: BLE001 — fail requests, not the server
                logger.exception(f"gateway: replica {rep.idx} scheduler step failed")
                self.telemetry.dump_flight("backend_error")
                # "other healthy replicas remain BESIDES this one":
                # healthy() still counts this not-yet-marked replica, so
                # > 1 is the real fleet-keeps-serving test — the LAST
                # healthy replica failing must take the fail-and-retry
                # path below, not sick the whole fleet into a state only
                # a manual resume can leave
                if len(self.replicas.healthy()) > 1:
                    # shed the sick replica, keep the fleet serving:
                    # its in-flight requests fail, placement avoids it,
                    # and its pump STOPS stepping it (a persistently-
                    # raising backend must not spin traceback/flight-
                    # dump loops or block drain) until resume()
                    self.replicas.mark_sick(rep.idx, "scheduler step failed")
                    self._fail_replica_in_flight(rep, "replica step failed")
                else:
                    # single replica (or the last healthy one): today's
                    # semantics — fail everything, stay up, retry on the
                    # next admitted request
                    self._fail_in_flight("scheduler step failed")
            # what a path that told of no landing delivered, or a step that
            # raised in the middle of one left behind
            self._flush_landing()
            self._settle_done()
            if primary:
                # every primary iteration, stepped or not: the program set
                # is SHARED, so another replica's stray shape must trip the
                # recompile watch even while replica 0 idles
                self._watch_recompiles()
                if self.slo is not None:
                    self.slo.maybe_evaluate()
                if self.autoscaler is not None and not self.draining:
                    # elastic fleet control: one consolidated snapshot, at
                    # most one actuation per interval (controller.py)
                    self.autoscaler.tick(self.fleet_signals())
                if self._flight_request is not None:
                    reason, self._flight_request = self._flight_request, None
                    self.telemetry.dump_flight(reason)
                if self.profiler is not None:
                    # belt-and-braces deadline: stops an overdue capture
                    # even if its timer thread was lost
                    self.profiler.poll()
            if rep.pending_drain or rep.retired:
                # elastic scale-down: once THIS pump observes its replica
                # idle it performs the retire itself (frees the slot pool
                # HBM on the thread that owns the scheduler) and exits;
                # add_replica reusing the index spawns a fresh pump
                if rep.retired or self.replicas.finish_scale_down(rep):
                    break
            if rep.idle() or rep.sick:
                if self.draining and not len(self._fair) and not self._active:
                    break
                with span("gateway/idle", record=False, observer=gap):
                    self._wake.wait(0.02)
                self._wake.clear()
        if gap is not None:
            gap.unbind_threads()  # this thread's clock dies with it
        # force-stop: anything still in flight is failed, not silently
        # dropped (any one pump suffices — _fail_in_flight spans the fleet)
        if self._force_stop and primary:
            self._fail_in_flight("gateway shutdown")

    def _watch_recompiles(self):
        """Flight-dump on unexpected XLA recompiles: after the first
        completed request the scheduler's compiled-program set is warm for
        the serving mix — later growth (a stray shape, a new sampling
        variant slipping past the O(1)-programs design) is exactly the
        anomaly the recorder exists for."""
        count = self.scheduler.compiled_program_count()
        if self._compile_baseline is None:
            if self.stats["completed"] >= 1:
                self._compile_baseline = count
        elif count > self._compile_baseline:
            tel = self.telemetry
            if tel.enabled:
                tel.counter("gateway/unexpected_recompiles",
                            count - self._compile_baseline)
                tel.dump_flight("xla_recompile",
                                {"programs": count,
                                 "baseline": self._compile_baseline})
            self._compile_baseline = count

    def _admit(self):
        """Move requests from the DRR queue into scheduler slots while the
        fleet has capacity (caller holds the dispatch lock). Each pop is
        placed by the replica set — prefix-sticky, else least-loaded — and
        every replica's FIFO is kept empty (admission is 1:1 with free
        capacity) so fair-queue order IS slot order."""
        tel = self.telemetry
        while True:
            if not self.replicas.any_capacity():
                if self.replicas.all_sick():
                    if len(self._fair):
                        self._fail_queue("no healthy serving replica")
                    if self.replicas.pending_migrations():
                        # parked handoffs have no adopter left either
                        self.replicas._fail_handoffs()
                return
            greq = self._fair.pop()
            if greq is None:
                return
            if tel.enabled:
                tel.gauge("gateway/queue_depth", len(self._fair))
            if greq.cancel_requested:
                if greq.trace is not None:
                    greq.trace.instant("cancelled", where="queue")
                self._post(greq, ("cancelled", greq.cancel_reason or "cancelled"))
                continue
            now = time.monotonic()
            if greq.deadline is not None and now >= greq.deadline:
                self.stats["deadline_expired"] += 1
                if tel.enabled:
                    tel.counter("gateway/deadline_expired")
                if greq.trace is not None:
                    greq.trace.phase("queued", status="expired")
                    greq.trace.instant("expired", where="queue")
                self._post(greq, ("failed", 504, "deadline expired in queue"))
                continue
            rep = self.replicas.route(greq.prompt, adapter=greq.adapter_id)
            if rep is None:
                # eligibility changed between the capacity check and the
                # pop (drain/sick/phase-role mutate under the ReplicaSet's
                # own lock): requeue at the flow head — the blip is fleet-
                # internal churn, not client overload, so a 503 here would
                # shed an already-accepted request for nothing. If the
                # fleet stays unplaceable the queue bounds still shed new
                # arrivals with honest Retry-After.
                self._fair.requeue(greq, greq.tenant, greq.priority,
                                   cost=greq.cost, adapter=greq.adapter_id)
                return
            try:
                handle = rep.scheduler.submit(
                    greq.prompt, max_new_tokens=greq.max_new_tokens,
                    eos_token_id=greq.eos_token_id, do_sample=greq.do_sample,
                    temperature=greq.temperature, top_k=greq.top_k,
                    top_p=greq.top_p, seed=greq.seed,
                    collect_logits=True if greq.return_logits else None,
                    on_token=self._make_on_token(greq), trace=greq.trace,
                    adapter_id=greq.adapter_id)
            except ValueError as e:
                self.stats["rejected"] += 1
                if greq.trace is not None:
                    greq.trace.instant("rejected", error=str(e))
                self._post(greq, ("failed", 400, str(e)))
                continue
            greq.handle = handle
            greq.replica = rep
            self.replicas.note_dispatch(rep)
            greq.admit_ts = now
            if greq.trace is not None:
                greq.trace.phase("queued",
                                 wait_ms=round((now - greq.enq_ts) * 1e3, 3))
                greq.trace.instant("admitted", replica=rep.idx)
            if tel.enabled:
                tel.histogram("gateway/queue_wait_ms", (now - greq.enq_ts) * 1e3)
            if handle.done:  # zero-budget edge: finished with no tokens
                self._finish(greq, ("done", "length"))
            else:
                self._active.add(greq)
                if tel.enabled:
                    tel.gauge("gateway/active_requests", len(self._active))

    def _make_on_token(self, greq):
        landing = self._landing

        def on_token(tok, done):
            greq.n_tokens += 1
            rows = landing.rows  # the batch of the thread that runs the hook
            row = rows.get(greq)
            if row is None:
                rows[greq] = row = [[], None]
            row[0].append(int(tok))
            if done:
                row[1] = ("stop" if (greq.eos_token_id is not None
                                     and tok == greq.eos_token_id) else "length")
                # account BEFORE the final token is handed on: the HTTP side
                # responds the moment the event lands, and a client that
                # reads the response then polls /v1/metrics must see its
                # own completion counted (the reverse order raced)
                self._finish(greq, None)
        return on_token

    def _flush_landing(self):
        """Hand what the calling thread's batch holds to the event loop in
        ONE wake-up (a lock and a byte down the loop's self-pipe, whatever
        the number of rows). Called by the scheduler when a landing is
        delivered (``on_landing``), by ``_post`` before any other event and
        by the pump behind every step; never raises. With the sink on the
        events count as the pump's posts BEFORE they are posted, so the
        loop's backlog is never read under 0."""
        landing = self._landing
        rows = landing.rows
        if not rows:
            return
        landing.rows = {}
        if landing.gap is not None:
            landing.gap.posted += len(rows)
        try:
            self._loop.call_soon_threadsafe(self._hand_over, rows)
        except RuntimeError:
            pass  # event loop closed mid-drain

    def _hand_over(self, rows):
        """On the event loop: a landing's batch, each row's tokens onto its
        response's queue as one ``("token", [ids], finish reason)`` event. A
        handler that has gone (it says so with the sink on alone) will never
        take one: counted unread here, by the thread that knows."""
        for greq, (toks, reason) in rows.items():
            if greq.unread:
                self._delivery.unread += 1
            else:
                greq.events.put_nowait(("token", toks, reason))

    def _finish(self, greq, event):
        """Request reached a terminal state on the pump side: account it,
        update the service-time EMA (feeds Retry-After), emit telemetry.

        Only requests that ran to natural completion count toward
        ``completed`` and the EMA: folding cancelled/disconnected/failed
        requests in would collapse the EMA toward the abort latency under
        overload with impatient clients, making ``Retry-After`` advertise
        far-too-small backoffs (a retry-storm amplifier). Token counters
        still accrue — the decode work happened, and the per-tenant counter
        is a billing/fairness audit.

        Exactly-once across pump threads: a cancel settling on one replica's
        pump can race the final token on another — the finish lock plus the
        ``finished`` flag make whichever lands first the terminal event."""
        with self._finish_lock:
            if greq.finished:
                return
            greq.finished = True
            self._active.discard(greq)
            completed = event is None or event[0] == "done"
            if completed:
                service = time.monotonic() - greq.enq_ts
                ema = self._ema_service_s
                self._ema_service_s = (service if ema is None
                                       else 0.9 * ema + 0.1 * service)
                if greq.replica is not None:
                    greq.replica.observe_service(service)
                self.stats["completed"] += 1
            self.stats["tokens"] += greq.n_tokens
        if event is not None:
            self._post(greq, event)
        tel = self.telemetry
        if tel.enabled:
            if completed:
                tel.counter("gateway/completed")
            tel.counter("gateway/tokens", greq.n_tokens)
            # cardinality cap: the tenant id is CLIENT-controlled, and sink
            # counters are never evicted — random ids must not grow the sink
            # (and every /v1/metrics payload) without bound
            tenant = greq.tenant
            if tenant not in self._tenant_labels:
                if len(self._tenant_labels) < 256:
                    self._tenant_labels.add(tenant)
                else:
                    tenant = "__other__"
            tel.counter(f"gateway/tenant/{tenant}/tokens", greq.n_tokens)
            tel.gauge("gateway/active_requests", len(self._active))

    def _enforce_cancellations(self):
        """Deadline expiry and HTTP-side cancellation (disconnect) propagate
        into the scheduler: ``handle.cancel()`` flags the slot, the next
        ``step()`` frees it (the scheduler never mutates mid-dispatch)."""
        now = time.monotonic()
        tel = self.telemetry
        for greq in list(self._active):
            if (not greq.cancel_requested and greq.deadline is not None
                    and now >= greq.deadline):
                greq.cancel_requested = True
                greq.cancel_reason = "deadline"
                self.stats["deadline_expired"] += 1
                if tel.enabled:
                    tel.counter("gateway/deadline_expired")
            if greq.cancel_requested and greq.handle is not None:
                greq.handle.cancel()

    def _settle_done(self):
        """Cancelled/failed requests finish via the scheduler's reap (done
        without a final on_token): confirm the terminal state to the HTTP
        side — a migration failure answers 500 with its reason, not a
        phantom "cancelled" the client never asked for."""
        for greq in list(self._active):
            if greq.handle is not None and greq.handle.done and not greq.finished:
                err = greq.handle._req.error
                if err is not None:
                    self._finish(greq, ("failed", 500, err))
                else:
                    self._finish(greq, ("cancelled", greq.cancel_reason or "cancelled"))

    def _fail_in_flight(self, msg):
        for greq in list(self._active):
            if greq.handle is not None:
                greq.handle.cancel()
            self._finish(greq, ("failed", 500, msg))
        self._fail_queue(msg)

    def _fail_replica_in_flight(self, rep, msg):
        """Fail ONLY the requests ``rep``'s scheduler currently OWNS (a sick
        replica sheds its own work; the rest of the fleet, and the queue,
        keep going). Ownership is asked of the scheduler rather than
        remembered from placement: a request whose prefill ``rep`` ran but
        whose KV already migrated out is owned by NO scheduler (or by its
        decode replica), so the prefill replica failing cannot kill it."""
        for greq in list(self._active):
            if greq.handle is not None and rep.scheduler.owns(greq.handle._req):
                greq.handle.cancel()
                self._finish(greq, ("failed", 500, msg))

    def _fail_queue(self, msg):
        while True:
            greq = self._fair.pop()
            if greq is None:
                break
            self._post(greq, ("failed", 503, msg))

    def _post(self, greq, event):
        """Pump -> HTTP handler handoff of every event but a token (``done``,
        ``cancelled``, ``failed``, ``handoff``); never raises (the response
        side may already be gone — its queue then just collects unread
        events). The tokens this thread still holds go first: nothing
        overtakes a token."""
        self._flush_landing()
        try:
            self._loop.call_soon_threadsafe(greq.events.put_nowait, event)
        except RuntimeError:
            pass  # event loop closed mid-drain

    # ------------------------------------------------------------------ elastic fleet
    def fleet_signals(self, now=None):
        """One consolidated :class:`FleetSignals` snapshot — the controller
        tick's entire world view, assembled here so the decision function
        never reads live gateway state (deterministic under test: tests
        construct FleetSignals directly)."""
        now = time.monotonic() if now is None else now
        burn_fast = burn_slow = 0.0
        if self.slo is not None:
            for obj in (self.slo._last_state or {}).get("objectives", []):
                burn_fast = max(burn_fast, float(obj.get("burn_fast") or 0.0))
                burn_slow = max(burn_slow, float(obj.get("burn_slow") or 0.0))
        reps = [r for r in self.replicas if not r.retired]
        active = [r for r in reps if r.available()]
        placeable = active or reps  # degenerate all-drained fleet: avoid /0
        pre_depth = (len(self._fair)
                     + sum(len(r.scheduler.queue) for r in active
                           if r.prefill_capable()))
        total_slots = sum(r.scheduler.num_slots for r in placeable) or 1
        busy = sum(r.busy_slots() for r in active)
        mfu = bw = 0.0
        goodput = 1.0
        cap = self.scheduler.capacity
        if cap is not None:
            goodput = float(cap.goodput_fraction)
            # per-program roofline entries hold the LAST sampled dispatch;
            # the max across programs is the "how hot is the device" signal
            for ent in cap.programs.values():
                mfu = max(mfu, float(ent.get("mfu", 0.0)))
                bw = max(bw, float(ent.get("hbm_bw_util", 0.0)))
        # host-gap fraction: the share of the fleet's pumps' syncs since the
        # previous snapshot that was the host's work and not the wait for
        # the device (busy / (busy + wait) of the pumps' accounts) — the
        # "the host is the bottleneck" veto input. The device-idle gap
        # cannot be it: behind a pump that runs ahead every gap reads 0
        host_gap_frac = 0.0
        gaps = [r.scheduler._gap for r in reps if r.scheduler._gap is not None]
        totals = (sum(g.busy_s for g in gaps), sum(g.wait_s for g in gaps))
        mark, self._gap_mark = self._gap_mark, totals
        if mark is not None:
            busy, wait = totals[0] - mark[0], totals[1] - mark[1]
            if busy + wait > 0.0:
                host_gap_frac = max(0.0, min(1.0, busy / (busy + wait)))
        return FleetSignals(
            now=now, burn_fast=burn_fast, burn_slow=burn_slow,
            queue_depth=len(self._fair),
            oldest_wait_s=self._fair.oldest_wait_s(),
            prefill_sat=pre_depth / max(1, self.replicas.phase_slots("prefill")),
            decode_sat=len(self._active) / max(1, self.replicas.phase_slots("decode")),
            mfu=mfu, hbm_bw_util=bw, host_gap_frac=host_gap_frac,
            goodput_fraction=goodput, occupancy=busy / total_slots,
            replicas=len(reps), replicas_active=len(active),
            inflight=len(self._active),
            disaggregated=self.replicas.disaggregated())

    def _scale_up(self):
        """Autoscaler actuator: grow the fleet by one replica over the
        SHARED weight tree + compiled-program set (zero new XLA programs —
        warmup is pool allocation; on_replica_added spawns its pump)."""
        if self.replicas.active_count() >= int(self.autoscaler.config.max_replicas):
            return False
        rep = self.replicas.add_replica()
        self.stats["replicas_added"] += 1
        logger.info(f"autoscaler: added replica {rep.idx} "
                    f"(fleet {self.replicas.active_count()})")
        self._wake.set()
        return True

    def _scale_down(self):
        """Autoscaler actuator: begin the two-phase retire of the
        highest-index drainable replica (never 0 — it owns the fleet-wide
        pump duties). Its own pump finishes the retire once idle."""
        victims = [r for r in self.replicas
                   if r.idx != 0 and not r.retired and not r.pending_drain
                   and not r.sick]
        if not victims:
            return False
        victim = max(victims, key=lambda r: r.idx)
        self.replicas.begin_scale_down(victim.idx)
        self.stats["replicas_retired"] += 1
        logger.info(f"autoscaler: draining replica {victim.idx} for retire")
        self._wake.set()
        return True

    def _rebalance(self, phase):
        """Autoscaler actuator: flip ONE replica's role toward the
        saturated ``phase``. Prefers a pure opposite-role replica, then a
        non-primary mixed one; set_role's both-phases-coverable invariant
        (ValueError) is the backstop — a rejected flip reports False and
        the controller retries after its cooldown."""
        opposite = "decode" if phase == "prefill" else "prefill"
        eligible = [r for r in self.replicas
                    if not r.retired and not r.sick and not r.pending_drain]
        cands = ([r for r in eligible if r.phase_role == opposite]
                 + [r for r in eligible
                    if r.phase_role == "mixed" and r.idx != 0])
        for rep in cands:
            was = rep.phase_role
            try:
                self.replicas.set_role(rep.idx, phase)
            except ValueError:
                continue
            logger.info(f"autoscaler: re-balanced replica {rep.idx} "
                        f"{was}->{phase}")
            self._wake.set()
            return True
        return False

    def _set_brownout(self, level):
        """Autoscaler actuator: move the shedding ladder to ``level``.
        Level 0 lifts the brownout (parked work resumes, the door reopens).
        Odd levels EVICT the FairQueue's flows below the level's tier (503
        + brownout Retry-After) and keep shedding arrivals below the bar at
        the door; even levels additionally PREEMPT in-flight work below the
        tier — cancelled outright, or parked for resume through the
        migrate-out transport when ``brownout_park`` is on and a KV demote
        tier exists. De-escalation never re-preempts: stepping DOWN from an
        even level releases parked work."""
        ctl = self.autoscaler
        cfg = ctl.config
        tel = self.telemetry
        prev = ctl.brownout_level
        if level <= 0:
            self._brownout_bar = None
            self._park_pending.clear()
            released = self.replicas.release_parked()
            if released or prev:
                logger.info(f"autoscaler: brownout lifted "
                            f"({released} parked request(s) released)")
            self._wake.set()
            return True
        tier = ctl.brownout_tier(level)
        bar = self._fair.tier_weight(tier)
        self._brownout_bar = bar
        escalating = level > prev
        if not escalating and prev % 2 == 0:
            # stepping down out of a preemption level: stop preempting and
            # let parked decode state resume (the calm signal that drove
            # the de-escalation says there is capacity again)
            self._park_pending.clear()
            self.replicas.release_parked()
        if escalating and level % 2 == 1:
            # evict the queued backlog below the tier, oldest first; each
            # evicted row owes its client a 503 + brownout Retry-After
            retry = str(int(cfg.brownout_retry_after_s))
            for greq, _tenant, _prio in self._fair.evict_flows(tier):
                self.stats["shed_503"] += 1
                self.stats["brownout_evicted"] += 1
                if tel.enabled:
                    tel.counter("gateway/shed_503")
                    tel.counter("autoscale/brownout_evicted")
                if greq.trace is not None:
                    greq.trace.instant("brownout_evicted", level=level)
                self._post(greq, ("failed", 503,
                                  "brownout: request tier shed under overload",
                                  [("Retry-After", retry)]))
        if escalating and level % 2 == 0:
            # preempt in-flight work below the tier: park when the migrate
            # transport can hold the KV for resume, else cancel
            park = bool(cfg.brownout_park) and self.scheduler.kv_tier is not None
            for greq in list(self._active):
                if greq.finished or self._fair.tier_weight(greq.priority) >= bar:
                    continue
                self.stats["brownout_preempted"] += 1
                if tel.enabled:
                    tel.counter("autoscale/brownout_preempted")
                if park:
                    self._park_pending.add(greq)
                else:
                    greq.cancel_requested = True
                    greq.cancel_reason = "brownout"
        logger.info(f"autoscaler: brownout level {prev}->{level} "
                    f"(shedding below {tier!r})")
        self._wake.set()
        return True

    def _park_owned(self, rep):
        """Park brownout-preempted requests whose decode state ``rep``'s
        scheduler owns — must run on its pump thread (migrate_out is a
        scheduler call). Unparkable requests (mid-prefill, already
        migrating, no demote tier) fall back to cancellation so an even
        brownout level always sheds the work one way or the other."""
        for greq in list(self._park_pending):
            if greq.finished or greq.handle is None:
                self._park_pending.discard(greq)
                continue
            req = greq.handle._req
            if req.done or req.cancelled or req.migrating:
                self._park_pending.discard(greq)
                continue
            if not rep.scheduler.owns(req):
                continue  # another replica's pump parks it
            self._park_pending.discard(greq)
            if self.replicas.park_out(rep, req) is not None:
                self.stats["brownout_parked"] += 1
                if self.telemetry.enabled:
                    self.telemetry.counter("autoscale/brownout_parked")
                if greq.trace is not None:
                    greq.trace.instant("brownout_parked", replica=rep.idx)
            else:
                greq.cancel_requested = True
                greq.cancel_reason = "brownout"

    def _flush_radix(self, rep):
        """Evict ``rep``'s whole radix trie through the KV tier (each
        eviction demotes to the prefix store — with a NetPrefixStore
        attached that makes every cached prefix directory-visible), then
        join the async demote fetches so the entries are probe-visible
        before the debug endpoint answers. Runs on ``rep``'s own pump."""
        sched = rep.scheduler
        try:
            if sched.radix is not None:
                while True:
                    victim = sched.radix.evict_lru()
                    if victim is None:
                        break
                    sched.cache.reclaim(victim)
            if sched.kv_tier is not None:
                sched.kv_tier.executor.drain_fetches()
        finally:
            self._flush_radix_pending.discard(rep.idx)

    # ------------------------------------------------------------------ multi-host handoff
    def _handoff_complete(self, req, desc):
        """A cross-process prefill->decode handoff's demote landed (called
        from the KV transfer thread by the WorkerAgent's migrate hook):
        finish the gateway request with a terminal ``("handoff", desc)``
        event — the response carries the descriptor instead of further
        tokens, and the ROUTER resumes the request on a decode worker.
        Not a completion (no EMA fold, no completed count): the request's
        life continues in another process. Returns False when no in-flight
        gateway request owns ``req`` (direct-drive caller)."""
        for greq in list(self._active):
            if greq.handle is not None and greq.handle._req is req:
                self.stats["handoffs_out"] += 1
                self._finish(greq, ("handoff", desc))
                self._wake.set()
                return True
        return False

    def _admit_resume(self, greq):
        """Admit a router-POSTed resume request (event-loop thread): bypass
        the fair queue — the request was already admitted fleet-wide by the
        prefill worker — and park it in the ReplicaSet's migration queue as
        a READY record whose entry points at the remote shard. The normal
        ``admit_migrations`` pull then restores it bit-identically."""
        try:
            handle = self.replicas.inject_resume(
                greq.resume, on_token=self._make_on_token(greq),
                trace=greq.trace, collect_logits=greq.return_logits)
        except (ValueError, KeyError, TypeError) as e:
            self.stats["rejected"] += 1
            self._post(greq, ("failed", 400, f"bad resume descriptor: {e}"))
            return
        greq.handle = handle
        greq.admit_ts = time.monotonic()
        self.stats["resumed_in"] += 1
        self._active.add(greq)
        if self.telemetry.enabled:
            self.telemetry.gauge("gateway/active_requests", len(self._active))
        self._wake.set()

    # ------------------------------------------------------------------ admission math
    def capacity_signals(self):
        """Live capacity-signals dict (``serving/capacity_math.py`` shape):
        the single source both the local Retry-After and the multi-host
        router's fleet-wide merge read. Backlog sums count AVAILABLE
        replicas only — a drained or pending-drain replica's queue is
        already excluded from ``total_slots``/``phase_slots``, and counting
        its backlog against capacity it no longer advertises would inflate
        the estimate for the whole drain."""
        reps = self.replicas
        sched_backlog = sum(len(r.scheduler.queue) for r in reps
                            if r.available())
        prefill_backlog = sum(len(r.scheduler.queue) for r in reps
                              if r.available() and r.prefill_capable())
        return {
            "queued": len(self._fair),
            # _active already covers parked handoffs (their handles are
            # not done) and soon-to-decode prefills — adding
            # pending_migrations() on top would double-count each parked
            # request and over-advertise the backoff
            "inflight": len(self._active),
            "sched_backlog": sched_backlog,
            "prefill_backlog": prefill_backlog,
            "total_slots": reps.total_slots(),
            "prefill_slots": reps.phase_slots("prefill"),
            "decode_slots": reps.phase_slots("decode"),
            "ema_service_s": self._ema_service_s,
            "disaggregated": reps.disaggregated(),
        }

    def _retry_after(self):
        """Advertised backoff, from live state: time for the current backlog
        to drain through the FLEET's slot pools at the measured per-request
        service time (EMA). Floor 1s; capped; integer seconds per RFC 9110.
        The math lives in ``serving/capacity_math.py`` so the multi-host
        router computes fleet-wide backoff with the SAME formula over
        merged per-worker signals (phase-aware under disaggregation: the
        estimate is the WORSE of queued-work/prefill-capacity and
        in-flight/decode-capacity, not the blended depth)."""
        return capacity_math.estimate_retry_after(
            self.capacity_signals(), self.config.retry_after_cap_s)

    def _next_rid(self):
        with self._rid_lock:
            self._rid += 1
            return self._rid

    # ------------------------------------------------------------------ HTTP layer
    async def _handle_conn(self, reader, writer):
        self._open_streams += 1
        try:
            req_line = await asyncio.wait_for(reader.readline(), 30.0)
            parts = req_line.decode("latin-1").split()
            if len(parts) < 2:
                return
            method, path = parts[0].upper(), parts[1]
            headers = {}
            # header-count bound (line LENGTH is already bounded by the
            # stream reader's 64 KiB limit): a client must not grow this
            # dict without limit
            for _ in range(128):
                line = await asyncio.wait_for(reader.readline(), 30.0)
                if line in (b"\r\n", b"\n", b""):
                    break
                key, _, val = line.decode("latin-1").partition(":")
                headers[key.strip().lower()] = val.strip()
            else:
                await self._json(writer, 431,
                                 {"error": {"message": "too many headers"}})
                return
            body = b""
            length = int(headers.get("content-length", "0") or 0)
            if length > int(self.config.max_body_bytes):
                # refuse BEFORE buffering: one fat POST must not OOM the
                # long-lived serving process
                await self._json(writer, 413,
                                 {"error": {"message": "request body exceeds "
                                            f"{self.config.max_body_bytes} bytes"}})
                return
            if length:
                body = await asyncio.wait_for(reader.readexactly(length), 30.0)
            await self._route(method, path, headers, body, reader, writer)
        except (asyncio.IncompleteReadError, asyncio.TimeoutError,
                ConnectionError):
            pass
        except Exception:  # noqa: BLE001 — one bad conn must not kill the server
            logger.exception("gateway: connection handler failed")
        finally:
            self._open_streams -= 1
            try:
                writer.close()
                await writer.wait_closed()
            except Exception:  # noqa: BLE001
                pass

    async def _route(self, method, path, headers, body, reader, writer):
        path, _, query = path.partition("?")
        if method == "GET" and path == "/healthz":
            await self._json(writer, 200, {"status": "alive"})
        elif method == "GET" and path == "/readyz":
            if self.ready and not self.draining:
                await self._json(writer, 200, {"status": "ready"})
            else:
                await self._json(writer, 503,
                                 {"status": "draining" if self.draining
                                  else "starting"},
                                 extra=[("Retry-After", str(self._retry_after()))])
        elif method == "GET" and path == "/v1/metrics":
            # content negotiation: a Prometheus scraper's Accept leads with
            # text/plain (or openmetrics); everyone else (curl */*,
            # explicit JSON) keeps the structured JSON payload
            accept = headers.get("accept", "")
            want_prom = ("format=prometheus" in query
                         or (("text/plain" in accept or "openmetrics" in accept)
                             and _JSON not in accept))
            if want_prom:
                text = prom.render(self.telemetry.snapshot(),
                                   extra_gauges=self._prom_extra()).encode()
                writer.write(self._head(
                    200, "text/plain; version=0.0.4; charset=utf-8",
                    length=len(text)) + text)
                await writer.drain()
            else:
                await self._json(writer, 200, self._metrics())
        elif method == "GET" and path == "/v1/slo":
            state = (self.slo.state() if self.slo is not None
                     else {"enabled": False,
                           "reason": "telemetry disabled or no objectives"})
            await self._json(writer, 200, state)
        elif method == "GET" and path == "/v1/debug/flight":
            dump = self.telemetry.dump_flight("debug_endpoint")
            if dump is None:
                await self._json(writer, 503,
                                 {"error": {"message": "flight recorder off, "
                                            "or rate-limited"}})
            else:
                await self._json(writer, 200,
                                 {"path": dump,
                                  "note": "file lands after the recorder's "
                                          "post-window elapses"})
        elif method == "POST" and path == "/v1/debug/profile":
            if self.profiler is None:
                await self._json(writer, 503,
                                 {"error": {"message": "telemetry disabled: "
                                            "no profile output path"}})
            else:
                try:
                    req = json.loads(body) if body else {}
                except ValueError:
                    req = {}
                duration_s = float(req.get("duration_ms", 1000.0) or 1000.0) / 1e3
                from ..telemetry.profiler import ProfileBusy
                try:
                    trace_dir = self.profiler.start(duration_s, tag="ondemand")
                except ProfileBusy as e:
                    await self._json(writer, 409, {"error": {"message": str(e)}})
                else:
                    await self._json(writer, 200,
                                     {"path": trace_dir,
                                      "duration_ms": duration_s * 1e3,
                                      "note": "trace files land when the "
                                              "capture window elapses"})
        elif method == "GET" and path == "/v1/autoscaler":
            if self.autoscaler is None:
                await self._json(writer, 200,
                                 {"enabled": False,
                                  "reason": "no continuous_batching.autoscaler "
                                            "config section"})
            else:
                await self._json(writer, 200, self.autoscaler.state())
        elif method == "POST" and path == "/v1/autoscaler":
            if self.autoscaler is None:
                await self._json(writer, 503,
                                 {"error": {"message": "no autoscaler "
                                            "configured"}})
                return
            try:
                req = json.loads(body.decode("utf-8") or "{}")
            except (UnicodeDecodeError, json.JSONDecodeError) as e:
                await self._json(writer, 400, {"error": {"message": str(e)}})
                return
            if not isinstance(req, dict) or \
                    not set(req) <= {"enabled", "dry_run"}:
                await self._json(writer, 400,
                                 {"error": {"message": "body must be a JSON "
                                            "object with only 'enabled' and/or "
                                            "'dry_run' keys"}})
                return
            changed = self.autoscaler.admin(req)
            self._wake.set()
            await self._json(writer, 200,
                             {"changed": changed, **self.autoscaler.state()})
        elif method == "POST" and path == "/v1/store/fetch":
            # multi-host prefix/handoff store: serve THIS shard's KV bytes
            # to a remote restore (memory/net_store.py's wire format: one
            # meta JSON line + concatenated raw leaf bytes). Runs in an
            # executor thread — the pop may do an NVMe load, and the event
            # loop must keep serving heartbeats meanwhile.
            if self.net_store is None:
                await self._json(writer, 404,
                                 {"error": {"message": "no networked store "
                                            "attached (worker mode only)"}})
                return
            try:
                req = json.loads(body.decode("utf-8") or "{}")
                key = tuple(int(t) for t in req["key"])
                consume = bool(req.get("consume", True))
            except (ValueError, KeyError, TypeError, UnicodeDecodeError) as e:
                await self._json(writer, 400, {"error": {"message": str(e)}})
                return
            loop = asyncio.get_running_loop()
            out = await loop.run_in_executor(
                None, lambda: self.net_store.serve_fetch(key, consume=consume))
            if out is None:
                await self._json(writer, 404,
                                 {"error": {"message": "entry not resident "
                                            "(claimed, reaped, or evicted)"}})
                return
            payload, blob = out
            writer.write(self._head(200, "application/octet-stream",
                                    length=len(payload) + len(blob)))
            writer.write(payload)
            writer.write(blob)
            await writer.drain()
        elif method == "POST" and path == "/v1/debug/flush_radix":
            # force-demote every replica's radix trie through the KV tier
            # (multihost tests drive cross-host prefix restore with this);
            # each pump flushes its own scheduler, the endpoint waits
            self._flush_radix_pending |= {
                r.idx for r in self.replicas
                if not r.retired and r.scheduler.radix is not None}
            self._wake.set()
            for _ in range(600):
                if not self._flush_radix_pending:
                    break
                await asyncio.sleep(0.05)
            await self._json(writer, 200,
                             {"flushed": not self._flush_radix_pending})
        elif method == "GET" and path == "/v1/replicas":
            await self._json(writer, 200, {"replicas": self.replicas.states()})
        elif method == "POST" and path.startswith("/v1/replicas/"):
            await self._replica_admin(path, body, writer)
        elif method == "POST" and path == "/v1/completions":
            await self._completions(headers, body, reader, writer)
        else:
            await self._json(writer, 404, {"error": {"message": f"no route {method} {path}"}})

    async def _replica_admin(self, path, body, writer):
        """``POST /v1/replicas/<idx>/drain`` stops placement onto a replica
        (in-flight work finishes; resumable); ``.../resume`` re-admits it
        (clearing drain AND sick — the operator asserting recovery);
        ``.../role`` (body ``{"role": "prefill"|"decode"|"mixed"}``) flips
        its phase role at runtime — disaggregation's per-replica override
        (the fleet must keep both phases coverable; violations 400)."""
        parts = path.strip("/").split("/")  # v1 replicas <idx> <action>
        if len(parts) != 4 or parts[3] not in ("drain", "resume", "role"):
            await self._json(writer, 404,
                             {"error": {"message": "POST /v1/replicas/<idx>/"
                                        "{drain|resume|role}"}})
            return
        try:
            idx = int(parts[2])
            if not 0 <= idx < len(self.replicas):
                raise ValueError
        except ValueError:
            await self._json(writer, 400,
                             {"error": {"message": f"no replica {parts[2]!r} "
                                        f"(fleet size {len(self.replicas)})"}})
            return
        if parts[3] == "role":
            try:
                req = json.loads(body.decode("utf-8") or "{}")
                role = req.get("role") if isinstance(req, dict) else None
                state = self.replicas.set_role(idx, role)
            except (ValueError, UnicodeDecodeError,
                    json.JSONDecodeError) as e:
                await self._json(writer, 400, {"error": {"message": str(e)}})
                return
        else:
            state = (self.replicas.drain(idx) if parts[3] == "drain"
                     else self.replicas.resume(idx))
        self._wake.set()
        await self._json(writer, 200, {"replica": state})

    def _prom_extra(self):
        """Gateway/scheduler state the sink doesn't own, exposed as plain
        gauges on the Prometheus surface so a scraper sees one coherent
        endpoint."""
        sched = self.scheduler
        out = {
            "gateway/ready": 1.0 if (self.ready and not self.draining) else 0.0,
            "gateway/queue_depth": float(len(self._fair)),
            "gateway/active_requests": float(len(self._active)),
            "gateway/oldest_queue_wait_s": self._fair.oldest_wait_s(),
            "gateway/retry_after_s": float(self._retry_after()),
            "scheduler/num_slots": float(sched.num_slots),
            "scheduler/active_slots": float(sched.cache.active_slots),
            "scheduler/slot_occupancy": float(sched.cache.occupancy()),
            "scheduler/compiled_programs": float(sched.compiled_program_count()),
            # elastic fleet: "replicas" is the LIVE (non-retired) count —
            # a scraped capacity dashboard must not count freed pools
            "serving/replicas": float(self.replicas.active_count()),
            "serving/replicas_available": float(
                sum(1 for r in self.replicas if r.available())),
            "serving/replicas_pending_drain": float(
                sum(1 for r in self.replicas
                    if r.pending_drain and not r.retired)),
            "serving/tp_size": float(sched.tp_size),
            "serving/ep_size": float(sched.ep_size),
        }
        if sched.experts is not None:
            out.update({
                "serving/experts_resident": sched.experts.resident_fraction(),
                "serving/expert_loads": float(sched.experts.loads),
                "serving/expert_evicts": float(sched.experts.evicts),
                # replays are per-scheduler state (the store is fleet-shared
                # but each replica runs its own replay loop): sum the fleet
                "serving/expert_replays": float(
                    sum(r.scheduler.expert_replays for r in self.replicas)),
            })
        if self.replicas.disaggregated():
            # phase split + handoff pressure (the decode-side half of the
            # phase-aware Retry-After, scrapeable): per-replica roles are in
            # /v1/replicas; migrations_{out,in} fold as {replica=...}
            # counter series through the telemetry sink
            out.update({
                "serving/replicas_prefill_capable": float(
                    sum(1 for r in self.replicas
                        if r.available() and r.prefill_capable())),
                "serving/replicas_decode_capable": float(
                    sum(1 for r in self.replicas
                        if r.available() and r.decode_capable())),
                "serving/migrations_pending": float(
                    self.replicas.pending_migrations()),
            })
        if sched.adapters is not None:
            out.update({
                "serving/adapters_registered": float(
                    len(sched.adapters.registered())),
                "serving/adapters_resident": float(
                    sched.adapters.stats()["resident"]),
                "serving/adapter_hit_rate": sched.adapters.hit_rate(),
            })
        if self.autoscaler is not None:
            out["autoscale/enabled"] = 1.0 if self.autoscaler.enabled else 0.0
            out["autoscale/brownout_level"] = float(self.autoscaler.brownout_level)
            for action, n in self.autoscaler.counters.items():
                out[f"autoscale/decisions_{action}"] = float(n)
        return out

    def _metrics(self):
        sched = self.scheduler
        return {
            "ready": self.ready,
            "draining": self.draining,
            "gateway": {**self.stats,
                        "queue_depth": len(self._fair),
                        "active_requests": len(self._active),
                        "queue_depth_per_flow": {"/".join(k): v
                                                 for k, v in self._fair.depths().items()},
                        "ema_service_s": self._ema_service_s,
                        "oldest_queue_wait_s": self._fair.oldest_wait_s(),
                        "retry_after_s": self._retry_after()},
            "slo": self.slo.state() if self.slo is not None else None,
            "scheduler": {"num_slots": sched.num_slots,
                          "active_slots": sched.cache.active_slots,
                          "queue_depth": len(sched.queue),
                          "slot_occupancy": sched.cache.occupancy(),
                          "compiled_programs": sched.compiled_program_count(),
                          "tp_size": sched.tp_size,
                          "ep_size": sched.ep_size,
                          # fused decode blocks: whether the step programs
                          # run 3 resident kernels/layer, and the per-
                          # condition reasons when they don't
                          "fused_decode_block": getattr(
                              sched, "_fused_block", False),
                          "fused_decode_reasons": list(getattr(
                              sched, "_fused_block_reasons", ())),
                          # step programs by the K/V commit they were built
                          # with: "scatter" ones relay the pool every step
                          "kv_commit_programs": dict(getattr(
                              sched, "kv_commit_programs", {})),
                          # ... and by their gated-delta layers' one-token
                          # state update: "xla" ones pass over the state twice
                          "gdn_step_programs": dict(getattr(
                              sched, "gdn_step_programs", {})),
                          # ... and by their Mamba-2 mixers'
                          "ssd_step_programs": dict(getattr(
                              sched, "ssd_step_programs", {})),
                          # the pool's geometry at rest: "packed" (K beside
                          # V in one leaf a layer), "split" or "latent"
                          "kv_pool_geometry": getattr(
                              sched, "kv_pool_geometry", None),
                          # what a position costs in rows, and what a slot
                          # costs in per-slot state and ring rows whatever
                          # its length (0 unless the model has recurrent or
                          # windowed layers)
                          "kv_bytes_per_token": sched.cache.bytes_per_token(),
                          "state_bytes_per_slot": sched.cache.state_bytes_per_slot(),
                          "window_bytes_per_slot": sched.cache.window_bytes_per_slot(),
                          # ... and, for MoE models, by where the rows go
                          # (NOT by how the pairs are evaluated, which the
                          # serving/moe_*_programs counters say): "dense"
                          # ones broadcast them over a mesh axis or to paged
                          # experts, "sparse" ones keep to the experts the
                          # device holds, "dense_held" of those run every
                          # held expert on every row (benchmarks/SERVING.md,
                          # "MoE serving")
                          "moe_dispatch_programs": dict(getattr(
                              sched, "moe_dispatch_programs", {}))},
            "adapters": (sched.adapters.stats()
                         if sched.adapters is not None else None),
            "expert_store": (sched.experts.stats()
                             if sched.experts is not None else None),
            "replicas": self.replicas.states(),
            # elastic fleet controller rollup (live detail: /v1/autoscaler)
            "autoscaler": (self.autoscaler.state()
                           if self.autoscaler is not None else None),
            # capacity rollup (telemetry/capacity.py): per-compiled-program
            # roofline table + goodput + host-gap totals for the primary
            # scheduler; the live gauges are in the telemetry snapshot
            "capacity": ({
                "programs": sched.capacity.program_table(),
                "goodput_fraction": sched.capacity.goodput_fraction,
                "samples": sched.capacity.samples,
                "host_gaps": sched._gap.gaps,
                "host_gap_total_s": round(sched._gap.total_gap_s, 6),
                "pump_busy_total_s": round(sched._gap.busy_s, 6),
                "pump_wait_total_s": round(sched._gap.wait_s, 6),
                # the event loop's delivery, in events (a row's tokens of
                # one landing, of streaming and unary responses; ``tokens``:
                # those inside the events written; the live view is
                # gateway/backlog_events)
                "delivery": ({
                    "posted": self._delivery.posted(),
                    "written": self._delivery.events,
                    "tokens": self._delivery.tokens,
                    "taken": self._delivery.taken,
                    "unread": self._delivery.unread,
                } if self._delivery is not None else None),
                "profiling": (self.profiler.active
                              if self.profiler is not None else None),
            } if sched.capacity is not None else None),
            # disaggregated serving rollup (per-replica phase_role and
            # migrations_{out,in} are in the replicas list above)
            "disaggregation": ({
                "roles": [r.phase_role for r in self.replicas],
                "migrations": sum(r.scheduler.migrations_out
                                  for r in self.replicas),
                "pending": self.replicas.pending_migrations(),
                "failed": self.replicas.migrations_failed,
                "migrate_min_tokens": self.replicas.migrate_min_tokens,
            } if self.replicas.disaggregated() else None),
            # multi-host serving: the networked shard's traffic counters
            # (net_bytes_{in,out}, remote_restores, leases_expired, ...) —
            # present only when a WorkerAgent attached a NetPrefixStore
            "net_store": (self.net_store.stats()
                          if self.net_store is not None else None),
            # seconds and counts of this process's program set-up, by phase
            # (trace, lower, backend, cache read): moves on compiles only
            "compile": compile_cache.stats(),
            "telemetry": self.telemetry.snapshot(),
        }

    # -------------------------------------------------------------- completions
    def _parse_completion(self, headers, body):
        """Request body -> kwargs. Raises ValueError with a client-facing
        message on malformed input."""
        try:
            req = json.loads(body.decode("utf-8") or "{}")
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            raise ValueError(f"body is not valid JSON: {e}")
        if not isinstance(req, dict):
            raise ValueError("body must be a JSON object")
        resume = req.get("resume")
        if resume is not None:
            # cross-process migration resume (router -> decode worker): the
            # descriptor IS the request — prompt/sampling params travel in
            # it so the resumed decode is bit-identical to the in-process
            # continuation it replaces
            if not isinstance(resume, dict):
                raise ValueError("'resume' must be a handoff descriptor object")
            for field in ("key", "kv_len", "version", "owner_url", "prompt",
                          "max_new_tokens"):
                if field not in resume:
                    raise ValueError(f"resume descriptor missing {field!r}")
            req = dict(req, prompt=resume["prompt"],
                       max_tokens=int(resume["max_new_tokens"]),
                       eos_token_id=resume.get("eos_token_id"),
                       do_sample=resume.get("do_sample", False),
                       temperature=resume.get("temperature", 0.0),
                       top_k=resume.get("top_k", 0),
                       top_p=resume.get("top_p", 1.0),
                       seed=resume.get("seed", 0),
                       adapter_id=resume.get("adapter_id"))
        prompt = req.get("prompt")
        if isinstance(prompt, str):
            try:
                prompt = [int(t) for t in prompt.split()]
            except ValueError:
                raise ValueError("string prompts must be whitespace-separated "
                                 "decimal token ids (the engine has no tokenizer)")
        if (not isinstance(prompt, (list, tuple)) or not prompt
                or not all(isinstance(t, int) and not isinstance(t, bool) for t in prompt)):
            raise ValueError("'prompt' must be a non-empty list of token ids")
        cfg = self.config
        max_tokens = req.get("max_tokens", cfg.default_max_tokens)
        if not isinstance(max_tokens, int) or max_tokens < 0:
            raise ValueError("'max_tokens' must be a non-negative integer")
        temperature = float(req.get("temperature") or 0.0)
        do_sample = bool(req.get("do_sample", temperature > 0.0))
        timeout_s = req.get("timeout_s")
        if timeout_s is None:
            timeout_s = float(cfg.request_timeout_s)  # <= 0: operator opt-out
        else:
            if not isinstance(timeout_s, (int, float)) \
                    or isinstance(timeout_s, bool) or timeout_s <= 0:
                # a client 0/negative must NOT mean "no deadline": only the
                # operator (request_timeout_s <= 0) can disable the policy
                raise ValueError("'timeout_s' must be a positive number")
            timeout_s = float(timeout_s)
            if cfg.request_timeout_s > 0:  # body overrides downward only
                timeout_s = min(timeout_s, float(cfg.request_timeout_s))
        tenant = (headers.get(cfg.tenant_header.lower())
                  or req.get("user") or "anonymous")
        priority = (headers.get(cfg.priority_header.lower())
                    or req.get("priority") or cfg.default_priority)
        sched = self.scheduler
        # model variant (multi-LoRA serving): `adapter_id` selects a
        # registered LoRA adapter; `model` doubles as the OpenAI-shaped
        # spelling when it names one. Unknown/unavailable ids 400 here —
        # never after queueing
        adapter_id = req.get("adapter_id")
        if adapter_id is None:
            m = req.get("model")
            if (isinstance(m, str) and sched.adapters is not None
                    and m in sched.adapters.registered()):
                adapter_id = m
        if adapter_id is not None:
            if not isinstance(adapter_id, str):
                raise ValueError("'adapter_id' must be a string")
            if sched.adapters is None:
                raise ValueError("multi-LoRA serving is not enabled "
                                 "(continuous_batching.multi_lora)")
            sched.adapters.check_registered(adapter_id)
        # capacity pre-check mirrors DecodeScheduler.submit's validation so
        # impossible requests 400 immediately instead of queueing first
        budget = _round_up(max(1, max_tokens), sched.steps_per_sync)
        # spannable capacity: one request may chain up to
        # long_context.max_extents slot extents
        cap = sched.cache.spannable_len
        if len(prompt) >= cap or len(prompt) + budget > cap:
            raise ValueError(
                f"prompt ({len(prompt)} tokens) + max_tokens ({max_tokens}) exceeds "
                f"the per-slot KV capacity {sched.max_len} x "
                f"{sched.cache.max_extents} extent(s) = {cap} spannable rows")
        return dict(
            prompt=np.asarray(prompt, np.int32),
            max_new_tokens=max_tokens,
            eos_token_id=req.get("eos_token_id"),
            do_sample=do_sample,
            temperature=temperature if temperature > 0 else 1.0,
            top_k=int(req.get("top_k") or 0),
            top_p=float(req.get("top_p") or 1.0),
            seed=int(req.get("seed") or 0),
            tenant=str(tenant),
            priority=str(priority),
            deadline=(time.monotonic() + timeout_s) if timeout_s > 0 else None,
            stream=bool(req.get("stream", False)),
            adapter_id=adapter_id,
            return_logits=bool(req.get("return_logits", False)),
            resume=resume,
        )

    async def _completions(self, headers, body, reader, writer):
        tel = self.telemetry
        self.stats["requests"] += 1
        if tel.enabled:
            tel.counter("gateway/requests")
        if self.draining or not self.ready:
            self.stats["shed_503"] += 1
            if tel.enabled:
                tel.counter("gateway/shed_503")
            await self._json(writer, 503,
                             {"error": {"message": "gateway is draining",
                                        "type": "unavailable"}},
                             extra=[("Retry-After", str(self._retry_after()))])
            return
        try:
            kwargs = self._parse_completion(headers, body)
        except (ValueError, TypeError) as e:
            # TypeError covers non-numeric JSON (e.g. "top_k": [1]) reaching
            # int()/float(): still a client error, must answer 400 — not a
            # logged exception and a silently dropped connection
            self.stats["rejected"] += 1
            await self._json(writer, 400,
                             {"error": {"message": str(e), "type": "invalid_request"}})
            return
        # brownout door: while the shedding ladder is engaged, arrivals in
        # priority classes below the bar 503 immediately with the brownout
        # Retry-After — evicting the backlog once and then re-queueing the
        # same tier would just rebuild it
        bar = self._brownout_bar
        if bar is not None and self._fair.tier_weight(kwargs["priority"]) < bar:
            self.stats["shed_503"] += 1
            self.stats["brownout_shed"] += 1
            if tel.enabled:
                tel.counter("gateway/shed_503")
                tel.counter("autoscale/brownout_shed")
            retry = str(int(self.autoscaler.config.brownout_retry_after_s))
            await self._json(writer, 503,
                             {"error": {"message": "brownout: request tier "
                                        "shed under overload",
                                        "type": "overloaded"}},
                             extra=[("Retry-After", retry)])
            return
        # request identity: accept an inbound W3C traceparent / x-request-id,
        # else mint one; echoed back as x-request-id and used as the span
        # tree's track id when request tracing is on
        trace_id, parent, _ = extract_trace_context(headers)
        trace = None
        if tel.enabled and getattr(tel, "trace_requests", False):
            trace = RequestTrace(tel, trace_id, parent,
                                 tenant=kwargs["tenant"],
                                 priority=kwargs["priority"])
            trace.mark("queued")
        greq = _GatewayRequest(self._next_rid(), trace=trace, trace_id=trace_id, **kwargs)
        if trace is not None:
            trace.rid = greq.rid
            # per-request track: a client may reuse an x-request-id across
            # concurrent retries, and two requests must never share one
            # async track (interleaved trees, colliding flow ids). The bare
            # id is still what x-request-id echoes.
            trace.track = f"{trace_id}:{greq.rid}"
        if greq.resume is not None:
            # cross-process resume: fleet-wide admission already happened on
            # the prefill worker — parking it behind the fair queue would
            # double-charge its tenant and could deadlock a full queue
            self._admit_resume(greq)
            if greq.stream:
                await self._respond_stream(greq, reader, writer)
            else:
                await self._respond_unary(greq, reader, writer)
            return
        try:
            self._fair.push(greq, greq.tenant, greq.priority, cost=greq.cost,
                            adapter=greq.adapter_id)
        except QueueFull:
            self.stats["shed_429"] += 1
            if tel.enabled:
                tel.counter("gateway/shed_429")
            await self._json(writer, 429,
                             {"error": {"message": "server overloaded: request "
                                        "queue is full, retry later",
                                        "type": "overloaded"}},
                             extra=[("Retry-After", str(self._retry_after())),
                                    ("x-request-id", greq.trace_id)])
            return
        if tel.enabled:
            tel.gauge("gateway/queue_depth", len(self._fair))
        self._wake.set()
        if greq.stream:
            await self._respond_stream(greq, reader, writer)
        else:
            await self._respond_unary(greq, reader, writer)

    async def _next_event(self, greq, eof_task):
        """One event from the pump, or ('disconnect',) when the client goes
        away first. The generous timeout is a safety net — the pump enforces
        the real deadline. With deadlines disabled by the OPERATOR
        (``request_timeout_s <= 0``) there is no safety net either: the
        opt-out must not collapse into a ~90s ceiling."""
        if self.config.request_timeout_s > 0:
            timeout = (self.config.request_timeout_s
                       + self.config.drain_timeout_s + 30)
        else:
            timeout = None
        get_task = asyncio.ensure_future(greq.events.get())
        done, _ = await asyncio.wait({get_task, eof_task}, timeout=timeout,
                                     return_when=asyncio.FIRST_COMPLETED)
        if get_task in done:
            return get_task.result()
        get_task.cancel()
        if eof_task in done:
            return ("disconnect", )
        # safety-net trip: CANCEL the request, don't just abandon it — an
        # orphan would sit in the fair queue (or its slot) and decode a full
        # budget for a client that already got the 500
        greq.cancel_requested = True
        greq.cancel_reason = "gateway timeout"
        self._wake.set()
        return ("failed", 500, "gateway timed out waiting on the scheduler")

    def _client_gone(self, greq):
        self.stats["disconnects"] += 1
        if self.telemetry.enabled:
            self.telemetry.counter("gateway/disconnects")
        greq.cancel_requested = True
        greq.cancel_reason = "disconnect"
        self._wake.set()

    @staticmethod
    async def _watch_eof(reader):
        """Resolves when the client closes its half of the connection (EOF
        past the request body = nothing more to pipeline on a
        Connection: close exchange)."""
        try:
            while True:
                data = await reader.read(4096)
                if not data:
                    return
        except Exception:  # noqa: BLE001 — reset == gone
            return

    def _chunk(self, greq, toks, finish_reason):
        return {"id": f"cmpl-{greq.rid}", "object": "text_completion.chunk",
                "model": type(self.engine.module).__name__,
                "choices": [{"index": 0,
                             "text": "".join(f"{t} " for t in toks),
                             "token_ids": toks,
                             "finish_reason": finish_reason}]}

    def _handler_gone(self, greq):
        """A response handler is leaving (sink on): token events still in
        its queue, and any posted from now on, will never be written. The
        first are counted taken here, the second ``unread`` where the loop
        hands them on (``_hand_over``), so the backlog stays what the loop
        has yet to do."""
        greq.unread = True
        left = 0
        while not greq.events.empty():
            left += greq.events.get_nowait()[0] == "token"
        self._delivery.taken += left

    async def _respond_stream(self, greq, reader, writer):
        eof_task = asyncio.ensure_future(self._watch_eof(reader))
        tel = self.telemetry
        # the loop's own totals of what it delivers, None with the sink off
        sent = self._delivery
        headers_sent = False
        try:
            while True:
                ev = await self._next_event(greq, eof_task)
                kind = ev[0]
                if kind == "disconnect":
                    self._client_gone(greq)
                    return
                if kind == "failed":
                    # optional 4th element: extra response headers (the
                    # brownout 503 carries its own Retry-After)
                    status, msg = ev[1], ev[2]
                    if not headers_sent:
                        await self._json(writer, status,
                                         {"error": {"message": msg}},
                                         extra=list(ev[3]) if len(ev) > 3 else ())
                    return
                if not headers_sent:
                    headers_sent = True
                    writer.write(self._head(200, "text/event-stream",
                                            [("Cache-Control", "no-cache"),
                                             ("x-request-id", greq.trace_id)]))
                    if tel.enabled:
                        tel.histogram("gateway/ttfb_ms",
                                      (time.monotonic() - greq.enq_ts) * 1e3)
                if kind == "token":
                    # the row's tokens of one landing: ONE document, one write
                    toks, reason = ev[1], ev[2]
                    payload = json.dumps(self._chunk(greq, toks, reason))
                    data = f"data: {payload}\n\n".encode()
                    if sent is None:
                        writer.write(data)
                        await writer.drain()
                    else:
                        n = sent.events + sent.taken + sent.unread + 1
                        if n & SEND_SPAN_EVERY:
                            writer.write(data)
                        else:
                            # one send in 64 under a profiler annotation (no
                            # sink event): a capture shows what a send takes
                            # beside the pump's spans and the device. One an
                            # event cost a traced cell-9 run 8% of its tokens
                            with tel.span("gateway/send", record=False):
                                writer.write(data)
                        # the bytes are with the transport (the selector
                        # transport has tried the socket inside write):
                        # delivered, as far as this process can tell. Plain
                        # arithmetic on totals this thread alone adds to,
                        # ``events`` last (Delivery)
                        landed = sent.landing_of(n)
                        if landed is not None:
                            lag = time.perf_counter() - landed
                            sent.lag_s += lag
                            if lag > sent.lag_max_s:
                                sent.lag_max_s = lag
                        sent.bytes += len(data)
                        sent.tokens += len(toks)
                        sent.writes += 1
                        sent.events += 1
                        await writer.drain()
                    if reason is not None:
                        break
                elif kind == "done":
                    payload = json.dumps(self._chunk(greq, [], ev[1]))
                    writer.write(f"data: {payload}\n\n".encode())
                    break
                elif kind == "cancelled":
                    payload = json.dumps(self._chunk(greq, [], ev[1]))
                    writer.write(f"data: {payload}\n\n".encode())
                    break
                elif kind == "handoff":
                    # cross-process migration: the stream ends HERE with the
                    # handoff descriptor — the router (the only client that
                    # ever sees this event) consumes it, resumes the request
                    # on a decode worker, and stitches that worker's stream
                    # onto everything already relayed
                    writer.write(f"data: {json.dumps({'handoff': ev[1]})}\n\n"
                                 .encode())
                    break
            writer.write(b"data: [DONE]\n\n")
            await writer.drain()
        except ConnectionError:
            self._client_gone(greq)
        finally:
            eof_task.cancel()
            if sent is not None:
                self._handler_gone(greq)

    async def _respond_unary(self, greq, reader, writer):
        eof_task = asyncio.ensure_future(self._watch_eof(reader))
        toks = []
        finish_reason = None
        handoff = None
        try:
            while True:
                ev = await self._next_event(greq, eof_task)
                kind = ev[0]
                if kind == "disconnect":
                    self._client_gone(greq)
                    return
                if kind == "failed":
                    status, msg = ev[1], ev[2]
                    await self._json(writer, status, {"error": {"message": msg}},
                                     extra=list(ev[3]) if len(ev) > 3 else ())
                    return
                if kind == "token":
                    reason = ev[2]
                    toks.extend(ev[1])
                    if self._delivery is not None:  # counted posted, here taken, never written
                        self._delivery.taken += 1
                    if reason is not None:
                        finish_reason = reason
                        break
                elif kind == "done":
                    finish_reason = ev[1]
                    break
                elif kind == "cancelled":
                    finish_reason = ev[1]
                    break
                elif kind == "handoff":
                    # cross-process migration: partial response — the tokens
                    # decoded so far plus the descriptor the router needs to
                    # resume the request on a decode worker and concatenate
                    finish_reason = "handoff"
                    handoff = ev[1]
                    break
            if finish_reason == "deadline" and not toks:
                await self._json(writer, 504,
                                 {"error": {"message": "deadline expired"}},
                                 extra=[("x-request-id", greq.trace_id)])
                return
            if self.telemetry.enabled:
                self.telemetry.histogram("gateway/ttfb_ms",
                                         (time.monotonic() - greq.enq_ts) * 1e3)
            out = {
                "id": f"cmpl-{greq.rid}", "object": "text_completion",
                "model": type(self.engine.module).__name__,
                "choices": [{"index": 0,
                             "text": " ".join(str(t) for t in toks),
                             "token_ids": toks,
                             "finish_reason": finish_reason}],
                "usage": {"prompt_tokens": int(len(greq.prompt)),
                          "completion_tokens": len(toks),
                          "total_tokens": int(len(greq.prompt)) + len(toks)},
            }
            if handoff is not None:
                out["handoff"] = handoff
            if greq.return_logits and greq.handle is not None:
                # float32 -> JSON double is exact: the logits survive the
                # process boundary bitwise (the multihost identity matrix
                # asserts on them)
                out["logits"] = [np.asarray(step, np.float32).tolist()
                                 for step in greq.handle._req.logits]
            await self._json(writer, 200, out,
                             extra=[("x-request-id", greq.trace_id)])
        except ConnectionError:
            self._client_gone(greq)
        finally:
            eof_task.cancel()
            if self._delivery is not None:
                self._handler_gone(greq)

    # ------------------------------------------------------------------ HTTP writing
    _REASONS = {200: "OK", 400: "Bad Request", 404: "Not Found",
                409: "Conflict",
                413: "Content Too Large", 429: "Too Many Requests",
                431: "Request Header Fields Too Large",
                503: "Service Unavailable", 504: "Gateway Timeout",
                500: "Internal Server Error"}

    def _head(self, status, ctype, extra=(), length=None):
        lines = [f"HTTP/1.1 {status} {self._REASONS.get(status, 'Unknown')}",
                 f"Content-Type: {ctype}", "Connection: close"]
        if length is not None:
            lines.append(f"Content-Length: {length}")
        for key, val in extra:
            lines.append(f"{key}: {val}")
        return ("\r\n".join(lines) + "\r\n\r\n").encode()

    async def _json(self, writer, status, obj, extra=()):
        body = json.dumps(obj).encode()
        writer.write(self._head(status, _JSON, extra, length=len(body)) + body)
        await writer.drain()
