"""Cross-request scheduler: a device-pool data plane with per-device
continuous batching, a shared multi-threaded host Tier-1 pool, and typed
admission control for encode, decode (region-read), and tensor jobs.

Before this module every encode request ran a private pipeline:
``encode_array`` spun up its own one-worker executor for host Tier-1 and
dispatched device programs with no coordination across requests, so two
concurrent ``load_image`` calls contended for the device, serialized
their host Tier-1 on single host threads, and re-paid dispatch overhead
per chunk. The scheduler is the process-wide service that owns device
access and host Tier-1 capacity instead:

- **Device pool** — one worker thread per ``jax.devices()`` entry
  (capped by ``bucketeer.sched.devices`` / ``BUCKETEER_SCHED_DEVICES``),
  all pulling from the one merged priority queue, so encode chunks, merged
  tensor-codec chunks and coefficient-dequant launches dispatch to whichever
  device is free. Workers spawn on demand: a serial workload runs on device
  0 exactly like the old single device thread (no gratuitous per-device
  recompiles); backlog beyond the idle workers brings the next device
  online. Launches stage their host batch with ``jax.device_put(...,
  device)`` so the compiled program runs on the worker's own core —
  committed inputs keep the downstream payload gather on that core instead
  of thrashing back to device 0. On a pool of several devices a front-end
  shape's first launch compiles (or loads) its front-end and gather programs
  on every pool device before it returns (codec/frontend._ready_on_pool): a
  warm-up that reaches a shape reaches it everywhere, and a worker spawned
  later compiles nothing.
- **Continuous batching** — compatible chunks from *different* requests
  (same tile plan, dtype and tile shape) are concatenated into one launch,
  padded to the existing power-of-two batch buckets (pipeline._bucket) so
  jitted programs are reused, not retraced. Each request gets back a sliced
  view of the merged result — per-tile results are bit-identical to a solo
  launch because every front-end reduction is within-tile. A worker only
  holds the aggregation window when no idle peer could take arriving work
  instead: with free devices, parallelism beats batching. Tensor-codec
  chunks (same dtype/row shape/backend) merge the same way into one pack+MQ
  launch, sliced per request — per-block coding is independent, so merged
  output is byte-identical.
- **Shared host Tier-1** — packed Tier-1 runs on one pool sized to
  host cores (``t1_encode_packed`` releases the GIL, proven in
  tests/test_native_t1.py), with per-request ordered
  reassembly: each request collects its own futures in submission
  order, so output stays byte-identical to the serial path.
- **Admission control** — a bounded queue with backpressure: when
  waiting+running requests exceed the depth, ``submit`` raises
  :class:`QueueFull` and the HTTP layer answers 503 with
  ``Retry-After``. Single-image requests are prioritized over batch
  items, and each request can carry a deadline that expires both while
  queued and at chunk-dispatch boundaries.
- **Typed jobs** — requests carry a ``kind`` (``"encode"`` |
  ``"decode"`` | ``"tensor"``). All kinds share the one bounded queue
  and slot pool (the resources are shared, so the admission bound must
  be too); decode jobs skip the encode pipeline seam, run on a
  least-loaded assigned device (``jax.default_device``), and
  interactive tile reads (:data:`PRIORITY_READ`) outrank every encode,
  so a deep-zoom viewer's 512² window is never starved behind a batch
  ingest. :meth:`read` is the decode-typed entry.

Observability (``set_metrics_sink``): ``encode.queue_wait`` /
``decode.queue_wait`` (stages), ``encode.batch_occupancy`` /
``tensor.batch_occupancy`` (value distributions: requests per device
launch), counters ``{encode,decode,tensor}.admission_rejects``,
``{encode,tensor,batchread}.device_launches`` plus the per-device
``....device_launches.d<N>`` split (real worker device ids — the PR 16
placeholder that booked everything on d0 is gone),
``{decode,tensor}.device_assigned.d<N>`` for request-thread placement,
``encode.batched_tiles`` plus its per-device ``encode.batched_tiles.d<N>``
(every launch reports every pool device, an unused one as 0),
``tensor.batched_blocks``,
``{encode,decode}.deadline_expired``. Merged-launch spans carry the
worker's ``device_id``. A ``sched`` reporter on the sink adds the
per-device occupancy gauge (``sched.device_occupancy.d<N>``: busy
fraction since the pool started) and the live device-queue depth to
``/metrics`` reports.

Continuous batching on the device axis is the same shape LLM serving
stacks use.
"""
from __future__ import annotations

import functools
import heapq
import itertools
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np

from .. import obs
from ..analysis.graftrace import seam
from . import faults

LOG = logging.getLogger(__name__)

PRIORITY_READ = -1       # interactive tile/region reads outrank encodes
PRIORITY_SINGLE = 0      # interactive single-image requests
PRIORITY_BATCHREAD = 0   # batch coefficient reads: strictly after
                         # interactive reads, strictly ahead of bulk
                         # encode/tensor batch items (graftrace scenario
                         # batch_fanout_vs_read pins both edges)
PRIORITY_BATCH = 1       # CSV batch items yield to interactive traffic
PRIORITY_TENSOR = 1      # tensor-codec jobs: batch-class, never ahead
                         # of interactive reads (graftrace scenario
                         # tensor_vs_read_priority pins this)

# Upper bound on tiles per merged device launch: keeps the padded HBM
# staging (rows buffers) bounded however many requests pile up.
_MAX_BATCH_TILES = int(os.environ.get("BUCKETEER_SCHED_MAX_BATCH_TILES",
                                      "64"))
# Same bound for merged tensor-codec chunks, in code-blocks.
_MAX_BATCH_BLOCKS = int(os.environ.get(
    "BUCKETEER_SCHED_MAX_BATCH_BLOCKS", "128"))
# And for merged coefficient-dequant launches, in images: each image
# contributes one full set of per-band planes, so the HBM staging is
# images x (sum of band planes).
_MAX_BATCH_IMAGES = int(os.environ.get(
    "BUCKETEER_SCHED_MAX_BATCH_IMAGES", "16"))

_STAGE_CAPS = {"frontend": _MAX_BATCH_TILES, "tensor": _MAX_BATCH_BLOCKS,
               "dequant": _MAX_BATCH_IMAGES}


class QueueFull(RuntimeError):
    """Admission rejected: the bounded request queue is at depth. The
    HTTP layer maps this to 503 + ``Retry-After: retry_after``."""

    def __init__(self, depth: int, retry_after: float,
                 kind: str = "encode") -> None:
        self.retry_after = retry_after
        super().__init__(
            f"{kind} queue full ({depth} requests queued or running); "
            f"retry after {retry_after:g}s")


class DeadlineExceeded(RuntimeError):
    """The request's deadline expired before (or while) encoding."""


class SchedulerClosed(RuntimeError):
    """The scheduler was shut down. New submissions are rejected with
    this, and work still queued (slot waiters, undisposed device jobs)
    at close() time fails with it instead of hanging — graftrace's
    shutdown_drain scenario proved the old close() left slot waiters
    parked forever on their grant event."""


@dataclass
class _Ticket:
    """One admitted request's place in the slot queue."""
    priority: int
    seq: int
    deadline: float | None            # absolute monotonic (seam clock)
    kind: str = "encode"              # metric namespace: encode | decode
    granted: threading.Event = field(
        default_factory=lambda: seam.make_event("Ticket.granted"))
    abandoned: bool = False           # expired while waiting
    closed: bool = False
    cancelled: bool = False           # close() cancelled it while queued

    def expired(self) -> bool:
        return (self.deadline is not None
                and seam.monotonic() > self.deadline)


@dataclass
class _DeviceJob:
    """One chunk's front-end launch request. ``ctx`` is the submitting
    request's graftscope span context, captured on the request thread
    (the worker thread has none): the merged launch span *links* every
    request whose chunks it batched through these."""
    plan: object
    tiles: np.ndarray
    n_tiles: int
    ctx: object = None
    priority: int = PRIORITY_SINGLE
    seq: int = 0
    event: threading.Event = field(
        default_factory=lambda: seam.make_event("DeviceJob.event"))
    result: object = None
    error: BaseException | None = None

    stage = "frontend"

    @property
    def key(self):
        # Merge-compatibility: identical jitted program + concatenable
        # host batch.
        return (self.plan, self.tiles.dtype.str, self.tiles.shape[1:])

    @property
    def size(self) -> int:
        return self.n_tiles


@dataclass
class _TensorJob:
    """One tensor-codec chunk's device launch request (pack + device
    MQ over ``n_blocks`` code-blocks). Merge-compatible jobs are
    concatenated like encode rows chunks; per-block coding is
    independent, so each request's slice is byte-identical to a solo
    launch."""
    rows: np.ndarray
    floors: np.ndarray
    backend: str
    n_blocks: int
    ctx: object = None
    priority: int = PRIORITY_TENSOR
    seq: int = 0
    event: threading.Event = field(
        default_factory=lambda: seam.make_event("TensorJob.event"))
    result: object = None
    error: BaseException | None = None

    stage = "tensor"

    @property
    def key(self):
        return ("tensor", self.backend, self.rows.dtype.str,
                self.rows.shape[1:])

    @property
    def size(self) -> int:
        return self.n_blocks


@dataclass
class _DequantJob:
    """One image's coefficient-dequant launch request (batch read
    fan-out). The dequant program is elementwise per band, so
    merge-compatible jobs (same reversibility + deltas + band shapes)
    are stacked along a new leading batch axis and launched once; each
    request's slice of the batched output is bit-identical to a solo
    launch. ``expected`` hints the merge window: one batchread request
    contributes N of these concurrently, so the worker waits for up to
    ``expected`` compatible peers rather than the running-request count
    (which would cut the window at group size 1)."""
    reversible: bool
    deltas: tuple
    arrays: list
    expected: int = 1
    ctx: object = None
    priority: int = PRIORITY_BATCHREAD
    seq: int = 0
    event: threading.Event = field(
        default_factory=lambda: seam.make_event("DequantJob.event"))
    result: object = None
    error: BaseException | None = None

    stage = "dequant"

    @property
    def key(self):
        return ("dequant", self.reversible, self.deltas,
                tuple(a.shape for a in self.arrays))

    @property
    def size(self) -> int:
        return 1


@dataclass
class _SlicedPending:
    """A request's share of a merged front-end launch: quacks like
    frontend.PendingFrontend (resolve_stats) but resolves to a
    FrontendResult windowed onto [tile_off, tile_off + n_tiles)."""
    merged: object            # frontend.PendingFrontend
    tile_off: int
    n_tiles: int

    def resolve_stats(self):
        return self.merged.resolve_stats(tile_off=self.tile_off,
                                         n_tiles=self.n_tiles)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


class EncodeScheduler:
    """Process-wide encode service: admission -> slot -> pipelined
    encode with scheduler-owned device-pool dispatch and host pool.

    Defaults (env-overridable):

    - ``BUCKETEER_SCHED_QUEUE_DEPTH`` (32): admission bound, queued +
      running requests.
    - ``BUCKETEER_SCHED_MAX_CONCURRENT`` (8): encode slots; beyond
      this, admitted requests wait (by priority, then FIFO).
    - ``BUCKETEER_SCHED_POOL`` (host cores): shared Tier-1 workers.
    - ``BUCKETEER_SCHED_WINDOW_MS`` (3): aggregation window a device
      worker waits for co-batchable chunks while other requests are in
      flight and no idle peer device could take them. 0 disables
      merging.
    - ``BUCKETEER_SCHED_DEVICES`` (0 = all): device-pool size cap; the
      pool has one worker per ``jax.devices()`` entry up to the cap.
    - ``BUCKETEER_SCHED_DEADLINE_S`` (0 = none): default per-request
      deadline.
    - ``BUCKETEER_SCHED_RETRY_AFTER_S`` (2): the Retry-After hint
      attached to :class:`QueueFull`.
    """

    def __init__(self, *, queue_depth: int | None = None,
                 max_concurrent: int | None = None,
                 pool_size: int | None = None,
                 window_s: float | None = None,
                 deadline_s: float | None = None,
                 retry_after_s: float | None = None,
                 devices: int | None = None) -> None:
        cores = os.cpu_count() or 2
        self.queue_depth = queue_depth if queue_depth is not None else \
            _env_int("BUCKETEER_SCHED_QUEUE_DEPTH", 32)
        self.max_concurrent = max_concurrent if max_concurrent is not \
            None else _env_int("BUCKETEER_SCHED_MAX_CONCURRENT", 8)
        self.pool_size = pool_size if pool_size is not None else \
            _env_int("BUCKETEER_SCHED_POOL", cores)
        if window_s is not None:
            self.window_s = window_s
        else:
            self.window_s = _env_float("BUCKETEER_SCHED_WINDOW_MS",
                                       3.0) / 1000.0
        if deadline_s is not None:
            self.default_deadline_s = deadline_s or None
        else:
            self.default_deadline_s = _env_float(
                "BUCKETEER_SCHED_DEADLINE_S", 0.0) or None
        self.retry_after_s = retry_after_s if retry_after_s is not None \
            else _env_float("BUCKETEER_SCHED_RETRY_AFTER_S", 2.0)
        self.devices = devices if devices is not None else \
            _env_int("BUCKETEER_SCHED_DEVICES", 0)

        self._pool = ThreadPoolExecutor(max_workers=max(1, self.pool_size),
                                        thread_name_prefix="sched-t1")
        self._lock = seam.make_lock("EncodeScheduler._lock")
        self._seq = itertools.count()
        self._waiting: list = []      # heap of (priority, seq, ticket)
        self._running = 0
        self._admitted = 0            # waiting + running
        self._closed = False          # admission-side close flag
        self._sink = None

        # -- device pool state (guarded by _dq_cv) --------------------
        self._dq_cv = seam.make_condition("EncodeScheduler._dq_cv")
        self._djobs: list = []        # the one merged priority queue
        self._dseq = itertools.count()
        self._devices: list | None = None   # resolved lazily
        self._workers: list = []      # per-device thread (or None)
        self._busy_s: list = []       # accumulated busy seconds
        self._busy_since: list = []   # launch start, None when idle
        self._inflight: list = []     # request-thread device assignments
        self._pool_t0: float | None = None
        self._stop = False            # device-side close flag
        # Test/graftrace seam: overrides codec.frontend.dispatch_frontend
        # so scenarios can explore the batching skeleton without JAX
        # (the pool simulates `devices or 1` deviceless workers then).
        self.launch_fn = None

    # -- metrics ------------------------------------------------------

    def set_metrics_sink(self, sink) -> None:
        """Install a server.metrics.Metrics-like sink (``record``,
        ``observe``, ``count``); None disables. Sinks with
        ``add_reporter`` also get the ``sched`` pool report (per-device
        occupancy gauge + queue depth) attached."""
        self._sink = sink
        if sink is not None and hasattr(sink, "add_reporter"):
            sink.add_reporter("sched", self.pool_report)

    def _count(self, name: str, n: int = 1) -> None:
        if self._sink is not None:
            self._sink.count(name, n)

    def pool_report(self) -> dict:
        """Live device-pool snapshot for /metrics: per-device occupancy
        (busy fraction since the pool came up) and queue depth. Safe as
        a Metrics reporter: report() calls reporters outside its own
        lock, so taking ``_dq_cv`` here cannot invert."""
        with self._dq_cv:
            now = seam.monotonic()
            out = {
                "devices": (len(self._devices)
                            if self._devices is not None else 0),
                "device_queue_depth": len(self._djobs),
            }
            if self._devices is not None and self._pool_t0 is not None:
                elapsed = max(now - self._pool_t0, 1e-9)
                for i in range(len(self._devices)):
                    busy = self._busy_s[i]
                    if self._busy_since[i] is not None:
                        busy += now - self._busy_since[i]
                    out[f"sched.device_occupancy.d{i}"] = round(
                        min(busy / elapsed, 1.0), 4)
            return out

    # -- configuration -------------------------------------------------

    def configure(self, *, queue_depth: int | None = None,
                  max_concurrent: int | None = None,
                  pool_size: int | None = None,
                  window_s: float | None = None,
                  deadline_s: float | None = None,
                  devices: int | None = None) -> None:
        """Apply deployment config (engine/core.py wires the
        ``bucketeer.sched.*`` keys through here). Resizing the pool
        swaps executors; in-flight jobs finish on the old one. The
        device cap applies to pools not yet spun up — a live pool keeps
        its resolved devices."""
        with self._lock:
            if queue_depth is not None and queue_depth > 0:
                self.queue_depth = queue_depth
            if max_concurrent is not None and max_concurrent > 0:
                self.max_concurrent = max_concurrent
                self._grant_next_locked()
            if window_s is not None and window_s >= 0:
                self.window_s = window_s
            if deadline_s is not None:
                self.default_deadline_s = deadline_s or None
            if devices is not None and devices >= 0:
                self.devices = devices
            if pool_size is not None and pool_size > 0 and \
                    pool_size != self.pool_size:
                old = self._pool
                self.pool_size = pool_size
                self._pool = ThreadPoolExecutor(
                    max_workers=pool_size, thread_name_prefix="sched-t1")
                # In-flight encodes captured the old pool at admission
                # and will still submit to it; shutting it down under
                # them would turn their next chunk into a RuntimeError.
                # Only close it when nothing is running — otherwise its
                # idle threads wind down at interpreter exit.
                if self._admitted == 0:
                    old.shutdown(wait=False)

    # -- admission + slots ---------------------------------------------

    def _admit(self, priority: int, deadline_s: float | None,
               kind: str = "encode") -> _Ticket:
        with self._lock:
            seam.read(self, "_closed")
            if self._closed:
                raise SchedulerClosed(
                    f"{kind} rejected: scheduler is closed")
            seam.read(self, "_admitted")
            if self._admitted >= self.queue_depth:
                self._count(f"{kind}.admission_rejects")
                raise QueueFull(self.queue_depth, self.retry_after_s,
                                kind)
            seam.write(self, "_admitted")
            self._admitted += 1
            if deadline_s is None:
                deadline_s = self.default_deadline_s
            deadline = (seam.monotonic() + deadline_s
                        if deadline_s else None)
            t = _Ticket(priority, next(self._seq), deadline, kind)
            if self._running < self.max_concurrent and not self._waiting:
                seam.write(self, "_running")
                self._running += 1
                t.granted.set()
            else:
                seam.write(self, "_waiting")
                heapq.heappush(self._waiting, (priority, t.seq, t))
            return t

    def _grant_next_locked(self) -> None:
        while self._waiting and self._running < self.max_concurrent:
            seam.write(self, "_waiting")
            _, _, t = heapq.heappop(self._waiting)
            if t.abandoned or t.closed or t.cancelled:
                continue
            seam.write(self, "_running")
            self._running += 1
            t.granted.set()

    def _await_slot(self, t: _Ticket) -> None:
        t0 = time.perf_counter()
        while not t.granted.is_set():
            timeout = None
            if t.deadline is not None:
                timeout = t.deadline - seam.monotonic()
                if timeout <= 0:
                    with self._lock:
                        t.abandoned = True
                    self._count(f"{t.kind}.deadline_expired")
                    raise DeadlineExceeded(
                        f"{t.kind} deadline expired while queued")
            t.granted.wait(timeout)
        seam.read(t, "cancelled")
        if t.cancelled:
            # close() woke us to fail typed, not to run.
            raise SchedulerClosed(
                f"{t.kind} request cancelled: scheduler closed while "
                "it was queued")
        if self._sink is not None:
            self._sink.record(f"{t.kind}.queue_wait",
                              time.perf_counter() - t0)

    def _finish(self, t: _Ticket) -> None:
        with self._lock:
            if t.closed:
                return
            t.closed = True
            seam.write(self, "_admitted")
            self._admitted -= 1
            # A cancelled ticket was granted only to deliver the typed
            # close error — it never occupied a running slot.
            if t.granted.is_set() and not t.cancelled:
                seam.write(self, "_running")
                self._running -= 1
                self._grant_next_locked()

    # -- the public encode surface -------------------------------------

    def submit(self, fn, *args, priority: int = PRIORITY_SINGLE,
               deadline_s: float | None = None, kind: str = "encode",
               **kwargs):
        """Run ``fn(*args, **kwargs)`` as one admitted request: wait for
        a slot (by priority, bounded by the deadline), then execute.
        ``kind="encode"`` jobs run with the encoder's device dispatch
        and host Tier-1 routed through this scheduler;
        ``kind="decode"`` jobs (region/tile reads) share the same
        bounded queue and slots, run on a least-loaded assigned pool
        device, and poll the deadline between Tier-1 code-blocks
        (t1_dec.decode_services) instead of the encode pipeline seam.
        Raises :class:`QueueFull` without blocking when the bounded
        queue is at depth, and :class:`SchedulerClosed` once
        :meth:`close` has run (including for requests that were queued
        when it ran — never a hang)."""
        from ..codec import encoder as encoder_mod

        # graftgremlin: lets a fault scenario force admission failures
        # (QueueFull -> 503 ladder) without filling the real queue.
        faults.point("sched.submit", kind=kind)
        ticket = self._admit(priority, deadline_s, kind)

        def check() -> None:
            """Deadline hook the encoder polls at chunk-dispatch
            boundaries (codec/encoder.py pipeline_services)."""
            if ticket.expired():
                self._count(f"{ticket.kind}.deadline_expired")
                raise DeadlineExceeded(
                    f"{ticket.kind} deadline expired mid-pipeline")

        # The whole admitted request is one latency sample: the
        # per-kind histogram behind /metrics' server-side p50/p95/p99
        # (bench configs 7/8 assert it against client-side timing).
        t_req = time.perf_counter()
        try:
            with obs.span(f"{kind}.queue_wait", priority=priority):
                self._await_slot(ticket)
            if kind == "tensor":
                from ..tensor import tensor_services
                with tensor_services(
                        check=check,
                        launch=functools.partial(
                            self.dispatch_tensor_chunk,
                            _priority=ticket.priority)):
                    with self._device_ctx(kind):
                        return fn(*args, **kwargs)
            if kind == "batchread":
                from ..tensor import coeff_services
                with coeff_services(
                        check=check,
                        launch=functools.partial(
                            self.dispatch_dequant,
                            _priority=ticket.priority)):
                    with self._device_ctx(kind):
                        return fn(*args, **kwargs)
            if kind != "encode":
                from ..codec.decode import t1_dec
                with t1_dec.decode_services(check=check):
                    with self._device_ctx(kind):
                        return fn(*args, **kwargs)
            with encoder_mod.pipeline_services(
                    dispatch=functools.partial(
                        self.dispatch_frontend,
                        _priority=ticket.priority),
                    pool=self._pool, check=check):
                return fn(*args, **kwargs)
        finally:
            self._finish(ticket)
            if self._sink is not None:
                self._sink.record(f"{kind}.request",
                                  time.perf_counter() - t_req)

    def read(self, fn, *args, priority: int = PRIORITY_READ,
             deadline_s: float | None = None, **kwargs):
        """Run a decode/region-read job through the shared admission
        queue at read priority: tile reads for interactive viewers are
        granted slots before any queued encode, and past the bounded
        queue the caller gets :class:`QueueFull` -> 503 + Retry-After
        exactly like encode submissions."""
        return self.submit(fn, *args, priority=priority,
                           deadline_s=deadline_s, kind="decode",
                           **kwargs)

    def submit_tensor(self, fn, *args, priority: int = PRIORITY_TENSOR,
                      deadline_s: float | None = None, **kwargs):
        """Run a tensor-codec job (encode_tensor / decode_tensor /
        decode_to_coefficients work) through the shared admission
        queue: tensor jobs are batch-class — interactive region reads
        (:data:`PRIORITY_READ`) are always granted slots first — and
        past the bounded queue the caller gets :class:`QueueFull` ->
        503 + Retry-After like every other kind. The codec's
        ``tensor_services`` deadline hook is installed for the job's
        duration (polled between chunks/blocks), and device-backend
        chunks route through :meth:`dispatch_tensor_chunk` so
        compatible chunks from concurrent tensor jobs merge into one
        pool launch."""
        return self.submit(fn, *args, priority=priority,
                           deadline_s=deadline_s, kind="tensor",
                           **kwargs)

    def submit_batchread(self, fn, *args,
                         priority: int = PRIORITY_BATCHREAD,
                         deadline_s: float | None = None, **kwargs):
        """Run a batch coefficient read (batches/assemble.py) through
        the shared admission queue as ONE admitted request: admission,
        deadline and queue-wait accounting happen at batch granularity,
        while the per-image dequant fan-out inside rides the device
        queue as :class:`_DequantJob` entries without per-item
        admission (per-item tickets could deadlock the slot queue
        against the batch's own ticket). Batch reads sit strictly
        after interactive reads and strictly ahead of bulk
        encode/tensor work in both the slot queue and the device
        queue."""
        return self.submit(fn, *args, priority=priority,
                           deadline_s=deadline_s, kind="batchread",
                           **kwargs)

    def encode_array(self, img, bitdepth: int = 8, params=None,
                     mesh=None, *, priority: int = PRIORITY_SINGLE,
                     deadline_s: float | None = None) -> bytes:
        from ..codec import encoder as encoder_mod

        return self.submit(encoder_mod.encode_array, img, bitdepth,
                           params, mesh=mesh, priority=priority,
                           deadline_s=deadline_s)

    def encode_jp2(self, img, bitdepth: int = 8, params=None,
                   jpx: bool = False, mesh=None, *,
                   priority: int = PRIORITY_SINGLE,
                   deadline_s: float | None = None) -> bytes:
        from ..codec import encoder as encoder_mod

        return self.submit(encoder_mod.encode_jp2, img, bitdepth,
                           params, jpx=jpx, mesh=mesh, priority=priority,
                           deadline_s=deadline_s)

    # -- device pool ---------------------------------------------------

    def _resolve_devices_locked(self) -> list:
        """The pool's device list: ``jax.devices()`` capped by the
        ``devices`` config, or ``devices or 1`` simulated (None)
        entries when a test/graftrace ``launch_fn`` is installed —
        scenarios explore the pool skeleton without importing JAX. A
        backend that fails to come up raises here: there is no
        deviceless worker to hide it behind."""
        cap = max(0, self.devices)
        if self.launch_fn is not None:
            return [None] * max(1, cap)
        import jax

        devs = list(jax.devices())
        return devs[:cap] if cap > 0 else devs

    def _ensure_devices_locked(self) -> None:
        if self._devices is not None:
            return
        seam.write(self, "_devices")
        self._devices = self._resolve_devices_locked()
        n = len(self._devices)
        seam.write(self, "_workers")
        self._workers = [None] * n
        seam.write(self, "_busy_s")
        self._busy_s = [0.0] * n
        seam.write(self, "_busy_since")
        self._busy_since = [None] * n
        seam.write(self, "_inflight")
        self._inflight = [0] * n
        # True from pop to launch completion: a worker inside its
        # aggregation window owns a job without being "busy" yet, and
        # must not read as idle to scale-up / idle-peer heuristics.
        seam.write(self, "_holding")
        self._holding = [False] * n
        self._pool_t0 = seam.monotonic()

    def _spawn_worker_locked(self, widx: int) -> None:
        seam.write(self, "_workers")
        self._workers[widx] = seam.start_thread(
            self._worker_loop, name=f"sched-device-{widx}",
            args=(widx,))

    def _ensure_workers(self) -> None:
        """Bring the pool up lazily: resolve the device list on first
        use and guarantee at least worker 0 is alive. Further workers
        spawn on demand (:meth:`_scale_up_locked`) — a serial workload
        stays on device 0 and never pays per-device recompiles.
        close() is permanent: a dispatch racing it gets the typed
        error, never a resurrected half-alive pool."""
        with self._dq_cv:
            seam.read(self, "_stop")
            if self._stop:
                raise SchedulerClosed("scheduler is closed")
            self._ensure_devices_locked()
            seam.read(self, "_workers")
            if not any(t is not None and t.is_alive()
                       for t in self._workers):
                self._spawn_worker_locked(0)

    def _scale_up_locked(self) -> None:
        """Called after queueing a job: if the backlog exceeds the idle
        live workers, bring the next device's worker online (also the
        restart path for a fatally-dead worker slot — no job is ever
        stranded on a dead worker)."""
        idle = 0
        seam.read(self, "_holding")
        for i, t in enumerate(self._workers):
            if t is not None and t.is_alive() \
                    and self._busy_since[i] is None \
                    and not self._holding[i]:
                idle += 1
        if idle >= len(self._djobs):
            return
        for i, t in enumerate(self._workers):
            if t is None or not t.is_alive():
                self._spawn_worker_locked(i)
                return

    def device_threads_alive(self) -> bool:
        """True while any pool worker thread is alive (tests and the
        graftrace shutdown scenarios assert close() really stopped the
        pool)."""
        with self._dq_cv:
            seam.read(self, "_workers")
            return any(t is not None and t.is_alive()
                       for t in self._workers)

    def _assign_device(self, kind: str):
        """Least-loaded request-thread device assignment for decode /
        tensor jobs (their compute runs on the request thread, not a
        pool worker). Serial traffic always lands on device 0 —
        identical placement to the pre-pool scheduler — and only
        concurrent requests spread. Returns ``(device, index)`` or
        ``(None, -1)`` when there is nothing to choose."""
        if self.launch_fn is not None:
            return None, -1
        with self._dq_cv:
            seam.read(self, "_stop")
            if self._stop:
                return None, -1
            self._ensure_devices_locked()
            devs = self._devices
            if len(devs) < 2 or devs[0] is None:
                return None, -1
            best = min(range(len(devs)),
                       key=lambda i: (self._inflight[i], i))
            seam.write(self, "_inflight")
            self._inflight[best] += 1
        self._count(f"{kind}.device_assigned.d{best}")
        return devs[best], best

    @contextmanager
    def _device_ctx(self, kind: str):
        """Pin a decode/tensor request thread to its assigned device
        for the duration (``jax.default_device``), releasing the
        load-balance slot on exit."""
        dev, idx = self._assign_device(kind)
        if dev is None:
            yield
            return
        import jax
        try:
            with jax.default_device(dev):
                yield
        finally:
            with self._dq_cv:
                seam.write(self, "_inflight")
                self._inflight[idx] -= 1

    # -- device batching -----------------------------------------------

    def dispatch_frontend(self, plan, tiles, *,
                          _priority: int = PRIORITY_SINGLE):
        """The encoder's device-dispatch hook: queue a front-end launch
        and block until a pool worker has dispatched it (the launch
        itself stays async — JAX returns before the program finishes).
        Compatible queued chunks are merged into one launch; the
        caller gets its slice. Raises :class:`SchedulerClosed` (never
        hangs) once :meth:`close` has run."""
        self._ensure_workers()
        job = _DeviceJob(plan, np.asarray(tiles), len(tiles),
                         ctx=obs.current_context(), priority=_priority)
        with self._dq_cv:
            seam.read(self, "_stop")
            if self._stop:
                raise SchedulerClosed("scheduler is closed")
            job.seq = next(self._dseq)
            seam.write(self, "_djobs")
            self._djobs.append(job)
            self._scale_up_locked()
            self._dq_cv.notify_all()
        job.event.wait()
        seam.read(job, "error")
        if job.error is not None:
            raise job.error
        seam.read(job, "result")
        return job.result

    def dispatch_tensor_chunk(self, rows, floors,
                              backend: str = "device", *,
                              _priority: int = PRIORITY_TENSOR):
        """The tensor codec's device-chunk hook (tensor_services
        ``launch``): queue one chunk's pack+MQ launch on the pool and
        block for its slice of the (possibly merged) result —
        ``(blocks, n_syms, device_seconds)`` shaped exactly like
        tensor.codec.encode_chunk_device."""
        self._ensure_workers()
        job = _TensorJob(np.asarray(rows), np.asarray(floors), backend,
                         len(rows), ctx=obs.current_context(),
                         priority=_priority)
        with self._dq_cv:
            seam.read(self, "_stop")
            if self._stop:
                raise SchedulerClosed("scheduler is closed")
            job.seq = next(self._dseq)
            seam.write(self, "_djobs")
            self._djobs.append(job)
            self._scale_up_locked()
            self._dq_cv.notify_all()
        job.event.wait()
        seam.read(job, "error")
        if job.error is not None:
            raise job.error
        seam.read(job, "result")
        return job.result

    def dispatch_dequant(self, reversible: bool, deltas: tuple,
                         arrays: list, *,
                         _priority: int = PRIORITY_BATCHREAD,
                         _expected: int = 1):
        """The coefficient reader's dequant hook (coeff_services
        ``launch``): queue one image's per-band dequant on the pool and
        block for its slice of the (possibly merged) launch — a tuple
        of device arrays, one per band, shaped exactly like the inline
        dispatch. ``_expected`` is the submitting batch's fan-out width
        (the merge window's fill target)."""
        self._ensure_workers()
        job = _DequantJob(reversible, tuple(deltas),
                          [np.asarray(a) for a in arrays],
                          expected=max(1, int(_expected)),
                          ctx=obs.current_context(),
                          priority=_priority)
        with self._dq_cv:
            seam.read(self, "_stop")
            if self._stop:
                raise SchedulerClosed("scheduler is closed")
            job.seq = next(self._dseq)
            seam.write(self, "_djobs")
            self._djobs.append(job)
            self._scale_up_locked()
            self._dq_cv.notify_all()
        job.event.wait()
        seam.read(job, "error")
        if job.error is not None:
            raise job.error
        seam.read(job, "result")
        return job.result

    def _pop_job_locked(self):
        """Pop the highest-priority (then FIFO) queued job; None when
        the queue is empty."""
        if not self._djobs:
            return None
        best = min(range(len(self._djobs)),
                   key=lambda i: (self._djobs[i].priority,
                                  self._djobs[i].seq))
        seam.write(self, "_djobs")
        return self._djobs.pop(best)

    def _idle_peer_locked(self, widx: int) -> bool:
        """True when another live worker is idle: holding the
        aggregation window then is futile (the peer would pop arrivals
        immediately) and harmful (a free device should parallelize, not
        wait to merge)."""
        seam.read(self, "_holding")
        for i, t in enumerate(self._workers):
            if i == widx or t is None or not t.is_alive():
                continue
            if self._busy_since[i] is None and not self._holding[i]:
                return True
        return False

    def _take_compatible_locked(self, group: list) -> int:
        """Move queued jobs merge-compatible with group[0] into the
        group (the _locked suffix is the codebase convention for
        "caller holds the lock" — here the queue cv; the lock-discipline
        lint, analysis/rules_locks.py, keys on it). Returns the group
        size total (tiles for frontend groups, blocks for tensor,
        images for dequant)."""
        lead = group[0]
        cap = _STAGE_CAPS[lead.stage]
        key = lead.key
        total = sum(j.size for j in group)
        kept: list = []
        for j in self._djobs:
            if j.stage == lead.stage and j.key == key and \
                    total + j.size <= cap:
                group.append(j)
                total += j.size
            else:
                kept.append(j)
        seam.write(self, "_djobs")
        self._djobs = kept
        return total

    def _running_count(self) -> int:
        """Granted-slot snapshot for the workers' merge heuristics.
        graftrace flagged the old bare ``self._running`` read here as a
        data race (every write happens under ``_lock``; the device loop
        read it under ``_dq_cv`` only), so the snapshot takes the lock
        — _dq_cv -> _lock nests nowhere in the reverse order (the
        lock-order-cycle rule keeps it that way)."""
        with self._lock:
            seam.read(self, "_running")
            return self._running

    def _drain_queued_locked(self) -> None:
        """Fail every still-queued device job typed at shutdown — every
        per-device queue view drains, no waiter hangs."""
        for j in self._djobs:
            seam.write(j, "error")
            j.error = SchedulerClosed(
                "scheduler closed before this chunk's device launch")
            j.event.set()
        seam.write(self, "_djobs")
        self._djobs = []

    def _worker_loop(self, widx: int) -> None:
        while True:
            with self._dq_cv:
                while True:
                    seam.read(self, "_stop")
                    if self._stop:
                        self._drain_queued_locked()
                        return
                    job = self._pop_job_locked()
                    if job is not None:
                        break
                    self._dq_cv.wait()
                seam.write(self, "_holding")
                self._holding[widx] = True
                # Idle peers re-check the queue.
                self._dq_cv.notify_all()
                group = [job]
                if self.window_s > 0 and not self._idle_peer_locked(widx):
                    # Continuous batching: wait up to the window for
                    # co-batchable chunks while other running requests
                    # could still contribute one — but only while no
                    # idle peer device could take them instead.
                    cap = _STAGE_CAPS[job.stage]
                    limit = seam.monotonic() + self.window_s
                    while True:
                        total = self._take_compatible_locked(group)
                        if job.stage == "dequant":
                            # One batchread request fans out N dequant
                            # jobs concurrently: the fill target is the
                            # request's own advertised width, not the
                            # running-request count (which would cut the
                            # window at group size 1).
                            target = min(cap, max(j.expected
                                                  for j in group))
                            if len(group) >= target or total >= cap:
                                break
                        else:
                            running = self._running_count()
                            if (len(group) >= max(1, running)
                                    or total >= cap):
                                break
                            # Futile-wait cut: if every other running
                            # request already has an incompatible job
                            # queued (each blocks on its own dispatch,
                            # one job per request), nothing mergeable
                            # can arrive — launch now instead of
                            # burning the window on their critical
                            # path.
                            if self._djobs and len(self._djobs) >= \
                                    running - len(group):
                                break
                        remaining = limit - seam.monotonic()
                        if remaining <= 0:
                            break
                        self._dq_cv.wait(remaining)
                        seam.read(self, "_stop")
                        if self._stop:
                            break
                else:
                    # No window (or an idle peer): merge only what is
                    # already queued.
                    self._take_compatible_locked(group)
                seam.write(self, "_busy_since")
                self._busy_since[widx] = seam.monotonic()
            fatal = False
            try:
                if job.stage == "frontend":
                    self._launch(group, widx)
                elif job.stage == "tensor":
                    self._launch_tensor(group, widx)
                else:
                    self._launch_dequant(group, widx)
            # The _launch* methods deliver per-job errors; anything
            # escaping is a scheduler bug (or a fatal interrupt) — log
            # it, fail the group's waiters so none hangs, and keep the
            # pool serving.
            except BaseException as exc:
                fatal = not isinstance(exc, Exception)
                LOG.exception("device worker %d error on a %d-job "
                              "group", widx, len(group))
                for j in group:
                    if not j.event.is_set():
                        seam.write(j, "error")
                        j.error = RuntimeError("device launch failed")
                        j.event.set()
            finally:
                with self._dq_cv:
                    seam.write(self, "_busy_s")
                    self._busy_s[widx] += \
                        seam.monotonic() - self._busy_since[widx]
                    seam.write(self, "_busy_since")
                    self._busy_since[widx] = None
                    seam.write(self, "_holding")
                    self._holding[widx] = False
                    if fatal and not self._stop:
                        # A fatally-interrupted worker replaces itself
                        # before exiting so queued jobs are never
                        # stranded on a dead slot.
                        self._spawn_worker_locked(widx)
            if fatal:
                return

    def _launch(self, group: list, widx: int) -> None:
        dev = self._devices[widx]
        launch = self.launch_fn
        if launch is None:
            from ..codec import frontend
            if dev is not None:
                # A pool of several devices: a shape's first launch
                # readies its programs on every one of them. A pool of
                # one launches as it always did.
                pool_kw = ({"pool": tuple(self._devices)}
                           if len(self._devices) > 1 else {})
                launch = functools.partial(frontend.dispatch_frontend,
                                           device=dev, **pool_kw)
            else:
                launch = frontend.dispatch_frontend

        # The merged launch belongs to no single request: it gets an
        # unparented span *linked* to every request span whose chunks
        # it batched, carrying occupancy.
        n_tiles = sum(j.n_tiles for j in group)
        attrs = {"occupancy": len(group), "tiles": n_tiles,
                 "mode": "rows", "device_id": widx}
        links = [j.ctx for j in group if j.ctx is not None]
        completed = False
        try:
            with obs.span("device.launch", ctx=None, links=links,
                          **attrs):
                if len(group) == 1:
                    result = launch(group[0].plan, group[0].tiles)
                    seam.write(group[0], "result")
                    group[0].result = result
                else:
                    tiles = np.concatenate([j.tiles for j in group])
                    merged = launch(group[0].plan, tiles)
                    off = 0
                    for j in group:
                        seam.write(j, "result")
                        j.result = _SlicedPending(merged, off,
                                                  j.n_tiles)
                        off += j.n_tiles
            completed = True
        # The whole group shares the failed launch; the error is
        # delivered to every waiting request and re-raised there, so no
        # waiter hangs and nothing is swallowed.
        except Exception as exc:    # graftlint: disable=swallowed-exception
            for j in group:
                seam.write(j, "error")
                j.error = exc
        finally:
            if self._sink is not None:
                self._sink.count("encode.device_launches")
                self._sink.count(f"encode.device_launches.d{widx}")
                self._sink.count("encode.batched_tiles", n_tiles)
                # Every pool device reports its tiles, an unused one 0.
                for i in range(len(self._devices)):
                    self._sink.count(f"encode.batched_tiles.d{i}",
                                     n_tiles if i == widx else 0)
                self._sink.observe("encode.batch_occupancy", len(group))
            for j in group:
                # A fatally-interrupted launch (BaseException in
                # flight) reached neither the result assignments nor
                # the except clause: the waiter must see a typed error,
                # never a silent None result.
                if not completed and j.error is None:
                    seam.write(j, "error")
                    j.error = RuntimeError("device launch failed")
                j.event.set()

    def _launch_tensor(self, group: list, widx: int) -> None:
        """One merged tensor-codec pack+MQ launch. Per-block coding is
        independent (codec/cxd.run_device_mq buckets each block by its
        own realized length), so each job's block slice is byte-
        identical to a solo launch; the aggregate symbol count and
        device seconds are attributed proportionally by block count —
        they feed stats/metrics, never output bytes."""
        dev = self._devices[widx]
        n_blocks = sum(j.n_blocks for j in group)
        attrs = {"occupancy": len(group), "blocks": n_blocks,
                 "mode": "tensor", "device_id": widx}
        links = [j.ctx for j in group if j.ctx is not None]
        completed = False
        try:
            with obs.span("device.launch", ctx=None, links=links,
                          **attrs):
                if len(group) == 1:
                    rows = group[0].rows
                    floors = group[0].floors
                else:
                    rows = np.concatenate([j.rows for j in group])
                    floors = np.concatenate(
                        [j.floors for j in group])
                if self.launch_fn is not None:
                    res = self.launch_fn(None, rows, mode="tensor")
                    off = 0
                    for j in group:
                        seam.write(j, "result")
                        j.result = (res, off, j.n_blocks)
                        off += j.n_blocks
                else:
                    from ..tensor import codec as tensor_codec
                    blocks, syms, dev_s = \
                        tensor_codec.encode_chunk_device(
                            rows, floors, group[0].backend, device=dev)
                    off = 0
                    for j in group:
                        share = j.n_blocks / max(1, n_blocks)
                        seam.write(j, "result")
                        j.result = (blocks[off:off + j.n_blocks],
                                    int(round(syms * share)),
                                    dev_s * share)
                        off += j.n_blocks
            completed = True
        except Exception as exc:    # graftlint: disable=swallowed-exception
            for j in group:
                seam.write(j, "error")
                j.error = exc
        finally:
            if self._sink is not None:
                self._sink.count("tensor.device_launches")
                self._sink.count(f"tensor.device_launches.d{widx}")
                self._sink.count("tensor.batched_blocks", n_blocks)
                self._sink.observe("tensor.batch_occupancy", len(group))
            for j in group:
                if not completed and j.error is None:
                    seam.write(j, "error")
                    j.error = RuntimeError("device launch failed")
                j.event.set()

    def _launch_dequant(self, group: list, widx: int) -> None:
        """One merged coefficient-dequant launch. The program is
        elementwise per band: stacking the group's per-band planes
        along a new leading batch axis and slicing the batched outputs
        back per image is bit-identical to solo launches (ISSUE 19's
        bit-exactness acceptance bar rides on this)."""
        dev = self._devices[widx]
        lead = group[0]
        attrs = {"occupancy": len(group), "images": len(group),
                 "mode": "dequant", "device_id": widx}
        links = [j.ctx for j in group if j.ctx is not None]
        completed = False
        try:
            with obs.span("device.launch", ctx=None, links=links,
                          **attrs):
                if self.launch_fn is not None:
                    res = self.launch_fn(
                        None, [j.arrays for j in group], mode="dequant")
                    for j in group:
                        seam.write(j, "result")
                        j.result = (res, len(group))
                else:
                    from ..tensor import coeffs as tcoeffs
                    if len(group) == 1:
                        seam.write(lead, "result")
                        lead.result = tcoeffs.run_dequant_inline(
                            lead.reversible, lead.deltas, lead.arrays,
                            device=dev)
                    else:
                        # Bucket the batch axis to a power of two:
                        # jit retraces per input shape, so launching
                        # whatever group size the merge window caught
                        # (5, then 3, ...) compiles a fresh program
                        # per size — a multi-hundred-ms stall mid
                        # request. Padded rows are zeros; the program
                        # is elementwise, so real rows are untouched.
                        width = 1 << (len(group) - 1).bit_length()
                        stacked = []
                        for b in range(len(lead.arrays)):
                            plane = np.zeros(
                                (width,) + lead.arrays[b].shape,
                                dtype=lead.arrays[b].dtype)
                            for g, j in enumerate(group):
                                plane[g] = j.arrays[b]
                            stacked.append(plane)
                        outs = tcoeffs.run_dequant_inline(
                            lead.reversible, lead.deltas, stacked,
                            device=dev)
                        # Lazy per-image views of the shared batched
                        # output: the batch assembler gathers sibling
                        # views in one fused program instead of paying
                        # a device slice dispatch per band per image.
                        for g, j in enumerate(group):
                            seam.write(j, "result")
                            j.result = tuple(
                                tcoeffs.BandSlice(o, g) for o in outs)
            completed = True
        except Exception as exc:    # graftlint: disable=swallowed-exception
            for j in group:
                seam.write(j, "error")
                j.error = exc
        finally:
            if self._sink is not None:
                self._sink.count("batchread.device_launches")
                self._sink.count(f"batchread.device_launches.d{widx}")
                self._sink.count("batchread.merged_images", len(group))
                self._sink.observe("batchread.batch_occupancy",
                                   len(group))
            for j in group:
                if not completed and j.error is None:
                    seam.write(j, "error")
                    j.error = RuntimeError("device launch failed")
                j.event.set()

    # -- lifecycle -----------------------------------------------------

    def close(self) -> None:
        """Shut down, permanently: stop admission, cancel queued slot
        waiters *typed* (:class:`SchedulerClosed`), let in-flight
        device groups finish, drain still-queued device jobs typed,
        then stop every pool worker and the host pool.

        The cancellation pass exists because graftrace's
        shutdown_drain scenario deadlocked the old close(): a request
        waiting for a slot parked on ``granted.wait()`` forever, since
        nothing ever granted or woke it after shutdown."""
        with self._lock:
            seam.write(self, "_closed")
            self._closed = True
            seam.write(self, "_waiting")
            while self._waiting:
                _, _, t = heapq.heappop(self._waiting)
                if not t.closed and not t.granted.is_set():
                    seam.write(t, "cancelled")
                    t.cancelled = True
                    t.granted.set()
        with self._dq_cv:
            seam.write(self, "_stop")
            self._stop = True
            self._dq_cv.notify_all()
            seam.read(self, "_workers")
            workers = list(self._workers)
        for t in workers:
            if t is not None:
                t.join(timeout=5)
        # Workers drain the queue on their way out; this final pass
        # covers jobs queued against a pool whose workers had already
        # died (nothing left to drain them) — every waiter fails typed.
        with self._dq_cv:
            self._drain_queued_locked()
        with self._lock:
            seam.read(self, "_admitted")
            busy = self._admitted > 0
        if not busy:
            self._pool.shutdown(wait=True)
        # else: granted in-flight requests still own the pool — a
        # shutdown under them turns their next Tier-1 chunk into an
        # untyped "cannot schedule new futures" RuntimeError, breaking
        # the completes-or-fails-typed contract. Leave it; its idle
        # threads wind down at interpreter exit (the same policy as
        # configure()'s pool swap).

    def stats(self) -> dict:
        with self._lock:
            seam.read(self, "_running")
            seam.read(self, "_admitted")
            out = {"running": self._running,
                   "waiting": len(self._waiting),
                   "admitted": self._admitted,
                   "queue_depth": self.queue_depth,
                   "max_concurrent": self.max_concurrent,
                   "pool_size": self.pool_size,
                   "closed": self._closed}
        # Pool stats live under the queue cv; _lock -> _dq_cv must not
        # nest (the lock-order-cycle rule), so this is a second scope.
        with self._dq_cv:
            out["devices"] = (len(self._devices)
                              if self._devices is not None
                              else self.devices)
            out["device_queue_depth"] = len(self._djobs)
        return out


# The class predates decode routing; the neutral name is the current
# one, the encode-flavored name stays for existing callers.
Scheduler = EncodeScheduler

_GLOBAL: EncodeScheduler | None = None
_GLOBAL_LOCK = threading.Lock()


def get_scheduler() -> EncodeScheduler:
    """The process-wide scheduler (lazily built): every converter and
    worker shares one instance, which is the whole point — cross-request
    batching only exists if requests meet in the same queues."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = EncodeScheduler()
        return _GLOBAL
