"""Host data pipeline: per-process sharded loading + device placement.

Mirrors the paper's DataLoader-with-DistributedSampler setup: each dp rank
sees a disjoint shard; weak-scaling mode subsets the dataset proportionally
to world size (the paper's §IV-A weak-scaling protocol — the sampled index
POOL is restricted, not just the epoch length).

Batches are **cursor-addressable**: ``batch_at(epoch, index)`` is a pure
function of ``(seed, epoch, index)``, so the TrainState data cursor
``(epoch, batch_index)`` saved by the elastic checkpoint layer names an
exact batch — a resumed run replays the identical stream from mid-epoch.

``Prefetcher`` is the timm-PrefetchLoader equivalent: a two-stage
background pipeline (synthesis thread -> host queue -> transfer thread ->
device queue, each ``depth`` deep) that overlaps batch synthesis, the
host->device ``device_put``, and the running compiled step. Dataset
sources keep images **uint8 on the host** (4x fewer transferred bytes than
fp32); the jitted step finishes them on device (upsample + normalize —
``data/augment.device_preprocess``).
"""
from __future__ import annotations

import math
import queue
import struct
import threading
import warnings
import weakref
import zlib
from typing import Iterator, Optional, Tuple

import jax
import numpy as np

from repro import obs
from repro.data.synthetic import DatasetSpec, make_image_batch, \
    make_token_batch
from repro.resilience import faults as _faults
from repro.resilience.backoff import BackoffPolicy

# prefetch-side retry of transient data-source errors: a flaky read
# (network blip, contended disk) resolves behind the prefetch overlap —
# the consumer only ever sees persistent failures
DEFAULT_DATA_BACKOFF = BackoffPolicy(max_attempts=3, base_delay=0.05,
                                     multiplier=2.0, max_delay=0.5,
                                     jitter=0.5)


def batch_seed(seed: int, epoch: int, i: int) -> int:
    """Stable 31-bit batch seed. Python's hash() is salted per process
    (PYTHONHASHSEED), so two launcher processes would derive *different*
    "identical" batches; crc32 over the packed tuple is process-invariant."""
    return zlib.crc32(struct.pack("<qqq", seed, epoch, i)) % (2 ** 31)


class DataPipeline:
    def __init__(self, *, kind: str, global_batch: int, seed: int = 0,
                 dataset: Optional[DatasetSpec] = None, vocab: int = 0,
                 seq_len: int = 0, resolution: Optional[int] = None,
                 weak_scaling_frac: float = 1.0, epoch_size: int = 0,
                 source=None):
        """kind: 'image' | 'token'. weak_scaling_frac: fraction of the
        dataset used (paper: n_gpus x 10%) — shortens the epoch AND
        restricts the index pool batches sample from (``sample_pool``),
        so each world size really trains on a proportional subset.
        ``source``: a :class:`repro.data.datasets.CIFARSource` or
        :class:`repro.data.streaming.ShardedSource` — image batches then
        come from its train split behind the same ``batch_at`` cursor
        contract (uint8, native resolution); without it, images are
        spec-shaped pre-normalized fp32 synthetic tensors."""
        assert kind in ("image", "token")
        if source is not None and kind != "image":
            raise ValueError("dataset sources only back the image kind")
        if not 0.0 < weak_scaling_frac <= 1.0:
            raise ValueError(
                f"weak_scaling_frac must be in (0, 1]: {weak_scaling_frac}")
        self.kind = kind
        self.global_batch = global_batch
        self.seed = seed
        self.dataset = source.spec if source is not None else dataset
        self.source = source
        self.vocab = vocab
        self.seq_len = seq_len
        self.resolution = source.resolution if source is not None \
            else resolution
        n = epoch_size or (source.train_size if source is not None
                           else self.dataset.num_images
                           if self.dataset else 50_000)
        self.epoch_size = int(n * weak_scaling_frac)
        # §IV-A weak scaling: restrict the SAMPLED pool, not just the
        # epoch length (regression: batches used to keep sampling the
        # full split, silently breaking the proportional-subset protocol)
        self.sample_pool = None
        if source is not None and weak_scaling_frac < 1.0:
            self.sample_pool = max(1, int(source.train_size
                                          * weak_scaling_frac))

    @property
    def steps_per_epoch(self) -> int:
        return max(1, math.floor(self.epoch_size / self.global_batch))

    def batch_at(self, epoch: int, index: int) -> dict:
        """The batch at data cursor ``(epoch, index)`` — pure in
        ``(self.seed, epoch, index)``, the addressability contract the
        checkpoint resume path depends on."""
        if not 0 <= index < self.steps_per_epoch:
            raise IndexError(
                f"batch_index {index} out of range for epoch of "
                f"{self.steps_per_epoch} steps")
        _faults.check("data", index)    # chaos harness (no-op in prod)
        seed = batch_seed(self.seed, epoch, index)
        if self.kind == "image":
            if self.source is not None:
                return self.source.train_batch(self.global_batch, seed=seed,
                                               pool=self.sample_pool)
            return make_image_batch(self.dataset, self.global_batch,
                                    seed=seed, resolution=self.resolution)
        return make_token_batch(self.vocab, self.global_batch,
                                self.seq_len, seed=seed)

    def batch_shapes(self) -> dict:
        """ShapeDtypeStructs of one batch, without synthesizing it (for
        deriving batch shardings before the first fetch). Dataset sources
        ship uint8 at the NATIVE grid (the on-device preprocess upsamples
        to the model resolution); the legacy synthetic stream stays
        pre-normalized fp32 at the model resolution."""
        b = self.global_batch
        if self.kind == "image":
            if self.source is not None:
                r = self.source.native_resolution
                return {"images": jax.ShapeDtypeStruct((b, r, r, 3),
                                                       np.uint8),
                        "labels": jax.ShapeDtypeStruct((b,), np.int32)}
            res = self.resolution or self.dataset.resolution
            return {"images": jax.ShapeDtypeStruct((b, res, res, 3),
                                                   np.float32),
                    "labels": jax.ShapeDtypeStruct((b,), np.int32)}
        return {"tokens": jax.ShapeDtypeStruct((b, self.seq_len), np.int32)}

    def next_cursor(self, epoch: int, index: int) -> Tuple[int, int]:
        """Cursor of the batch after ``(epoch, index)`` — rolls the REAL
        epoch counter (epoch+1, not a reused step count, so batch seeds
        never repeat across epochs)."""
        index += 1
        if index >= self.steps_per_epoch:
            return epoch + 1, 0
        return epoch, index

    def batches(self, epoch: int = 0, start: int = 0) -> Iterator[dict]:
        for i in range(start, self.steps_per_epoch):
            yield self.batch_at(epoch, i)

    def prefetch(self, epoch: int = 0, index: int = 0, *, shardings=None,
                 depth: int = 2,
                 retry: Optional[BackoffPolicy] = DEFAULT_DATA_BACKOFF
                 ) -> "Prefetcher":
        """Background prefetcher starting at cursor ``(epoch, index)``
        (e.g. a restored TrainState's cursor), rolling epochs forever.
        ``depth`` bounds the batches in flight at EACH stage (synthesis
        and device transfer run in separate threads — see Prefetcher).
        Transient source errors are retried per ``retry`` before anything
        reaches the consumer (None = no retry)."""
        return Prefetcher(self, epoch, index, shardings=shardings,
                          depth=depth, retry=retry)

    def device_put(self, batch, shardings=None):
        if shardings is None:
            return jax.tree.map(jax.device_put, batch)
        return jax.tree.map(jax.device_put, batch, shardings)

    def local_shard(self, batch, rank: int, world: int):
        """The per-process slice a multi-host launcher would load (tested on
        one host; used by the launcher's process-sharded path). A batch
        that does not divide evenly across the world is an error — the
        old silent truncation trained on a shorter batch than requested."""
        def slc(x):
            if x.shape[0] % world:
                raise ValueError(
                    f"global batch dimension {x.shape[0]} not divisible "
                    f"by world size {world}; the remainder would be "
                    f"silently dropped")
            per = x.shape[0] // world
            return x[rank * per:(rank + 1) * per]
        return jax.tree.map(slc, batch)


class Prefetcher:
    """N-deep background batch prefetcher, two pipelined stages.

    Stage 1 (``data-synth`` thread) synthesizes/loads host batches and
    rolls the cursor; stage 2 (``data-transfer`` thread) ``device_put``s
    them (against ``shardings`` when given, so arrival is already in the
    final dp layout). Each stage is decoupled by a ``depth``-deep queue,
    so with depth N: the compiled step consumes batch k while batch k+1
    transfers and batches up to k+1+N synthesize — synthesis and transfer
    no longer serialize per batch (the double-buffered timm-PrefetchLoader
    overlap). ``next()`` yields ``(cursor, batch, next_cursor)``:
    ``cursor`` is the position of the yielded batch, ``next_cursor`` is
    what a checkpoint taken AFTER the step consuming this batch must
    record as the TrainState data cursor.

    Iterate forever (epochs roll automatically); ``close()`` (or the
    context manager) stops both threads. TRANSIENT synthesis errors
    (``OSError``, incl. the fault harness's ``TransientError``) are
    retried in the synthesis stage with bounded jittered backoff — the
    retry sleeps are stop-aware, so ``close()`` is never blocked by a
    retry in progress; only persistent errors (or exhausted retries)
    re-raise on the consumer side.

    Lifecycle guarantees (regression-tested in test_data_pipeline.py):
    every queue interaction on the producer side is **stop-aware** — in
    particular the error hand-off, which previously used a blocking
    ``put`` and stranded the thread forever when the producer raised
    while the queue was full and the consumer had stopped consuming.
    ``close()`` is idempotent, always joins both threads, and — instead
    of silently leaking a producer that outlives the join timeout — warns
    with the pending cursor so a hung data source is diagnosable.
    ``__next__`` after ``close()`` raises ``StopIteration`` instead of
    blocking on the drained queue; dropping the last reference without
    ``close()`` still reclaims the threads via ``__del__`` (belt-and-
    braces — the context manager is the intended API).
    """

    JOIN_TIMEOUT = 5.0

    def __init__(self, pipe: DataPipeline, epoch: int = 0, index: int = 0,
                 *, shardings=None, depth: int = 2,
                 retry: Optional[BackoffPolicy] = DEFAULT_DATA_BACKOFF):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1: {depth}")
        self._pipe = pipe
        self._shardings = shardings
        self.depth = depth
        self._host_q: queue.Queue = queue.Queue(maxsize=depth)
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        # cursor of the batch the synthesis stage is currently producing
        # (mutated in place by the synth thread; read by close() for the
        # leak diagnostic) — a plain list so the thread needs no strong
        # reference to self
        self._cursor_box = [int(epoch), int(index)]
        # the thread targets must NOT hold a strong ref to self: the
        # consumer dropping its last reference is what lets __del__ stop
        # the producers (a bound-method target would keep the Prefetcher
        # alive from the thread's own frame, making the leak
        # unreclaimable)
        ref = weakref.ref(self)
        self._synth_thread = threading.Thread(
            target=_synth_loop,
            args=(ref, pipe, self._host_q, self._stop, int(epoch),
                  int(index), retry, self._cursor_box),
            name="data-synth", daemon=True)
        self._xfer_thread = threading.Thread(
            target=_xfer_loop,
            args=(ref, pipe, self._host_q, self._q, self._stop, shardings),
            name="data-transfer", daemon=True)
        self._threads = (self._synth_thread, self._xfer_thread)
        for t in self._threads:
            t.start()

    def __iter__(self):
        return self

    def __next__(self):
        with obs.span(obs.DATA_WAIT):
            while True:
                try:
                    kind, item = self._q.get(timeout=0.1)
                    break
                except queue.Empty:
                    if self._stop.is_set():
                        raise StopIteration("prefetcher closed")
                    if not any(t.is_alive() for t in self._threads):
                        # producers exited: already-delivered error
                        # consumed, or they died before enqueueing —
                        # surface either way
                        if self._error is not None:
                            raise RuntimeError("data prefetch thread "
                                               "failed") from self._error
                        raise StopIteration("prefetch thread exited")
        if kind == "error":
            raise RuntimeError("data prefetch thread failed") from item
        return item

    def _drain(self):
        for q in (self._host_q, self._q):
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass

    def close(self):
        """Idempotent: stop both stages, unblock any pending put by
        draining, and join the threads. A thread still alive after the
        join timeout is a HUNG producer (wedged data source / device
        transfer) — warn with the pending cursor instead of leaking it
        silently."""
        self._stop.set()
        self._drain()
        for t in self._threads:
            t.join(timeout=self.JOIN_TIMEOUT)
        self._drain()       # anything put between drain and thread exit
        hung = [t.name for t in self._threads if t.is_alive()]
        if hung:
            warnings.warn(
                f"Prefetcher.close(): {', '.join(hung)} still alive "
                f"{self.JOIN_TIMEOUT:.0f}s after the join — the thread is "
                f"leaked (pending cursor (epoch {self._cursor_box[0]}, "
                f"batch {self._cursor_box[1]})); the data source or "
                f"device transfer is likely hung there",
                RuntimeWarning, stacklevel=2)

    def __del__(self):
        try:
            if not self._stop.is_set():
                self.close()
        except Exception:   # noqa: BLE001 — interpreter-shutdown tolerant
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def _stop_aware_put(q: queue.Queue, stop: threading.Event, msg) -> bool:
    """Put that gives up (drops the message) once the consumer has
    closed, instead of blocking forever on a full queue."""
    while not stop.is_set():
        try:
            q.put(msg, timeout=0.1)
            return True
        except queue.Full:
            continue
    return False


def _deliver_error(ref, q, stop, exc):
    """Record the error on the owner (weakly — see _synth_loop) and hand
    it down the pipeline with a stop-aware put."""
    owner = ref()
    if owner is not None and owner._error is None:
        owner._error = exc
        del owner           # drop the strong ref before parking in put
    _stop_aware_put(q, stop, ("error", exc))


def _synth_loop(ref, pipe: DataPipeline, host_q: queue.Queue,
                stop: threading.Event, epoch: int, index: int,
                retry: Optional[BackoffPolicy], cursor_box):
    """Stage-1 body (module-level — see Prefetcher.__init__ on why it
    only weakly references its owner): synthesize host batches, roll the
    cursor, hand them to the transfer stage. ``retry`` bounds the
    transient-error retries of the source fetch; the backoff sleeps wait
    on the stop event, so a close() during a retry returns immediately."""
    def fetch(e, i):
        if retry is None:
            return pipe.batch_at(e, i)
        return retry.retry(
            lambda: pipe.batch_at(e, i), retryable=(OSError,),
            sleep=lambda d: stop.wait(d),
            on_retry=lambda a, d, exc: print(
                f"[data] transient source error at ({e}, {i}) attempt "
                f"{a + 1} ({exc}); retrying in {d:.2f}s", flush=True))

    try:
        while not stop.is_set():
            cursor_box[0], cursor_box[1] = epoch, index
            with obs.span(obs.DATA_SYNTH):
                batch = fetch(epoch, index)
            item = ((epoch, index), batch, pipe.next_cursor(epoch, index))
            if not _stop_aware_put(host_q, stop, ("ok", item)):
                return
            epoch, index = item[2]
    except BaseException as e:  # noqa: BLE001 — re-raised by consumer
        _deliver_error(ref, host_q, stop, e)


def _xfer_loop(ref, pipe: DataPipeline, host_q: queue.Queue,
               dev_q: queue.Queue, stop: threading.Event, shardings):
    """Stage-2 body: move host batches onto the devices. Runs in its own
    thread so the (possibly sharded) ``device_put`` of batch k+1 overlaps
    BOTH the running step on batch k and the synthesis of k+2 — the
    double-buffered transfer the one-thread prefetcher couldn't give."""
    try:
        while not stop.is_set():
            try:
                kind, item = host_q.get(timeout=0.1)
            except queue.Empty:
                continue
            if kind == "error":
                # forward the synthesis failure and shut the stage down
                _stop_aware_put(dev_q, stop, ("error", item))
                return
            cursor, batch, nxt = item
            with obs.span(obs.DATA_TRANSFER):
                batch = pipe.device_put(batch, shardings)
            if not _stop_aware_put(dev_q, stop, ("ok", (cursor, batch,
                                                        nxt))):
                return
    except BaseException as e:  # noqa: BLE001 — re-raised by consumer
        _deliver_error(ref, dev_q, stop, e)
