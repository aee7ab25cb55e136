"""Asynchronous input pipeline: the reference's py_reader / double_buffer
analog (reference: python/paddle/fluid/layers/io.py:449 `py_reader`,
operators/reader/create_double_buffer_reader_op.cc,
reader/lod_tensor_blocking_queue.h).

TPU-native redesign: a background thread pulls batches from a python reader
and converts them via DataFeeder (host-side work) into a bounded queue; the
consumer thread issues the `jax.device_put` at yield time — PJRT enqueues
the copy asynchronously, so it still overlaps the previous step's compute
(the double-buffer property) without driving the device from two threads.
No in-graph reader ops are needed because feeds enter the jitted step as
arguments.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Dict, Iterable, Optional

import jax

from . import flags as _flags
from .observe import metrics as _metrics
from .observe import steplog as _steplog


class AsyncFeeder:
    """`for feed in AsyncFeeder(feeder, reader, capacity=4): exe.run(feed=feed)`

    feeder: DataFeeder (or any fn batch->feed dict); reader: batched reader
    (yields lists of samples). device/sharding: optional placement applied
    ahead of the step (ParallelExecutor passes its batch sharding).
    """

    def __init__(self, feeder, reader: Callable[[], Iterable], capacity: int = 4,
                 device=None, sharding=None, pad_to: int = 0, prepared=None):
        self._feeder = feeder
        self._reader = reader
        self._capacity = capacity
        self._device = device
        self._sharding = sharding
        self._pad_to = pad_to
        if prepared is not None and device is None and sharding is None:
            # pair with an Executor.prepare() handle: transfers target the
            # device the prepared step dispatches to, so each batch's H2D
            # is enqueued (async under PJRT) while the PREVIOUS prepared
            # step still runs — host dispatch and feed placement overlap
            # the step end-to-end
            self._device = prepared.device

    def _convert(self, batch) -> Dict:
        """Host-side conversion only — runs on the producer thread."""
        feed = (self._feeder.feed(batch, pad_to=self._pad_to)
                if hasattr(self._feeder, "feed") else self._feeder(batch))
        return feed

    def _place(self, feed) -> Dict:
        """Device placement at yield time, on the CONSUMER thread: PJRT
        device_put is an async enqueue, so the copy still overlaps the
        previous step's compute, and every transfer is issued from the
        thread that dispatches the steps."""
        target = self._sharding or self._device
        if target is None:
            return feed
        out = {}
        with _steplog.span(_steplog.FEEDER_PUT):
            for k, v in feed.items():
                if isinstance(v, tuple):
                    out[k] = tuple(jax.device_put(x, target) for x in v)
                else:
                    out[k] = jax.device_put(v, target)
        return out

    def __iter__(self):
        q: queue.Queue = queue.Queue(maxsize=self._capacity)
        end = object()
        err = []
        stop = threading.Event()

        def producer():
            try:
                for batch in self._reader():
                    item = self._convert(batch)
                    while not stop.is_set():
                        try:
                            q.put(item, timeout=0.2)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        return  # consumer abandoned the iteration
            except Exception as e:  # surface reader errors on the consumer
                err.append(e)
            finally:
                # the end sentinel must be DELIVERED, not best-effort: a
                # full queue here (consumer slower than producer) would
                # drop it and hang the consumer after it drains
                while not stop.is_set():
                    try:
                        q.put(end, timeout=0.2)
                        break
                    except queue.Full:
                        continue

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                if _flags.get_flag("observe"):
                    # queue-depth/starvation gauges: a consumer wait with
                    # an empty queue means the producer (reader + host
                    # conversion) is the bottleneck — the overlap the
                    # feeder exists to provide is NOT happening
                    t0 = time.perf_counter()
                    starved = q.empty()
                    item = q.get()
                    wait = time.perf_counter() - t0
                    _metrics.gauge(
                        "feeder_queue_depth",
                        "batches buffered ahead of the consumer").set(
                            q.qsize())
                    if item is not end:
                        _metrics.counter(
                            "feeder_batches_total",
                            "batches delivered to the consumer").inc()
                        _metrics.histogram(
                            "feeder_consumer_wait_seconds",
                            "time the consumer blocked waiting for a batch"
                        ).observe(wait)
                        if starved:
                            _metrics.counter(
                                "feeder_starvation_total",
                                "consumer arrivals that found the queue "
                                "empty (producer-bound pipeline)").inc()
                else:
                    item = q.get()
                if item is end:
                    break
                yield self._place(item)
        finally:
            # on break/close: release the producer and drop buffered batches
            stop.set()
            while not q.empty():
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
        if err:
            raise err[0]
