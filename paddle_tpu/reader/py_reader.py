"""py_reader: blocking-queue input pipeline decoupling the python producer
from the compiled step.

Capability parity with the reference in-graph reader stack (reference:
python/paddle/fluid/layers/io.py:449 `py_reader` + `read_file`;
paddle/fluid/operators/reader/lod_tensor_blocking_queue.h — bounded queue
fed from python, consumed by the executor's read op; EOF raises
core.EOFException).

TPU-native redesign: there is no in-graph read op — the jitted step takes
feeds as arguments — so the blocking queue sits at the feed boundary: a
producer thread converts batches (DataFeeder) and optionally pre-transfers
them to device, and `Executor.run(feed=None)` on a program bound to a
PyReader pops the next batch (raising EOFException at end-of-data, exactly
the reference's drain contract). The capacity bound gives backpressure; the
device pre-transfer gives the double_buffer H2D overlap."""

from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, List, Optional, Sequence

import jax

from ..core import ir
from ..core.executor import EOFException
from ..data_feeder import DataFeeder
from ..layer_helper import LayerHelper
from ..observe import steplog as _steplog

_EOF = object()


class PyReader:
    def __init__(self, feed_vars: List[ir.Variable], capacity: int,
                 program: Optional[ir.Program] = None,
                 use_double_buffer: bool = True):
        self.feed_vars = feed_vars
        self.capacity = capacity
        self.use_double_buffer = use_double_buffer
        self._program = program or ir.default_main_program()
        self._program._py_reader = self
        self._feeder = DataFeeder(feed_list=feed_vars,
                                  program=self._program)
        self._queue: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop_event: Optional[threading.Event] = None
        self._producer_error: Optional[BaseException] = None
        self._batch_reader: Optional[Callable[[], Iterable]] = None
        self._tensor_provider: Optional[Callable[[], Iterable]] = None

    # -- binding (reference decorate_paddle_reader / decorate_tensor_provider)
    def decorate_paddle_reader(self, reader: Callable[[], Iterable]):
        """`reader()` yields BATCHES: lists of per-var sample tuples
        (compose with paddle_tpu.reader.batch)."""
        self._batch_reader = reader
        return self

    def decorate_tensor_provider(self, provider: Callable[[], Iterable]):
        """`provider()` yields ready feed dicts (or per-var array lists)."""
        self._tensor_provider = provider
        return self

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if self._batch_reader is None and self._tensor_provider is None:
            raise ValueError("bind a source first: decorate_paddle_reader "
                             "or decorate_tensor_provider")
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("py_reader already started; call reset() "
                               "after EOFException before restarting")
        self._queue = queue.Queue(maxsize=self.capacity)
        self._producer_error = None
        self._stop_event = threading.Event()
        self._thread = threading.Thread(
            target=self._produce, args=(self._queue, self._stop_event),
            daemon=True, name="py_reader")
        self._thread.start()

    def reset(self):
        """Drain after EOF — or abandon a mid-epoch producer (reference
        reader->reset per epoch). A still-running producer is signalled to
        stop so it cannot stay blocked on the abandoned queue pinning
        device-resident batches."""
        if self._stop_event is not None:
            self._stop_event.set()
        if self._queue is not None:
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
        if self._thread is not None:
            self._thread.join(timeout=5)
        self._thread = None
        self._queue = None
        self._producer_error = None

    def _produce(self, q, stop):
        def put(item):
            # bounded put that honours reset(): without the stop check a
            # producer abandoned mid-epoch would block on the full old
            # queue forever
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
            return False

        try:
            if self._tensor_provider is not None:
                for item in self._tensor_provider():
                    feed = (item if isinstance(item, dict) else
                            {v.name: a for v, a in zip(self.feed_vars, item)})
                    if not put(self._maybe_transfer(feed)):
                        return
            else:
                for batch in self._batch_reader():
                    feed = self._feeder.feed(batch)
                    if not put(self._maybe_transfer(feed)):
                        return
        except BaseException as e:  # surfaced by next_feed, NOT silent EOF
            self._producer_error = e
        finally:
            put(_EOF)

    def _maybe_transfer(self, feed):
        if not self.use_double_buffer:
            return feed
        # pre-transfer dense arrays so the step's H2D overlaps prior compute
        out = {}
        for k, v in feed.items():
            if isinstance(v, tuple):
                out[k] = (jax.device_put(v[0]), v[1])
            else:
                out[k] = jax.device_put(v)
        return out

    # -- executor hook -----------------------------------------------------
    def next_feed(self):
        if self._queue is None:
            raise RuntimeError("py_reader not started — call reader.start()")
        with _steplog.span(_steplog.READER_POP):   # the wait for data
            item = self._queue.get()
        if item is _EOF:
            if self._producer_error is not None:
                err = self._producer_error
                raise RuntimeError(
                    "py_reader producer thread failed (this is NOT "
                    "end-of-data)") from err
            raise EOFException("py_reader drained (end of data pass)")
        return item

    def __iter__(self):
        """Also usable as a plain feed iterator."""
        while True:
            try:
                yield self.next_feed()
            except EOFException:
                return


def py_reader(capacity, shapes, dtypes, lod_levels=None, name=None,
              use_double_buffer=True):
    """Declare feed vars + blocking-queue reader (reference io.py:449).
    Returns (reader, feed_vars) — the reference's read_file(reader) step is
    folded in because feeds are explicit here."""
    helper = LayerHelper("py_reader", name=name)
    lod_levels = lod_levels or [0] * len(shapes)
    feed_vars = []
    from ..layers import io as lio
    for i, (shape, dtype, lod) in enumerate(zip(shapes, dtypes, lod_levels)):
        v = lio.data(name=f"{helper.name}.slot{i}", shape=list(shape),
                     dtype=dtype, lod_level=lod, append_batch_size=False)
        feed_vars.append(v)
    reader = PyReader(feed_vars, capacity,
                      use_double_buffer=use_double_buffer)
    return reader, feed_vars
