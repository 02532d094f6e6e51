"""PyReader / DataLoader: python generators → prefetched device feeds.

Reference analog: python/paddle/fluid/reader.py (PyReader:47) — a python
generator feeds a C++ `LoDTensorBlockingQueue` consumed by a `read` op, with
`buffered_reader` double-buffering H2D copies on a CUDA stream
(operators/reader/buffered_reader.cc).

TPU-native redesign: the compiled XLA step consumes plain device arrays, so
the reader pipeline is a host-side bounded queue (the blocking-queue analog)
filled by a background thread, plus a put-ahead stage that issues
`jax.device_put` for the *next* batch while the current step runs —
host→device transfer overlaps device compute exactly like the reference's
double-buffer, but via XLA's async dispatch instead of explicit streams.
"""

from __future__ import annotations

import queue
import threading

import numpy as np

from . import framework
from .data_feeder import DataFeeder

__all__ = ["PyReader", "DataLoader"]


class _EndOfEpoch:
    pass


class PyReader:
    """Iterable reader bound to a list of feed vars.

    with decorate_sample_list_generator(reader_creator): each item from the
    creator is a *batch* (list of sample tuples) converted via DataFeeder.
    with decorate_batch_generator: each item is already a feed dict or a
    tuple of arrays.
    """

    def __init__(self, feed_list=None, capacity=4, use_double_buffer=True,
                 iterable=True, return_list=False):
        self.feed_list = feed_list or []
        self.capacity = max(2, int(capacity))
        self.use_double_buffer = use_double_buffer
        self.iterable = iterable
        self.return_list = return_list
        self._creator = None  # zero-arg callable → iterator of feed dicts
        self._started = False
        self._queue = None
        self._thread = None

    # -- decoration ----------------------------------------------------------
    def decorate_sample_list_generator(self, reader, places=None):
        feeder = DataFeeder(self.feed_list)

        def creator():
            for batch in reader():
                yield feeder.feed(batch)

        self._creator = creator
        return self

    def decorate_batch_generator(self, reader, places=None):
        names = [v.name if not isinstance(v, str) else v for v in self.feed_list]

        def creator():
            for item in reader():
                if isinstance(item, dict):
                    yield item
                else:
                    arrs = item if isinstance(item, (list, tuple)) else (item,)
                    yield dict(zip(names, [np.asarray(a) for a in arrs]))

        self._creator = creator
        return self

    def decorate_sample_generator(self, sample_generator, batch_size,
                                  drop_last=True, places=None):
        """Reference signature: a per-*sample* generator + explicit batch_size
        (reference reader.py decorate_sample_generator)."""
        from .. import reader as _decorators

        return self.decorate_sample_list_generator(
            _decorators.batch(sample_generator, batch_size, drop_last=drop_last),
            places=places)

    # -- iteration -----------------------------------------------------------
    def _put_ahead(self, feed):
        """Issue async H2D for every array in the feed (device put-ahead)."""
        if not self.use_double_buffer:
            return feed
        import jax

        dev = jax.devices()[0]
        return {k: jax.device_put(v, dev) for k, v in feed.items()}

    def __call__(self):
        return self.__iter__()

    def __iter__(self):
        assert self._creator is not None, (
            "PyReader not decorated: call decorate_sample_list_generator or "
            "decorate_batch_generator first")
        q: queue.Queue = queue.Queue(maxsize=self.capacity)
        stop = threading.Event()
        error = []

        def put(item):
            """Bounded put that gives up when the consumer is gone — an
            abandoned iteration must not leave this thread blocked forever."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def fill():
            try:
                for feed in self._creator():
                    if not put(feed):
                        return
            except BaseException as e:  # re-raised in the consumer
                error.append(e)
            finally:
                put(_EndOfEpoch)

        t = threading.Thread(target=fill, daemon=True)
        t.start()
        try:
            pending = None
            while True:
                feed = q.get()
                if feed is _EndOfEpoch:
                    if error:
                        raise error[0]
                    break
                staged = self._put_ahead(feed)
                if pending is not None:
                    yield pending
                pending = staged
            if pending is not None:
                yield pending
        finally:
            stop.set()

    # -- non-iterable (start/reset) parity -----------------------------------
    def start(self):
        """Legacy non-iterable protocol: start() then exe.run() in a loop,
        catch EOFException, reset().  Our executor pulls feeds explicitly, so
        start() materializes the background iterator and `next_feed` hands
        batches to Executor.run via feed=reader.next_feed()."""
        self._iter = iter(self)
        self._started = True

    def next_feed(self):
        if not self._started:
            raise RuntimeError("PyReader.start() not called")
        try:
            return next(self._iter)
        except StopIteration:
            raise EOFError("end of epoch; call reset()")

    def reset(self):
        self._started = False
        self._iter = None


class DataLoader:
    """paddle.io.DataLoader-style factory (later-API parity)."""

    @staticmethod
    def from_generator(feed_list=None, capacity=4, use_double_buffer=True,
                       iterable=True, return_list=False, use_multiprocess=False):
        return PyReader(feed_list=feed_list, capacity=capacity,
                        use_double_buffer=use_double_buffer, iterable=iterable,
                        return_list=return_list)
