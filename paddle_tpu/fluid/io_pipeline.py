"""Asynchronous double-buffered host->device input pipeline.

Reference: the C++ BufferedReader double-buffering H2D copies on a
dedicated CUDA stream (operators/reader/buffered_reader.cc:63-95) behind
`double_buffered_reader` / `buffered_reader` (python/paddle/reader/
decorator.py), fed by GeneratorLoader's LoDTensorBlockingQueue.

TPU-native realisation: a bounded background producer thread decodes batch
N+1 and dispatches its ``jax.device_put`` while step N computes, so the
host-decode + host->HBM transfer overlaps compute instead of preceding it
on the step's critical path (PERF.md "remaining lever": every banked bench
number so far feeds device-resident batches; real traffic pays the host
feed serially without this). ``jax.device_put`` is asynchronous — the
producer thread only pays enqueue cost, the copy itself overlaps the
running step — and the queue bound (``FLAGS_reader_buffer_size``, default
2 = classic double buffering) caps how much HBM prefetched batches pin.

Degradation is graceful: with no place (unit tests, host-only readers) or
no importable jax backend the feeder passes host batches through unchanged
— same thread overlap, no device staging.
"""

from __future__ import annotations

import queue
import threading

import numpy as np

from . import core
from . import flags as _flags
from . import profiler as _profiler
from ..observability import trace as _obs_trace

__all__ = ["DeviceFeedBatch", "DeviceFeeder", "buffer_size"]


def buffer_size():
    """Queue depth for the double-buffered feed (FLAGS_reader_buffer_size,
    clamped to >= 1)."""
    try:
        return max(int(_flags.get_flag("reader_buffer_size", 2)), 1)
    except (TypeError, ValueError):
        return 2


class DeviceFeedBatch(dict):
    """A feed dict whose values are ALREADY committed device arrays.

    ``device`` is the jax Device every value was put on, or None when any
    value could not be staged (LoDTensor feeds keep their host form so the
    executor can extract sequence-length companions). The executor's feed
    fast lane keys off a non-None ``device``: it skips the per-value
    re-``device_put``/``np.asarray`` normalization walk and the LoD scan
    entirely."""

    __slots__ = ("device",)

    def __init__(self, mapping, device=None):
        super().__init__(mapping)
        self.device = device


class _Sentinel(object):
    __slots__ = ()


_END = _Sentinel()


def resolve_device(place):
    """Place -> jax Device; None only when no place was given (the caller
    then hands out host batches). A place that names no device raises."""
    if isinstance(place, (list, tuple)):
        place = place[0] if place else None
    if place is None:
        return None
    return core.get_jax_device(place)


class DeviceFeeder(object):
    """Bounded background producer over an iterable of batches.

    The producer thread pulls from ``source`` (host decode runs there, off
    the consumer's critical path), stages each dict batch onto ``place``'s
    device via async ``jax.device_put``, and parks at most ``depth``
    staged batches in a queue. The consumer iterates; order is preserved;
    a producer exception re-raises at the consumer's next pull; ``close()``
    (also called on normal exhaustion) shuts the thread down without
    leaking it."""

    def __init__(self, source, place=None, depth=None, stage=True):
        self._source = source
        self._device = resolve_device(place) if stage else None
        if depth is None:
            depth = buffer_size() if self._device is not None else 8
        self._q = queue.Queue(maxsize=max(int(depth), 1))
        self._stop = threading.Event()
        self._error = []
        self._done = False
        self._thread = threading.Thread(
            target=self._produce, name="io_pipeline_feeder", daemon=True
        )
        self._thread.start()

    # -- producer side --
    def _stage(self, batch):
        dev = self._device
        if dev is None or not isinstance(batch, dict):
            return batch
        staged = {}
        all_on_device = True
        for k, v in batch.items():
            if isinstance(v, core.LoDTensor):
                # LoD batches keep their host form: the executor derives
                # the @SEQ_LEN companion feeds from the offset stack
                staged[k] = v
                all_on_device = False
                continue
            try:
                import jax

                if isinstance(v, jax.Array):
                    staged[k] = jax.device_put(v, dev)
                else:
                    # same np.asarray -> device_put chain the executor
                    # would run per step; here it runs one batch AHEAD,
                    # on this thread, overlapping the current step
                    staged[k] = jax.device_put(np.asarray(v), dev)
            except Exception:
                staged[k] = v
                all_on_device = False
        batch = DeviceFeedBatch(
            staged, device=dev if all_on_device else None
        )
        if all_on_device:
            _profiler.bump_counter("io_pipeline_h2d_batches")
        return batch

    def _put(self, item):
        """Bounded put that re-checks stop so an aborted consumer can never
        strand the producer on a full queue. Returns False when stopped."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self):
        from ..testing import chaos as _chaos

        try:
            for batch in self._source:
                if self._stop.is_set():
                    break
                # the feed-path span covers chaos delay + staging so a
                # degraded input host is visible on the producer thread's
                # trace row (overlap vs the consumer's executor_run row
                # is exactly what the timeline exists to show)
                with _obs_trace.span("feed_stage", cat="feed"):
                    # fault-injection point: chaos slow_feed_ms models a
                    # degraded input host on the producer thread (no-op
                    # when disarmed), so feed-stall behavior is testable
                    _chaos.maybe_slow_feed()
                    staged = self._stage(batch)
                if not self._put(staged):
                    break
        except BaseException as e:  # surfaced at the consumer's next pull
            self._error.append(e)
        finally:
            self._put(_END)
            close = getattr(self._source, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    pass

    # -- consumer side --
    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        while True:
            if self._stop.is_set():
                self._done = True
                raise StopIteration
            try:
                item = self._q.get(timeout=0.5)
                break
            except queue.Empty:
                if not self._thread.is_alive() and self._q.empty():
                    # producer died without managing to park the sentinel
                    self._done = True
                    if self._error:
                        raise self._error[0]
                    raise StopIteration
        if isinstance(item, _Sentinel):
            self._done = True
            self.close()
            if self._error:
                raise self._error[0]
            raise StopIteration
        return item

    def close(self, join_timeout=5.0):
        """Idempotent shutdown: stop the producer, drain the queue so a
        blocked put unsticks, and join the thread."""
        self._stop.set()
        self._done = True
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        if self._thread.is_alive():
            self._thread.join(timeout=join_timeout)

    @property
    def device(self):
        return self._device
