"""DataLoader / GeneratorLoader / PyReader: host data pipeline.

Parity surface: /root/reference/python/paddle/fluid/reader.py
(DataLoader:112, from_generator:372, GeneratorLoader:953, PyReader:1213)
and the C++ reader ops (operators/reader/buffered_reader.cc — async
double buffering).

TPU-native design: the reference pushes LoDTensors into a C++ blocking
queue consumed by read ops inside the program. Here feeding is explicit
(Executor.run(feed=...)), so the loader's job is pipelining: a background
thread drains the user generator into a bounded queue (double buffering)
while the previous step runs on device; batches come out as feed dicts.
The file-backed path is the native C++ feed (paddle_tpu/native)."""
from __future__ import annotations

import queue
import threading
from typing import Callable, List, Optional, Sequence

import numpy as np

from . import framework
from .profiler import RecordEvent

# ---------------------------------------------------------------------------
# data-pipeline instrumentation (ISSUE 15): break the opaque data_wait
# scalar into stages. Every stage is one RecordEvent (fluid/profiler.py),
# the executor's span: a TraceAnnotation in any profiler session, on the
# line of the thread that runs it (the consumer's spans on the training
# loop's, the producer's on its own), and the stage's histogram while a
# telemetry consumer exists (PADDLE_METRICS_PATH sink or the
# PADDLE_GOODPUT ledger) — flag-off the histograms are not created and
# the produced batches are bit-identical either way.
#
#   span                     histogram
#   DataLoader::produce      data_fetch_ms    pulling one item/batch from
#                                             the user's reader or indexing
#                                             the dataset (producer side)
#   DataLoader::collate      data_decode_ms   collate_fn over the fetched
#                                             samples (DataLoader)
#   DataLoader::stack        data_batch_ms    stacking samples into batch
#                                             arrays (_stack_samples)
#   DataLoader::next                          the consumer's side of one
#                                             batch: the wait on the
#                                             prefetch queue, and inside it
#     DataLoader::materialize data_h2d_ms     host array materialization
#                                             (np.asarray before the feed;
#                                             the device transfer itself is
#                                             the executor's dispatch)
#   data_queue_depth prefetch queue depth sampled at each consumer get
#                    (0 = the consumer is starved, the producer is the
#                    bottleneck; capacity = producer ahead, healthy)
#
# No span stays open across a `yield`: a generator's span would otherwise
# hold whatever its consumer does between two batches.
# ---------------------------------------------------------------------------

def _pipeline_armed() -> bool:
    from ..telemetry import goodput, sink

    return sink.enabled() or goodput.enabled()


_STAGES = ("fetch", "decode", "batch", "h2d")


def _stage_obs() -> dict:
    """The per-stage histograms, each None when no consumer is armed.
    Resolved from the registry per call (get-or-create dict lookups) so
    a registry reset() never strands observations on orphaned metrics;
    callers hold the returned dict for the whole epoch."""
    if not _pipeline_armed():
        return dict.fromkeys(_STAGES)
    from ..telemetry import get_registry

    reg = get_registry()
    return dict(
        fetch=reg.histogram(
            "data_fetch_ms",
            help="input pipeline: user reader / dataset fetch"),
        decode=reg.histogram(
            "data_decode_ms",
            help="input pipeline: collate_fn (decode) time"),
        batch=reg.histogram(
            "data_batch_ms",
            help="input pipeline: sample stacking into batches"),
        h2d=reg.histogram(
            "data_h2d_ms",
            help="input pipeline: host batch-array materialization"),
    )


def _queue_gauge(loader: str):
    """Prefetch queue-depth gauge for one loader flavor, or None."""
    if not _pipeline_armed():
        return None
    from ..telemetry import get_registry

    return get_registry().gauge(
        "data_queue_depth",
        help="prefetch queue depth at consumer get (0 = starved)",
        loader=loader)


def _produced(it, hist):
    """Wrap an iterator so each next() is a DataLoader::produce span
    (fetch stage), closed before the item is handed on."""
    it = iter(it)
    while True:
        with RecordEvent("DataLoader::produce", hist):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


def _materialized(item, hist):
    with RecordEvent("DataLoader::materialize", hist):
        return [np.asarray(a) for a in item]


def _generator_producer(q, reader):
    """Child body for GeneratorLoader.use_multiprocess (module-level so
    spawn can pickle it by reference)."""
    try:
        for batch in reader():
            q.put([np.asarray(a) for a in batch])
        q.put(None)
    except Exception as e:  # noqa: BLE001 — shipped to parent
        q.put(("__error__", repr(e)))
    except KeyboardInterrupt:
        pass

_END = object()


class GeneratorLoader:
    """Reference reader.py:953. iterable mode only (the non-iterable
    start()/reset() protocol existed for in-program read ops, which the
    whole-block XLA executor does not need)."""

    def __init__(self, feed_list=None, capacity=64, iterable=True,
                 return_list=False, drop_last=True, use_multiprocess=False):
        self._feed_list = list(feed_list or [])
        self._names = [
            v.name if isinstance(v, framework.Variable) else str(v)
            for v in self._feed_list
        ]
        self._capacity = int(capacity)
        self._iterable = iterable
        self._return_list = return_list
        self._drop_last = drop_last
        # run the generator in a fork()ed child instead of a thread — the
        # reference DygraphGeneratorLoader's use_multiprocess (reader.py:660):
        # heavy Python preprocessing stops sharing the GIL with the trainer
        self._use_multiprocess = use_multiprocess
        self._batch_reader: Optional[Callable] = None

    # -- generator flavors (reference from_generator API) ----------------
    def set_sample_generator(self, reader, batch_size, drop_last=True, places=None):
        def batch_reader():
            batch = []
            for sample in reader():
                if not isinstance(sample, (tuple, list)):
                    sample = (sample,)
                batch.append(sample)
                if len(batch) == batch_size:
                    yield _stack_samples(batch)
                    batch = []
            if batch and not drop_last:
                yield _stack_samples(batch)

        self._batch_reader = batch_reader
        return self

    def set_sample_list_generator(self, reader, places=None):
        def batch_reader():
            for sample_list in reader():
                yield _stack_samples(sample_list)

        self._batch_reader = batch_reader
        return self

    def set_batch_generator(self, reader, places=None):
        self._batch_reader = reader
        return self

    # -- iteration with background prefetch ------------------------------
    def __iter__(self):
        if self._batch_reader is None:
            raise RuntimeError(
                "DataLoader: call set_sample_generator / "
                "set_sample_list_generator / set_batch_generator first"
            )
        if self._use_multiprocess:
            yield from self._iter_multiprocess()
            return
        obs = _stage_obs()
        depth = _queue_gauge("generator")
        q: queue.Queue = queue.Queue(maxsize=self._capacity)
        err: List[BaseException] = []
        stop = threading.Event()

        def _put(item) -> bool:
            # timed put + stop flag: when the consumer abandons iteration
            # (break / early stop) the worker exits instead of blocking on
            # a full queue forever (one leaked thread per abandoned epoch)
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                # fetch stage: each batch pulled from the user's reader,
                # a span of the producer thread
                for batch in _produced(self._batch_reader(), obs["fetch"]):
                    if not _put(batch):
                        return
            except BaseException as e:  # noqa: BLE001 — re-raised on consumer
                err.append(e)
            finally:
                _put(_END)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        try:
            while True:
                if depth is not None:
                    depth.set(q.qsize())
                with RecordEvent("DataLoader::next"):
                    item = q.get()
                    if item is _END:
                        if err:
                            raise err[0]
                        return
                    arrays = _materialized(item, obs["h2d"])
                if self._return_list or not self._names:
                    yield arrays
                else:
                    yield dict(zip(self._names, arrays))
        finally:
            stop.set()

    def _iter_multiprocess(self):
        """One off-process producer streaming batches over an mp queue.
        Spawn when the reader pickles (fork under the multithreaded JAX
        runtime risks child deadlock); fork otherwise."""
        import multiprocessing as mp

        from .dataloader import _child_env, _spawn_safe

        if _spawn_safe(self._batch_reader, None, None):
            method = "spawn"
        else:
            import warnings

            warnings.warn(
                "GeneratorLoader: the batch reader is not picklable; "
                "falling back to fork() for the producer process, which "
                "can deadlock under the multithreaded JAX runtime — use a "
                "module-level reader function to enable spawn",
                RuntimeWarning, stacklevel=3,
            )
            method = "fork"
        ctx = mp.get_context(method)
        q = ctx.Queue(maxsize=self._capacity)

        p = ctx.Process(target=_generator_producer,
                        args=(q, self._batch_reader), daemon=True)
        with _child_env():
            p.start()
        try:
            while True:
                try:
                    item = q.get(timeout=1.0)
                except queue.Empty:
                    if not p.is_alive():
                        raise RuntimeError(
                            "DataLoader: generator worker process died"
                        ) from None
                    continue
                if item is None:
                    return
                if isinstance(item, tuple) and len(item) == 2 and item[0] == "__error__":
                    raise RuntimeError(f"DataLoader worker failed: {item[1]}")
                arrays = [np.asarray(a) for a in item]
                if self._return_list or not self._names:
                    yield arrays
                else:
                    yield dict(zip(self._names, arrays))
        finally:
            if p.is_alive():
                p.terminate()
            p.join(timeout=5)
            q.cancel_join_thread()
            q.close()


def _buffered_gen(gen, capacity=2, depth_gauge=None):
    """Background-thread prefetch (double buffering) with abandon-safe
    shutdown: a stop flag checked by the timed put releases the worker
    when the consumer breaks early. `depth_gauge` (ISSUE 15) samples
    the queue depth at every consumer get."""
    q: queue.Queue = queue.Queue(maxsize=capacity)
    err: List[BaseException] = []
    stop = threading.Event()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for item in gen:
                if not _put(item):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised on consumer
            err.append(e)
        finally:
            _put(_END)

    threading.Thread(target=worker, daemon=True).start()
    try:
        while True:
            if depth_gauge is not None:
                depth_gauge.set(q.qsize())
            item = q.get()
            if item is _END:
                if err:
                    raise err[0]
                return
            yield item
    finally:
        stop.set()


def _stack_samples(samples):
    with RecordEvent("DataLoader::stack", _stage_obs()["batch"]):
        ncol = len(samples[0])
        return [np.stack([np.asarray(s[i]) for s in samples])
                for i in range(ncol)]


class DataLoader:
    """Reference reader.py:112: map-style Dataset + BatchSampler +
    multiprocess workers (fluid/dataloader/), plus the from_generator /
    from_dataset constructors.

    num_workers=0 loads inline; num_workers=N forks N worker processes
    that collate index-batches in parallel — submission order is restored,
    so N>0 yields the identical batch sequence (dataloader/__init__.py).
    """

    def __init__(self, dataset, feed_list=None, places=None, return_list=False,
                 batch_sampler=None, batch_size=1, shuffle=False,
                 drop_last=False, collate_fn=None, num_workers=0,
                 use_buffer_reader=True, use_shared_memory=False, timeout=0,
                 worker_init_fn=None, multiprocessing_context=None):
        from .dataloader import BatchSampler, IterableDataset, default_collate_fn

        self._dataset = dataset
        self._names = [
            v.name if isinstance(v, framework.Variable) else str(v)
            for v in (feed_list or [])
        ]
        self._return_list = return_list or not self._names
        self._iterable_ds = isinstance(dataset, IterableDataset)
        if self._iterable_ds:
            if num_workers > 0:
                raise ValueError(
                    "IterableDataset cannot be index-sharded across workers; "
                    "use num_workers=0 (or GeneratorLoader for off-process "
                    "streaming)"
                )
            if batch_sampler is not None:
                raise ValueError("IterableDataset does not take a batch_sampler")
            self._batch_size, self._drop_last = int(batch_size), drop_last
            self._batch_sampler = None
        else:
            self._batch_sampler = batch_sampler or BatchSampler(
                dataset=dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last,
            )
        self._collate = collate_fn or default_collate_fn
        self._num_workers = int(num_workers)
        self._use_buffer = use_buffer_reader
        self._timeout = timeout
        self._worker_init_fn = worker_init_fn
        self._mp_context = multiprocessing_context

    def __len__(self):
        if self._batch_sampler is None:
            raise TypeError("len() of an IterableDataset loader")
        return len(self._batch_sampler)

    def _raw_batches(self):
        obs = _stage_obs()

        def _collated(items):
            with RecordEvent("DataLoader::collate", obs["decode"]):
                return self._collate(items)

        if self._iterable_ds:
            buf = []
            for sample in _produced(self._dataset, obs["fetch"]):
                buf.append(sample)
                if len(buf) == self._batch_size:
                    yield _collated(buf)
                    buf = []
            if buf and not self._drop_last:
                yield _collated(buf)
            return
        batches = list(self._batch_sampler)
        if self._num_workers > 0:
            from .dataloader import _MultiprocessIter

            yield from _MultiprocessIter(
                self._dataset, batches, self._collate, self._num_workers,
                self._worker_init_fn, self._timeout,
                mp_context=self._mp_context,
            )
        else:
            for idx in batches:
                with RecordEvent("DataLoader::produce", obs["fetch"]):
                    items = [self._dataset[i] for i in idx]
                yield _collated(items)

    def __iter__(self):
        obs = _stage_obs()
        gen = self._raw_batches()
        if self._use_buffer and self._num_workers == 0:
            gen = _buffered_gen(gen, capacity=2,
                                depth_gauge=_queue_gauge("dataloader"))
        gen = iter(gen)
        while True:
            # the wait for the batch (the prefetch queue's get, or the
            # workers') runs inside next(gen), in this thread
            with RecordEvent("DataLoader::next"):
                try:
                    arrays = next(gen)
                except StopIteration:
                    return
                arrays = _materialized(arrays, obs["h2d"])
            yield arrays if self._return_list else dict(zip(self._names, arrays))

    @staticmethod
    def from_generator(feed_list=None, capacity=64, use_double_buffer=True,
                       iterable=True, return_list=False, use_multiprocess=False,
                       drop_last=True):
        return GeneratorLoader(
            feed_list=feed_list, capacity=capacity, iterable=iterable,
            return_list=return_list, drop_last=drop_last,
            use_multiprocess=use_multiprocess,
        )

    @staticmethod
    def from_dataset(dataset, places=None, drop_last=True):
        """Iterate a Dataset (fluid/dataset.py) as feed dicts."""
        return dataset._as_loader(drop_last=drop_last)


class PyReader:
    """Legacy wrapper (reference reader.py:1213): decorate_* map onto the
    GeneratorLoader flavors."""

    def __init__(self, feed_list=None, capacity=64, iterable=True,
                 return_list=False):
        self._loader = GeneratorLoader(feed_list, capacity, iterable, return_list)

    def decorate_sample_generator(self, sample_generator, batch_size,
                                  drop_last=True, places=None):
        self._loader.set_sample_generator(sample_generator, batch_size, drop_last)

    def decorate_sample_list_generator(self, reader, places=None):
        self._loader.set_sample_list_generator(reader)

    def decorate_batch_generator(self, reader, places=None):
        self._loader.set_batch_generator(reader)

    def __iter__(self):
        return iter(self._loader)
