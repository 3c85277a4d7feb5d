"""Batch iteration with prefetch + HBM staging.

Parity: python/ray/data/iterator.py + _internal/block_batching/ (format
conversion, prefetching). TPU-native: ``device_put`` stages the next
batch into device memory while the current one is being consumed
(double buffering over the host->HBM DMA), which is how a training loop
hides input latency behind compute.

Two ``tracing.phase`` spans say where a batch's time goes:
``data.stage_batch`` in the prefetch thread (get -> concat -> slice ->
contiguous -> ``device_put`` of one batch) and ``data.next_batch`` on
the consumer's side (its wait on the queue). Their seconds and counts
add up in ``stats`` (``Dataset.iter_stats``), one count a batch.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Iterator, List, Optional

import numpy as np

from ray_tpu.util.tracing import PhaseStats, phase

from .block import Block, BlockAccessor

_SENTINEL = object()


def _rebatch(block_refs, batch_size: Optional[int], drop_last: bool) -> Iterator[Block]:
    """Coalesce/slice streamed blocks into exact-size batches."""
    import ray_tpu

    buf: List[Block] = []
    buffered = 0
    for ref in block_refs:
        block = ray_tpu.get(ref)
        n = BlockAccessor.for_block(block).num_rows()
        if n == 0:
            continue
        if batch_size is None:
            yield block
            continue
        buf.append(block)
        buffered += n
        while buffered >= batch_size:
            merged = BlockAccessor.concat(buf)
            acc = BlockAccessor.for_block(merged)
            yield acc.slice(0, batch_size)
            rest = acc.slice(batch_size, acc.num_rows())
            buf = [rest]
            buffered = BlockAccessor.for_block(rest).num_rows()
    if batch_size is None:
        return
    if buffered and not drop_last:
        merged = BlockAccessor.concat(buf)
        if BlockAccessor.for_block(merged).num_rows():
            yield merged


def iter_batches(
    block_refs,
    *,
    batch_size: Optional[int],
    batch_format: str,
    prefetch_batches: int,
    drop_last: bool,
    device_put: Any = None,
    stats: Optional[PhaseStats] = None,
) -> Iterator[Any]:
    def timed(name: str, fn):
        """fn() under the span ``name``; counted unless it returns the
        sentinel (the probe that finds the data exhausted is no batch)."""
        t0 = time.perf_counter()
        with phase(name):
            item = fn()
        if stats is not None and item is not _SENTINEL:
            stats.add(name, time.perf_counter() - t0)
        return item

    blocks = _rebatch(block_refs, batch_size, drop_last)

    def stage():
        block = next(blocks, _SENTINEL)
        if block is _SENTINEL:
            return _SENTINEL
        batch = BlockAccessor.for_block(block).to_batch(batch_format)
        if device_put is not None:
            import jax

            batch = jax.tree.map(
                lambda v: jax.device_put(np.ascontiguousarray(v), device_put)
                if isinstance(v, np.ndarray) and v.dtype != object
                else v,
                batch,
            )
        return batch

    def produce() -> Iterator[Any]:
        while (batch := timed("data.stage_batch", stage)) is not _SENTINEL:
            yield batch

    if prefetch_batches <= 0:
        yield from produce()
        return

    q: "queue.Queue" = queue.Queue(maxsize=prefetch_batches)
    err: List[BaseException] = []
    stop = threading.Event()

    def worker():
        try:
            for item in produce():
                # bounded put that aborts if the consumer abandoned the
                # iterator (otherwise this thread would pin prefetched
                # HBM batches for the life of the process)
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.2)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
        except BaseException as e:  # surfaced on the consumer side
            err.append(e)
        finally:
            while not stop.is_set():  # consumer still listening
                try:
                    q.put(_SENTINEL, timeout=0.2)
                    break
                except queue.Full:
                    continue

    t = threading.Thread(target=worker, daemon=True, name="data-prefetch")
    t.start()
    try:
        while (item := timed("data.next_batch", q.get)) is not _SENTINEL:
            yield item
        if err:
            raise err[0]
    finally:
        stop.set()
