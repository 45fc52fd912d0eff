"""Out-of-core corpus streaming (DESIGN.md §10).

The paper's MapReduce pipeline never holds the collection in one memory: splits
stream through mappers. ``CorpusStream`` is that discipline for this repo —
a RE-ITERABLE stream of fixed-shape ``(chunk, dim)`` host blocks plus per-row
weights (1.0 real / 0.0 padding; only the last chunk is padded). Fixed shapes
mean every jitted per-chunk op compiles exactly once, and re-iterability means
multi-pass algorithms (two-pass tf-idf, K-Means iterations) recompute chunks
instead of storing them: peak residency is O(chunk·d), never O(n·d).

Consumers (core/kmeans, core/bkc, core/buckshot, core/sampling,
distrib/cluster, text/tfidf) duck-type on ``.chunks()`` / ``.n`` / ``.dim`` /
``.chunk`` and drive every pass through ONE streaming executor —
``run_pass`` below, a bounded double-buffered prefetcher (DESIGN.md §11): a
background thread regenerates chunk ``i+1`` while the caller's thread folds
chunk ``i`` on device, so host chunk generation and device compute overlap
instead of serializing. Prefetch is ON by default;
``REPRO_STREAM_PREFETCH=0`` (or ``prefetch=0``) turns it off for benches.
Core/distrib import the executor lazily inside their pass drivers, so the
layering stays acyclic. The resident paths are the one-chunk specialization:
``CorpusStream.from_array(x)`` yields the whole array as a single chunk, and
every streaming entry point run on it reproduces the resident oracle.

``from_array`` streams rows that already sit whole in host RAM, and they are
fixed for the stream's life: its blocks are built once, with the stream —
full chunks as views of ``x``, the short last chunk padded then — and every
pass yields those same read-only arrays. Sources that regenerate their rows
(``from_blocks``, ``from_texts``, ``concat_streams``, ``map``) pad their last
chunk in every pass, since a source may change between passes.
"""

from __future__ import annotations

import functools
import math
import os
import queue
import threading
from typing import Any, Callable, Iterable, Iterator, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation


class StreamChunk(NamedTuple):
    """One fixed-shape block of the corpus.

    ``x`` is a host numpy block for source streams; mapped streams (e.g.
    tf-idf pass 2) may carry device arrays — every consumer is jit-traced per
    chunk, so either works.
    """

    x: "np.ndarray"  # (chunk, dim) f32 rows (padding rows all-zero)
    w: "np.ndarray"  # (chunk,) f32, 1.0 real / 0.0 padding
    start: int  # global row index of this chunk's first row


def to_device(ch: StreamChunk) -> tuple[jax.Array, jax.Array]:
    """The chunk's rows and weights on the default device: the one place a
    fold moves a host chunk to the device, under the ``stream.h2d`` span."""
    with TraceAnnotation("stream.h2d"):
        return jnp.asarray(ch.x), jnp.asarray(ch.w)


def _pad_block(block: np.ndarray, chunk: int) -> tuple[np.ndarray, np.ndarray]:
    """``block`` (at most ``chunk`` rows) zero-padded to ``chunk`` rows, and
    its weights. A short block is padded under the ``stream.pad`` span."""
    r = block.shape[0]
    w = np.ones((r,), np.float32)
    if r < chunk:
        with TraceAnnotation("stream.pad"):
            block = np.concatenate(
                [block, np.zeros((chunk - r,) + block.shape[1:], block.dtype)]
            )
            w = np.concatenate([w, np.zeros((chunk - r,), np.float32)])
    return block, w


class CorpusStream:
    """Re-iterable stream of fixed-shape corpus chunks.

    ``make_chunks`` returns a FRESH iterator of ``StreamChunk`` on every call
    (each pass over the stream regenerates the data — the out-of-core
    contract). Use the constructors below instead of calling this directly.
    """

    def __init__(
        self,
        make_chunks: Callable[[], Iterator[StreamChunk]],
        *,
        n: int,
        dim: int,
        chunk: int,
    ):
        if chunk <= 0:
            raise ValueError(f"chunk must be positive, got {chunk}")
        self._make_chunks = make_chunks
        self.n = int(n)
        self.dim = int(dim)
        self.chunk = int(chunk)

    @property
    def n_chunks(self) -> int:
        return -(-self.n // self.chunk)

    def chunks(self) -> Iterator[StreamChunk]:
        """A fresh pass over the stream."""
        return self._make_chunks()

    # ------------------------------------------------------------ builders

    @staticmethod
    def from_blocks(
        make_blocks: Callable[[], Iterable[np.ndarray]],
        *,
        n: int,
        dim: int,
        chunk: int,
    ) -> "CorpusStream":
        """Wrap a factory of raw row blocks (≤ chunk rows each, ``n`` total;
        only the final block may be short). Pads each block to the fixed
        chunk shape and threads the weights. The contract is ENFORCED — a
        short mid-stream block would put pad rows in the middle of the
        logical row order, which every consumer's tail-trim would silently
        mis-read as real documents."""

        def gen() -> Iterator[StreamChunk]:
            start = 0
            short_at = -1
            for block in make_blocks():
                r = block.shape[0]
                if short_at >= 0:
                    raise ValueError(
                        f"short block ({short_at} rows) before the final one:"
                        f" only the last block may have < {chunk} rows"
                    )
                if r > chunk:
                    raise ValueError(f"block of {r} rows exceeds chunk {chunk}")
                if r < chunk:
                    short_at = r
                x, w = _pad_block(np.asarray(block, np.float32), chunk)
                yield StreamChunk(x=x, w=w, start=start)
                start += r
            if start != n:
                raise ValueError(f"stream yielded {start} rows, declared n={n}")

        return CorpusStream(gen, n=n, dim=dim, chunk=chunk)

    @staticmethod
    def from_array(x, *, chunk: int | None = None) -> "CorpusStream":
        """Resident array -> stream. ``chunk=None`` keeps the whole array as
        ONE chunk — the thin wrapper that makes every resident path a
        one-chunk specialization of the streaming path.

        The rows of ``x`` are fixed for the stream's life. The chunks are
        built once, here: full chunks are views of ``x`` sharing one weight
        vector of ones, and a short last chunk is padded now, not in every
        pass. Every pass yields these same arrays, all read-only, so a
        consumer that writes into a chunk fails instead of corrupting the
        next pass."""
        x = np.asarray(x, np.float32)
        n, dim = x.shape
        chunks: list[StreamChunk] = []
        # the constructor checks chunk before the loop below steps by it
        stream = CorpusStream(
            lambda: iter(chunks), n=n, dim=dim, chunk=n if chunk is None else chunk
        )
        c = stream.chunk
        ones = np.ones((c,), np.float32)
        for start in range(0, n, c):
            block, w = x[start : start + c], ones
            if block.shape[0] < c:
                block, w = _pad_block(block, c)
            for a in (block, w):
                a.setflags(write=False)
            chunks.append(StreamChunk(x=block, w=w, start=start))
        return stream

    @staticmethod
    def from_texts(
        texts: Sequence[str], *, dim: int = 2048, chunk: int = 4096
    ) -> "CorpusStream":
        """Chunked hashing ingest: texts -> (chunk, dim) unsigned hashed token
        count blocks (text/hashing.vectorize_chunks)."""
        from repro.text import hashing

        return CorpusStream.from_blocks(
            lambda: hashing.vectorize_chunks(texts, dim, chunk=chunk),
            n=len(texts),
            dim=dim,
            chunk=chunk,
        )

    # ------------------------------------------------------------ transforms

    def map(self, fn: Callable, *, dim: int | None = None) -> "CorpusStream":
        """Lazily transform every chunk: ``fn(x, w) -> x'`` (same row count;
        fn is applied per chunk on arrival, e.g. the tf-idf pass-2 rescale
        running jitted on device)."""

        def gen() -> Iterator[StreamChunk]:
            for ch in self.chunks():
                yield ch._replace(x=fn(ch.x, ch.w))

        return CorpusStream(
            gen, n=self.n, dim=self.dim if dim is None else dim, chunk=self.chunk
        )

    def concat(self, *others: "CorpusStream") -> "CorpusStream":
        """``concat_streams(self, *others)`` with this stream's chunk size."""
        return concat_streams(self, *others, chunk=self.chunk)

    def materialize(self) -> np.ndarray:
        """Concatenate the stream back into a resident (n, dim) array —
        tests/oracles only; defeats the point everywhere else."""
        parts = [np.asarray(ch.x) for ch in self.chunks()]
        if not parts:  # an n == 0 stream yields no chunks
            return np.zeros((0, self.dim), np.float32)
        return np.concatenate(parts, axis=0)[: self.n]


def concat_streams(*streams, chunk: int | None = None) -> "CorpusStream":
    """Concatenate row streams into ONE fixed-chunk stream.

    Naive back-to-back chunk iteration would violate the ``from_blocks``
    contract (each source's padded tail would land mid-stream), so chunks are
    re-packed: padding rows (w == 0) are stripped and real rows re-blocked at
    the target chunk size, preserving global row order. The result is
    re-iterable like any stream — each pass re-opens every source — and
    byte-identical to a single stream built over the concatenated rows with
    the same chunk size (same blocks, same padding), so every downstream fold
    (df, reservoir, K-Means) matches that oracle bit-for-bit.

    The service's refit path is the motivating consumer: the fitted base
    corpus (recomputed from texts) plus the already-vectorized ingested rows
    stream as one corpus without materializing either.
    """
    if not streams:
        raise ValueError("concat_streams needs at least one stream")
    dims = {s.dim for s in streams}
    if len(dims) != 1:
        raise ValueError(f"streams disagree on dim: {sorted(dims)}")
    dim = dims.pop()
    chunk = int(chunk if chunk is not None else streams[0].chunk)
    n = sum(s.n for s in streams)

    def blocks() -> Iterator[np.ndarray]:
        buf: list[np.ndarray] = []
        have = 0
        for s in streams:
            for ch in s.chunks():
                w = np.asarray(ch.w)
                rows = np.asarray(ch.x, np.float32)[w > 0]
                if rows.shape[0] == 0:
                    continue
                buf.append(rows)
                have += rows.shape[0]
                while have >= chunk:
                    block = buf[0] if len(buf) == 1 else np.concatenate(buf)
                    yield block[:chunk]
                    rest = block[chunk:]
                    buf = [rest] if rest.shape[0] else []
                    have = rest.shape[0]
        if have:
            yield buf[0] if len(buf) == 1 else np.concatenate(buf)

    return CorpusStream.from_blocks(blocks, n=n, dim=dim, chunk=chunk)


# ------------------------------------------------------------------ executor
#
# THE streaming executor: every per-algorithm pass (core/kmeans._stream_pass,
# core/sampling.reservoir_sample_stream, text/tfidf.df_stream,
# distrib/cluster._fold_pass, ...) drives its chunks through run_pass, which
# wraps each fresh pass in a bounded double-buffered prefetcher: a background
# thread pulls chunk i+1 out of the source generator (host rng / hashing /
# mapped device dispatch) while the caller's thread folds chunk i. The chunk
# ORDER and VALUES are untouched — prefetch on/off runs the identical compute
# graph, so results are bit-identical either way (tests/test_streaming.py).
#
# run_pass is also where the resilience layer attaches (DESIGN.md §12):
# producer-side faults retry per chunk with bounded backoff (RetryPolicy), a
# consumer-side watchdog turns a wedged producer into StreamTimeout, an
# optional Checkpointer snapshots (pass_id, chunk, carry) every N chunks so a
# SIGKILLed pass resumes mid-stream bit-identically, and guard="finite"
# raises GuardError with pass/chunk attribution the moment NaN/Inf reaches
# the carry. Deterministic fault injection (repro/testing/faults.py) hooks
# the producer right where real faults would occur.
#
# The executor names its host time in the profiler's trace with fixed-name
# spans (jax.profiler.TraceAnnotation; about half a microsecond each while no
# profiler runs): ``stream.pass`` around a pass, ``stream.wait`` around each
# take from the source on the consumer thread, ``stream.produce`` around each
# pull from the source on the producer thread (inline when prefetch is off),
# ``stream.h2d`` in ``to_device``, ``stream.sync`` where a driver blocks on a
# device result, and ``stream.pad`` where ``_pad_block`` pads a short block
# (once per ``from_array`` stream, once per pass for the other sources).


class _Raise:
    """Producer-side exception, carried through the queue and re-raised on
    the consumer thread (the from_blocks contract checks must surface)."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


_END = object()  # producer-exhausted sentinel


class _PrefetchIter:
    """Iterator over ``source`` with up to ``depth`` items produced ahead by
    a daemon thread. ``close()`` stops the producer early (abandoned pass).

    ``timeout`` arms the consumer-side watchdog: if the producer goes silent
    past the deadline, ``__next__`` raises ``queue.Empty`` (run_pass maps it
    to ``StreamTimeout`` with pass/chunk attribution) instead of blocking the
    pass forever behind a wedged generator."""

    def __init__(self, source: Iterator[Any], depth: int, *, timeout: float | None = None):
        self._q: "queue.Queue[Any]" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._done = False
        self._timeout = timeout
        self._thread = threading.Thread(
            target=self._produce, args=(source,), daemon=True,
            name="corpus-stream-prefetch",
        )
        self._thread.start()

    def _produce(self, source: Iterator[Any]) -> None:
        try:
            for item in _produced(source):
                if not self._put(item):
                    return  # consumer closed the pass
            self._put(_END)
        except BaseException as e:  # noqa: BLE001 — forwarded, not swallowed
            self._put(_Raise(e))

    def _put(self, item: Any) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def __iter__(self) -> "_PrefetchIter":
        return self

    def __next__(self) -> Any:
        if self._done:
            raise StopIteration
        # watchdog: queue.Empty escapes to run_pass, which owns attribution
        item = self._q.get(timeout=self._timeout)
        if item is _END:
            self._done = True
            self._thread.join()
            raise StopIteration
        if isinstance(item, _Raise):
            self._done = True
            self._thread.join()
            raise item.exc
        return item

    def close(self) -> None:
        """Stop the producer without draining the pass (early exit)."""
        if self._done:
            return
        self._done = True
        self._stop.set()
        while True:  # unblock a producer stuck in put()
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)

    def __del__(self) -> None:  # pragma: no cover — GC safety net
        try:
            self.close()
        except Exception:
            pass


def _produced(source: Iterator[Any]) -> Iterator[Any]:
    """``source``'s items, each pulled under a ``stream.produce`` span.
    Closing this generator closes ``source``."""
    try:
        while True:
            with TraceAnnotation("stream.produce"):
                try:
                    item = next(source)
                except StopIteration:
                    return
            yield item
    finally:
        close = getattr(source, "close", None)
        if close is not None:
            close()


def _resolve_prefetch(prefetch: Any) -> int:
    """Prefetch depth: explicit arg wins, else ``REPRO_STREAM_PREFETCH``
    (unset -> 2, the double buffer; 0/'off' disables — the bench switch)."""
    if prefetch is None:
        env = os.environ.get("REPRO_STREAM_PREFETCH", "").strip().lower()
        if env in ("", "on", "true"):
            return 2
        if env in ("off", "false"):
            return 0
        try:
            return max(0, int(env))
        except ValueError:
            raise ValueError(
                f"REPRO_STREAM_PREFETCH={env!r}: expected an integer depth"
                " (0 disables) or on/true/off/false"
            ) from None
    if prefetch is True:
        return 2
    if prefetch is False:
        return 0
    return max(0, int(prefetch))


def iter_chunks(stream, *, prefetch: Any = None) -> Iterator[StreamChunk]:
    """A fresh prefetched pass over any ``.chunks()`` duck-typed stream.

    Re-iteration semantics are the stream's own: each call opens a NEW pass
    (fresh generator, fresh prefetch thread), so multi-pass algorithms see
    fresh chunks and never an exhausted iterator."""
    it = stream.chunks()
    depth = _resolve_prefetch(prefetch)
    if depth <= 0:
        return it
    return _PrefetchIter(it, depth)


def _chunk_source(
    stream, pass_id: str, policy, start_chunk: int
) -> Iterator[tuple[int, StreamChunk]]:
    """Producer generator: ``(chunk_index, chunk)`` pairs with per-chunk
    retry and fault injection applied.

    Chunks below ``start_chunk`` (already folded into a restored checkpoint
    carry) are regenerated and discarded — recompute-over-store means replay
    is always legal, and the fold never sees them. A producer exception at
    chunk ``ci`` re-opens the pass (fresh ``stream.chunks()``), fast-forwards
    to ``ci``, and retries after exponential backoff; past the budget the
    original error surfaces (retries=0, the seed behavior) or a StreamFault
    with chunk attribution (retries>0, the cause chained)."""
    from repro.testing import faults as _faults

    def opened(skip: int) -> Iterator[tuple[int, StreamChunk]]:
        plan = _faults.active()
        it = stream.chunks()
        for ci, ch in enumerate(it):
            if plan is not None:
                ch = plan.on_chunk(pass_id, ci, ch)
            if ci < skip:
                continue
            yield ci, ch

    ci = start_chunk
    attempts = 0
    it = opened(start_chunk)
    while True:
        try:
            item = next(it)
        except StopIteration:
            return
        except Exception as e:
            attempts += 1
            if attempts > policy.retries:
                if policy.retries == 0:
                    raise  # fail-fast: surface the original error unwrapped
                from repro.resilience import StreamFault

                raise StreamFault(pass_id, ci, attempts, e) from e
            policy.sleep(attempts)
            it = opened(ci)  # replay up to the failed chunk, then retry it
        else:
            ci = item[0] + 1
            attempts = 0
            yield item


@functools.lru_cache(maxsize=256)
def _finite_reducer(shape: tuple, dtype: str):
    del shape, dtype  # cache key only: one compiled reducer per leaf shape
    return jax.jit(lambda a: jnp.all(jnp.isfinite(a)))


def _carry_finite(carry: Any, seen: set | None = None) -> bool:
    """All inexact array leaves of the carry are finite. Device leaves reduce
    to a scalar on device (one tiny compiled all-isfinite per leaf shape);
    only the scalar syncs to the host.

    ``seen`` memoizes verified HOST arrays by identity across folds: collected
    per-chunk output blocks accumulate in carry lists but never mutate, so
    re-scanning them every chunk would make the guard O(chunks²) over a pass.
    Device leaves are always re-checked (the running accumulators DO change)."""
    for leaf in jax.tree_util.tree_leaves(carry):
        if isinstance(leaf, jax.Array):
            if jnp_issubdtype_inexact(leaf.dtype) and not bool(
                _finite_reducer(tuple(leaf.shape), str(leaf.dtype))(leaf)
            ):
                return False
        elif isinstance(leaf, np.ndarray):
            if seen is not None and id(leaf) in seen:
                continue
            if jnp_issubdtype_inexact(leaf.dtype) and not np.all(np.isfinite(leaf)):
                return False
            if seen is not None:
                seen.add(id(leaf))
        elif isinstance(leaf, float):
            if not math.isfinite(leaf):
                return False
    return True


def jnp_issubdtype_inexact(dtype) -> bool:
    return np.issubdtype(np.dtype(dtype), np.inexact)


def run_pass(
    stream,
    fold: Callable,
    carry: Any,
    *,
    prefetch: Any = None,
    pass_id: str = "pass",
    checkpoint: Any = None,
    retry: Any = None,
    timeout: Any = None,
    guard: Any = None,
    meta: dict | None = None,
    restore_carry: Callable | None = None,
):
    """One full pass over ``stream``: ``fold(carry, chunk, index) -> carry``.

    ``fold`` runs on the caller's thread (device dispatch + any host-side
    collection) while the prefetcher's background thread regenerates the
    next chunk — the host chunk-generation and device fold of consecutive
    chunks overlap. Returns the final carry (the initial ``carry`` for an
    n == 0 stream). The pass is closed on any exit, so a fold that raises
    does not leave a producer thread spinning.

    Resilience (all opt-in; defaults preserve the seed behavior exactly):
      pass_id     names the pass for checkpoint keys and error attribution.
      checkpoint  a resilience.Checkpointer: snapshots (chunk, carry) every
                  ``checkpoint.every`` folded chunks; on entry a matching
                  snapshot restores the carry and the producer skips already-
                  folded chunks, so a killed pass resumes bit-identically.
                  The snapshot is deleted when the pass completes.
      retry       RetryPolicy | int budget | None (env REPRO_STREAM_RETRIES;
                  default 0 = fail fast with the original exception).
      timeout     producer watchdog seconds (env REPRO_STREAM_TIMEOUT;
                  default off) -> StreamTimeout instead of a hang. Forces the
                  source through a (depth >= 1) prefetch thread so the
                  deadline can be enforced from the consumer side.
      guard       'finite' (env REPRO_STREAM_GUARD) checks every inexact
                  carry leaf after each fold -> GuardError(pass, chunk).
      meta        extra snapshot-validity keys (stream signature is always
                  included): a snapshot folded under different centers or rng
                  key must not resume this pass.
      restore_carry  host-snapshot -> live carry override (distributed folds
                  re-shard restored leaves onto their mesh).
    """
    from repro.resilience import policy as _policy

    policy = _policy.RetryPolicy.resolve(retry)
    wd = _policy.resolve_timeout(timeout)
    guard = _policy.resolve_guard(guard)

    start_chunk = 0
    fingerprint = None
    full_meta = None
    if checkpoint is not None:
        from repro.resilience import (
            carry_fingerprint,
            carry_from_host,
            carry_to_host,
        )

        fingerprint = carry_fingerprint(carry)
        full_meta = {
            "stream": {"n": stream.n, "dim": stream.dim, "chunk": stream.chunk},
            **(meta or {}),
        }
        snap = checkpoint.load(pass_id, fingerprint=fingerprint, meta=full_meta)
        if snap is not None:
            restore = restore_carry or carry_from_host
            carry = restore(snap["carry"])
            start_chunk = snap["chunk"]

    source = _chunk_source(stream, pass_id, policy, start_chunk)
    depth = _resolve_prefetch(prefetch)
    if wd is not None:
        depth = max(depth, 1)  # the watchdog needs the producer on a thread
    expect = start_chunk
    guard_seen: set | None = set() if guard == "finite" else None
    with TraceAnnotation("stream.pass"):
        it: Any = (
            _PrefetchIter(source, depth, timeout=wd)
            if depth > 0
            else _produced(source)
        )
        try:
            while True:
                try:
                    with TraceAnnotation("stream.wait"):
                        item = next(it)
                except StopIteration:
                    break
                except queue.Empty:
                    from repro.resilience import StreamTimeout

                    raise StreamTimeout(pass_id, expect, wd) from None
                ci, ch = item
                carry = fold(carry, ch, ci)
                if guard == "finite" and not _carry_finite(carry, guard_seen):
                    from repro.resilience import GuardError

                    raise GuardError(pass_id, ci)
                expect = ci + 1
                if (
                    checkpoint is not None
                    and expect % checkpoint.every == 0
                    and expect < stream.n_chunks
                ):
                    checkpoint.save(
                        pass_id,
                        chunk=expect,
                        carry_host=carry_to_host(carry),
                        fingerprint=fingerprint,
                        meta=full_meta,
                    )
        finally:
            it.close()
    if checkpoint is not None:
        checkpoint.delete(pass_id)  # pass completed: snapshot is stale
    return carry
