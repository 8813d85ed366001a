"""Host input pipeline: file/stream sources -> decoded, sharded, batched
numpy feed with device prefetch.

Re-creates the reference's tf.data chain (ps:112-169, hvd:104-161):
glob + file-list shuffle (ps:418-432), record-level ``shard`` per the 4-way
matrix (data/sharding.py), ``batch(drop_remainder=True)`` then **vectorized**
decode of the whole batch (the "vectorized-map" trick, hvd:151-153), epoch
repeat, and prefetch — with tf.data's C++ runtime replaced by a reader
thread + double-buffered ``jax.device_put`` (deepfm_tpu/native's C++ reader
slots in as the record source when built).

Unlike tf.data's lazy graphs, the pipeline here is plain Python iterators
over numpy — simple, inspectable, and fast enough once decode is native;
the TPU never waits on the host thanks to the prefetch depth.
"""

from __future__ import annotations

import glob as globlib
import itertools
import os
import queue
import random
import threading
from typing import Callable, Iterable, Iterator

import numpy as np

from ..core.config import DataConfig
from ..obs.trace import get_span_recorder
from .example_proto import decode_ctr_batch
from .object_store import get_store, is_url, open_source
from .sharding import ShardDecision, WorkerTopology, shard_plan
from .tfrecord import read_records


def discover_files(
    data_dir: str, patterns: Iterable[str] = ("tr", "train"), *, shuffle: bool = True,
    seed: int | None = None,
) -> list[str]:
    """Recursive glob for ``<pattern>*.tfrecords`` (the reference globs
    tr*/va*/te* recursively and shuffles the FILE list only, ps:418-432).

    ``data_dir`` may be an object-store URL (``http(s)://host/bucket/prefix``
    — the S3-channel capability, ps nb cell 4): listing goes through
    ListObjectsV2 with the same name-filter and deterministic seeded-shuffle
    semantics, so multi-host runs enumerate remote files identically."""
    files: list[str] = []
    if is_url(data_dir):
        base = data_dir.rstrip("/") + "/"
        for url in get_store().list_prefix(base):
            name = url.rsplit("/", 1)[-1]
            if any(
                name.startswith(pat) and name.endswith((".tfrecords", ".tfrecord"))
                for pat in patterns
            ):
                files.append(url)
    else:
        for pat in patterns:
            files.extend(
                globlib.glob(os.path.join(data_dir, "**", f"{pat}*.tfrecords"), recursive=True)
            )
            files.extend(
                globlib.glob(os.path.join(data_dir, "**", f"{pat}*.tfrecord"), recursive=True)
            )
    files = sorted(set(files))
    if shuffle:
        random.Random(seed).shuffle(files)
    return files


def record_stream(
    sources: Iterable[str | os.PathLike],
    *,
    decision: ShardDecision | None = None,
    verify_crc: bool = False,
) -> Iterator[bytes]:
    """Flatten files/FIFOs into one record stream, applying round-robin
    record sharding (``dataset.shard`` semantics: record i -> shard i % n)."""
    idx = 0
    n = decision.num_shards if decision else 1
    mine = decision.shard_index if decision else 0
    for src in sources:
        # object URLs stream through a live HTTP response (bounded memory,
        # drop-resuming); read_records consumes any binary file-like
        stream = get_store().open_read_resuming(src) if is_url(src) else None
        try:
            for rec in read_records(
                stream if stream is not None else src, verify=verify_crc
            ):
                if idx % n == mine:
                    yield rec
                idx += 1
        finally:
            if stream is not None:
                stream.close()


def batched_ctr_batches(
    records: Iterator[bytes],
    *,
    batch_size: int,
    field_size: int,
    drop_remainder: bool = True,
    permute_vocab: int = 0,
    skip_counter: list[int] | None = None,
) -> Iterator[dict]:
    """batch -> vectorized decode -> feature dict (ps:158-161 ordering).

    ``skip_counter``: single-element mutable counter of whole batches to
    fast-forward past (input-position resume).  Skipped batches are counted
    at the raw-record level and never proto-decoded; the counter is shared
    across epoch iterators so the caller can spread a skip over epochs."""
    from ..parallel.embedding import permute_ids

    def emit(buf: list[bytes]) -> dict:
        feats, labels = decode_ctr_batch(buf, field_size)
        ids = feats["feat_ids"]
        if permute_vocab:
            ids = permute_ids(ids, permute_vocab, True)
        return {"feat_ids": ids, "feat_vals": feats["feat_vals"], "label": labels}

    n_buf = 0
    buf: list[bytes] = []
    for rec in records:
        if skip_counter is not None and skip_counter[0] > 0:
            n_buf += 1
            if n_buf == batch_size:
                skip_counter[0] -= 1
                n_buf = 0
            continue
        buf.append(rec)
        if len(buf) == batch_size:
            yield emit(buf)
            buf = []
    if not drop_remainder:
        # a partial tail IS a step when remainders are kept, so a skip that
        # ends mid-tail must consume it too or resume shifts by one batch
        if skip_counter is not None and skip_counter[0] > 0 and n_buf:
            skip_counter[0] -= 1
        elif buf:
            yield emit(buf)


def ctr_batches_from_sources(
    sources: Iterable[str | os.PathLike],
    *,
    batch_size: int,
    field_size: int,
    decision: ShardDecision | None = None,
    drop_remainder: bool = True,
    permute_vocab: int = 0,
    verify_crc: bool | None = None,
    skip_counter: list[int] | None = None,
    parallel_readers: int = 1,
) -> Iterator[dict]:
    """Source files/FIFOs -> decoded batches, via the C++ reader when built.

    The native path (deepfm_tpu/native) fuses framing + CRC + record-level
    sharding + Example decode and hands back whole numpy batches; the
    pure-Python chain (record_stream -> batched_ctr_batches) is the portable
    fallback with identical semantics (tests assert parity).

    ``parallel_readers > 1`` with multiple sources streams the sources
    through concurrent per-source C++ readers (data/parallel_ingest.py) —
    same batches in the same order, decoded on several cores.

    ``verify_crc=None`` means "verify when it's cheap": the native reader
    checks (hardware crc32c is ~free), the Python fallback skips (software
    CRC would dominate decode time).  Pass an explicit bool to force either.
    """
    sources = [os.fspath(s) if not isinstance(s, str) else s for s in sources]
    shard_n = decision.num_shards if decision else 1
    shard_i = decision.shard_index if decision else 0
    from .. import native

    if native.available() and any(is_url(s) for s in sources):
        # Remote sources ride the native decode path through FIFO bridges
        # (the C++ reader is already FIFO-capable for pipe-mode parity).
        # Each bridge's writer thread blocks opening its FIFO until a
        # reader opens that source, so live HTTP streams are bounded by
        # the consumer's concurrency (1 sequential, parallel_readers with
        # the concurrent merger) and memory by the kernel pipe buffer.
        import tempfile

        from .object_store import FifoBridge

        with tempfile.TemporaryDirectory(prefix="deepfm_remote_") as td:
            bridges: list[FifoBridge] = []
            mapped: list[str] = []
            for i, s in enumerate(sources):
                if is_url(s):
                    name = f"{i:05d}_" + s.rsplit("/", 1)[-1]
                    b = FifoBridge(s, td, name)
                    bridges.append(b)
                    mapped.append(b.path)
                else:
                    mapped.append(s)
            completed = False
            try:
                yield from ctr_batches_from_sources(
                    mapped,
                    batch_size=batch_size,
                    field_size=field_size,
                    decision=decision,
                    drop_remainder=drop_remainder,
                    permute_vocab=permute_vocab,
                    verify_crc=verify_crc,
                    skip_counter=skip_counter,
                    parallel_readers=parallel_readers,
                )
                completed = True
            finally:
                for b in bridges:
                    if completed:
                        # surface transfer failures that a reader EOF masks
                        b.finish()
                    else:
                        b.close()  # early exit: unblock + reap quietly
        return

    if native.available():
        from ..parallel.embedding import permute_ids

        # threads only help with cores to run them: cap at host CPUs so a
        # 1-core host transparently takes the sequential path (thread
        # hand-off costs ~15% there for zero parallelism).
        # DEEPFM_FORCE_PARALLEL_READERS=1 skips the cap (tests/benches).
        # Record-level round-robin sharding (shard_n > 1) also stays
        # sequential: the C++ reader skips DECODING other shards' records,
        # while the parallel merger decodes everything and strides after —
        # shard_n x the decode work, a regression for exactly the
        # multi-host file-mode runs that hit this branch.
        from ..core.platform import host_cpu_count

        if os.environ.get("DEEPFM_FORCE_PARALLEL_READERS"):
            threads = parallel_readers
        else:
            threads = min(parallel_readers, host_cpu_count())
        if threads > 1 and len(sources) > 1 and shard_n == 1:
            from .parallel_ingest import parallel_ctr_batches

            reader = parallel_ctr_batches(
                sources,
                batch_size=batch_size,
                field_size=field_size,
                shard_n=shard_n,
                shard_i=shard_i,
                drop_remainder=drop_remainder,
                verify=True if verify_crc is None else verify_crc,
                skip_counter=skip_counter,
                num_threads=threads,
            )
        else:
            reader = native.NativeCtrReader(
                sources,
                batch_size=batch_size,
                field_size=field_size,
                shard_n=shard_n,
                shard_i=shard_i,
                drop_remainder=drop_remainder,
                verify=True if verify_crc is None else verify_crc,
                skip_counter=skip_counter,
            )
        for b in reader:
            if permute_vocab:
                b["feat_ids"] = permute_ids(b["feat_ids"], permute_vocab, True)
            yield b
        return
    yield from batched_ctr_batches(
        record_stream(sources, decision=decision, verify_crc=bool(verify_crc)),
        batch_size=batch_size,
        field_size=field_size,
        drop_remainder=drop_remainder,
        permute_vocab=permute_vocab,
        skip_counter=skip_counter,
    )


def shuffle_batches(
    batches: Iterator[dict], *, buffer_records: int, seed: int = 0
) -> Iterator[dict]:
    """Windowed record-level shuffle over a decoded batch stream — the
    ``tf.data.shuffle(buffer_size)`` capability (the reference declared a
    ``perform_shuffle`` hyperparameter but never wired it, SURVEY §2a; here
    ``DataConfig.shuffle_buffer`` wires it for real).

    Accumulates ~``buffer_records`` rows, permutes the pool, emits the front
    half as batches and keeps the tail to mix with the next window — an
    approximation of reservoir sampling that works identically over the
    native (whole-batch) and pure-Python sources.  Deterministic given
    ``seed``.  Note: combined with input-position resume, the skip applies
    to the SOURCE stream; the shuffled order after resume differs from the
    uninterrupted run (same records, different order).
    """
    rng = np.random.default_rng(seed)
    pool: list[dict] = []
    pooled = 0
    batch_size = None

    def drain(keep_tail: bool) -> Iterator[dict]:
        nonlocal pool, pooled
        if not pool:
            return
        keys = list(pool[0])
        merged = {k: np.concatenate([b[k] for b in pool]) for k in keys}
        n = merged[keys[0]].shape[0]
        order = rng.permutation(n)
        emit_rows = (n // 2 // batch_size) * batch_size if keep_tail else n
        for i in range(0, emit_rows, batch_size):
            idx = order[i : i + batch_size]
            yield {k: v[idx] for k, v in merged.items()}
        tail = order[emit_rows:]
        pool = [{k: v[tail] for k, v in merged.items()}] if tail.size else []
        pooled = tail.size

    for b in batches:
        if batch_size is None:
            batch_size = int(b["label"].shape[0])
        pool.append(b)
        pooled += int(b["label"].shape[0])
        if pooled >= buffer_records + batch_size:
            yield from drain(keep_tail=True)
    yield from drain(keep_tail=False)


class InMemoryDataset:
    """Decode-once cache: the whole dataset as contiguous arrays.

    The right representation when the data fits host RAM (eval sets, bench,
    the bundled 10k-record sample): batches are O(1) slices, epochs are free,
    and record-shuffle (absent in the reference — SURVEY §2a notes
    ``perform_shuffle`` was dead) becomes an optional permutation.
    """

    def __init__(self, feat_ids: np.ndarray, feat_vals: np.ndarray, label: np.ndarray):
        self.feat_ids = feat_ids
        self.feat_vals = feat_vals
        self.label = label

    @classmethod
    def from_files(
        cls, files: Iterable[str], field_size: int,
        *, decision: ShardDecision | None = None, permute_vocab: int = 0,
    ) -> "InMemoryDataset":
        batches = list(
            ctr_batches_from_sources(
                files,
                batch_size=8192,
                field_size=field_size,
                decision=decision,
                drop_remainder=False,
                permute_vocab=permute_vocab,
            )
        )
        if not batches:
            return cls(
                np.zeros((0, field_size), np.int64),
                np.zeros((0, field_size), np.float32),
                np.zeros((0,), np.float32),
            )
        return cls(
            np.concatenate([b["feat_ids"] for b in batches]),
            np.concatenate([b["feat_vals"] for b in batches]),
            np.concatenate([b["label"] for b in batches]),
        )

    def __len__(self) -> int:
        return self.label.shape[0]

    def batches(
        self, batch_size: int, *, num_epochs: int = 1, drop_remainder: bool = True,
        shuffle: bool = False, seed: int = 0,
    ) -> Iterator[dict]:
        n = len(self)
        for epoch in range(num_epochs):
            order = np.arange(n)
            if shuffle:
                np.random.default_rng(seed + epoch).shuffle(order)
            end = n - (n % batch_size) if drop_remainder else n
            for i in range(0, end, batch_size):
                idx = order[i : i + batch_size]
                yield {
                    "feat_ids": self.feat_ids[idx],
                    "feat_vals": self.feat_vals[idx],
                    "label": self.label[idx],
                }


def make_input_pipeline(
    cfg: DataConfig,
    topo: WorkerTopology,
    *,
    field_size: int,
    channel: str = "training",
    data_dir: str | None = None,
    num_epochs: int | None = None,
    feature_size: int = 0,
    seed: int = 0,
    skip_batches: int = 0,
) -> Iterator[dict]:
    """The ``input_fn`` equivalent (ps:112-169): wire the shard matrix, the
    source mode (file glob vs stream FIFO), batching and epochs together.

    ``skip_batches`` fast-forwards the deterministic file-mode stream past
    batches an interrupted run already consumed (raw-record level, no
    decode), spread across epochs.  Stream mode ignores it — a live FIFO
    delivers fresh, never-repeated data, so there is nothing to replay."""
    decision = shard_plan(
        topo,
        stream_mode=cfg.stream_mode,
        pre_sharded=cfg.s3_shard,
        multi_path=cfg.multi_path,
    )
    permute_vocab = feature_size if cfg.permute_ids else 0
    epochs = cfg.num_epochs if num_epochs is None else num_epochs
    base_dir = data_dir if data_dir is not None else cfg.training_data_dir

    def maybe_shuffled(batches: Iterator[dict], epoch: int) -> Iterator[dict]:
        if cfg.shuffle_buffer > 0:
            return shuffle_batches(
                batches, buffer_records=cfg.shuffle_buffer,
                seed=seed + 7919 * epoch,   # reshuffle each epoch
            )
        return batches

    if cfg.stream_mode:
        # stream channels live at <dir>/<channel> (+ "-<k>" per extra local
        # worker, mirroring the reference's channel naming, hvd nb cell 8);
        # an object-URL base streams the channel object over HTTP — the
        # PipeModeDataset-from-S3 capability (ps:150) without the platform
        suffix = f"-{decision.channel_index}" if decision.channel_index else ""
        if is_url(base_dir):
            fifo = base_dir.rstrip("/") + f"/{channel}{suffix}"
        else:
            fifo = os.path.join(base_dir, f"{channel}{suffix}")
        yield from maybe_shuffled(
            ctr_batches_from_sources(
                [fifo],
                batch_size=cfg.batch_size,
                field_size=field_size,
                decision=decision,
                drop_remainder=cfg.drop_remainder,
                permute_vocab=permute_vocab,
            ),
            0,
        )
        return
    # seeded shuffle: every host MUST enumerate files in the same order, or
    # round-robin record sharding would overlap/drop records across hosts
    files = discover_files(
        base_dir, cfg.file_patterns, shuffle=cfg.shuffle_files, seed=seed,
    )
    if not files:
        raise FileNotFoundError(
            f"no {tuple(cfg.file_patterns)}*.tfrecords under {base_dir!r}"
        )
    skip_counter = [max(0, skip_batches)]
    for epoch in range(max(1, epochs)):
        yield from maybe_shuffled(
            ctr_batches_from_sources(
                files,
                batch_size=cfg.batch_size,
                field_size=field_size,
                decision=decision,
                drop_remainder=cfg.drop_remainder,
                permute_vocab=permute_vocab,
                skip_counter=skip_counter,
                parallel_readers=cfg.parallel_readers,
            ),
            epoch,
        )


class DevicePrefetcher:
    """Double-buffered host->device feed (the AUTOTUNE-prefetch capability,
    ps:165): a daemon thread decodes/device_puts ``depth`` batches ahead so
    the accelerator never waits on the host.

    ``observer`` (optional) sees each RAW host batch in the worker thread
    before placement — i.e. up to ``depth`` batches before the training
    loop consumes it.  This is the tiered embedding store's ahead-of-time
    prefetch hook (deepfm_tpu/tiered): the pipeline knows the next
    batches' ids before the step needs them, so
    ``TieredTrainer.observer()`` pushes them to the cold→host pager here.
    Observers must be fast and non-raising (an exception would kill the
    feed); the tiered observer just enqueues ids to a background worker.

    Both threads record into the training path's span recorder
    (``obs/trace.py``): the worker ``feed.source`` / ``feed.put`` /
    ``feed.offer`` under the seq it mints per batch, the consumer
    ``feed.take`` — its ``q.get()`` and nothing else — under the same seq.

    Abandoning iteration early?  Call ``close()`` (or use as a context
    manager) — otherwise the worker would sit blocked on a full queue holding
    ``depth`` device-resident batches alive.
    """

    _DONE = object()

    def __init__(
        self,
        batches: Iterator[dict],
        put: Callable[[dict], dict],
        *,
        depth: int = 2,
        observer: Callable[[dict], None] | None = None,
    ):
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._err: BaseException | None = None
        self._stop = threading.Event()
        # the queue is FIFO with one consumer, so the n-th take gets the
        # batch the worker minted seq n for
        self._taken = 0
        rec = self._rec = get_span_recorder()

        def offer(item) -> bool:
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                it = iter(batches)
                for seq in itertools.count():
                    with rec.span("feed.source", seq):
                        b = next(it, self._DONE)
                    if b is self._DONE:
                        return
                    if observer is not None:
                        observer(b)
                    with rec.span("feed.put", seq):
                        placed = put(b)
                    with rec.span("feed.offer", seq):
                        if not offer(placed):
                            return
            except BaseException as e:  # surfaced on next __next__
                self._err = e
            finally:
                offer(self._DONE)

        self._thread = threading.Thread(target=worker, daemon=True)
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        with self._rec.span("feed.take", self._taken):
            item = self._q.get()
        if item is self._DONE:
            # keep the sentinel in the queue: next() after exhaustion must
            # re-raise StopIteration, not block on an empty queue forever
            self._q.put(item)
            if self._err is not None:
                raise self._err
            raise StopIteration
        self._taken += 1
        return item

    def close(self) -> None:
        """Stop the worker and release buffered batches."""
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
