"""Append-only event-log sources for online continuous training.

The reference's Pipe-mode pipeline streams training data past the model
instead of staging it (README.md:15, ``PipeModeDataset``) — but a FIFO has
no *position*: a restarted consumer can only start over or miss data.  This
module gives the streaming feed durable coordinates, the log-segment model
every production event bus converges on:

* **Segments, not appends.**  An event log is a directory (or object-store
  prefix) into which producers publish immutable TFRecord *segments* with
  monotonically increasing names (``segment_name(seq)`` — zero-padded so
  lexicographic order == publish order).  A segment appears atomically
  (tmp-file + rename locally; single PUT remotely), so a tailing reader
  never observes a half-written file.
* **Monotone cursors.**  A :class:`StreamCursor` is ``(segment, record)``:
  every segment sorting strictly before ``segment`` is fully consumed, and
  ``record`` records of ``segment`` itself are consumed.  Cursors only move
  forward, and replay from a persisted cursor re-reads *at least* every
  record at or after it — the at-least-once contract.  Exactly-once comes
  from the consumer committing the cursor atomically with its own state
  (see ``online/trainer.py``).
* **Watermarks.**  ``EventLogReader.watermark()`` is the publish time of the
  newest fully-consumed segment: every event at or before it has been read.
  Event→served lag is measured against exactly this quantity.

Both tails share one reader; only listing/opening differ:
``DirectoryTail`` stats the filesystem, ``PrefixTail`` lists an
object-store prefix through ``data/object_store.py`` (ListObjectsV2), so a
training stream can live on the same S3-wire endpoint as the reference's
channels.

Reader bookkeeping (record counts, first-seen times) is pruned as the
cursor passes each segment, so a long-lived tail's memory tracks the live
window.  The per-poll LIST still enumerates every retained segment name —
bound that with log retention: segments strictly *behind* every consumer's
cursor may be deleted or archived at any time (the reader skips names
behind its cursor without opening them); never remove a segment at or
ahead of a live cursor.
"""

from __future__ import annotations

import os
import threading
import time
from typing import BinaryIO, Iterator, NamedTuple, Sequence

import numpy as np

from ..data.example_proto import decode_ctr_batch, serialize_ctr_example
from ..data.object_store import get_store, is_url, join_url
from ..data.tfrecord import frame_record, read_records

_SEGMENT_SUFFIXES = (".tfrecords", ".tfrecord")


class StreamCursor(NamedTuple):
    """Durable stream position: segments ``< segment`` are fully consumed,
    plus ``record`` records of ``segment`` itself.  The empty cursor
    (``StreamCursor()``) means "start of log"."""

    segment: str = ""
    record: int = 0

    def advanced_past(self, name: str) -> bool:
        """True when ``name`` is fully behind this cursor (never re-read)."""
        return bool(self.segment) and name < self.segment


def segment_name(seq: int, *, suffix: str = ".tfrecords") -> str:
    """Zero-padded so lexicographic order == numeric publish order."""
    return f"{seq:012d}{suffix}"


class DirectoryTail:
    """Tail a local directory of immutable TFRecord segments."""

    def __init__(self, path: str):
        self.path = path

    def list_segments(self) -> list[str]:
        if not os.path.isdir(self.path):
            return []
        out = [
            name
            for name in os.listdir(self.path)
            if name.endswith(_SEGMENT_SUFFIXES)
            and not name.startswith((".", "_"))
            and os.path.isfile(os.path.join(self.path, name))
        ]
        return sorted(out)

    def open_segment(self, name: str) -> BinaryIO:
        return open(os.path.join(self.path, name), "rb")

    def segment_time(self, name: str) -> float:
        """Publish time (mtime — the rename that made the segment visible)."""
        try:
            return os.path.getmtime(os.path.join(self.path, name))
        except OSError:
            return 0.0


class PrefixTail:
    """Tail an object-store prefix of immutable TFRecord segments.

    The S3 wire subset exposes no reliable server-side mtime, so publish
    times are *first-seen* times observed by this tail — an upper bound on
    event time, which keeps the watermark conservative (freshness lag is
    never under-reported)."""

    def __init__(self, url: str):
        self.url = url.rstrip("/")
        self._store = get_store()
        self._seen: dict[str, float] = {}

    def list_segments(self) -> list[str]:
        base = self.url + "/"
        now = time.time()
        out = []
        for obj in self._store.list_prefix(base):
            name = obj[len(base):]
            if "/" in name or not name.endswith(_SEGMENT_SUFFIXES):
                continue
            if name.startswith((".", "_")):
                continue
            self._seen.setdefault(name, now)
            out.append(name)
        return sorted(out)

    def open_segment(self, name: str) -> BinaryIO:
        return self._store.open_read_resuming(join_url(self.url, name))

    def segment_time(self, name: str) -> float:
        return self._seen.get(name, 0.0)

    def forget(self, name: str) -> None:
        """Reader hint: ``name`` is permanently behind the cursor — its
        first-seen record is no longer needed (the watermark is a monotone
        max, so dropping history cannot move it backwards)."""
        self._seen.pop(name, None)


def open_tail(root: str) -> DirectoryTail | PrefixTail:
    """The one switch between local-dir and object-prefix event logs."""
    return PrefixTail(root) if is_url(root) else DirectoryTail(root)


def publish_segment(root: str, name: str, payload: bytes) -> str:
    """Make one immutable segment visible atomically (producer side).

    Local segments are written to a ``_tmp.`` name (tail listings skip the
    ``_`` prefix) and renamed into place; remote segments are a single PUT
    (objects appear whole or not at all).  Re-publishing an existing name
    with identical bytes is a safe no-op either way — the idempotence the
    flywheel join's publish-then-checkpoint crash window relies on.
    Returns the segment name."""
    if is_url(root):
        get_store().put(join_url(root, name), payload)
        return name
    os.makedirs(root, exist_ok=True)
    tmp = os.path.join(root, f"_tmp.{name}")
    with open(tmp, "wb") as f:
        f.write(payload)
    os.replace(tmp, os.path.join(root, name))
    return name


def append_segment(
    root: str,
    labels: Sequence[float],
    ids: np.ndarray,
    vals: np.ndarray,
    *,
    seq: int,
) -> str:
    """Publish one immutable segment of CTR events (producer side).

    One-shot convenience over :func:`publish_segment`; returns the
    segment name."""
    records = [
        serialize_ctr_example(float(labels[i]), ids[i], vals[i])
        for i in range(len(labels))
    ]
    payload = b"".join(frame_record(r) for r in records)
    return publish_segment(root, segment_name(seq), payload)


class SegmentWriter:
    """Buffered producer with the size/age segment-roll policy.

    Producers that emit records continuously (the flywheel impression
    logger, the join service's output stream) share one question: *when
    does the buffer become a segment?*  This writer owns the answer —
    roll when the framed buffer reaches ``roll_bytes``, or when the
    oldest buffered record has waited ``roll_age_secs`` (checked by
    :meth:`poll`, which the owning drain loop ticks) — and the atomic
    publish discipline of :func:`publish_segment`.

    * ``roll_bytes <= 0`` disables the size trigger, ``roll_age_secs <= 0``
      the age trigger; with both disabled only explicit :meth:`flush`
      publishes (the join service does exactly this for its checkpoint-
      aligned, deterministic output segments).
    * The bytes trigger is a pure function of the appended records —
      producers that must re-emit a bit-exact stream after a crash keep
      determinism by never enabling the age trigger.
    * Sequence numbers continue after existing segments in ``root`` so a
      restarted producer never overwrites published history.

    Single-writer: not thread-safe; the owning thread appends and polls.
    """

    def __init__(
        self,
        root: str,
        *,
        roll_bytes: int = 1 << 20,
        roll_age_secs: float = 10.0,
        start_seq: int | None = None,
        clock=time.time,
    ):
        self.root = root
        self._roll_bytes = int(roll_bytes)
        self._roll_age = float(roll_age_secs)
        self._clock = clock
        if start_seq is None:
            names = open_tail(root).list_segments()
            start_seq = (
                int(names[-1].split(".", 1)[0]) + 1 if names else 0
            )
        self._seq = int(start_seq)
        self._buf: list[bytes] = []
        self._buf_bytes = 0
        self._oldest: float | None = None
        self.segments_published_total = 0
        self.records_published_total = 0

    @property
    def next_seq(self) -> int:
        return self._seq

    @property
    def pending_records(self) -> int:
        return len(self._buf)

    @property
    def pending_bytes(self) -> int:
        return self._buf_bytes

    def append(self, record: bytes) -> str | None:
        """Buffer one serialized record; returns the segment name when
        this append tripped the size trigger, else None."""
        framed = frame_record(record)
        if self._oldest is None:
            self._oldest = self._clock()
        self._buf.append(framed)
        self._buf_bytes += len(framed)
        if self._roll_bytes > 0 and self._buf_bytes >= self._roll_bytes:
            return self.flush()
        return None

    def poll(self) -> str | None:
        """Age trigger: publish the buffer when its oldest record has
        waited ``roll_age_secs``.  Drain loops tick this between appends
        so a trickle of records still reaches readers promptly."""
        if (
            self._buf
            and self._roll_age > 0
            and self._clock() - self._oldest >= self._roll_age
        ):
            return self.flush()
        return None

    def flush(self) -> str | None:
        """Publish all buffered records as the next segment (None when
        the buffer is empty — an empty segment is never published)."""
        if not self._buf:
            return None
        name = publish_segment(self.root, segment_name(self._seq),
                               b"".join(self._buf))
        self.records_published_total += len(self._buf)
        self.segments_published_total += 1
        self._seq += 1
        self._buf = []
        self._buf_bytes = 0
        self._oldest = None
        return name


class EventLogReader:
    """Decode an event log into training mini-batches with cursor tracking.

    Each yielded item is ``(batch, cursor)`` where ``batch`` is the standard
    CTR host batch ({feat_ids [B,F], feat_vals [B,F], label [B]}) and
    ``cursor`` is the position *after* consuming that batch — persisting it
    and replaying from it yields exactly the remaining records.  Batches may
    span segments; a trailing partial batch is held until more events arrive
    (``follow=True``) or flushed at end-of-log (``follow=False``).
    """

    def __init__(
        self,
        source: DirectoryTail | PrefixTail,
        *,
        field_size: int,
        batch_size: int,
        poll_interval_secs: float = 0.2,
        max_segment_failures: int = 3,
    ):
        self._source = source
        self._fields = int(field_size)
        self._batch = int(batch_size)
        self._poll = float(poll_interval_secs)
        self._watermark = 0.0
        self._lock = threading.Lock()
        # record counts of segments read to their end: segments are
        # immutable, so a known-exhausted segment is skipped without
        # re-opening it — otherwise every tail poll would re-read (and for
        # a prefix tail, re-GET) the whole newest segment just to discard
        # already-consumed records
        self._counts: dict[str, int] = {}
        # segment quarantine (follow mode): a segment whose read keeps
        # failing AFTER the store layer's own retries/resumes is retried on
        # ``max_segment_failures`` consecutive polls (ordering preserved —
        # later segments wait), then quarantined: skipped with a metric so
        # one poisoned object degrades completeness, never liveness.  In
        # one-shot mode (follow=False) read errors stay loud instead.
        self._max_segment_failures = max(1, int(max_segment_failures))
        self._fail_counts: dict[str, int] = {}
        self._quarantined: set[str] = set()
        self.segments_quarantined_total = 0
        self.read_failures_total = 0

    def watermark(self) -> float:
        """Publish time of the newest fully-consumed segment (0.0 before
        any segment completes): every event at or before it has been read."""
        with self._lock:
            return self._watermark

    def stats(self) -> dict:
        """Fault-handling observability: quarantine + failure counters
        (``quarantined`` lists only segments not yet behind the cursor —
        the set is pruned as the cursor passes; the total is monotone)."""
        with self._lock:
            return {
                "read_failures_total": self.read_failures_total,
                "segments_quarantined": self.segments_quarantined_total,
                "quarantined": sorted(self._quarantined),
            }

    def _note_read_failure(self, name: str, err: BaseException) -> bool:
        """Record one failed read of ``name``; True once it crossed the
        quarantine threshold (callers then skip it instead of retrying)."""
        import logging

        with self._lock:
            self.read_failures_total += 1
            n = self._fail_counts.get(name, 0) + 1
            self._fail_counts[name] = n
            quarantine = n >= self._max_segment_failures
            if quarantine:
                self._quarantined.add(name)
                self.segments_quarantined_total += 1
                self._fail_counts.pop(name, None)
        log = logging.getLogger(__name__)
        if quarantine:
            from ..obs import flight as obs_flight

            obs_flight.record(
                "segment_quarantine", subsystem="stream", segment=name,
                failures=n, error=f"{type(err).__name__}: {err}",
            )
            log.warning(
                "segment %s quarantined after %d failed reads "
                "(skipping it; last error: %s)", name, n, err)
        else:
            log.warning(
                "segment %s read failed (%d/%d before quarantine): %s",
                name, n, self._max_segment_failures, err)
        return quarantine

    def _records_from(self, cursor: StreamCursor, *,
                      suppress_errors: bool = False,
                      ) -> Iterator[tuple[bytes, StreamCursor]]:
        """Raw records strictly after ``cursor`` among currently-listed
        segments, each paired with the cursor that marks it consumed.

        ``suppress_errors`` (follow mode) turns a failed segment read into
        a retry-next-poll (this listing pass stops there so ordering holds)
        and, past the quarantine threshold, a permanent skip.  One-shot
        mode (``suppress_errors=False``) neither skips quarantined
        segments nor feeds the quarantine: its errors stay loud — silent
        omission on the batch/oracle path would be data loss."""
        for name in self._source.list_segments():
            if cursor.advanced_past(name):
                # fully behind the cursor forever (cursors are monotone):
                # drop its bookkeeping — including quarantine membership —
                # so a long-lived tail's memory tracks the live window, not
                # the log's age
                self._counts.pop(name, None)
                with self._lock:
                    self._fail_counts.pop(name, None)
                    self._quarantined.discard(name)
                forget = getattr(self._source, "forget", None)
                if forget is not None:
                    forget(name)
                continue
            if suppress_errors and name in self._quarantined:
                continue
            skip = cursor.record if name == cursor.segment else 0
            known = self._counts.get(name)
            if known is not None and skip >= known:
                if skip > known:
                    raise ValueError(
                        f"segment {name!r} has {known} records but the "
                        f"cursor claims {skip} consumed — segments must be "
                        f"immutable"
                    )
                # fully consumed on a prior pass: nothing to read
                self._bump_watermark(name)
                continue
            idx = 0
            try:
                with self._source.open_segment(name) as f:
                    for rec in read_records(f):
                        idx += 1
                        if idx <= skip:
                            continue
                        yield rec, StreamCursor(segment=name, record=idx)
            except OSError as e:
                # the store layer already retried (policy) and resumed
                # (ResumingStream): reaching here means the object is
                # persistently unreadable right now.  Records yielded
                # before the failure carry valid cursors — nothing torn.
                if not suppress_errors:
                    # loud mode: count the failure but do NOT feed the
                    # quarantine — a later follow-mode tail must not skip
                    # a segment that only ever failed loudly
                    with self._lock:
                        self.read_failures_total += 1
                    raise
                if idx > skip:
                    # this pass delivered NEW records before failing: the
                    # quarantine budget bounds consecutive zero-progress
                    # polls, not total failures over a big segment on a
                    # degraded link (same principle as ResumingStream's
                    # progress-reset resume budget) — the next poll resumes
                    # from the advanced cursor
                    import logging

                    with self._lock:
                        self.read_failures_total += 1
                        self._fail_counts.pop(name, None)
                    logging.getLogger(__name__).warning(
                        "segment %s read failed after yielding %d new "
                        "records (will resume next poll): %s",
                        name, idx - skip, e)
                    return
                if self._note_read_failure(name, e):
                    continue  # skip-with-metric; later segments proceed
                return  # stop this pass; retry the segment next poll
            with self._lock:
                # clean pass through a previously-flaky segment: clear its
                # quarantine budget (stats()/other threads read this map
                # under the same lock)
                self._fail_counts.pop(name, None)
            self._counts[name] = idx
            if idx < skip:
                # segment shrank?  immutability violated — fail loudly
                # rather than silently rewinding the cursor
                raise ValueError(
                    f"segment {name!r} has {idx} records but the cursor "
                    f"claims {skip} consumed — segments must be immutable"
                )
            self._bump_watermark(name)

    def _bump_watermark(self, name: str) -> None:
        with self._lock:
            self._watermark = max(
                self._watermark, self._source.segment_time(name)
            )

    def batches(
        self,
        cursor: StreamCursor = StreamCursor(),
        *,
        follow: bool = False,
        stop: threading.Event | None = None,
        idle_timeout_secs: float = 0.0,
        max_batches: int = 0,
    ) -> Iterator[tuple[dict, StreamCursor]]:
        """Mini-batches from ``cursor`` onward.

        ``follow=False`` reads the log as it stands and flushes a final
        partial batch.  ``follow=True`` tails: at end-of-log it polls for
        new segments every ``poll_interval_secs``, stopping on ``stop`` /
        after ``idle_timeout_secs`` without new data (0 = never) /
        after ``max_batches`` yielded (0 = unbounded).
        """
        buf: list[tuple[bytes, StreamCursor]] = []
        yielded = 0
        last_progress = time.time()
        while True:
            progressed = False
            try:
                for rec, rec_cursor in self._records_from(
                    buf[-1][1] if buf else cursor,
                    suppress_errors=follow,
                ):
                    buf.append((rec, rec_cursor))
                    progressed = True
                    if len(buf) >= self._batch:
                        yield self._decode(buf)
                        cursor = buf[-1][1]
                        buf = []
                        yielded += 1
                        if max_batches and yielded >= max_batches:
                            return
                    if stop is not None and stop.is_set():
                        break
            except OSError as e:
                # a failed LIST (store outage) — in follow mode the tailer
                # outlives the outage and re-polls; one-shot reads stay loud
                if not follow:
                    raise
                import logging

                with self._lock:
                    self.read_failures_total += 1
                logging.getLogger(__name__).warning(
                    "event-log poll failed (will retry): %s", e)
            if progressed:
                last_progress = time.time()
            if stop is not None and stop.is_set():
                break
            if not follow:
                break
            if (idle_timeout_secs > 0
                    and time.time() - last_progress >= idle_timeout_secs):
                break
            if stop is not None:
                stop.wait(self._poll)
            else:
                time.sleep(self._poll)
        if buf:
            yield self._decode(buf)

    def _decode(self, buf: list[tuple[bytes, StreamCursor]]) -> tuple[dict, StreamCursor]:
        feats, labels = decode_ctr_batch((r for r, _ in buf), self._fields)
        batch = {**feats, "label": labels}
        return batch, buf[-1][1]
