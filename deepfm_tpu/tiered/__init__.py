"""Tiered giant-vocab embedding store: HBM hot cache ← host ← object store.

Production CTR vocabularies are 10⁸–10⁹ rows; a fully-resident table (and
its two Adam moments) cannot live in device memory.  This package pages embedding rows through three tiers:

* **hot** — a fixed-capacity device-resident cache of rows *plus their
  lazy-Adam moments* (the lazy step only ever touches seen rows, so rows
  and moments co-evict as one record; ``step.py``).  The steady-state
  train step is ONE jit-stable executable over slot space: batch ids are
  translated to cache slots on the host, and the deduped unique-id stream
  (the same structure as PR 5's exchange plan) is the cache-probe key
  stream — slot ids are bounded by the capacity, so the packed single-key
  sort (``ops/embedding.py``) always engages.
* **host** — a pinned-host-memory backing store (``host.py``) with an
  async double-buffered staging path: misses resolved between steps fill
  one staging buffer while the device consumes the other, and a
  background prefetcher fed by the input pipeline's id stream
  (``data/pipeline.py`` ``DevicePrefetcher(observer=...)``) pulls
  upcoming rows cold→host before the step needs them.
* **cold** — the existing object store (``store.py``): immutable base
  segments read with HTTP ``Range`` GETs (a row page never downloads a
  whole segment) plus copy-on-write page overlays for dirty writeback,
  all under the PR 3 retry/fault discipline — a cold-tier outage stalls
  training (which resumes) and leaves serving stale-but-alive on
  hot/host-resident rows.

Checkpointing streams tiers instead of gathering (``trainer.py``
``save``/``restore``): dirty rows+moments write back hot→host→cold and a
small metadata record commits — no full-table host gather, attacking the
measured 322 s / 2.4× peak-RSS resident save path.  The same flush
composes with the online publisher so a served snapshot is consistent
(``online/publisher.py`` ``tiered=``).
"""

from .host import HostTier
from .pager import DevicePager
from .serving import TieredScorer
from .step import (
    PagedHot,
    PagedState,
    make_paged_predict,
    make_paged_train_step,
    make_readback,
)
from .store import ColdTier, RecordLayout
from .trainer import TieredTrainer, resolve_tiered

__all__ = [
    "ColdTier",
    "DevicePager",
    "HostTier",
    "PagedHot",
    "PagedState",
    "RecordLayout",
    "TieredScorer",
    "TieredTrainer",
    "make_paged_predict",
    "make_paged_train_step",
    "make_readback",
    "resolve_tiered",
]
