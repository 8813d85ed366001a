"""Full recommendation funnel: sharded top-K retrieval -> ranking as one
version-consistent system.

* ``index.py`` — the on-device exact-scored index (item-tower embeddings
  row-sharded over the serve mesh; per-shard matmul + ``lax.top_k``,
  candidate-pack ``all_gather``, lexicographic global merge inside one
  precompiled executable; index arrays ride as ARGUMENTS) plus the
  brute-force reference.
* ``publish.py`` — funnel versions: ranking weights + query tower + index
  under ONE marker-last manifest (``index`` section), so retrieval and
  ranking can never skew versions.
* ``serve.py`` — ``/v1/recommend`` through the micro-batching engine:
  retrieve K candidates, expand+rank through the live DeepFM weights,
  return the top N — one payload, one swap, structurally zero
  mixed-version responses.
"""

from .index import (  # noqa: F401
    FunnelContext,
    FunnelIndex,
    brute_force_topk,
    build_index,
    build_rank_topn_with,
    build_retrieve_with,
    index_hash,
    make_funnel_context,
    stage_funnel_payload,
)
from .publish import (  # noqa: F401
    FunnelPublisher,
    export_funnel_servable,
    is_funnel_servable,
    load_funnel_artifact,
)
