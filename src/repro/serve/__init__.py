"""repro.serve — batched + continuous-batching inference loops.

``config`` owns the unified :class:`EngineConfig` every entry point
consumes; ``engine`` owns
the device loops (fixed-batch ``generate``, slot-based
``serve_continuous`` — contiguous or paged cache, pow2 prompt-bucketed
prefill, copy-on-write prefix sharing — and frame-by-frame
``rnn_serve_frames``), all of which run sharded under the ``dist``
rules when a mesh is supplied; ``disagg`` splits the engine into a
prefill tier and a fixed-slot decode tier joined by explicit
:class:`PageHandoff` remaps; ``speculative`` drafts with a CSB-pruned
copy of the target and verifies ``spec_k``-token runs in one
multi-position decode step; ``router`` places a request trace over N
engine replicas (load-aware via ``simulate_admission``) and simulates
fleet-wide SLO attainment; ``scheduler`` owns request admission and
slot/page-granular cache reuse; ``paging`` owns the fixed-size
token-page pool (free list + dense page table + refcounted prefix
trie) behind the paged cache; ``transducer`` serves a streaming RNN-T
chunk by chunk (``rnnt_serve_frames``: encoder layers through the frame
server, then one greedy-decode program a chunk). See docs/serving.md
for the end-to-end tour.
"""
from .config import EngineConfig
from .disagg import (
    DecodeTier,
    PageHandoff,
    PrefillTier,
    serve_disaggregated,
)
from .engine import (
    ServeResult,
    bucket_len,
    generate,
    rnn_serve_frames,
    serve_continuous,
    shard_cell_params,
)
from .paging import PagePool, SharedInfo, pages_for
from .router import (
    POLICIES,
    Router,
    RouterResult,
    make_arrival_trace,
    route,
    simulate_replicas,
)
from .speculative import (
    derive_draft_params,
    generate_speculative,
    serve_continuous_speculative,
)
from .transducer import init_rnnt_state, rnnt_serve_frames
from .scheduler import (
    Request,
    SlotScheduler,
    cache_len_of,
    copy_page_cache,
    evict_slot,
    evict_slot_state,
    fit_cache_len,
    grow_cache,
    insert_paged_cache,
    insert_paged_span,
    insert_slot_cache,
    simulate_admission,
)

__all__ = [
    "EngineConfig", "ServeResult", "bucket_len",
    "generate", "rnn_serve_frames", "serve_continuous",
    "shard_cell_params", "init_rnnt_state", "rnnt_serve_frames",
    "DecodeTier", "PageHandoff", "PrefillTier", "serve_disaggregated",
    "POLICIES", "Router", "RouterResult", "make_arrival_trace", "route",
    "simulate_replicas",
    "PagePool", "SharedInfo", "pages_for",
    "derive_draft_params", "generate_speculative",
    "serve_continuous_speculative",
    "Request", "SlotScheduler", "cache_len_of", "copy_page_cache",
    "evict_slot", "evict_slot_state", "fit_cache_len", "grow_cache",
    "insert_paged_cache", "insert_paged_span", "insert_slot_cache",
    "simulate_admission",
]
