"""Continuous-batching LLM serving engine (paged KV cache).

The training side of this framework compiles ONE XLA program per
(model, config) and streams batches through it; this package gives
inference the same shape discipline under serving traffic:

- ``kv_cache``: the cache, one kind a layer as the model's
  ``paged_cache_spec()`` lists them. ``KVPages``: a block-paged KV
  cache — a fixed pool of ``[num_blocks, block_size, kv_heads,
  head_dim]`` pages per layer, per-request block tables, and a
  host-side allocator with an explicit out-of-blocks signal (the
  vLLM/Ragged-Paged-Attention memory model, PAPERS.md arxiv
  2604.15464). ``SlotState``: fixed arrays indexed by slot (a recurrent
  state, a convolution tail), reset by the prefill that takes the
  slot.
- ``kernels.paged_attention``: a Pallas ragged paged-attention decode
  kernel (one query token per slot; the pools stay in HBM and the
  kernel fetches a slot's live pages through the block table, a group
  of pages sized from the page's bytes per loop trip, the loop bounded
  by the slot's length) with a jnp fallback that is exact against
  ``masked_decode_attention``.
- ``scheduler`` / ``engine``: request lifecycle (queued → prefill →
  decoding → finished/preempted), FCFS admission control, slot reuse on
  EOS, preemption-with-requeue on pool exhaustion — all driven by ONE
  jitted decode step over a fixed ``max_slots`` batch, so XLA compiles
  the decode exactly once per (model, engine config).
- ``metrics``: per-request TTFT/TPOT/queue-time and engine-level
  throughput/occupancy counters as plain dicts, plus chrome-trace spans
  through the csrc/trace.cc host recorder.
- graceful degradation (resilience layer, all knobs default-off):
  per-request queue-TTL deadlines (terminal ``expired`` status),
  bounded admission queue (``QueueFullError`` load shedding), a
  preemption-count cap (livelock breaker), poison-request quarantine
  (a step exception fails the one request, not the engine), and
  ``Engine.drain()`` — finish in-flight work while rejecting
  admissions (``DrainingError``), the fleet building block.

Reference analog: the AnalysisPredictor serving stack
(/root/reference/paddle/fluid/inference/api/analysis_predictor.cc) —
rebuilt TPU-first around paged blocks + a shape-stable compiled step.
"""
from .engine import (  # noqa: F401
    AdmissionError,
    DrainingError,
    Engine,
    QueueFullError,
)
from .kv_cache import (  # noqa: F401
    BlockAllocator,
    KVPages,
    PagedKVCache,
    SlotState,
)
from .prefix_cache import RadixPrefixCache  # noqa: F401
from .scheduler import Request, RequestState, Scheduler  # noqa: F401
