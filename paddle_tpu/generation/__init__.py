"""paddle_tpu.generation — the TPU-native autoregressive decoding
engine (SURVEY §1 row 9's inference tier, grown from one-shot forward
passes to token streams).

* `PagedKVCache` — the KV store is a block pool, one
  ``[num_blocks, block_size, H*D]`` array per layer for K and for V
  that every step writes in place, plus a host per-slot block
  table (`BlockPool` refcounted allocation, PagedAttention layout);
  the pool is provisioned to the MEAN sequence length instead of
  ``slots * max_len``, and the decode step gathers K/V through the
  table (`ops.cached_attention`) so shapes stay static and the
  step still compiles ONCE.  `KVCache` keeps the dense PR-15 layout as
  the A/B baseline and the speculative draft's cache;
* `PrefixCache` — refcounted FULL-block prefix reuse keyed by a
  token-chain hash: requests sharing a system prompt share physical
  blocks and skip the shared prefill;
* prefill/decode split — prefill rides the bucketed flash-attention
  path (optionally chunked, interleaved with decode steps) and writes
  its K/V through the table; the decode step is a single-token
  attention-over-cache kernel with length masking;
* `GenerationEngine` — slot-based continuous batching: requests claim
  cache slots, finished sequences free slots mid-flight and queued
  requests prefill into freed slots while other slots keep decoding —
  token-for-token identical to serving one request at a time
  (`sequential_oracle`).  Under pool pressure it evicts cached
  prefixes, then preempts (restart semantics).  Opt-ins: int8 KV
  (``kv_dtype="int8"``, documented-tolerance policy) and speculative
  decoding (``draft_model``/``draft_len``, greedy-exact acceptance);
* `SamplingParams` / `sample_tokens` — greedy, temperature, top-k,
  top-p with per-slot `jax.random` key streams;
* serving: `paddle_tpu.serving.generation` puts engine replicas behind
  the PR-9 front with chunked token streaming, slot-occupancy
  admission, and requeue-once replica fault tolerance.

The legacy static-graph `fluid.contrib.decoder.BeamSearchDecoder`
recomputes the full prefix every step; this engine is the recommended
path for autoregressive serving.
"""

from .engine import (  # noqa: F401
    EngineDeadError,
    GenerationEngine,
    GenerationRequest,
    RequestHandle,
    default_prefill_buckets,
    sequential_oracle,
)
from .kv_cache import (  # noqa: F401
    BlockPool,
    KVCache,
    PagedKVCache,
    PoolExhausted,
    PrefixCache,
)
from .sampling import (  # noqa: F401
    SamplingParams,
    make_base_key,
    sample_tokens,
    token_logprobs,
)
