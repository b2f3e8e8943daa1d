"""`GenerationEngine`: slot-based continuous-batching autoregressive
decoding (Orca-style iteration-level scheduling) over a PAGED KV cache.

The execution model, and why the executable set stays enumerable:

* **prefill** — a new request claims a free cache slot, its prompt is
  padded to a bucket from the prefill ladder (PR-2 discipline: a
  bounded executable set, one per bucket length), and ONE jitted
  ``prefill`` call runs the full causal forward on the flash-attention
  path, scatters every layer's K/V through the slot's BLOCK TABLE into
  the pool, and samples the first token from the last real position's
  logits.  The first token is emitted immediately — the TTFT path.
* **decode** — every scheduler iteration runs ONE jitted step over ALL
  slots: one token per slot in, attention through the block table
  (`ops.cached_attention.cached_attention`), one sampled token
  per slot out.
  Pool arrays are donated, the table is passed as DATA, shapes never
  change — the step compiles once per engine config and
  `_decode_cache_size()` plus the PR-4 compile accumulator pin it.
  Attention walks only the live slots, each as far as it reaches: who
  is live is data too (the table rows `_decode_tables` zeroes; a dense
  engine sends its active mask), and `generation_attn_walk_share`
  says each step what part of slots x positions that was.  Sampling
  (`sampling.sample_tokens`) is one argmax in a step whose live rows
  are all greedy, a parked slot reading as greedy (`_park`);
  `generation_sampling_step_share` says how many steps were not.
  The plain step keeps ONE step in flight (`_decode_once`): step t+1
  is dispatched on the device's own copy of step t's sampled tokens,
  and only then are step t's fetched (one transfer) and delivered, so
  the host's work between steps runs while the device computes.
  Lengths, steps and the cache advance at dispatch.  A request that
  ends by length is known at dispatch and computes no extra row; one
  that ends on a stop token, is preempted or fails with a row in
  flight has that row dropped at delivery
  (`generation_decode_rows_discarded_total`), matched by the slot's
  state and not its index.  `generation_decode_overlapped_total` over
  the decode steps says how often the step before was un-fetched.
* **paged KV** (the PR-17 rebuild) — the store is a block pool, one
  ``[num_blocks, block_size, H*D]`` array per layer for K and for V
  (`kv_cache` says why that shape), plus a host per-slot block table
  (`kv_cache.PagedKVCache`).  Every step function takes the
  ``2 * num_layers`` arrays as donated operands and writes its new
  rows into them in place (`ops.cached_attention.kv_write`):
  no step slices, stacks or copies a pool.  Slots allocate blocks as they
  grow instead of reserving ``max_len`` rows up front, so the pool is
  provisioned to the MEAN sequence length; when it runs dry the engine
  evicts cached prefixes, then preempts the least-progressed slot
  (restart semantics, the fleet's requeue discipline) rather than
  crashing.  ``paged=False`` keeps the dense PR-15 layout as the A/B
  baseline (`benchmarks/generation_bench.py`).
* **prefix caching** — with ``prefix_cache=True``, full prompt blocks
  are published under a token-chain hash (`kv_cache.PrefixCache`).  A
  new request sharing a cached prefix adopts those blocks by reference
  and prefills only the suffix — identical system prompts skip their
  prefill entirely.  Only FULL blocks are shared, so the writable tail
  is private and copy-on-write never arises.
* **chunked prefill** — ``prefill_chunk=C`` feeds long prompts through
  C-token chunks, ONE chunk per scheduler iteration, so decode steps
  of in-flight requests interleave with a long prefill instead of
  stalling behind it (prefix-hit suffixes ride the same path).
* **int8 KV** — ``kv_dtype="int8"`` stores the pool quantized with
  per-row per-head scales, quartering decode's KV-read bytes.  Opt-in,
  never a default: logits move within quantization error (the bounds
  are in tests/test_generation.py), so token streams may differ from
  the f32 engine.
* **speculative decoding** — with ``draft_model``/``draft_len=k``, a
  small draft LM (its own dense cache) proposes k greedy tokens and
  ONE batched verify call scores all k+1 positions; greedy slots
  accept the longest matching prefix and emit up to k+1 tokens per
  iteration.  Greedy acceptance is distribution-exact (the emitted
  stream is the target model's own greedy stream); sampled slots
  accept nothing and sample row 0 with their normal key/step, so their
  streams stay per-request-PRNG exact.  Acceptance counters live in
  the PR-4 metrics registry.

* **block diffusion** — a model whose ``cfg.block_length`` B is over 1
  generates by diffusion over blocks of B tokens (Arriola et al.,
  arXiv:2503.09573) and a step is no longer one token a slot.  The
  prompt's whole blocks go into the cache through the chunk path
  (``prefill_chunk`` rows a scheduler iteration, default
  `BLOCK_PREFILL_CHUNK`, the rows of one block seeing each other; no
  token is sampled there), so a long prompt stalls the live streams
  for one chunk at a time; the ``len(prompt) % B`` tokens left
  open the first block as positions already revealed.  A block starts
  as B mask tokens; ONE jitted ``generation_block_step`` over all
  slots runs the model over each slot's B positions against the cache
  (a *pass*), draws a token for every still-masked position, and
  reveals ``B / denoising_steps`` of them by the ``remasking`` rule
  (``sequential``: the leftmost; ``low_confidence_static``: the most
  confident; ``low_confidence_dynamic``: all above
  ``confidence_threshold``, at least the static number).  Once none
  is masked one more pass over the clean block leaves its K/V in the
  cache (the *commit*) and the next block begins; slots at different
  passes share the call.  Which positions are revealed is the
  engine's own state, never read off token ids (a prompt may hold the
  mask id).  A token is streamed once it and every position before
  it is revealed, with the raw log-probability it had at the pass
  that revealed it; a request ends on the token that meets
  ``max_new_tokens``, its last block cut and not committed.

Exactness: scheduling is invisible in the tokens.  Per-request PRNG
streams (`sampling.py`) + row-independent slot math make the engine's
output token-for-token identical to serving the same requests one at a
time (`sequential_oracle`) — the property `tests/test_generation.py`
drills with slots freed, refilled, and preempted mid-run.  Standard
traffic (no prefix hit, no chunking) prefills through the same flash
executable as the dense engine, so paged-vs-dense streams match
token for token; chunk/verify calls use the f32 reference attention
and are exactness-tested empirically at fixed seeds.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from ..fluid import framework
from ..observability import locks as _locks
from ..observability import trace as _trace
from ..observability.metrics import default_registry, unique_instance_label
from .kv_cache import (
    KVCache,
    PagedKVCache,
    PoolExhausted,
    PrefixCache,
    flatten_layers,
    group_layers,
)
from .sampling import (
    SamplingParams,
    make_base_key,
    sample_tokens,
    token_logprobs,
)

__all__ = [
    "EngineDeadError",
    "GenerationEngine",
    "GenerationRequest",
    "RequestHandle",
    "default_prefill_buckets",
    "sequential_oracle",
]


class EngineDeadError(RuntimeError):
    """The engine died mid-generation (injected drill death or a loop
    crash) — affected requests were NOT completed and are safe to
    re-queue exactly once (`serving.generation.GenerationFleet`)."""


# jit TRACING rebinds the (possibly shared) model's VarBase data and the
# process-global dygraph tracer — two engine threads tracing at once
# would corrupt each other.  One process-wide lock around every jitted
# invocation serializes that window; compiled-cache hits pay only an
# uncontended acquire (in-process replicas share a device anyway — real
# parallel engines are separate processes/chips behind the fleet).
# named but UNLEVELED: it nests inside the engine lock, and jit
# tracing fires jax.monitoring -> metrics updates underneath it, so
# only cycle detection (not the ordered hierarchy) applies
_TRACE_LOCK = _locks.named_lock("generation.trace")


def _named(fn, name):
    """``fn`` under a stable name: `jax.jit` names the compiled module
    after it, so a device trace tells the step functions apart on its
    ``XLA Modules`` line whatever the closures are called."""
    fn.__name__ = fn.__qualname__ = name
    return fn


class _DeviceCall:
    """A span around a device call (its dispatch, or the fetch that
    waits for it), whose duration also counts as the step's device
    share: `generation_sched_host_ms` is what a step took beyond these."""

    __slots__ = ("_engine", "_span", "_t0")

    def __init__(self, engine, name, args=None, trace_id=None):
        self._engine = engine
        self._span = _trace.span(name, cat="generation", args=args,
                                 trace_id=trace_id)

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._engine._device_s += time.perf_counter() - self._t0
        return self._span.__exit__(*exc)


def _shed_error(reason, retry_after_s, detail):
    from ..serving.admission import ShedError

    return ShedError(reason, retry_after_s, detail)


def _entry_request(entry):
    """Pending-queue entries are raw `GenerationRequest`s or
    `tp_serving.disagg.KVHandoff`s (which carry one)."""
    return entry if isinstance(entry, GenerationRequest) \
        else entry.request


def default_prefill_buckets(max_len):
    """Power-of-two prompt-length ladder up to max_len (PR-2's default
    batch-bucket shape discipline, applied to the sequence axis)."""
    out = []
    b = 8
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return out


class GenerationRequest:
    """One prompt in, one token stream out."""

    _ids = itertools.count()

    def __init__(self, prompt_ids, max_new_tokens=16, sampling=None,
                 stop_token_ids=(), request_id=None):
        self.prompt_ids = [int(t) for t in np.asarray(prompt_ids).ravel()]
        if not self.prompt_ids:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.sampling = sampling or SamplingParams.greedy()
        self.stop_token_ids = frozenset(int(t) for t in stop_token_ids)
        self.request_id = (request_id if request_id is not None
                           else "genreq-%d" % next(self._ids))


class RequestHandle:
    """The caller's end of one request: a stream of ``(index, token)``
    plus terminal events.  ``restart`` events reset the index stream to
    0 (the fleet's requeue-after-replica-death path and the paged
    engine's preempt-on-pool-exhaustion path both re-run the request
    from scratch; a consumer discards what it saw before)."""

    def __init__(self, request, trace=None):
        self.request = request
        self._q = queue.Queue()
        # perf_counter of each put, beside the queue: the event tuples
        # keep their shape (the ndjson stream is built from them)
        self._put_times = deque()
        self.t_event = None            # put time of the event last read
        self._done = threading.Event()
        self._tokens = []
        self._logprobs = []            # filled only on logprob engines
        # block-diffusion engines: for each token, the denoise pass of
        # its block that revealed it (0 = the block's first)
        self.reveal_passes = []
        self.finish_reason = None
        self.error = None
        self.requeued = False          # fleet's requeue-once latch
        self.t_submit = time.perf_counter()
        self.t_queued = None           # entry of the submit that queued it
        self.t_first_token = None
        # the cross-process trace context: ONE per request, created at
        # first submission and carried by the handle thereafter — the
        # fleet requeue path re-attaches THIS handle, so death ->
        # requeue -> restart land on the original trace_id
        self.trace = trace if trace is not None else _trace.TraceContext()
        self._sink = None              # engine's per-request record sink

    # -- engine side ------------------------------------------------------
    def _put(self, event):
        self._put_times.append(time.perf_counter())
        self._q.put(event)

    def _emit(self, index, token, logprob=None):
        if index == 0:
            self.t_first_token = time.perf_counter()
        self._tokens.append(int(token))
        tr = _trace.default_tracer()
        if tr.enabled:
            tr.async_instant("token", self.trace.trace_id,
                             cat="generation", args={"index": index})
        if logprob is None:
            # logprobs disabled: the event tuple (and hence the ndjson
            # stream upstream) is byte-identical to a pre-logprob engine
            self._put(("token", index, int(token)))
        else:
            self._logprobs.append(float(logprob))
            self._put(("token", index, int(token), float(logprob)))

    def _restart(self):
        self._tokens = []
        self._logprobs = []
        self.reveal_passes = []
        tr = _trace.default_tracer()
        if tr.enabled:
            tr.async_instant("restart", self.trace.trace_id,
                             cat="generation")
        self._put(("restart", None, None))

    def _finish(self, reason):
        self.finish_reason = reason
        self._record("ok", reason=reason)
        tr = _trace.default_tracer()
        if tr.enabled:
            tr.async_end("request", self.trace.trace_id,
                         cat="generation", args={"reason": reason})
        self._put(("done", reason, None))
        self._done.set()

    def _fail(self, error):
        self.error = str(error)
        self._record("error", error=str(error))
        tr = _trace.default_tracer()
        if tr.enabled:
            tr.async_end("request", self.trace.trace_id,
                         cat="generation", args={"error": str(error)})
        self._put(("error", str(error), None))
        self._done.set()

    def _record(self, outcome, **extra):
        """Build + sink the per-request SLO record (`observability.slo`
        schema).  t_submit spans requeues — TTFT after a replica death
        is honest end-to-end latency, not the replacement's view."""
        now = time.perf_counter()
        n = len(self._tokens)
        ttft = ((self.t_first_token - self.t_submit) * 1e3
                if self.t_first_token is not None else None)
        itl = ((now - self.t_first_token) * 1e3 / (n - 1)
               if n > 1 and self.t_first_token is not None else None)
        rec = {"request_id": self.request.request_id,
               "trace_id": self.trace.trace_id,
               "t_wall": time.time(),
               "outcome": outcome,
               "ttft_ms": ttft,
               "itl_ms": itl,
               "n_tokens": n,
               "duration_ms": (now - self.t_submit) * 1e3}
        rec.update(extra)
        sink = self._sink
        if sink is not None:
            try:
                sink(rec)
            except Exception:
                pass
        return rec

    # -- caller side ------------------------------------------------------
    def events(self, timeout=30.0):
        """Yield raw events: ("token", i, t) / ("restart",..) until the
        terminal ("done", reason) / ("error", msg) which is yielded
        last.  ``timeout`` bounds the wait for EACH event; exceeding it
        raises TimeoutError (never a bare queue.Empty — the HTTP front
        turns it into a terminal error record, see handle_generate).
        ``t_event`` is the perf_counter at which the engine put the
        event just yielded (the front's stream lag counts from it)."""
        while True:
            try:
                ev = self._q.get(timeout=timeout)
            except queue.Empty:
                raise TimeoutError(
                    "request %s produced no event within %.1fs"
                    % (self.request.request_id, timeout)) from None
            self.t_event = self._put_times.popleft()
            yield ev
            if ev[0] in ("done", "error"):
                return

    def tokens(self, timeout=30.0):
        """Yield ``(index, token)``; restart resets the stream."""
        for ev in self.events(timeout=timeout):
            if ev[0] == "token":
                yield ev[1], ev[2]
            elif ev[0] == "error":
                raise RuntimeError(ev[1])

    def result(self, timeout=30.0):
        """Block until done; the complete generated token list."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                "request %s not finished" % self.request.request_id)
        if self.error is not None:
            raise RuntimeError(self.error)
        return list(self._tokens)

    def logprobs(self, timeout=30.0):
        """Block until done; per-token logprobs of the generated tokens
        (`sampling.token_logprobs` semantics).  Empty unless the engine
        was built with ``logprobs=True``."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                "request %s not finished" % self.request.request_id)
        if self.error is not None:
            raise RuntimeError(self.error)
        return list(self._logprobs)

    @property
    def done(self):
        return self._done.is_set()


class _Slot:
    __slots__ = ("request", "handle", "generated", "in_flight")

    def __init__(self, request, handle):
        self.request = request
        self.handle = handle
        self.generated = 0             # tokens delivered to the stream
        self.in_flight = 0             # decode rows dispatched, not fetched


REMASKING_RULES = ("sequential", "low_confidence_static",
                   "low_confidence_dynamic")

# Rows of a block-diffusion prompt's prefill chunk unless the engine is
# told another width: as many as a 32-slot step of blocks of 4 runs, so
# that a chunk costs about a pass (both read every weight once) and a
# prompt of any length holds the live streams up by one pass's worth
# at a time.
BLOCK_PREFILL_CHUNK = 128


def choose_reveals(masked, logprobs, count, rule, threshold):
    """Which still-masked positions of each block a denoise pass reveals:
    masked [N, B] bool, logprobs [N, B] (of the token drawn for each
    position, raw softmax), ``count`` the static number a pass.
    ``sequential``: the ``count`` leftmost masked; ``low_confidence_
    static``: the ``count`` most confident (ties: leftmost);
    ``low_confidence_dynamic``: those and every one whose probability
    is above ``threshold``.  A block with nothing masked (a commit
    pass) reveals nothing."""
    b = masked.shape[1]
    if rule == "sequential":
        return masked & (jnp.cumsum(masked, axis=1) <= count)
    idx = jnp.arange(b)
    score = jnp.where(masked, logprobs, -jnp.inf)
    ahead = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None])
        & (idx[None, None, :] < idx[None, :, None]))
    chosen = masked & (jnp.sum(ahead, axis=-1) < count)
    if rule == "low_confidence_dynamic":
        chosen |= masked & (logprobs > np.log(threshold))
    return chosen


class _ChunkState:
    """A slot mid-way through chunked prefill (not yet decoding)."""

    __slots__ = ("request", "handle", "pos", "key", "t0")

    def __init__(self, request, handle, pos, key, t0):
        self.request = request
        self.handle = handle
        self.pos = pos                 # prompt tokens already in cache
        self.key = key
        self.t0 = t0


class GenerationEngine:
    """See module docstring.

    ``model`` is a decode-capable dygraph Layer with the
    `models.TransformerLM` forward contract (``use_cache`` prefill /
    ``caches`` decode).  ``slots`` x ``max_len`` is the engine's
    compiled identity; ``prefill_buckets`` bounds the prefill
    executable set (default: pow2 ladder).  ``max_queue`` bounds the
    pending queue — beyond it `submit` sheds with the slot-occupancy
    signal (`ShedError` -> HTTP 503 + Retry-After upstream).
    ``step_hook(step_no)`` runs before every decode step (the fault
    drill's kill seam).

    Paged knobs: ``paged`` (default True) selects the block-pool cache;
    ``block_size`` is the pool's row granularity; ``kv_blocks`` sizes
    the pool (default: dense parity — ``slots * ceil(max_len /
    block_size) + 1``; provision BELOW that to bank the paged HBM win
    and let preemption absorb the tail).  ``prefix_cache`` enables
    full-block prefix reuse; ``prefill_chunk`` chunk-prefills prompts
    C tokens per scheduler iteration; ``kv_dtype="int8"`` quantizes
    the pool (documented-tolerance opt-in); ``draft_model`` +
    ``draft_len`` enable speculative decoding.

    Block diffusion: a model with a mask granule (``cfg.block_length``
    over 1) makes every decode step a ``generation_block_step``;
    ``denoising_steps`` (a divisor of the block, default as many as
    positions) and ``remasking`` (`REMASKING_RULES`) say how a block is
    revealed, ``confidence_threshold`` is the dynamic rule's.  It needs
    the paged cache and ``max_len`` a multiple of the block, prefills
    every prompt in chunks (``prefill_chunk``, a multiple of the block;
    default `BLOCK_PREFILL_CHUNK`), and has no prefix cache, draft
    model or prefill hand-off."""

    tp = 1      # head shards a step runs over (`tp_serving`'s engine: more)

    def __init__(self, model, *, slots=4, max_len=256,
                 prefill_buckets=None, max_queue=64, name="gen",
                 metrics_registry=None, step_hook=None, donate=None,
                 logprobs=False, paged=True, block_size=16,
                 kv_blocks=None, prefix_cache=False, prefill_chunk=None,
                 kv_dtype=None, draft_model=None, draft_len=0,
                 request_sink=None, denoising_steps=None,
                 remasking="sequential", confidence_threshold=0.9):
        cfg = model.cfg
        self.model = model
        self.cfg = cfg
        self.return_logprobs = bool(logprobs)
        self.slots = int(slots)
        self.max_len = int(max_len)
        if self.max_len > cfg.max_position_embeddings:
            raise ValueError(
                "max_len %d exceeds the model's max_position_embeddings %d"
                % (self.max_len, cfg.max_position_embeddings))
        self.prefill_buckets = sorted(
            int(b) for b in (prefill_buckets
                             or default_prefill_buckets(self.max_len)))
        if self.prefill_buckets[-1] > self.max_len:
            raise ValueError("prefill bucket %d exceeds max_len %d"
                             % (self.prefill_buckets[-1], self.max_len))
        self.max_queue = int(max_queue)
        self.paged = bool(paged)
        if not self.paged and (prefix_cache or prefill_chunk
                               or kv_dtype is not None):
            raise ValueError("prefix_cache / prefill_chunk / kv_dtype "
                             "require paged=True")
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        # a mask granule over 1 is a model that generates by diffusion
        # over blocks; 0 here: an autoregressive one
        granule = int(getattr(cfg, "block_length", 1))
        self.block_length = granule if granule > 1 else 0
        if self.block_length:
            self._init_block_diffusion(
                denoising_steps, remasking, confidence_threshold,
                prefix_cache or draft_model is not None)
        elif denoising_steps is not None:
            raise ValueError("denoising_steps given for a model without "
                             "a block mask (cfg.block_length)")
        self._params = {k: jnp.asarray(v.data)
                        for k, v in model.state_dict().items()}
        n = self.slots
        # a row of the cache is the model's K/V heads (fewer than its
        # query heads where they are grouped), in the weights' type
        kv_heads = getattr(cfg, "num_kv_heads", cfg.num_heads)
        cache_dtype = getattr(cfg, "dtype", "float32")
        if self.paged:
            self.block_size = int(block_size)
            mbps = -(-self.max_len // self.block_size)
            if kv_blocks is None:
                kv_blocks = n * mbps + 1        # dense-parity capacity
            self.cache = PagedKVCache(
                cfg.num_layers, int(kv_blocks), self.block_size,
                kv_heads, cfg.head_dim, n, self.max_len,
                dtype=cache_dtype, kv_dtype=kv_dtype)
            self._slot_blocks = [[] for _ in range(n)]
            self._prefix = (PrefixCache(self.cache.pool, self.block_size)
                            if prefix_cache else None)
        else:
            self.block_size = None
            self.cache = KVCache(cfg.num_layers, n, self.max_len,
                                 kv_heads, cfg.head_dim, dtype=cache_dtype)
            self._slot_blocks = None
            self._prefix = None
        self._nc = len(self.cache.arrays())    # donated cache operands
        # speculative decoding: draft proposes, one verify call scores
        self.draft_len = int(draft_len) if draft_model is not None else 0
        self.draft_model = draft_model if self.draft_len > 0 else None
        if draft_model is not None and draft_len < 1:
            raise ValueError("draft_model needs draft_len >= 1")
        if self.draft_model is not None and not self.paged:
            raise ValueError("speculative decoding requires paged=True")
        # host mirrors of per-slot state.  Device state is the cache and
        # the plain decode step's token operand: step t+1 reads step t's
        # sampled tokens where they are, so ``_last_tokens`` is a device
        # array from the start (one jit signature).  ``_tok_host`` holds
        # what the host knows of it, and ``_fresh`` marks the slots whose
        # token the host set since the last dispatch (`_set_token`: an
        # admission's first token, a verify step's last), which
        # `_dispatch_decode` writes over the device's copy
        self._lengths = np.zeros(n, np.int32)
        self._steps = np.zeros(n, np.int32)
        self._last_tokens = jnp.zeros(n, jnp.int32)
        self._tok_host = np.zeros(n, np.int32)
        self._fresh = np.zeros(n, bool)
        self._merge_tokens = jax.jit(_named(
            lambda tokens, fresh, mine: jnp.where(fresh, mine, tokens),
            "generation_token_merge"))
        # the plain decode step dispatched and not yet fetched:
        # ``(its outputs after the cache's, [(slot, _Slot), ...])``
        self._in_flight = None
        self._t_fetch = 0.0            # when the last step's fetch returned
        self._prefill_since = False    # a prompt went in since then
        self._keys = np.zeros((n, 2), np.uint32)
        self._temp = np.zeros(n, np.float32)
        self._top_k = np.zeros(n, np.int32)
        self._top_p = np.ones(n, np.float32)
        self._active = np.zeros(n, bool)
        self._slot_state = [None] * n          # _Slot | None
        self._chunking = [None] * n            # _ChunkState | None
        self._free = list(range(n))
        self._pending = []                     # [(request, handle)]
        self._lock = _locks.named_rlock("generation.engine",
                                        level="engine")
        # the work-available condition SHARES the engine lock — one
        # graph node, one critical section
        self._work = _locks.named_condition(
            "generation.engine", lock=self._lock)
        self._dead = False
        self._stop = False
        self._thread = None
        self._decode_steps = 0
        self._step_hook = step_hook
        self.on_death = None           # fleet requeue hook
        self._t0 = time.perf_counter()
        # per-request SLO records: a bounded local ring (the sentinel's
        # live window) plus an optional forwarding sink (the fleet's
        # SLOEngine.record)
        self._request_sink = request_sink
        self._recent = deque(maxlen=256)
        # donation only where the backend implements it (CPU warns)
        if donate is None:
            donate = jax.default_backend() in ("tpu", "gpu")
        self._donate = bool(donate)
        donate_kv = tuple(range(1, 1 + self._nc)) if donate else ()
        self._donate_kv = donate_kv
        # a block-diffusion engine's step is a program of its own, and
        # its prompts take the chunk path alone; every other engine
        # builds the programs it always has
        make_step, step_name = (
            (self._make_block_step_fn, "generation_block_step")
            if self.block_length else
            (self._make_decode_fn, "generation_decode"))
        self._decode_step_fn = jax.jit(
            _named(make_step(), step_name), donate_argnums=donate_kv)
        self._prefill_fns = {} if self.block_length else {
            b: jax.jit(_named(self._make_prefill_fn(b),
                              "generation_prefill_%d" % b),
                       donate_argnums=donate_kv)
            for b in self.prefill_buckets
        }
        self._chunk_fns = {}           # chunk width -> jitted fn (lazy)
        if self.draft_model is not None:
            dcfg = self.draft_model.cfg
            if self.max_len > dcfg.max_position_embeddings:
                raise ValueError("draft model max_position_embeddings %d "
                                 "< engine max_len %d"
                                 % (dcfg.max_position_embeddings,
                                    self.max_len))
            self._draft_params = {
                k: jnp.asarray(v.data)
                for k, v in self.draft_model.state_dict().items()}
            self._draft_cache = KVCache(
                dcfg.num_layers, n, self.max_len, dcfg.num_heads,
                dcfg.head_dim)
            ddonate = (tuple(range(1, 1 + 2 * dcfg.num_layers))
                       if donate else ())
            self._draft_decode_fn = jax.jit(
                _named(self._make_draft_decode_fn(),
                       "generation_draft_decode"),
                donate_argnums=ddonate)
            self._draft_prefill_fns = {
                b: jax.jit(_named(self._make_draft_prefill_fn(b),
                                  "generation_draft_prefill_%d" % b),
                           donate_argnums=ddonate)
                for b in self.prefill_buckets
            }
            self._verify_fn = jax.jit(
                _named(self._make_verify_fn(), "generation_verify"),
                donate_argnums=donate_kv)
        else:
            self._draft_cache = None
            self._verify_fn = None
            self._draft_decode_fn = None
            self._draft_prefill_fns = {}

        reg = metrics_registry or default_registry()
        self.metrics_registry = reg
        self._engine = unique_instance_label(name)
        lbl = ("engine",)
        self._m_requests = reg.counter(
            "generation_requests_total", "Submitted generation requests",
            labelnames=lbl).labels(self._engine)
        self._m_tokens = reg.counter(
            "generation_tokens_total", "Generated tokens",
            labelnames=lbl).labels(self._engine)
        self._m_shed = reg.counter(
            "generation_shed_total", "Requests refused at admission",
            labelnames=("engine", "reason"))
        self._m_ttft = reg.histogram(
            "generation_ttft_ms", "Submit -> first token (ms)",
            labelnames=lbl).labels(self._engine)
        self._m_itl = reg.histogram(
            "generation_itl_ms",
            "What a live stream waits for its next token while no prompt "
            "goes in: one decode step's fetch to the next one's (ms)",
            labelnames=lbl).labels(self._engine)
        self._m_prefill_ms = reg.histogram(
            "generation_prefill_ms", "Prefill call wall time (ms)",
            labelnames=lbl).labels(self._engine)
        self._m_queue_wait = reg.histogram(
            "generation_queue_wait_ms",
            "Entry of submit -> the pop that admits the request (ms)",
            labelnames=lbl).labels(self._engine)
        self._m_walk = reg.histogram(
            "generation_attn_walk_share",
            "Cache positions a decode/verify step's attention fetches, "
            "over slots x positions", labelnames=lbl,
            buckets=(0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0)
        ).labels(self._engine)
        self._m_sampling = reg.histogram(
            "generation_sampling_step_share",
            "1 for a decode/verify step with a live sampling row (the "
            "sampler's selections run), 0 for an all-greedy one (one "
            "argmax)", labelnames=lbl, buckets=(0.0, 1.0)
        ).labels(self._engine)
        self._m_sched_host = reg.histogram(
            "generation_sched_host_ms",
            "A step() that decoded, less its time in device calls (ms)",
            labelnames=lbl).labels(self._engine)
        self._device_s = 0.0           # this step's time in device calls
        self._m_occupancy = reg.gauge(
            "generation_slot_occupancy", "Occupied-slot fraction",
            labelnames=lbl).labels(self._engine)
        self._m_queue = reg.gauge(
            "generation_queue_depth", "Pending (unslotted) requests",
            labelnames=lbl).labels(self._engine)
        self._m_preempt = reg.counter(
            "generation_preempt_total",
            "Slots preempted on KV pool exhaustion",
            labelnames=lbl).labels(self._engine)
        self._m_overlapped = reg.counter(
            "generation_decode_overlapped_total",
            "Plain decode steps dispatched while the step before was "
            "un-fetched (over decode steps: the overlap share)",
            labelnames=lbl).labels(self._engine)
        self._m_discarded = reg.counter(
            "generation_decode_rows_discarded_total",
            "Decode rows computed for a slot that had stopped, was "
            "preempted or failed while the row was in flight",
            labelnames=lbl).labels(self._engine)
        if self.paged:
            self._m_blocks_used = reg.gauge(
                "generation_kv_blocks_used", "KV pool blocks in use",
                labelnames=lbl).labels(self._engine)
            self._m_blocks_free = reg.gauge(
                "generation_kv_blocks_free", "KV pool blocks free",
                labelnames=lbl).labels(self._engine)
        if self._prefix is not None:
            self._m_prefix_hits = reg.counter(
                "generation_prefix_hits_total",
                "Prefill prefix-cache hits", labelnames=lbl).labels(
                    self._engine)
            self._m_prefix_misses = reg.counter(
                "generation_prefix_misses_total",
                "Prefill prefix-cache misses", labelnames=lbl).labels(
                    self._engine)
            self._m_prefix_tokens = reg.counter(
                "generation_prefix_hit_tokens_total",
                "Prompt tokens served from the prefix cache",
                labelnames=lbl).labels(self._engine)
        if self.block_length:
            self._m_block_passes = reg.counter(
                "generation_block_passes_total",
                "Passes of the model over a slot's block (denoise and "
                "commit), one a live slot a step",
                labelnames=lbl).labels(self._engine)
            self._m_block_commits = reg.counter(
                "generation_block_commits_total",
                "Passes that wrote a finished block's K/V (commits)",
                labelnames=lbl).labels(self._engine)
            self._m_revealed = reg.counter(
                "generation_tokens_revealed_total",
                "Masked positions revealed by denoise passes",
                labelnames=lbl).labels(self._engine)
            self._m_block_rows = reg.counter(
                "generation_block_cache_rows_total",
                "Cache rows the live slots of a block step attend over",
                labelnames=lbl).labels(self._engine)
            # a model may hand back small arrays of its own with a step
            # (``aux``) and say what they count (`step_observer`)
            self._observe_aux = (
                self.model.step_observer(reg, self._engine)
                if hasattr(self.model, "step_observer") else None)
        if self.draft_model is not None:
            self._m_spec_proposed = reg.counter(
                "generation_spec_proposed_total",
                "Draft tokens proposed to greedy slots",
                labelnames=lbl).labels(self._engine)
            self._m_spec_accepted = reg.counter(
                "generation_spec_accepted_total",
                "Draft tokens accepted by the verify step",
                labelnames=lbl).labels(self._engine)

    # -- traced functions --------------------------------------------------
    def _apply_model(self, params, fn, model=None):
        """Run ``fn(model)`` with params rebound to traced arrays under
        a fresh inference-mode tracer (ShardedTrainStep's rebinding
        idiom, dropout off)."""
        from ..fluid.dygraph.tracer import Tracer

        model = model if model is not None else self.model
        old = framework._dygraph_tracer
        tracer = Tracer()
        tracer.train_mode = False
        tracer._has_grad = False
        framework._dygraph_tracer = tracer
        try:
            sd = model.state_dict()
            for vb in sd.values():
                tracer.register_var(vb)
            saved = {}
            for name, arr in params.items():
                var = sd[name]
                saved[name] = var.data
                var.data = arr
            try:
                return fn(model)
            finally:
                for name, arr in saved.items():
                    sd[name].data = arr
        finally:
            framework._dygraph_tracer = old

    def _run_cached(self, model, params, ids, pos, arrays,
                    cache_positions, where, aux=False):
        """``model``'s cached forward (decode / chunk / verify): ids and
        pos ``[B, S]``, the S tokens of row b written at
        ``cache_positions[b]..+S-1`` of each layer's own cache arrays.
        ``where`` says which rows are live: a paged cache's block
        tables ``[B, max_blocks]`` (a zeroed row is a dead slot), a
        dense cache's ``[B]`` bool.  Returns ``(logits [B, S, V],
        updated arrays)``, and with ``aux`` the small arrays the model
        hands back beside them (its forward's ``aux=True``)."""
        from ..fluid.dygraph import to_variable

        def run(m):
            logits, layers, *rest = m(
                to_variable(ids), to_variable(pos),
                caches=group_layers(arrays, len(m.blocks)),
                cache_positions=cache_positions,
                **self._cache_index(where), **({"aux": True} if aux else {}))
            return (logits.data, flatten_layers(layers), *rest)

        return self._apply_model(params, run, model=model)

    def _cache_index(self, where):
        """A cached forward's keywords for the operand that says which
        rows are live: block tables ``[B, max_blocks]`` or, for a dense
        cache (the draft model's is one in a paged engine), a mask
        ``[B]``."""
        if jnp.ndim(where) == 2:
            return {"block_tables": where, "block_size": self.block_size}
        return {"cache_live": where}

    def _run_prefill(self, model, params, tokens, bucket):
        """``model``'s full causal forward on the flash path over
        ``tokens [1, bucket]``: ``(logits, [(k, v), ...])``, the
        per-layer ``[1, bucket, H, D]`` rows for the cache."""
        from ..fluid.dygraph import to_variable

        def run(m):
            pos = jnp.arange(bucket, dtype=jnp.int32)[None]
            logits, kvs = m(to_variable(tokens), to_variable(pos),
                            use_cache=True)
            return logits.data, kvs

        return self._apply_model(params, run, model=model)

    # the three hooks a tensor-parallel engine overrides: the served
    # model's two forwards, and what wraps a step function's body
    def _forward_cached(self, params, ids, pos, arrays, cache_positions,
                        where, aux=False):
        return self._run_cached(self.model, params, ids, pos, arrays,
                                cache_positions, where, aux=aux)

    def _forward_prefill(self, params, tokens, bucket):
        return self._run_prefill(self.model, params, tokens, bucket)

    def _wrap_step(self, body):
        return body

    def _make_decode_fn(self):
        """ONE decode step over all slots (see module docstring)."""
        nc = self._nc

        def decode(params, *args):
            arrays = args[:nc]
            # the last operand says who is live: the block tables of a
            # paged engine, the active mask of a dense one
            (lengths, tokens, keys, steps, temp, top_k, top_p,
             where) = args[nc:]
            logits, new_arrays = self._forward_cached(
                params, tokens[:, None].astype(jnp.int32),
                lengths[:, None].astype(jnp.int32), arrays, lengths,
                where)
            nxt = sample_tokens(logits[:, 0], keys, steps, temp,
                                top_k, top_p)
            if self.return_logprobs:
                return (*new_arrays, nxt,
                        token_logprobs(logits[:, 0], nxt))
            return (*new_arrays, nxt)

        return self._wrap_step(decode)

    def _prefill_rows(self, bucket, where):
        """Cache indices ``(i0, i1)`` of a prompt's ``bucket`` rows (the
        arguments of `kv_write`).  ``where`` is the slot's block-table
        row ``[1, max_blocks]`` (paged: position p -> pool block
        table[p // bs], row p % bs; padded positions past the allocated
        blocks hit table entry 0, the reserved garbage block) or the
        slot itself (dense: row p of the slot)."""
        p = jnp.arange(bucket, dtype=jnp.int32)
        if jnp.ndim(where) == 0:
            return jnp.full((bucket,), where, jnp.int32), p
        bs = self.block_size
        logical = jnp.clip(p // bs, 0, where.shape[1] - 1)
        return where[0][logical], p % bs

    def _write_prefill(self, arrays, kvs, where):
        """Every layer's prompt rows into that layer's own arrays."""
        from ..ops.cached_attention import kv_write

        i0, i1 = self._prefill_rows(int(kvs[0][0].shape[1]), where)
        return flatten_layers([
            kv_write(mine, i0, i1, k[0], v[0])
            for mine, (k, v) in zip(group_layers(arrays, len(kvs)), kvs)])

    def _make_prefill_fn(self, bucket):
        nc = self._nc

        def prefill(params, *args):
            """tokens [1, bucket]; one flash forward (the dense and the
            paged engine's are bit-identical), every layer's K/V rows
            written into the slot's cache rows (`_prefill_rows`:
            ``where`` is the slot's table row, or for a dense cache the
            slot), generated token 0 sampled from the last real
            position."""
            arrays = args[:nc]
            tokens, length, where, key, temp, top_k, top_p = args[nc:]
            logits, kvs = self._forward_prefill(params, tokens, bucket)
            out = self._write_prefill(arrays, kvs, where)
            last = jax.lax.dynamic_index_in_dim(
                logits[0], length - 1, axis=0)          # [1, V]
            tok0 = sample_tokens(last, key[None],
                                 jnp.zeros((1,), jnp.int32),
                                 temp[None], top_k[None], top_p[None])[0]
            if self.return_logprobs:
                return (*out, tok0, token_logprobs(last, tok0[None])[0])
            return (*out, tok0)

        return self._wrap_step(prefill)

    def _make_chunk_fn(self, width):
        """One prefill chunk for ONE slot: ``width`` prompt tokens
        written at ``start..start+width-1`` through the slot's table
        row, attention with per-row causal limits (the chunked-prefill
        math in `ops.cached_attention`).  Always samples from row
        ``last_index`` — the host ignores the sample on non-final
        chunks, so every chunk runs the same executable."""
        nc = self._nc

        def chunk(params, *args):
            arrays = args[:nc]
            (tokens, start, table, last_index, key, temp, top_k,
             top_p) = args[nc:]
            pos = start + jnp.arange(width, dtype=jnp.int32)[None]
            logits, new_arrays = self._forward_cached(
                params, tokens, pos, arrays, jnp.reshape(start, (1,)),
                table)
            last = jax.lax.dynamic_index_in_dim(
                logits[0], last_index, axis=0)          # [1, V]
            tok = sample_tokens(last, key[None],
                                jnp.zeros((1,), jnp.int32),
                                temp[None], top_k[None], top_p[None])[0]
            if self.return_logprobs:
                return (*new_arrays, tok,
                        token_logprobs(last, tok[None])[0])
            return (*new_arrays, tok)

        return self._wrap_step(chunk)

    def _make_verify_fn(self):
        """Speculative verify: feed ``[last, d_1..d_k]`` per slot at
        positions ``L..L+k`` in ONE call; row i is sampled with the
        slot's key at ``steps + i`` so accepted tokens consume exactly
        the PRNG states plain decode would have."""
        nc = self._nc
        s_len = self.draft_len + 1

        def verify(params, *args):
            arrays = args[:nc]
            (lengths, tok_in, keys, steps, temp, top_k, top_p,
             tables) = args[nc:]
            pos = (lengths[:, None]
                   + jnp.arange(s_len, dtype=jnp.int32)[None])
            logits, new_arrays = self._forward_cached(
                params, tok_in, pos, arrays, lengths, tables)
            toks = jnp.stack(
                [sample_tokens(logits[:, i], keys, steps + i, temp,
                               top_k, top_p) for i in range(s_len)],
                axis=1)                                 # [N, S]
            if self.return_logprobs:
                lps = jnp.stack(
                    [token_logprobs(logits[:, i], toks[:, i])
                     for i in range(s_len)], axis=1)
                return (*new_arrays, toks, lps)
            return (*new_arrays, toks)

        return self._wrap_step(verify)

    def _make_draft_decode_fn(self):
        """One greedy draft-model decode step over all slots (dense
        draft cache, PR-15 layout)."""
        def ddecode(params, *args):
            *arrays, lengths, tokens, live = args
            logits, new_arrays = self._run_cached(
                self.draft_model, params,
                tokens[:, None].astype(jnp.int32),
                lengths[:, None].astype(jnp.int32), arrays, lengths, live)
            return (*new_arrays,
                    jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32))

        return ddecode

    def _make_draft_prefill_fn(self, bucket):
        """Write the prompt's K/V into the draft model's dense cache
        (no sampling — the draft only ever proposes from decode)."""
        def dprefill(params, *args):
            *arrays, tokens, slot = args
            _, kvs = self._run_prefill(self.draft_model, params, tokens,
                                       bucket)
            return self._write_prefill(arrays, kvs, slot)

        return dprefill

    # -- block diffusion ---------------------------------------------------
    def _init_block_diffusion(self, denoising_steps, remasking, threshold,
                              other_paths):
        """Check and keep the block-diffusion knobs, and make the
        per-slot state of the block in flight (host mirrors, like the
        rest of a slot's state)."""
        b, n = self.block_length, self.slots
        steps = int(denoising_steps or b)
        if steps < 1 or b % steps:
            raise ValueError("denoising_steps %d does not divide "
                             "block_length %d" % (steps, b))
        if remasking not in REMASKING_RULES:
            raise ValueError("remasking %r is none of %s"
                             % (remasking, REMASKING_RULES))
        if self.prefill_chunk is None:
            self.prefill_chunk = min(BLOCK_PREFILL_CHUNK, self.max_len)
        # chunks tile the slot's positions: a prompt's last chunk is
        # padded to its width, and must not run past ``max_len``
        if (not self.paged or self.prefill_chunk % b
                or self.max_len % self.prefill_chunk or other_paths):
            raise ValueError(
                "block diffusion needs paged=True, prefill_chunk a "
                "multiple of the model's block_length and max_len one of "
                "prefill_chunk, and has no prefix_cache or draft_model")
        self.denoising_steps = steps
        self.remasking = remasking
        self.confidence_threshold = float(threshold)
        self._mask_id = int(self.cfg.mask_token_id)
        self._blk_tokens = np.full((n, b), self._mask_id, np.int32)
        self._blk_revealed = np.zeros((n, b), bool)
        # positions past a request's last token: never drawn or revealed
        self._blk_beyond = np.zeros((n, b), bool)
        self._blk_streamed = np.zeros(n, np.int32)  # positions sent so far
        self._blk_pass = np.zeros(n, np.int32)      # denoise passes done
        self._blk_reveal_pass = np.zeros((n, b), np.int32)
        self._blk_logprobs = np.zeros((n, b), np.float32)

    def _make_block_step_fn(self):
        """ONE pass over every slot's block (see module docstring):
        tokens ``[N, B]`` at positions ``lengths..lengths+B-1``, written
        to the cache and attended under the model's block mask;
        position j of a block draws with the slot's key at ``steps +
        j``, its generated index, wherever and whenever it is drawn."""
        nc, b = self._nc, self.block_length
        count = b // self.denoising_steps

        def block_step(params, *args):
            arrays = args[:nc]
            (lengths, tokens, revealed, keys, steps, temp, top_k, top_p,
             tables) = args[nc:]
            offs = jnp.arange(b, dtype=jnp.int32)[None]
            logits, new_arrays, *aux = self._forward_cached(
                params, tokens, lengths[:, None] + offs, arrays, lengths,
                tables, **({"aux": True} if self._observe_aux else {}))

            def each(a, k):         # a slot's value for k of its rows
                return jnp.repeat(a, k, axis=0)

            def draw(rows, at, wanted):
                """A token and its raw log-probability for rows ``[N, K,
                V]`` at block positions ``at [N, K]``; a row that is not
                ``wanted`` reads as greedy, so a step whose wanted rows
                are all greedy is one argmax."""
                k = at.shape[1]
                flat = rows.reshape((-1, rows.shape[-1]))
                tok = sample_tokens(
                    flat, each(keys, k), (steps[:, None] + at).ravel(),
                    jnp.where(wanted.ravel(), each(temp, k), 0.0),
                    each(top_k, k), each(top_p, k))
                return (tok.reshape(at.shape),
                        token_logprobs(flat, tok).reshape(at.shape))

            masked = ~revealed
            if self.remasking == "sequential":
                # which positions a pass reveals follows from the mask
                # alone, so only they are drawn: ``count`` rows a slot
                # through the sampler, not every masked one
                reveal = choose_reveals(masked, None, count, "sequential",
                                        None)
                # hit[n, c, j]: position j is the c-th one slot n reveals
                hit = reveal[:, None, :] & (
                    jnp.cumsum(reveal, axis=1)[:, None, :] - 1
                    == jnp.arange(count)[None, :, None])
                at = jnp.sum(jnp.where(hit, offs, 0), axis=-1)
                tok, lp = draw(jnp.take_along_axis(logits, at[:, :, None],
                                                   axis=1),
                               at, jnp.any(hit, axis=-1))
                drawn = jnp.sum(jnp.where(hit, tok[:, :, None], 0), axis=1)
                lps = jnp.sum(jnp.where(hit, lp[:, :, None], 0.0), axis=1)
            else:
                # a confidence rule ranks every masked position's draw
                drawn, lps = draw(logits, jnp.broadcast_to(offs, tokens.shape),
                                  masked)
                reveal = choose_reveals(masked, lps, count, self.remasking,
                                        self.confidence_threshold)
            return (*new_arrays, jnp.where(reveal, drawn, tokens),
                    revealed | reveal, jnp.where(reveal, lps, 0.0), *aux)

        return self._wrap_step(block_step)

    def _make_block_chunk_fn(self, width):
        """One prefill chunk of a block-diffusion prompt, ONE slot:
        ``width`` tokens (whole blocks) written at ``start..`` through
        the slot's table row, each row seeing the cache before it and
        its own block (the model's mask).  Nothing is sampled from a
        prompt, so no logits are computed (the head is dead code)."""
        nc = self._nc

        def chunk(params, *args):
            tokens, start, table = args[nc:]
            pos = start + jnp.arange(width, dtype=jnp.int32)[None]
            return self._forward_cached(
                params, tokens, pos, args[:nc], jnp.reshape(start, (1,)),
                table)[1]

        return self._wrap_step(chunk)

    def _block_prefill_into(self, slot, request, handle):
        """Claim the prompt's blocks and start its whole blocks down
        the chunk path (`_block_chunk_step`, the first chunk now); a
        prompt shorter than one block opens its first generated block
        at once.  False when the pool is dry."""
        if self._claim_blocks(slot, request) is None:
            return False
        if len(request.prompt_ids) < self.block_length:
            self._block_begin(slot, request, handle)
            return True
        self._chunking[slot] = _ChunkState(
            request, handle, 0, None, time.perf_counter())
        self._block_chunk_step(slot)
        return True

    def _block_chunk_step(self, slot):
        """One chunk of a prompt's whole blocks into the cache (one
        call of ``generation_prefill_chunk_<width>``; rows past the
        prompt's last whole block are padding nobody attends: a row
        sees no further than its own block).  The host waits for the
        device on a prompt's last chunk only."""
        cs = self._chunking[slot]
        request, handle = cs.request, cs.handle
        b, width = self.block_length, self.prefill_chunk
        whole = len(request.prompt_ids) - len(request.prompt_ids) % b
        c_real = min(width, whole - cs.pos)
        if width not in self._chunk_fns:
            self._chunk_fns[width] = jax.jit(
                _named(self._make_block_chunk_fn(width),
                       "generation_prefill_chunk_%d" % width),
                donate_argnums=self._donate_kv)
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :c_real] = request.prompt_ids[cs.pos:cs.pos + c_real]
        last = cs.pos + c_real >= whole
        with _DeviceCall(self, "generation.prefill_chunk",
                         args={"width": width, "slot": int(slot),
                               "pos": cs.pos,
                               "request_id": request.request_id},
                         trace_id=handle.trace.trace_id):
            with _TRACE_LOCK:
                out = self._chunk_fns[width](
                    self._params, *self.cache.arrays(), tokens,
                    np.int32(cs.pos),
                    self.cache.table_row(slot)[None].astype(np.int32))
            if last:
                jax.block_until_ready(out[0])
        self.cache.update(*out)
        cs.pos += c_real
        if last:
            self._chunking[slot] = None
            self._m_prefill_ms.observe((time.perf_counter() - cs.t0) * 1e3)
            self._block_begin(slot, request, handle)

    def _block_begin(self, slot, request, handle):
        """The prompt's whole blocks are in the cache: arm the slot and
        open its first generated block with what is left of the
        prompt."""
        b = self.block_length
        n_prompt = len(request.prompt_ids)
        whole = n_prompt - n_prompt % b
        sp = request.sampling
        self._slot_state[slot] = _Slot(request, handle)
        self._lengths[slot] = whole
        self._steps[slot] = -(n_prompt % b)     # block position 0's index
        self._keys[slot] = make_base_key(sp.seed).astype(np.uint32)
        self._temp[slot] = sp.temperature
        self._top_k[slot] = sp.top_k
        self._top_p[slot] = sp.top_p
        self._open_block(slot, request.prompt_ids[whole:])
        self._active[slot] = True
        return True

    def _open_block(self, slot, given=()):
        """A new block in ``slot``: ``given`` tokens (what a prompt left
        over) revealed and not to be streamed, mask tokens after them;
        those past the request's last token stay mask tokens (its last
        block is cut: a pass reveals only what will be streamed)."""
        self._blk_tokens[slot] = self._mask_id
        self._blk_tokens[slot, :len(given)] = given
        self._blk_revealed[slot] = False
        self._blk_revealed[slot, :len(given)] = True
        self._blk_streamed[slot] = len(given)
        self._blk_pass[slot] = 0
        request = self._slot_state[slot].request
        self._blk_beyond[slot] = (
            self._lengths[slot] + np.arange(self.block_length)
            >= len(request.prompt_ids) + request.max_new_tokens)

    def _block_once(self):
        """One `generation_block_step` over the active slots and what the
        host does with it: a slot whose block was clean has committed
        it and opens the next; any other adopts what the pass revealed
        and streams every token that has none masked before it."""
        b = self.block_length
        with _trace.span("generation.grow", cat="generation"):
            for slot in list(np.nonzero(self._active)[0]):
                if self._active[slot] and not self._grow_or_preempt(
                        slot, int(self._lengths[slot]) + b):
                    self._fail_slot(
                        slot, "kv pool exhausted: no preemptable slot "
                        "left to make room")
            if not self._active.any():
                return
        live = np.nonzero(self._active)[0]
        committing = self._blk_revealed[live].all(axis=1)
        t0 = time.perf_counter()
        with _trace.span("generation.block_step", cat="generation",
                         args={"passes": len(live),
                               "committing": int(committing.sum())}) as sp:
            with _DeviceCall(self, "generation.decode_dispatch",
                             args=self._step_shares(b)):
                with _TRACE_LOCK:
                    out = self._decode_step_fn(*self._decode_operands())
            # the host waits here while the device works
            with _DeviceCall(self, "generation.decode_fetch"):
                tokens, revealed, lps, *aux = jax.device_get(
                    out[self._nc:])
            revealed = revealed & ~self._blk_beyond
            newly = int((revealed[live] & ~self._blk_revealed[live]).sum())
            sp.add_args(revealed=newly)
        self.cache.update(*out[:self._nc])
        self._decode_steps += 1
        self._m_itl.observe((time.perf_counter() - t0) * 1e3)
        self._m_block_passes.inc(len(live))
        self._m_block_commits.inc(int(committing.sum()))
        self._m_revealed.inc(newly)
        self._m_block_rows.inc(int(self._lengths[live].sum()) + b * len(live))
        if aux:
            self._observe_aux(aux[0])
        with _trace.span("generation.emit", cat="generation"):
            for slot, commit in zip(live, committing):
                if commit:
                    self._lengths[slot] += b
                    self._steps[slot] += b
                    self._open_block(slot)
                    continue
                new = revealed[slot] & ~self._blk_revealed[slot]
                # a token keeps the pass and the log-probability of its
                # reveal until the positions before it let it stream
                self._blk_reveal_pass[slot, new] = self._blk_pass[slot]
                self._blk_logprobs[slot, new] = lps[slot, new]
                self._blk_pass[slot] += 1
                self._blk_tokens[slot] = tokens[slot]
                self._blk_revealed[slot] = revealed[slot]
                st = self._slot_state[slot]
                while self._active[slot] and self._blk_streamed[slot] < b \
                        and revealed[slot, self._blk_streamed[slot]]:
                    j = int(self._blk_streamed[slot])
                    self._blk_streamed[slot] += 1
                    st.handle.reveal_passes.append(
                        int(self._blk_reveal_pass[slot, j]))
                    self._emit(slot, st, int(tokens[slot, j]),
                               float(self._blk_logprobs[slot, j])
                               if self.return_logprobs else None)
                    if st.generated == 1:
                        self._m_ttft.observe(
                            (time.perf_counter() - st.handle.t_submit)
                            * 1e3)

    # -- block accounting (paged) -----------------------------------------
    def _set_block_gauges(self):
        self._m_blocks_used.set(self.cache.pool.used_blocks)
        self._m_blocks_free.set(self.cache.pool.free_blocks)

    def _ensure_blocks(self, slot, n_tokens):
        """Grow the slot's table to cover ``n_tokens`` cache rows.
        Falls back to prefix-cache eviction under pool pressure; False
        when the pool is dry even then (the caller preempts/sheds)."""
        need = self.cache.blocks_for(n_tokens) - len(self._slot_blocks[slot])
        if need <= 0:
            return True
        try:
            ids = self.cache.pool.alloc(need)
        except PoolExhausted:
            if self._prefix is not None:
                self._prefix.evict(need)
            try:
                ids = self.cache.pool.alloc(need)
            except PoolExhausted:
                return False
        base = len(self._slot_blocks[slot])
        for j, b in enumerate(ids):
            self.cache.assign(slot, base + j, b)
        self._slot_blocks[slot].extend(ids)
        self._set_block_gauges()
        return True

    def _release_blocks(self, slot):
        """Drop the slot's reference on every block it holds (shared
        prefix blocks stay alive under the registry's reference) and
        point its table row back at the garbage block."""
        ids = self._slot_blocks[slot]
        if ids:
            self.cache.pool.decref(ids)
            self._slot_blocks[slot] = []
        self.cache.clear_slot(slot)
        self._set_block_gauges()

    def _preempt_slot(self, slot, why):
        """Pool-pressure eviction of a running request: every block
        returns to the pool and the request restarts from the front of
        the queue (the handle's stream resets — restart semantics,
        same contract as the fleet's requeue path)."""
        if self._slot_state[slot] is not None:
            st = self._slot_state[slot]
            self._slot_state[slot] = None
        else:
            cs = self._chunking[slot]
            st = _Slot(cs.request, cs.handle)
            self._chunking[slot] = None
        self._park(slot)
        self._release_blocks(slot)
        self._free.append(slot)
        st.handle._restart()
        st.handle.t_queued = time.perf_counter()   # a new wait starts
        self._pending.insert(0, (st.request, st.handle))
        self._m_queue.set(len(self._pending))
        self._m_preempt.inc()
        _trace.instant("generation.preempt", cat="generation",
                       args={"slot": int(slot), "why": why,
                             "request_id": st.request.request_id})

    def _grow_or_preempt(self, slot, n_tokens):
        """Grow ``slot`` to ``n_tokens`` rows, preempting the least-
        progressed OTHER slot (deterministic: fewest generated tokens,
        lowest id) until it fits; False when no victim is left."""
        while not self._ensure_blocks(slot, n_tokens):
            victims = [
                s for s in range(self.slots)
                if s != slot and (self._slot_state[s] is not None
                                  or self._chunking[s] is not None)
            ]
            if not victims:
                return False
            def _progress(s):
                st = self._slot_state[s]
                return (st.generated if st is not None else 0, s)
            self._preempt_slot(min(victims, key=_progress),
                               "pool_exhausted")
        return True

    def _fail_slot(self, slot, msg):
        st = self._slot_state[slot]
        self._slot_state[slot] = None
        self._park(slot)
        if self.paged:
            self._release_blocks(slot)
        self._free.append(slot)
        st.handle._fail(msg)

    def _park(self, slot):
        """Nobody decodes in ``slot`` any more, and it reads as greedy:
        a step whose live rows are all greedy is then all greedy, and
        the sampler's one argmax."""
        self._active[slot] = False
        self._temp[slot] = 0.0
        self._top_k[slot] = 0
        self._top_p[slot] = 1.0

    def _step_shares(self, rows):
        """The `generation.decode_dispatch` arguments of the step about
        to run, ``rows`` new tokens a slot."""
        sampling = float((self._temp[self._active] > 0.0).any())
        self._m_sampling.observe(sampling)
        return {"attn_walk_share": self._walk_share(rows),
                "sampling_step_share": sampling}

    def _walk_share(self, rows):
        """`generation_attn_walk_share` of the step about to run, its
        ``rows`` new tokens a slot: what the device's loops will walk,
        from the same `walk_plan` (the TP engine's head shards walk the
        same positions: the fork past `_BLOCK_DIAGONAL_ROWS` is by local
        heads)."""
        from ..ops.cached_attention import attention_walk_share

        heads = self.cfg.num_heads // self.tp
        positions = (self.cache.block_tables.shape[1] * self.block_size
                     if self.paged else self.max_len)
        share = float(attention_walk_share(
            np.where(self._active, self._lengths + rows, 0).astype(np.int32),
            rows * heads, positions, self.block_size))
        self._m_walk.observe(share)
        return share

    def _decode_tables(self):
        """The table operand for batched decode/verify: rows of slots
        that are NOT actively decoding are zeroed so their dead-row
        writes land in the reserved garbage block — a mid-chunk slot's
        real blocks must never take a stale-position decode write."""
        return np.where(self._active[:, None], self.cache.block_tables,
                        0).astype(np.int32)

    # -- admission / submission -------------------------------------------
    def submit(self, request, _handle=None):
        """Queue a request; returns its `RequestHandle`.  Sheds
        (`ShedError`, reason ``slots_full``) when the pending queue is
        at ``max_queue`` — the slot-occupancy admission signal; the
        Retry-After estimate prices the queue in measured decode
        steps.  ``_handle`` re-attaches an existing handle (the fleet's
        requeue-after-death path: the stream restarts, the handle
        doesn't change hands)."""
        t_enter = time.perf_counter()  # queue wait counts the lock too
        if not isinstance(request, GenerationRequest):
            request = GenerationRequest(request)
        if len(request.prompt_ids) > self.prefill_buckets[-1]:
            raise ValueError(
                "prompt length %d exceeds the largest prefill bucket %d"
                % (len(request.prompt_ids), self.prefill_buckets[-1]))
        need = len(request.prompt_ids) + request.max_new_tokens
        if need > self.max_len:
            raise ValueError(
                "prompt + max_new_tokens = %d exceeds max_len %d"
                % (need, self.max_len))
        if self.paged and \
                self.cache.blocks_for(need) > self.cache.num_blocks - 1:
            raise ValueError(
                "request needs %d blocks, pool has %d usable"
                % (self.cache.blocks_for(need),
                   self.cache.num_blocks - 1))
        with self._lock:
            if self._dead:
                raise EngineDeadError("engine %s is dead" % self._engine)
            if len(self._pending) >= self.max_queue:
                err = _shed_error(
                    "slots_full", self._retry_after_locked(),
                    "all %d slots busy and %d requests queued"
                    % (self.slots, len(self._pending)))
                self._m_shed.labels(self._engine, err.reason).inc()
                self._record_request({
                    "request_id": request.request_id, "trace_id": None,
                    "t_wall": time.time(), "outcome": "shed",
                    "ttft_ms": None, "itl_ms": None, "n_tokens": 0,
                    "duration_ms": 0.0})
                raise err
            handle = _handle if _handle is not None \
                else RequestHandle(request)
            handle._sink = self._record_request
            handle.t_queued = t_enter
            tr = _trace.default_tracer()
            if tr.enabled:
                tid = handle.trace.trace_id
                if _handle is not None:
                    # requeue-after-death: SAME trace_id — the merged
                    # timeline shows death -> requeue -> restart on one
                    # track
                    tr.async_instant("requeue", tid, cat="generation",
                                     args={"engine": self._engine})
                else:
                    tr.async_begin("request", tid, cat="generation",
                                   args={"request_id": request.request_id})
                tr.async_begin("queue", tid, cat="generation")
            self._pending.append((request, handle))
            self._m_requests.inc()
            self._m_queue.set(len(self._pending))
            self._work.notify_all()
        return handle

    def _retry_after_locked(self):
        """Queue depth priced in measured generation throughput."""
        rate = self._tokens_per_s()
        if rate <= 0:
            return 1
        backlog_tokens = sum(
            _entry_request(e).max_new_tokens
            for e, _ in self._pending) or 1
        return max(1.0, backlog_tokens / rate)

    def _tokens_per_s(self):
        try:
            tot = self._m_tokens.value
            elapsed = time.perf_counter() - self._t0
        except AttributeError:
            return 0.0
        return tot / elapsed if elapsed > 0 else 0.0

    def _record_request(self, rec):
        """Sink for per-request SLO records (handles call this as their
        ``_sink``): stamp the engine, keep a bounded local window, and
        forward to the configured ``request_sink`` (the fleet's
        `SLOEngine.record`).  Never raises into the serving path."""
        rec = dict(rec, engine=self._engine)
        self._recent.append(rec)
        sink = self._request_sink
        if sink is not None:
            try:
                sink(rec)
            except Exception:
                pass

    def recent_requests(self):
        """Snapshot of the bounded per-request record window."""
        return list(self._recent)

    # -- scheduler ---------------------------------------------------------
    def step(self):
        """One scheduler iteration: advance every mid-flight chunked
        prefill by ONE chunk, refill free slots (prefill), then one
        decode step over the active batch.  A plain decode step is
        dispatched here and its tokens are delivered by the NEXT
        iteration, after that one's dispatch (`_decode_once`); an
        iteration that only delivers counts as work.  Returns True when
        any work happened: False means nothing is queued, live or
        un-fetched."""
        t_step = time.perf_counter()
        with _trace.span("generation.step", cat="generation"):
            with _trace.span("generation.lock_wait", cat="generation"):
                self._lock.acquire()
            try:
                return self._step_locked(t_step)
            finally:
                self._lock.release()

    def _step_locked(self, t_step):
        if self._dead:
            raise EngineDeadError("engine %s is dead" % self._engine)
        self._device_s = 0.0
        decoded = self._decode_steps
        progressed = False
        chunk_step = (self._block_chunk_step if self.block_length
                      else self._chunk_step)
        for slot in range(self.slots):
            if self._chunking[slot] is not None:
                chunk_step(slot)
                progressed = True
        while self._free and self._pending:
            entry, handle = self._pending[0]
            with _trace.span(
                    "generation.admit", cat="generation",
                    args={"request_id": _entry_request(entry).request_id},
                    trace_id=handle.trace.trace_id):
                admitted = self._admit_next()
            if not admitted:
                break
            progressed = True
        if self._active.any() or self._in_flight is not None:
            self._decode_once()
            progressed = True
        self._m_occupancy.set(
            float(self._active.sum()) / max(self.slots, 1))
        if self._decode_steps != decoded:
            self._m_sched_host.observe(
                (time.perf_counter() - t_step - self._device_s) * 1e3)
        return progressed

    def _admit_next(self):
        """Move the head of the pending queue into a free slot.  False
        when the pool was dry: the request is back at the head (or
        failed, if nothing is running that could ever free blocks)."""
        t_pop = time.perf_counter()
        entry, handle = self._pending.pop(0)
        slot = self._free.pop(0)
        self._m_queue.set(len(self._pending))
        tr = _trace.default_tracer()
        if tr.enabled:
            tr.async_end("queue", handle.trace.trace_id, cat="generation")
        # an entry is either a raw GenerationRequest (prefill here) or a
        # KVHandoff from a prefill worker (adopt the finished pages —
        # decode-only workers never prefill)
        admit = (self._prefill_into if isinstance(entry, GenerationRequest)
                 else self._inject_into)
        if admit(slot, entry, handle):
            self._m_queue_wait.observe((t_pop - handle.t_queued) * 1e3)
            return True
        # pool dry at admission: requeue (the wait keeps its first
        # stamp) until a running request frees blocks — unless nothing
        # is running, in which case it never will
        self._free.insert(0, slot)
        if self._active.any() or self._in_flight is not None or any(
                c is not None for c in self._chunking):
            self._pending.insert(0, (entry, handle))
            self._m_queue.set(len(self._pending))
            if tr.enabled:
                tr.async_begin("queue", handle.trace.trace_id,
                               cat="generation")
        else:
            handle._fail(
                "kv pool exhausted: request %s needs more "
                "blocks than the pool can ever free"
                % _entry_request(entry).request_id)
        return False

    def run_until_idle(self, max_steps=100000):
        """Drive `step()` until no pending and no active work is left."""
        for _ in range(max_steps):
            if not self.step():
                return
        raise RuntimeError("run_until_idle: still busy after %d steps"
                           % max_steps)

    def _bucket_for(self, n):
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError("prompt length %d exceeds bucket ladder" % n)

    # -- prefill -----------------------------------------------------------
    def _prefill_into(self, slot, request, handle):
        """Claim blocks and start the prompt.  Standard traffic (no
        prefix hit, no chunking) runs the whole-prompt flash prefill —
        the SAME executable and logits as the dense engine.  A prefix
        hit or ``prefill_chunk`` routes through the chunked path.
        Returns False (nothing claimed) when the pool is dry."""
        if self.block_length:
            return self._block_prefill_into(slot, request, handle)
        sp = request.sampling
        n_prompt = len(request.prompt_ids)
        key = make_base_key(sp.seed).astype(np.uint32)
        if self.paged:
            n_cached = self._claim_blocks(slot, request)
            if n_cached is None:
                return False
            if n_cached > 0 or self.prefill_chunk is not None:
                tr = _trace.default_tracer()
                if tr.enabled:
                    tr.async_begin("prefill", handle.trace.trace_id,
                                   cat="generation",
                                   args={"chunked": True,
                                         "prefix_cached": n_cached})
                self._chunking[slot] = _ChunkState(
                    request, handle, n_cached, key, time.perf_counter())
                self._chunk_step(slot)
                return True
        # whole-prompt flash prefill into the slot's rows
        bucket = self._bucket_for(n_prompt)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :n_prompt] = request.prompt_ids
        t0 = time.perf_counter()
        tr = _trace.default_tracer()
        if tr.enabled:
            tr.async_begin("prefill", handle.trace.trace_id,
                           cat="generation", args={"bucket": bucket})
        # the span holds the fetch of the sampled token too: the host
        # waits there for the device to finish the prefill
        with _DeviceCall(self, "generation.prefill",
                         args={"bucket": bucket, "slot": int(slot),
                               "request_id": request.request_id},
                         trace_id=handle.trace.trace_id):
            with _TRACE_LOCK:
                out = self._prefill_fns[bucket](
                    self._params, *self.cache.arrays(), tokens,
                    np.int32(n_prompt), self._prefill_where(slot), key,
                    np.float32(sp.temperature), np.int32(sp.top_k),
                    np.float32(sp.top_p))
            tok0 = int(out[self._nc])
            lp0 = (float(out[self._nc + 1]) if self.return_logprobs
                   else None)
        self.cache.update(*out[:self._nc])
        self._m_prefill_ms.observe((time.perf_counter() - t0) * 1e3)
        if tr.enabled:
            tr.async_end("prefill", handle.trace.trace_id,
                         cat="generation")
        self._activate(slot, request, handle, tok0, lp0, key)
        return True

    def _prefill_where(self, slot):
        """The prefill executable's ``where`` operand: the slot's block
        table row, or for a dense cache the slot."""
        if self.paged:
            return self.cache.table_row(slot)[None].astype(np.int32)
        return np.int32(slot)

    def _claim_blocks(self, slot, request):
        """Adopt cached prefix blocks and allocate the rest of the
        prompt's.  Returns the number of prompt tokens already cached,
        or None (nothing claimed) when the pool is dry."""
        n_cached, shared = (self._prefix.lookup(request.prompt_ids)
                            if self._prefix is not None else (0, []))
        if self._prefix is not None:
            if n_cached:
                self._m_prefix_hits.inc()
                self._m_prefix_tokens.inc(n_cached)
            else:
                self._m_prefix_misses.inc()
        for j, b in enumerate(shared):
            self.cache.assign(slot, j, b)
        self._slot_blocks[slot] = list(shared)
        if not self._ensure_blocks(slot, len(request.prompt_ids)):
            self._release_blocks(slot)
            return None
        return n_cached

    def _chunk_step(self, slot):
        """Advance one chunked prefill by one chunk (one executable
        call).  Chunk width is ``prefill_chunk`` when set, else the
        whole remaining suffix bucketed to the prefill ladder (the
        prefix-hit suffix path)."""
        cs = self._chunking[slot]
        request, handle = cs.request, cs.handle
        sp = request.sampling
        n_prompt = len(request.prompt_ids)
        remaining = n_prompt - cs.pos
        self._prefill_since = True
        width = (self.prefill_chunk if self.prefill_chunk is not None
                 else self._bucket_for(remaining))
        c_real = min(width, remaining)
        if not self._grow_or_preempt(slot, cs.pos + c_real):
            self._chunking[slot] = None
            self._release_blocks(slot)
            self._free.append(slot)
            handle._fail("kv pool exhausted mid-prefill for request %s"
                         % request.request_id)
            return
        if width not in self._chunk_fns:
            self._chunk_fns[width] = jax.jit(
                _named(self._make_chunk_fn(width),
                       "generation_prefill_chunk_%d" % width),
                donate_argnums=self._donate_kv)
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :c_real] = request.prompt_ids[cs.pos:cs.pos + c_real]
        table = self.cache.table_row(slot)[None].astype(np.int32)
        last = cs.pos + c_real >= n_prompt
        tok0 = lp0 = None
        with _DeviceCall(self, "generation.prefill_chunk",
                         args={"width": width, "slot": int(slot),
                               "pos": cs.pos,
                               "request_id": request.request_id},
                         trace_id=handle.trace.trace_id):
            with _TRACE_LOCK:
                out = self._chunk_fns[width](
                    self._params, *self.cache.arrays(), tokens,
                    np.int32(cs.pos), table, np.int32(c_real - 1),
                    cs.key, np.float32(sp.temperature),
                    np.int32(sp.top_k), np.float32(sp.top_p))
            if last:                   # the host waits for the sample
                tok0 = int(out[self._nc])
                if self.return_logprobs:
                    lp0 = float(out[self._nc + 1])
        self.cache.update(*out[:self._nc])
        cs.pos += c_real
        if last:
            self._chunking[slot] = None
            self._m_prefill_ms.observe(
                (time.perf_counter() - cs.t0) * 1e3)
            tr = _trace.default_tracer()
            if tr.enabled:
                tr.async_end("prefill", handle.trace.trace_id,
                             cat="generation")
            self._activate(slot, request, handle, tok0, lp0, cs.key)

    def _activate(self, slot, request, handle, tok0, lp0, key):
        """Prompt fully in cache; publish its prefix blocks, prefill
        the draft model, arm the slot's decode state, emit token 0."""
        sp = request.sampling
        n_prompt = len(request.prompt_ids)
        if self._prefix is not None:
            self._prefix.register(request.prompt_ids,
                                  self._slot_blocks[slot])
        if self.draft_model is not None:
            bucket = self._bucket_for(n_prompt)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :n_prompt] = request.prompt_ids
            with _TRACE_LOCK:
                self._draft_cache.update(*self._draft_prefill_fns[bucket](
                    self._draft_params, *self._draft_cache.arrays(),
                    tokens, np.int32(slot)))
        st = _Slot(request, handle)
        self._slot_state[slot] = st
        self._lengths[slot] = n_prompt
        self._set_token(slot, tok0)
        self._steps[slot] = 1
        self._keys[slot] = key
        self._temp[slot] = sp.temperature
        self._top_k[slot] = sp.top_k
        self._top_p[slot] = sp.top_p
        self._active[slot] = True
        self._prefill_since = True
        self._emit(slot, st, tok0, lp0)
        self._m_ttft.observe(
            (time.perf_counter() - handle.t_submit) * 1e3)

    # -- decode ------------------------------------------------------------
    def _decode_once(self):
        """One decode step over the active slots.  The plain step keeps
        one step in flight: step t+1 is dispatched on the device's own
        copy of step t's tokens, and only then are step t's fetched and
        delivered, so that the fetch's round trip, the streams'
        wake-ups and the loop's own Python run while the device
        computes.  A block step and a verify step decide on the host
        from what they fetch, and stay synchronous."""
        if self._active.any():
            if self._step_hook is not None:
                try:
                    self._step_hook(self._decode_steps)
                except EngineDeadError:
                    self._die("injected death at decode step %d"
                              % self._decode_steps)
                    raise
            if self.block_length:
                return self._block_once()
            if self.draft_model is not None:
                with _trace.span("generation.grow", cat="generation"):
                    viable = self._spec_viable()
                if viable and self._spec_once():
                    return
            # plain step: make room for ONE new row per active slot
            if self.paged:
                with _trace.span("generation.grow", cat="generation"):
                    for slot in list(np.nonzero(self._active)[0]):
                        if not self._active[slot]:
                            continue   # preempted as an earlier victim
                        if not self._grow_or_preempt(
                                slot, int(self._lengths[slot]) + 1):
                            self._fail_slot(
                                slot, "kv pool exhausted: no preemptable "
                                "slot left to make room")
        before, self._in_flight = self._in_flight, None
        if self._active.any():
            self._dispatch_decode(overlapped=before is not None)
        if before is not None:
            self._deliver(*before)
        if self.draft_model is not None and self._in_flight is not None:
            # a verify step proposes from the tokens on the host
            flight, self._in_flight = self._in_flight, None
            self._deliver(*flight)

    def _set_token(self, slot, token):
        """The host decides ``slot``'s next input token (its prefill's
        sample, a verify step's last): the next plain dispatch writes
        it over the device's copy."""
        self._tok_host[slot] = token
        self._fresh[slot] = True

    def _dispatch_decode(self, overlapped):
        """Launch one plain decode step over the active slots and count
        it as done on the host: lengths, steps and the cache advance
        here, not at delivery, so the next step can be built before
        this one's tokens are known.  A slot whose row in flight is its
        last by length is not in the next step."""
        rows = np.nonzero(self._active)[0]
        args = self._step_shares(1)
        args["overlapped"] = int(overlapped)
        t0 = time.perf_counter()
        with _DeviceCall(self, "generation.decode_dispatch", args=args):
            with _TRACE_LOCK:
                if self._fresh.any():
                    self._last_tokens = self._merge_tokens(
                        self._last_tokens, self._fresh, self._tok_host.copy())
                    self._fresh = np.zeros(self.slots, bool)
                out = self._decode_step_fn(*self._decode_operands())
        self.cache.update(*out[:self._nc])
        self._last_tokens = out[self._nc]
        self._decode_steps += 1
        if overlapped:
            self._m_overlapped.inc()
        else:                          # nothing to measure its gap from
            self._t_fetch, self._prefill_since = t0, False
        # the step writes every ACTIVE slot's new token at its length
        # (inactive rows compute garbage nobody reads: their writes go
        # to the garbage block)
        self._lengths[rows] += 1
        self._steps[rows] += 1
        flight = []
        for slot in rows:
            st = self._slot_state[slot]
            st.in_flight += 1
            flight.append((slot, st))
            if self._length_reason(st, st.generated + st.in_flight):
                self._park(slot)       # ends when this row is delivered
        self._in_flight = (out[self._nc:], flight)

    def _deliver(self, outputs, flight):
        """Fetch a dispatched step's tokens (and log-probabilities) in
        one transfer and hand each to its stream.  A row whose slot no
        longer holds the `_Slot` it was computed for (the request
        stopped on a token, was preempted or failed since the dispatch)
        is dropped: a restarted stream never sees a token of its former
        life."""
        # the host waits here, while the device works on the next step
        with _DeviceCall(self, "generation.decode_fetch"):
            nxt, *lps = jax.device_get(outputs)
        now = time.perf_counter()
        if not self._prefill_since:    # that wait is generation_prefill_ms's
            self._m_itl.observe((now - self._t_fetch) * 1e3)
        self._t_fetch, self._prefill_since = now, False
        with _trace.span("generation.emit", cat="generation"):
            for slot, st in flight:
                st.in_flight -= 1
                if self._slot_state[slot] is not st:
                    self._m_discarded.inc()
                    continue
                token = int(nxt[slot])
                self._tok_host[slot] = token
                self._emit(slot, st, token,
                           float(lps[0][slot]) if lps else None)

    # -- speculative decoding ----------------------------------------------
    def _spec_viable(self):
        """A verify step writes draft_len+1 rows per slot — every
        active slot needs that much max_len headroom, and the pool must
        cover it (otherwise this iteration falls back to plain decode,
        which only needs one row)."""
        active = np.nonzero(self._active)[0]
        if len(active) == 0:
            return False
        s_len = self.draft_len + 1
        if not (self._lengths[active] + s_len <= self.max_len).all():
            return False
        for slot in active:
            if not self._ensure_blocks(
                    slot, int(self._lengths[slot]) + s_len):
                return False
        return True

    def _spec_once(self):
        """Draft k greedy proposals, ONE batched verify, host-side
        acceptance: greedy slots emit the longest draft prefix the
        target agrees with plus the correction token; sampled slots
        emit exactly their row-0 sample (their PRNG stream is
        untouched).  Cache rows for rejected drafts are garbage past
        the new length — later writes overwrite them."""
        k = self.draft_len
        n = self.slots
        drafts = np.zeros((n, k), np.int32)
        cur = self._tok_host.copy()
        t0 = time.perf_counter()
        for i in range(k):
            with _DeviceCall(self, "generation.decode_dispatch"):
                with _TRACE_LOCK:
                    *darrays, nxt = self._draft_decode_fn(
                        self._draft_params, *self._draft_cache.arrays(),
                        self._lengths + np.int32(i), cur, self._active)
            self._draft_cache.update(*darrays)
            with _DeviceCall(self, "generation.decode_fetch"):
                cur = np.asarray(nxt)
            drafts[:, i] = cur
        tok_in = np.concatenate(
            [self._tok_host[:, None], drafts], axis=1).astype(np.int32)
        tables = self._decode_tables()
        with _DeviceCall(self, "generation.decode_dispatch",
                         args=self._step_shares(k + 1)):
            with _TRACE_LOCK:
                out = self._verify_fn(
                    self._params, *self.cache.arrays(), self._lengths,
                    tok_in, self._keys, self._steps, self._temp,
                    self._top_k, self._top_p, tables)
        with _DeviceCall(self, "generation.decode_fetch"):
            toks = np.asarray(out[self._nc])           # [N, S]
            lps = (np.asarray(out[self._nc + 1]) if self.return_logprobs
                   else None)
        self.cache.update(*out[:self._nc])
        self._decode_steps += 1
        self._m_itl.observe((time.perf_counter() - t0) * 1e3)
        with _trace.span("generation.emit", cat="generation"):
            for slot in np.nonzero(self._active)[0]:
                greedy = self._temp[slot] <= 0.0
                j = 0
                if greedy:
                    while j < k and drafts[slot, j] == toks[slot, j]:
                        j += 1
                    self._m_spec_proposed.inc(k)
                    self._m_spec_accepted.inc(j)
                st = self._slot_state[slot]
                for i in range(j + 1):
                    self._lengths[slot] += 1
                    self._steps[slot] += 1
                    t = int(toks[slot, i])
                    self._set_token(slot, t)
                    self._emit(slot, st, t,
                               float(lps[slot, i]) if lps is not None
                               else None)
                    if not self._active[slot]:
                        break          # stop token / limits mid-accept
        return True

    # -- token delivery ----------------------------------------------------
    def _emit(self, slot, st, token, logprob=None):
        """Deliver one generated token and apply stop conditions."""
        st.handle._emit(st.generated, token, logprob)
        st.generated += 1
        self._m_tokens.inc()
        reason = ("stop_token" if token in st.request.stop_token_ids
                  else self._length_reason(st, st.generated))
        if reason is not None:
            self._finish_slot(slot, reason)

    def _length_reason(self, st, count):
        """Why a request ends with its ``count``-th token whatever that
        token is, or None: what a dispatch knows of a row still in
        flight.  (An autoregressive slot's cache holds its prompt and
        all but the newest of its tokens; a block-diffusion request
        always meets ``max_new_tokens`` first.)"""
        if count >= st.request.max_new_tokens:
            return "max_new_tokens"
        if len(st.request.prompt_ids) + count >= self.max_len:
            return "cache_full"
        return None

    def _finish_slot(self, slot, reason):
        st = self._slot_state[slot]
        st.handle._finish(reason)
        self._slot_state[slot] = None
        self._park(slot)
        if self.paged:
            self._release_blocks(slot)
        self._free.append(slot)
        _trace.instant("generation.finish", cat="generation",
                       args={"slot": int(slot), "reason": reason,
                             "request_id": st.request.request_id})

    # -- death (drills / fleet) -------------------------------------------
    def _die(self, why):
        self._dead = True
        self._in_flight = None         # abandoned: its streams restart
        affected = []
        for slot, st in enumerate(self._slot_state):
            if st is not None:
                affected.append(st.handle)
                self._slot_state[slot] = None
            if self._chunking[slot] is not None:
                affected.append(self._chunking[slot].handle)
                self._chunking[slot] = None
            if self.paged and self._slot_blocks[slot]:
                self._release_blocks(slot)
        self._park(slice(None))
        for _, handle in self._pending:
            affected.append(handle)
        self._pending = []
        self._affected_on_death = affected
        _trace.instant("generation.engine_death", cat="generation",
                       args={"engine": self._engine, "why": why})
        tr = _trace.default_tracer()
        if tr.enabled:
            for h in affected:
                tr.async_instant("replica_death", h.trace.trace_id,
                                 cat="generation",
                                 args={"engine": self._engine,
                                       "why": why})
        if self.on_death is not None:
            self.on_death(self, affected)
        else:
            for h in affected:
                h._fail("engine %s died: %s" % (self._engine, why))

    def kill(self, why="killed"):
        """Drill/operator kill: in-flight + queued handles become the
        fleet's requeue set (`affected_on_death`)."""
        with self._lock:
            if not self._dead:
                self._die(why)
            self._work.notify_all()

    @property
    def dead(self):
        return self._dead

    @property
    def affected_on_death(self):
        """Handles that were in flight or queued when the engine died."""
        return list(getattr(self, "_affected_on_death", ()))

    # -- background loop ---------------------------------------------------
    def start(self):
        """Run the scheduler on a background thread (serving mode)."""
        if self._thread is not None:
            return self
        self._t0 = time.perf_counter()
        self._stop = False
        self._thread = threading.Thread(
            target=self._loop, name="genloop-%s" % self._engine,
            daemon=True)
        self._thread.start()
        return self

    def _loop(self):
        while True:
            with self._lock:
                if self._stop or self._dead:
                    return
                busy = (bool(self._pending) or bool(self._active.any())
                        or self._in_flight is not None
                        or any(c is not None for c in self._chunking))
                if not busy:
                    # no work: an idle device here is the traffic's doing
                    with _trace.span("generation.idle_wait",
                                     cat="generation"):
                        self._work.wait(0.05)
                    continue
            try:
                self.step()
            except EngineDeadError:
                return
            except Exception as e:     # pragma: no cover - defensive
                with self._lock:
                    self._die("engine loop crashed: %s: %s"
                              % (type(e).__name__, e))
                return

    def stop(self):
        with self._lock:
            self._stop = True
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    # -- disaggregated prefill/decode (paddle_tpu.tp_serving.disagg) ------
    def prefill_extract(self, request, trace=None):
        """PREFILL-ROLE half of the DistServe split: run ONE prefill
        for ``request`` (whole-prompt flash path), lift the finished KV
        pages + first token off the engine, release the slot, and
        return the `tp_serving.disagg.KVHandoff` a decode-role engine
        ingests with `inject_prefilled`.  Never touches the decode
        executable — a prefill worker's executable set is its prefill
        buckets only.

        ``trace``: optional `TraceContext` (or its wire dict) — the
        prefill span + handoff-begin land on that request's track, and
        the handoff carries the context to the decode worker."""
        from ..tp_serving.disagg import KVHandoff

        if not self.paged or self.block_length:
            raise ValueError("prefill_extract requires paged=True and no "
                             "block diffusion")
        if not isinstance(request, GenerationRequest):
            request = GenerationRequest(request)
        tc = _trace.TraceContext.from_wire(trace)
        fresh_trace = tc is None
        if fresh_trace:
            tc = _trace.TraceContext()
        tr0 = _trace.default_tracer()
        if fresh_trace and tr0.enabled:
            # this prefill opens the request's track (no upstream front
            # began it)
            tr0.async_begin("request", tc.trace_id, cat="generation",
                            args={"request_id": request.request_id})
        sp = request.sampling
        n_prompt = len(request.prompt_ids)
        key = make_base_key(sp.seed).astype(np.uint32)
        with self._lock:
            if self._dead:
                raise EngineDeadError("engine %s is dead" % self._engine)
            if not self._free:
                raise _shed_error(
                    "slots_full", self._retry_after_locked(),
                    "prefill worker %s has no free slot" % self._engine)
            slot = self._free.pop(0)
            self._slot_blocks[slot] = []
            if not self._ensure_blocks(slot, n_prompt):
                self._free.insert(0, slot)
                raise _shed_error(
                    "kv_pool_exhausted", self._retry_after_locked(),
                    "prefill worker %s pool dry" % self._engine)
            bucket = self._bucket_for(n_prompt)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :n_prompt] = request.prompt_ids
            t0 = time.perf_counter()
            tr = _trace.default_tracer()
            if tr.enabled:
                tr.async_begin("prefill", tc.trace_id, cat="generation",
                               args={"bucket": bucket,
                                     "engine": self._engine})
            with _TRACE_LOCK:
                out = self._prefill_fns[bucket](
                    self._params, *self.cache.arrays(), tokens,
                    np.int32(n_prompt), self._prefill_where(slot), key,
                    np.float32(sp.temperature), np.int32(sp.top_k),
                    np.float32(sp.top_p))
            self.cache.update(*out[:self._nc])
            tok0 = int(out[self._nc])
            lp0 = (float(out[self._nc + 1]) if self.return_logprobs
                   else None)
            self._m_prefill_ms.observe((time.perf_counter() - t0) * 1e3)
            if tr.enabled:
                tr.async_end("prefill", tc.trace_id, cat="generation")
            idx = np.asarray(self._slot_blocks[slot], np.int32)
            pages = tuple(np.asarray(a[idx])
                          for a in self.cache.arrays())
            self._release_blocks(slot)
            self._free.append(slot)
        handoff = KVHandoff(
            request=request, n_prompt=n_prompt, tok0=tok0, lp0=lp0,
            key=np.asarray(key), pages=pages,
            block_size=self.block_size,
            kv_dtype=self.cache.kv_dtype,
            trace=tc.child("prefill").to_wire())
        if tr.enabled:
            tr.async_begin("handoff", tc.trace_id, cat="generation",
                           args={"bytes": handoff.nbytes})
        return handoff

    def inject_prefilled(self, handoff, _handle=None):
        """DECODE-ROLE half: queue a `KVHandoff` for adoption into this
        engine's pool (fresh block ids, table row rebuilt).  The
        scheduler arms the slot's decode state and emits token 0 — the
        request decodes here without this engine EVER running a prefill
        executable (`stats()["executables"]["prefill"]` stays untraced,
        the perf-gate pin).  Queueing mirrors `submit`: handoffs wait
        in the same pending queue when slots are busy and shed at
        ``max_queue``.  ``_handle`` re-attaches an existing handle on
        the fleet requeue path."""
        t_enter = time.perf_counter()
        if not self.paged or self.block_length:
            raise ValueError("inject_prefilled requires paged=True and no "
                             "block diffusion")
        if handoff.block_size != self.block_size:
            raise ValueError("handoff block_size %d != engine %d"
                             % (handoff.block_size, self.block_size))
        if handoff.kv_dtype != self.cache.kv_dtype:
            raise ValueError("handoff kv_dtype %r != engine %r"
                             % (handoff.kv_dtype, self.cache.kv_dtype))
        # the wire form is the pool's own: one page array per pool
        # array, [n_blocks, block_size, H*D] (scales [.., H])
        pools = self.cache.arrays()
        if len(handoff.pages) != len(pools) or any(
                page.shape[1:] != a.shape[1:]
                for page, a in zip(handoff.pages, pools)):
            raise ValueError(
                "handoff page geometry %r x %d does not fit pool %r x %d"
                % (handoff.pages[0].shape, len(handoff.pages),
                   pools[0].shape, len(pools)))
        with self._lock:
            if self._dead:
                raise EngineDeadError("engine %s is dead" % self._engine)
            if len(self._pending) >= self.max_queue:
                err = _shed_error(
                    "slots_full", self._retry_after_locked(),
                    "decode worker %s: all %d slots busy and %d "
                    "requests queued"
                    % (self._engine, self.slots, len(self._pending)))
                self._m_shed.labels(self._engine, err.reason).inc()
                self._record_request({
                    "request_id": handoff.request.request_id,
                    "trace_id": None, "t_wall": time.time(),
                    "outcome": "shed", "ttft_ms": None, "itl_ms": None,
                    "n_tokens": 0, "duration_ms": 0.0})
                raise err
            handle = _handle if _handle is not None \
                else RequestHandle(
                    handoff.request,
                    trace=_trace.TraceContext.from_wire(
                        getattr(handoff, "trace", None)))
            handle._sink = self._record_request
            handle.t_queued = t_enter
            tr = _trace.default_tracer()
            if tr.enabled:
                tid = handle.trace.trace_id
                tr.async_end("handoff", tid, cat="generation",
                             args={"engine": self._engine})
                tr.async_begin("queue", tid, cat="generation")
            self._pending.append((handoff, handle))
            self._m_requests.inc()
            self._m_queue.set(len(self._pending))
            self._work.notify_all()
        return handle

    def _inject_into(self, slot, handoff, handle):
        """Adopt a handoff's pages under the lock: alloc fresh blocks,
        rebuild the table row, copy pages in, arm decode.  Returns
        False (caller requeues) when the pool is dry."""
        self._slot_blocks[slot] = []
        n_blocks = int(handoff.pages[0].shape[0])
        try:
            ids = self.cache.pool.alloc(n_blocks)
        except PoolExhausted:
            if self._prefix is not None:
                self._prefix.evict(n_blocks)
            try:
                ids = self.cache.pool.alloc(n_blocks)
            except PoolExhausted:
                return False
        for j, b in enumerate(ids):
            self.cache.assign(slot, j, b)
        self._slot_blocks[slot] = ids
        self._set_block_gauges()
        idx = np.asarray(ids, np.int32)
        arrays = tuple(
            jnp.asarray(a).at[idx].set(page)
            for a, page in zip(self.cache.arrays(), handoff.pages))
        self.cache.update(*arrays)
        tr = _trace.default_tracer()
        if tr.enabled:
            tr.async_instant("inject", handle.trace.trace_id,
                             cat="generation",
                             args={"slot": int(slot),
                                   "blocks": n_blocks})
        self._activate(slot, handoff.request, handle, handoff.tok0,
                       handoff.lp0, handoff.key)
        return True

    # -- weight hot-swap ---------------------------------------------------
    def snapshot_params(self):
        """Host copies of the serving weights — a rollback point for
        `paddle_tpu.rl`'s gated promotion."""
        with self._lock:
            return {k: np.asarray(v) for k, v in self._params.items()}

    def swap_params(self, params):
        """Replace serving weights in place (policy hot-swap).

        The new arrays must match the current parameter names, shapes
        and dtypes exactly — same shapes means the already-compiled
        prefill/decode executables keep serving, so in-flight requests
        see at most one token drawn from the old policy and the swap
        costs zero recompiles and zero failed requests."""
        with self._lock:
            if self._dead:
                raise EngineDeadError("swap_params on dead engine")
            cur = self._params
            new_names = set(map(str, params.keys()))
            if new_names != set(cur.keys()):
                missing = sorted(set(cur.keys()) - new_names)
                extra = sorted(new_names - set(cur.keys()))
                raise ValueError("swap_params name mismatch: missing=%r "
                                 "extra=%r" % (missing, extra))
            staged = {}
            for k, old in cur.items():
                arr = jnp.asarray(params[k])
                if arr.shape != old.shape or arr.dtype != old.dtype:
                    raise ValueError(
                        "swap_params %r: got %s %s, engine serves %s %s"
                        % (k, arr.shape, arr.dtype, old.shape, old.dtype))
                staged[k] = arr
            self._params = staged

    # -- introspection -----------------------------------------------------
    @staticmethod
    def _jit_cache_size(fn):
        try:
            return int(fn._cache_size())
        except Exception:
            return -1

    def decode_hlo(self):
        """Optimized HLO of the ACTUAL decode executable, lowered with
        the engine's live operands — what the TP comm drills pin
        `decode_comm_estimate` against and `chip_smoke.py` searches for
        a kernel's custom call."""
        with self._lock, _TRACE_LOCK:      # lowering retraces the model
            lowered = self._decode_step_fn.lower(*self._decode_operands())
        return lowered.compile().as_text()

    def _decode_operands(self):
        """The decode executable's live operands; the last one says who
        is live: a paged engine's block tables, a dense one's mask."""
        if self.block_length:
            return (self._params, *self.cache.arrays(), self._lengths,
                    self._blk_tokens,
                    self._blk_revealed | self._blk_beyond, self._keys,
                    self._steps, self._temp, self._top_k, self._top_p,
                    self._decode_tables())
        # the host's arrays are copies: the step may still be reading
        # them when a slot's state is next written
        return (self._params, *self.cache.arrays(), self._lengths.copy(),
                self._last_tokens, self._keys.copy(), self._steps.copy(),
                self._temp.copy(), self._top_k.copy(), self._top_p.copy(),
                self._decode_tables() if self.paged else self._active.copy())

    def _decode_cache_size(self):
        """Jit-cache entries of the decode step — the compile-once pin."""
        return self._jit_cache_size(self._decode_step_fn)

    def occupancy(self):
        with self._lock:
            return {
                "slots": self.slots,
                "active": int(self._active.sum()),
                "chunking": sum(c is not None for c in self._chunking),
                "free": len(self._free),
                "pending": len(self._pending),
            }

    def stats(self):
        occ = self.occupancy()
        occ.update({
            "engine": self._engine,
            "dead": self._dead,
            "decode_steps": self._decode_steps,
            "max_len": self.max_len,
            "prefill_buckets": list(self.prefill_buckets),
            "cache": self.cache.describe(),
            "decode_executables": self._decode_cache_size(),
            "preempted": int(self._m_preempt.value),
            # over ``decode_steps``: the share of plain steps dispatched
            # while the step before was un-fetched
            "decode_overlapped": int(self._m_overlapped.value),
            "decode_rows_discarded": int(self._m_discarded.value),
            # mean over the decode/verify steps so far (None before one)
            "attn_walk_share": self._m_walk.summary().get("mean"),
            "sampling_step_share": self._m_sampling.summary().get("mean"),
        })
        ex = {
            "decode_step": self._decode_cache_size(),
            "prefill": {b: self._jit_cache_size(f)
                        for b, f in self._prefill_fns.items()},
            "chunk": {w: self._jit_cache_size(f)
                      for w, f in self._chunk_fns.items()},
        }
        if self.draft_model is not None:
            ex["verify"] = self._jit_cache_size(self._verify_fn)
            ex["draft_decode"] = self._jit_cache_size(
                self._draft_decode_fn)
            ex["draft_prefill"] = {
                b: self._jit_cache_size(f)
                for b, f in self._draft_prefill_fns.items()}
        occ["executables"] = ex
        if self._prefix is not None:
            occ["prefix_cache"] = self._prefix.stats()
        if self.block_length:
            # ``decode_steps`` counts calls of the block step; a call is
            # one pass for each of its live slots
            occ["block_diffusion"] = {
                "block_length": self.block_length,
                "denoising_steps": self.denoising_steps,
                "remasking": self.remasking,
                "passes": int(self._m_block_passes.value),
                "commits": int(self._m_block_commits.value),
                "tokens_streamed": int(self._m_tokens.value),
            }
        if self.draft_model is not None:
            proposed = int(self._m_spec_proposed.value)
            accepted = int(self._m_spec_accepted.value)
            occ["speculative"] = {
                "draft_len": self.draft_len,
                "proposed": proposed,
                "accepted": accepted,
                "acceptance_rate": (accepted / proposed) if proposed
                else 0.0,
            }
        return occ

    # -- convenience -------------------------------------------------------
    def generate(self, prompts, max_new_tokens=16, sampling=None,
                 stop_token_ids=(), timeout=120.0):
        """Synchronous batch helper: submit all, drive to idle, return
        token lists in prompt order."""
        handles = []
        for i, p in enumerate(prompts):
            sp = sampling[i] if isinstance(sampling, (list, tuple)) \
                else sampling
            handles.append(self.submit(GenerationRequest(
                p, max_new_tokens=max_new_tokens, sampling=sp,
                stop_token_ids=stop_token_ids)))
        if self._thread is None:
            self.run_until_idle()
        return [h.result(timeout=timeout) for h in handles]


def sequential_oracle(make_engine, requests, timeout=120.0):
    """The exactness reference: a FRESH engine per request, one request
    at a time — no continuous batching, no slot reuse, no shared state.
    Returns the per-request token lists.  `make_engine()` must build an
    engine with the same (slots, max_len, buckets) config as the engine
    under test."""
    out = []
    for r in requests:
        eng = make_engine()
        h = eng.submit(GenerationRequest(
            r.prompt_ids, max_new_tokens=r.max_new_tokens,
            sampling=r.sampling, stop_token_ids=r.stop_token_ids,
            request_id=r.request_id + ":oracle"))
        eng.run_until_idle()
        out.append(h.result(timeout=timeout))
    return out
