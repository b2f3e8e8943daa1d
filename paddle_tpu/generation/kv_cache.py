"""KV caches: the decode step's working set, dense and PAGED.

Both caches hold ONE ARRAY PER LAYER for K and for V, heads and head
dimension merged into one last dimension (`_layer_shape` says why):
``arrays()`` is the flat tuple ``(k_0..k_{L-1}, v_0..v_{L-1})`` (int8
pools: then the scales the same way), the donated operands of every
step function in the engine's argument order, and ``update(*arrays)``
its inverse.  A step writes its new rows into each layer's own donated
array (`ops.cached_attention.kv_write`) and that array is the
step's output: nothing pool-sized is sliced, stacked or copied.

`KVCache` (PR 15) is the dense layout — ``[slots, max_len, H*D]`` per
layer, every slot paying ``max_len`` HBM whether its sequence is 20
tokens or 2000.

`PagedKVCache` rebuilds the store as a BLOCK POOL:

* device arrays ``[num_blocks, block_size, H*D]`` per layer (k and v)
  — a fixed-shape pool every slot draws from, so the compiled decode
  executable never changes as blocks migrate between requests;
* a host-side per-slot block table ``[slots, max_blocks_per_slot]``
  int32 mapping logical block j to a physical pool block.  The table
  is passed to the jitted step as DATA;
* `BlockPool` — the refcounted allocator.  Block 0 is the reserved
  garbage block: inactive slots' table rows point at it, so the
  batched decode step's dead-row writes land somewhere nobody reads;
* `PrefixCache` — refcounted FULL-block reuse keyed by a token-chain
  hash (vLLM's prefix caching): two requests sharing a system prompt
  share the physical blocks, and the second skips that prefill
  entirely.  Only full blocks are ever shared, so the writable tail is
  always private and copy-on-write never arises;
* optional int8 storage (``kv_dtype="int8"``): pools hold int8 rows
  plus per-row per-head f32 scales ``[num_blocks, block_size, H]`` — halving (vs f32: quartering) the
  KV bytes the memory-bound step streams; opt-in, since values move
  within the quantization error (bounds in tests/test_generation.py).

Capacity math: dense charges ``slots * max_len`` rows; the pool charges
``num_blocks * block_size`` rows — provisioned to the MEAN sequence
length rather than the max (``analysis.perf.decode_step_cost`` prices
both).  When the pool runs dry the engine preempts, requeues, and
retries — admission is measured, not provisioned-for-worst-case.
"""

from __future__ import annotations

import hashlib
import heapq

import jax.numpy as jnp
import numpy as np

__all__ = ["BlockPool", "KVCache", "PagedKVCache", "PoolExhausted",
           "PrefixCache", "flatten_layers", "group_layers"]


def group_layers(arrays, num_layers):
    """The flat ``arrays()`` tuple as one tuple per layer,
    ``[(k_l, v_l), ...]`` (an int8 pool: ``(k_l, v_l, k_scale_l,
    v_scale_l)``): what a cached forward takes, so that a model hands
    each block its own arrays and never learns the engine's operand
    order."""
    return [tuple(arrays[li::num_layers]) for li in range(num_layers)]


def flatten_layers(layers):
    """Inverse of `group_layers`: per-layer tuples back into the order
    of ``arrays()`` (each kind of array runs over the layers)."""
    return tuple(a for kind in zip(*layers) for a in kind)


def _layer_shape(rows, row_len, num_heads, head_dim):
    """One layer's K (or V) array: ``[rows, row_len, H*D]``.

    Why the heads are merged into the last dimension: the chip tiles an
    array's two minor dimensions into (8, 128) float32 tiles.  With
    ``[..., H, D]`` and D = 64 every tile would be half padding, so for
    ``f32[1025, 16, 16, 64]`` the compiler instead stores the array
    with the BLOCK index minor-most (``{0,3,2,1:T(8,128)}``, 1025
    padded to 1152), a layout in which nothing can be scattered or
    gathered by block: every step then transposed each layer's pool to
    row-major, updated it and transposed it back (PERF.md section 6,
    PR 27).  With H*D a multiple of 128 (1024 = 8 lane tiles for
    GPT-2-medium) the default layout of the merged form is row-major
    and unpadded, a row write is an in-place scatter into the donated
    array and a block gather reads contiguous rows.  Where H*D is not
    such a multiple (the tests' tiny models) the last dimension is
    padded up to the next one, which is still never more than the
    padding of a separate ``[H, D]`` pair, so the one form serves every
    head shape."""
    return (int(rows), int(row_len), int(num_heads) * int(head_dim))


def _adopt(cache, arrays):
    """`update` of both caches: adopt the arrays a donated call
    returned, in the order of `arrays()` (the old handles are invalid
    once donated — never keep them)."""
    if len(arrays) != len(cache._arrays):
        raise ValueError("expected %d cache arrays, got %d"
                         % (len(cache._arrays), len(arrays)))
    cache._arrays = tuple(arrays)


def _nbytes(arrays):
    return int(sum(a.size * a.dtype.itemsize for a in arrays))


class KVCache:
    """Dense host-side handle (see module doc) — the PR-15 layout, kept
    as the paged engine's A/B baseline and the draft model's cache."""

    def __init__(self, num_layers, slots, max_len, num_heads, head_dim,
                 dtype=jnp.float32):
        self.num_layers = int(num_layers)
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.dtype = jnp.dtype(dtype)
        self.layer_shape = _layer_shape(self.slots, self.max_len,
                                        self.num_heads, self.head_dim)
        self._arrays = tuple(jnp.zeros(self.layer_shape, self.dtype)
                             for _ in range(2 * self.num_layers))

    @property
    def nbytes(self):
        return _nbytes(self._arrays)

    def arrays(self):
        """``(k_0..k_{L-1}, v_0..v_{L-1})``: the donated operands."""
        return self._arrays

    def update(self, *arrays):
        """Adopt donated-call outputs (order of `arrays`)."""
        _adopt(self, arrays)

    def describe(self):
        return {
            "layers": self.num_layers, "slots": self.slots,
            "max_len": self.max_len, "heads": self.num_heads,
            "head_dim": self.head_dim, "dtype": str(self.dtype),
            "bytes": self.nbytes, "paged": False,
        }


class PoolExhausted(RuntimeError):
    """No free block — the engine's preempt/requeue trigger."""


class BlockPool:
    """Refcounted allocator over the pool's block axis (host-side).

    Deterministic: allocation always hands out the LOWEST free block id
    (a heap), so a fixed request schedule produces a fixed block
    layout — the exactness drills rely on nothing, but debuggability
    does.  Block 0 is reserved (the garbage block) and never leaves the
    pool."""

    def __init__(self, num_blocks):
        if num_blocks < 2:
            raise ValueError("pool needs >= 2 blocks (block 0 is "
                             "reserved), got %d" % num_blocks)
        self.num_blocks = int(num_blocks)
        self._ref = np.zeros(self.num_blocks, np.int32)
        self._ref[0] = 1                       # garbage block, pinned
        self._free = list(range(1, self.num_blocks))
        heapq.heapify(self._free)

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def used_blocks(self):
        return self.num_blocks - 1 - len(self._free)

    def alloc(self, n):
        """n fresh blocks (refcount 1 each) or `PoolExhausted` — the
        caller decides whether to evict, preempt, or shed."""
        if n > len(self._free):
            raise PoolExhausted(
                "need %d blocks, %d free of %d"
                % (n, len(self._free), self.num_blocks))
        ids = [heapq.heappop(self._free) for _ in range(n)]
        for b in ids:
            self._ref[b] = 1
        return ids

    def incref(self, ids):
        for b in ids:
            if self._ref[b] <= 0:
                raise ValueError("incref on free block %d" % b)
            self._ref[b] += 1

    def decref(self, ids):
        """Drop one reference per id; blocks hitting zero return to the
        free list.  Returns the freed ids (the leak drill's assert)."""
        freed = []
        for b in ids:
            if b == 0:
                raise ValueError("decref on the reserved garbage block")
            if self._ref[b] <= 0:
                raise ValueError("double free of block %d" % b)
            self._ref[b] -= 1
            if self._ref[b] == 0:
                heapq.heappush(self._free, b)
                freed.append(b)
        return freed

    def refcount(self, block_id):
        return int(self._ref[block_id])


class PrefixCache:
    """Refcounted full-block prefix reuse keyed by a token-chain hash.

    Key of block j = H(key_{j-1} || tokens of block j) — a chain, so a
    lookup walks the prompt's full blocks until the first miss and
    every hit is an EXACT token-prefix match (hash collisions aside;
    sha1 over the literal token bytes).  The registry holds one pool
    reference per cached block; each slot using a block holds another —
    a shared block frees only when the last user AND the registry let
    go.  Eviction is LRU over chains with no registry children and no
    outside users, triggered by allocation pressure."""

    def __init__(self, pool, block_size):
        self.pool = pool
        self.block_size = int(block_size)
        # key -> [block_id, parent_key, last_use, n_child]
        self._entries = {}
        self._clock = 0
        self.hits = 0
        self.misses = 0
        self.hit_tokens = 0

    def __len__(self):
        return len(self._entries)

    @staticmethod
    def _key(parent, tokens):
        h = hashlib.sha1()
        h.update(parent.encode() if parent else b"root")
        h.update(np.asarray(tokens, np.int64).tobytes())
        return h.hexdigest()

    def _chain_keys(self, prompt_ids, max_tokens):
        """Keys of the full blocks covering <= max_tokens prompt
        tokens, in order."""
        bs = self.block_size
        keys, parent = [], ""
        for j in range(max_tokens // bs):
            parent = self._key(parent, prompt_ids[j * bs:(j + 1) * bs])
            keys.append(parent)
        return keys

    def lookup(self, prompt_ids):
        """Longest cached prefix of ``prompt_ids``, capped one token
        short of the full prompt (a hit must still leave >= 1 token to
        prefill — its logits seed generation).  Returns
        ``(n_tokens, block_ids)`` with one pool reference taken per
        returned block (the caller's to decref on release)."""
        keys = self._chain_keys(prompt_ids, len(prompt_ids) - 1)
        blocks = []
        for key in keys:
            ent = self._entries.get(key)
            if ent is None:
                break
            self._clock += 1
            ent[2] = self._clock
            blocks.append(ent[0])
        if blocks:
            self.pool.incref(blocks)
            self.hits += 1
            self.hit_tokens += len(blocks) * self.block_size
        else:
            self.misses += 1
        return len(blocks) * self.block_size, blocks

    def register(self, prompt_ids, block_ids):
        """Publish a freshly prefilled prompt's FULL blocks.  The
        registry increfs what it adopts; already-registered prefixes
        (including the ones this request was served from) are left
        alone."""
        keys = self._chain_keys(prompt_ids, len(prompt_ids))
        parent = ""
        for j, key in enumerate(keys):
            if key not in self._entries:
                self._clock += 1
                self.pool.incref([block_ids[j]])
                self._entries[key] = [block_ids[j], parent,
                                      self._clock, 0]
                if parent:
                    self._entries[parent][3] += 1
            parent = key

    def evict(self, n_blocks_needed):
        """Free LRU chains (leaf-first, registry-only references) until
        ``n_blocks_needed`` blocks are free or nothing evictable is
        left.  Returns the number of blocks actually freed."""
        freed = 0
        while self.pool.free_blocks < n_blocks_needed:
            victims = [
                (ent[2], key) for key, ent in self._entries.items()
                if ent[3] == 0 and self.pool.refcount(ent[0]) == 1
            ]
            if not victims:
                break
            _, key = min(victims)
            ent = self._entries.pop(key)
            if ent[1]:
                self._entries[ent[1]][3] -= 1
            freed += len(self.pool.decref([ent[0]]))
        return freed

    def stats(self):
        total = self.hits + self.misses
        return {
            "entries": len(self._entries),
            "hits": self.hits, "misses": self.misses,
            "hit_rate": (self.hits / total) if total else 0.0,
            "hit_tokens": self.hit_tokens,
        }


class PagedKVCache:
    """Host-side handle of the paged device pool (see module doc).

    ``num_blocks`` INCLUDES block 0 (the reserved garbage block); the
    usable capacity is ``(num_blocks - 1) * block_size`` token rows."""

    def __init__(self, num_layers, num_blocks, block_size, num_heads,
                 head_dim, slots, max_len, dtype=jnp.float32,
                 kv_dtype=None):
        self.num_layers = int(num_layers)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.max_blocks_per_slot = -(-self.max_len // self.block_size)
        self.dtype = jnp.dtype(dtype)
        if kv_dtype not in (None, "int8"):
            raise ValueError("kv_dtype must be None or 'int8', got %r"
                             % (kv_dtype,))
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == "int8"
        store = jnp.int8 if self.quantized else self.dtype
        self.layer_shape = _layer_shape(self.num_blocks, self.block_size,
                                        self.num_heads, self.head_dim)
        n = 2 * self.num_layers
        self._arrays = tuple(jnp.zeros(self.layer_shape, store)
                             for _ in range(n))
        if self.quantized:
            self._arrays += tuple(
                jnp.zeros(self.layer_shape[:2] + (self.num_heads,),
                          jnp.float32) for _ in range(n))
        self.pool = BlockPool(self.num_blocks)
        self.block_tables = np.zeros(
            (self.slots, self.max_blocks_per_slot), np.int32)

    @property
    def nbytes(self):
        return _nbytes(self._arrays)

    @property
    def capacity_tokens(self):
        return (self.num_blocks - 1) * self.block_size

    def arrays(self):
        """The donated operands, in the engine's argument order:
        ``(k_0..k_{L-1}, v_0..v_{L-1})``, for an int8 pool followed by
        its scales the same way."""
        return self._arrays

    def update(self, *arrays):
        """Adopt donated-call outputs (order of `arrays`)."""
        _adopt(self, arrays)

    # -- slot bookkeeping (host) ------------------------------------------
    def blocks_for(self, n_tokens):
        return -(-int(n_tokens) // self.block_size)

    def table_row(self, slot):
        return self.block_tables[slot]

    def assign(self, slot, logical_index, block_id):
        self.block_tables[slot, logical_index] = block_id

    def clear_slot(self, slot):
        """Zero the table row — every entry points back at the garbage
        block.  Reference bookkeeping is the ENGINE's job (it knows
        which entries were shared); this only kills the indirection."""
        self.block_tables[slot, :] = 0

    def describe(self):
        return {
            "layers": self.num_layers, "slots": self.slots,
            "max_len": self.max_len, "heads": self.num_heads,
            "head_dim": self.head_dim, "dtype": str(self.dtype),
            "bytes": self.nbytes, "paged": True,
            "block_size": self.block_size,
            "num_blocks": self.num_blocks,
            "capacity_tokens": self.capacity_tokens,
            "kv_dtype": self.kv_dtype or str(self.dtype),
            "blocks_used": self.pool.used_blocks,
            "blocks_free": self.pool.free_blocks,
        }
