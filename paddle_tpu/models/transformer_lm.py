"""Decoder-only transformer LM — the `paddle_tpu.generation` model.

Reuses the BERT blocks (`MultiHeadAttention` with fused QKV, gelu FFN)
in the pre-LN arrangement with causal self-attention and tied
input/output embeddings (GPT-style).  Three forward modes:

* ``forward(ids, pos)`` — full causal forward (training / the
  recompute-prefix baseline `benchmarks/generation_bench.py` A/Bs the
  KV cache against);
* ``forward(..., use_cache=True)`` — prefill: same math on the flash
  path, but every layer also hands back its projected ``(k, v)``
  ``[B, S, H, Dh]`` arrays for the engine to copy into its slot cache;
* ``forward(..., caches=[(k_0, v_0), ...], cache_positions=pos)`` —
  decode: one token per row; each layer's K/V written at ``pos`` into
  that layer's own ``[N, T, H*Dh]`` cache arrays (`generation.kv_cache`:
  one array per layer, heads merged into the last dimension) and
  attention runs over the cache (`ops.cached_attention.
  cached_attention`), returning the updated arrays, again one tuple
  per layer.  Fixed shapes, so the engine's decode step compiles ONCE.
"""

from __future__ import annotations

from ..fluid import dygraph, layers
from .bert import BertConfig, MultiHeadAttention, _winit


class TransformerLMConfig:
    def __init__(
        self,
        vocab_size=32000,
        hidden_size=768,
        num_layers=12,
        num_heads=12,
        intermediate_size=3072,
        max_position_embeddings=1024,
        dropout=0.1,
        initializer_range=0.02,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.dropout = dropout
        self.initializer_range = initializer_range

    @staticmethod
    def tiny():
        """For tests, CPU smoke benches, and dry runs."""
        return TransformerLMConfig(
            vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
            intermediate_size=64, max_position_embeddings=128,
            dropout=0.0)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    def _bert_cfg(self):
        """Adapter so the shared BERT blocks read their hyperparams."""
        return BertConfig(
            vocab_size=self.vocab_size,
            hidden_size=self.hidden_size,
            num_attention_heads=self.num_heads,
            intermediate_size=self.intermediate_size,
            max_position_embeddings=self.max_position_embeddings,
            hidden_dropout_prob=self.dropout,
            attention_probs_dropout_prob=self.dropout,
            initializer_range=self.initializer_range,
        )


class TransformerLMBlock(dygraph.Layer):
    """Pre-LN decoder block: causal self-attention + gelu FFN."""

    def __init__(self, cfg: TransformerLMConfig):
        super().__init__()
        bcfg = cfg._bert_cfg()
        d = cfg.hidden_size
        self.ln1 = dygraph.LayerNorm(d)
        self.attn = MultiHeadAttention(bcfg, self_attention=True)
        self.ln2 = dygraph.LayerNorm(d)
        self.fc1 = dygraph.Linear(d, cfg.intermediate_size,
                                  param_attr=_winit(bcfg))
        self.fc2 = dygraph.Linear(cfg.intermediate_size, d,
                                  param_attr=_winit(bcfg))
        self.dropout = dygraph.Dropout(
            cfg.dropout, dropout_implementation="upscale_in_train")

    def forward(self, x, cache=None, use_cache=False):
        a = self.attn(self.ln1(x), causal=cache is None, cache=cache,
                      use_cache=use_cache)
        kv = None
        if use_cache or cache is not None:
            a, kv = a
        x = x + a
        f = self.fc2(layers.gelu(self.fc1(self.ln2(x))))
        x = x + self.dropout(f)
        return (x, kv) if kv is not None else x


class TransformerLM(dygraph.Layer):
    """See module docstring.  ``logits = h @ word_embedding^T`` (tied)."""

    def __init__(self, cfg: TransformerLMConfig):
        super().__init__()
        self.cfg = cfg
        bcfg = cfg._bert_cfg()
        self.word = dygraph.Embedding(
            [cfg.vocab_size, cfg.hidden_size], param_attr=_winit(bcfg))
        self.position = dygraph.Embedding(
            [cfg.max_position_embeddings, cfg.hidden_size],
            param_attr=_winit(bcfg))
        self.dropout = dygraph.Dropout(
            cfg.dropout, dropout_implementation="upscale_in_train")
        self.blocks = dygraph.LayerList(
            [TransformerLMBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = dygraph.LayerNorm(cfg.hidden_size)

    def forward(self, input_ids, position_ids, caches=None,
                cache_positions=None, use_cache=False,
                block_tables=None, block_size=None, cache_live=None):
        """input_ids/position_ids: [B, S] int.  With ``caches`` given
        (decode/chunk: S tokens per row written at positions
        ``cache_positions..+S-1``, row i attending the cache through
        position ``cache_positions+i``), the return is
        ``(logits [B, S, V], updated cache arrays)``; with
        ``use_cache=True`` (prefill) it is ``(logits, [(k, v), ...])``
        per layer; otherwise just ``logits [B, S, V]``.

        ``caches`` is one tuple per layer (`generation.kv_cache.
        group_layers` of a cache's arrays): ``(k_l, v_l)`` dense
        ``[B, T, H*Dh]`` arrays (PR-15), or — when ``block_tables``
        ``[B, max_blocks]`` and ``block_size`` are given — PAGED pools
        ``[NB, bs, H*Dh]``, optionally followed by the layer's int8
        per-row scales ``[NB, bs, H]`` (``(k_l, v_l, k_scale_l,
        v_scale_l)``).  Rows that are not live attend nothing: those
        whose table row is all zeros, or for dense caches those where
        ``cache_live`` ``[B]`` bool, if given, is false."""
        s_len = int(input_ids.shape[1])
        emb = self.word(input_ids) + self.position(position_ids)
        # the lookup op squeezes Paddle's [B, 1] ids convention; decode
        # (S == 1) needs the sequence axis back
        emb = layers.reshape(emb, [0, s_len, self.cfg.hidden_size])
        h = self.dropout(emb)
        new_kv = []
        if caches is not None:
            tail = ((cache_positions, cache_live) if block_tables is None
                    else (cache_positions, block_tables, block_size))
            out_caches = []
            for block, mine in zip(self.blocks, caches):
                # the block writes into ITS arrays and hands them back;
                # nothing is sliced out of a stack or stacked again
                h, updated = block(h, cache=tuple(mine) + tail)
                out_caches.append(tuple(updated))
        else:
            for block in self.blocks:
                if use_cache:
                    h, kv = block(h, use_cache=True)
                    new_kv.append(kv)
                else:
                    h = block(h)
        h = self.ln_f(h)
        logits = layers.matmul(h, self.word.weight, transpose_y=True)
        if caches is not None:
            return logits, out_caches
        if use_cache:
            return logits, new_kv
        return logits

    def loss(self, logits, labels):
        """Next-token cross entropy ([B, S, V] vs [B, S] shifted ids)."""
        vocab = int(logits.shape[-1])
        flat = layers.reshape(logits, [-1, vocab])
        lab = layers.reshape(labels, [-1, 1])
        return layers.reduce_mean(
            layers.softmax_with_cross_entropy(flat, lab))

    def token_logprob(self, logits, labels):
        """Per-token log-probability of ``labels`` under the raw
        softmax ([B, S, V] vs [B, S] -> [B, S]) — the dygraph mirror of
        `generation.sampling.token_logprobs`.  `paddle_tpu.rl`
        recomputes new-policy logprobs through this so train-time and
        rollout-time densities agree token for token."""
        vocab = int(logits.shape[-1])
        flat = layers.reshape(logits, [-1, vocab])
        lab = layers.reshape(labels, [-1, 1])
        nll = layers.softmax_with_cross_entropy(flat, lab)
        return layers.reshape(layers.scale(nll, scale=-1.0),
                              [int(labels.shape[0]), int(labels.shape[1])])
