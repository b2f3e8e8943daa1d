"""BERT/ERNIE-base encoder + pretraining heads — the flagship model.

Capability parity: the BASELINE.md north star is the PaddleNLP ERNIE-1.0 /
BERT-base pretraining recipe (reference repo ships the framework; the model
recipe comes from the companion models repo).  Architecture: learned
word/position/segment embeddings -> N transformer encoder layers
(post-LN, gelu FFN) -> MLM + NSP heads, matching bert-base hyperparameters.

Attention uses the fused `flash_attention` op (pallas kernel on TPU).
"""

from __future__ import annotations

import math

from ..fluid import dygraph, layers
from ..fluid.initializer import NormalInitializer, ConstantInitializer
from ..fluid.layer_helper import ParamAttr
from ..fluid.layers.common import append_simple_op


class BertConfig:
    def __init__(
        self,
        vocab_size=30522,
        hidden_size=768,
        num_hidden_layers=12,
        num_attention_heads=12,
        intermediate_size=3072,
        max_position_embeddings=512,
        type_vocab_size=2,
        hidden_dropout_prob=0.1,
        attention_probs_dropout_prob=0.1,
        initializer_range=0.02,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.initializer_range = initializer_range

    @staticmethod
    def base():
        return BertConfig()

    @staticmethod
    def tiny():
        """For tests and dry runs."""
        return BertConfig(
            vocab_size=128, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=64, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0,
        )


def _winit(cfg):
    return ParamAttr(initializer=NormalInitializer(0.0, cfg.initializer_range))


def convert_legacy_qkv_state_dict(state_dict, target_keys):
    """Fuse pre-fusion checkpoints (separate q_proj/k_proj/v_proj weights)
    into the fused qkv_proj layout so old checkpoints keep loading."""
    import numpy as np

    def val(v):
        return np.asarray(getattr(v, "data", v))

    out = dict(state_dict)
    for key in target_keys:
        if not key.endswith("qkv_proj.weight") or key in out:
            continue
        base = key[: -len("qkv_proj.weight")]
        try:
            w = [val(out.pop(base + p + "_proj.weight"))
                 for p in ("q", "k", "v")]
            b = [val(out.pop(base + p + "_proj.bias"))
                 for p in ("q", "k", "v")]
        except KeyError:
            continue
        out[key] = np.concatenate(w, axis=1)
        out[base + "qkv_proj.bias"] = np.concatenate(b, axis=0)
    return out


class _QkvCompatMixin:
    def set_state_dict(self, state_dict, include_sublayers=True):
        state_dict = convert_legacy_qkv_state_dict(
            state_dict, self.state_dict(include_sublayers).keys())
        return super().set_state_dict(state_dict, include_sublayers)


class MultiHeadAttention(dygraph.Layer):
    """Self/cross attention over the fused flash_attention op."""

    def __init__(self, cfg, d_model=None, n_head=None, dropout=None,
                 self_attention=False):
        super().__init__()
        d = d_model or cfg.hidden_size
        self.n_head = n_head or cfg.num_attention_heads
        self.d_head = d // self.n_head
        self.fused_qkv = bool(self_attention)
        if self.fused_qkv:
            # self-attention: ONE fused [D, 3D] projection (one MXU matmul
            # instead of three; megatron fused-qkv column-parallel layout)
            self.qkv_proj = dygraph.Linear(d, 3 * d, param_attr=_winit(cfg))
        else:
            self.q_proj = dygraph.Linear(d, d, param_attr=_winit(cfg))
            self.k_proj = dygraph.Linear(d, d, param_attr=_winit(cfg))
            self.v_proj = dygraph.Linear(d, d, param_attr=_winit(cfg))
        self.out_proj = dygraph.Linear(d, d, param_attr=_winit(cfg))
        self.dropout = dygraph.Dropout(
            dropout if dropout is not None else cfg.attention_probs_dropout_prob,
            dropout_implementation="upscale_in_train",
        )

    def _split(self, x, seq_len):
        # [B, S, D] -> [B, S, H, Dh], the cache's layout; the flash op
        # takes it as "BSHD" and transposes around its head-major kernels
        return layers.reshape(x, [0, seq_len, self.n_head, self.d_head])

    def forward(self, query, key=None, value=None, attn_bias=None,
                causal=False, segment_ids=None, cache=None,
                use_cache=False):
        """``cache``/``use_cache`` are the decode-engine hooks
        (`paddle_tpu.generation`):

        * ``use_cache=True`` (prefill): the normal forward, but also
          returns the projected ``(k, v)`` as raw ``[B, S, H, Dh]``
          jax arrays — what the engine copies into its slot cache.
        * ``cache=(k_cache, v_cache, pos)`` (decode): ``query`` is ONE
          token per row; its K/V are written into this layer's
          ``[B, T, H*Dh]`` cache arrays at index ``pos`` ([B] int) and
          attention runs over the cache with positions ``<= pos`` live
          (`ops.cached_attention.cached_attention`).
          Returns ``(out, (k_cache', v_cache'))``.  The paged forms
          are in `_decode_with_cache`.
        """
        key = key if key is not None else query
        value = value if value is not None else key
        q_len = int(query.shape[1])
        kv_len = int(key.shape[1])
        if self.fused_qkv:
            if key is not query or value is not key:
                raise ValueError(
                    "fused-qkv attention is self-attention only; build "
                    "with self_attention=False for cross attention")
            qkv = self.qkv_proj(query)           # [B, S, 3D]
            d = self.n_head * self.d_head
            q = self._split(layers.slice(qkv, [2], [0], [d]), q_len)
            k = self._split(layers.slice(qkv, [2], [d], [2 * d]), kv_len)
            v = self._split(layers.slice(qkv, [2], [2 * d], [3 * d]), kv_len)
        else:
            q = self._split(self.q_proj(query), q_len)
            k = self._split(self.k_proj(key), kv_len)
            v = self._split(self.v_proj(value), kv_len)
        if cache is not None:
            return self._decode_with_cache(q, k, v, cache)
        ins = {"Q": q, "K": k, "V": v}
        if attn_bias is not None:
            ins["Bias"] = attn_bias
        if segment_ids is not None:
            # packed batch: a [B, S] id array (self-attention) or a
            # (q_seg, kv_seg) pair for cross-attention over packed memory;
            # attention is confined to equal ids
            if isinstance(segment_ids, (tuple, list)):
                qseg, kseg = segment_ids
            else:
                if key is not query:
                    raise ValueError(
                        "cross-attention with packed segments needs a "
                        "(q_seg, kv_seg) pair, got a single id array"
                    )
                qseg = kseg = segment_ids
            ins["QSeg"] = qseg
            ins["KSeg"] = kseg
        ctxv = append_simple_op(
            "flash_attention",
            ins,
            {"scale": self.d_head ** -0.5, "causal": causal,
             "layout": "BSHD"},
        )
        ctxv = layers.reshape(ctxv, [0, q_len, self.n_head * self.d_head])
        out = self.dropout(self.out_proj(ctxv))
        if use_cache:
            # BSHD is the cache-native layout
            return out, (k.data, v.data)
        return out

    def _decode_with_cache(self, q, k, v, cache):
        """Decode/chunk attention over one layer's cache: the C query
        tokens' K/V are written at positions ``pos..pos+C-1`` of the
        layer's own cache arrays and row i attends positions
        ``<= pos+i``.  The cache tuple forms (dense, paged, paged int8;
        arrays with heads and head dimension merged) and the math are
        `ops.cached_attention.cached_attention`'s, which the
        tensor-parallel forward shares.  Returns ``(out, updated cache
        arrays)`` in the order the tuple carried them."""
        import jax.numpy as jnp

        from ..fluid.dygraph import to_variable
        from ..ops.cached_attention import cached_attention

        ctx, new_cache = cached_attention(
            jnp.asarray(q.data), jnp.asarray(k.data), jnp.asarray(v.data),
            cache, scale=self.d_head ** -0.5)
        ctxv = to_variable(ctx)                      # [B, C, H, Dh]
        ctxv = layers.reshape(
            ctxv, [0, int(q.shape[1]), self.n_head * self.d_head])
        return self.dropout(self.out_proj(ctxv)), new_cache


class TransformerEncoderLayer(dygraph.Layer):
    """Post-LN encoder block (BERT style)."""

    def __init__(self, cfg):
        super().__init__()
        d = cfg.hidden_size
        self.attn = MultiHeadAttention(cfg, self_attention=True)
        self.ln1 = dygraph.LayerNorm(d)
        self.fc1 = dygraph.Linear(d, cfg.intermediate_size, param_attr=_winit(cfg))
        self.fc2 = dygraph.Linear(cfg.intermediate_size, d, param_attr=_winit(cfg))
        self.ln2 = dygraph.LayerNorm(d)
        self.dropout = dygraph.Dropout(
            cfg.hidden_dropout_prob, dropout_implementation="upscale_in_train"
        )

    def forward(self, x, attn_bias=None, segment_ids=None):
        h = self.ln1(
            x + self.attn(x, attn_bias=attn_bias, segment_ids=segment_ids)
        )
        f = self.fc2(layers.gelu(self.fc1(h)))
        return self.ln2(h + self.dropout(f))


class BertEmbeddings(dygraph.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.word = dygraph.Embedding(
            [cfg.vocab_size, cfg.hidden_size], param_attr=_winit(cfg)
        )
        self.position = dygraph.Embedding(
            [cfg.max_position_embeddings, cfg.hidden_size], param_attr=_winit(cfg)
        )
        self.token_type = dygraph.Embedding(
            [cfg.type_vocab_size, cfg.hidden_size], param_attr=_winit(cfg)
        )
        self.ln = dygraph.LayerNorm(cfg.hidden_size)
        self.dropout = dygraph.Dropout(
            cfg.hidden_dropout_prob, dropout_implementation="upscale_in_train"
        )

    def forward(self, input_ids, token_type_ids, position_ids):
        emb = (
            self.word(input_ids)
            + self.position(position_ids)
            + self.token_type(token_type_ids)
        )
        return self.dropout(self.ln(emb))


class BertModel(_QkvCompatMixin, dygraph.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = BertEmbeddings(cfg)
        self.encoder = dygraph.LayerList(
            [TransformerEncoderLayer(cfg) for _ in range(cfg.num_hidden_layers)]
        )
        self.pooler = dygraph.Linear(
            cfg.hidden_size, cfg.hidden_size, act="tanh", param_attr=_winit(cfg)
        )

    def forward(self, input_ids, token_type_ids, position_ids,
                attention_mask=None, segment_ids=None):
        """attention_mask: [B, S] with 1 = attend, 0 = pad (reference input
        convention); converted to an additive bias for the fused op.
        segment_ids: [B, S] int ids for packed batches (several sequences
        per row, in-graph LoD parity) — attention stays within a segment;
        feed per-segment restarting position_ids alongside."""
        attn_bias = None
        if attention_mask is not None:
            m = layers.cast(attention_mask, "float32")
            m = layers.reshape(m, [0, 1, 1, int(attention_mask.shape[-1])])
            attn_bias = (m + (-1.0)) * 10000.0  # 0 -> -1e4, 1 -> 0
        h = self.embeddings(input_ids, token_type_ids, position_ids)
        for layer in self.encoder:
            h = layer(h, attn_bias=attn_bias, segment_ids=segment_ids)
        pooled = self.pooler(h[:, 0] if _eager() else _first_token(h))
        return h, pooled


def _eager():
    from ..fluid import framework

    return framework.in_dygraph_mode()


def _first_token(h):
    # static mode: slice [B, 1, D] -> [B, D]
    s = layers.slice(h, axes=[1], starts=[0], ends=[1])
    return layers.reshape(s, [0, int(h.shape[-1])])


class BertForPretraining(_QkvCompatMixin, dygraph.Layer):
    """MLM + NSP heads (BERT pretrain objective; ERNIE-1.0 uses the same
    framework path with different masking)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.bert = BertModel(cfg)
        d = cfg.hidden_size
        self.mlm_transform = dygraph.Linear(d, d, act="gelu", param_attr=_winit(cfg))
        self.mlm_ln = dygraph.LayerNorm(d)
        # decoder shares the word-embedding matrix (weight tying)
        self.mlm_bias = self.create_parameter(
            [cfg.vocab_size], attr=ParamAttr(initializer=ConstantInitializer(0.0))
        )
        self.nsp = dygraph.Linear(d, 2, param_attr=_winit(cfg))

    def forward(self, input_ids, token_type_ids, position_ids,
                attention_mask=None, segment_ids=None,
                masked_positions=None):
        """masked_positions: optional [B, P] int positions of the masked
        tokens.  When given, the MLM head runs only on those P rows
        (reference BERT/ERNIE static graph gathers mask_pos before the
        decoder matmul) — the full-vocab projection drops from S to P
        positions, ~15-20% of total pretrain FLOPs at S=512."""
        seq, pooled = self.bert(
            input_ids, token_type_ids, position_ids, attention_mask,
            segment_ids=segment_ids,
        )
        if masked_positions is not None:
            import numpy as _np

            if isinstance(masked_positions, _np.ndarray):
                from ..fluid.dygraph import to_variable

                masked_positions = to_variable(masked_positions)
            idx = layers.reshape(
                masked_positions, list(masked_positions.shape) + [1])
            seq = layers.take_along_axis(seq, idx, axis=1)  # [B, P, D]
        h = self.mlm_ln(self.mlm_transform(seq))
        logits = layers.matmul(
            h, self.bert.embeddings.word.weight, transpose_y=True
        )
        logits = logits + self.mlm_bias
        nsp_logits = self.nsp(pooled)
        return logits, nsp_logits

    def loss(self, logits, nsp_logits, mlm_labels, mlm_weights, nsp_labels):
        """Masked-LM loss over masked positions + NSP loss.

        mlm_labels: [B, S] target ids; mlm_weights: [B, S] 1.0 at masked
        positions; nsp_labels: [B, 1].
        """
        vocab = int(logits.shape[-1])
        flat_logits = layers.reshape(logits, [-1, vocab])
        flat_labels = layers.reshape(mlm_labels, [-1, 1])
        mlm_loss = layers.softmax_with_cross_entropy(flat_logits, flat_labels)
        w = layers.reshape(mlm_weights, [-1, 1])
        mlm_loss = layers.reduce_sum(mlm_loss * w) / (
            layers.reduce_sum(w) + 1e-6
        )
        nsp_loss = layers.reduce_mean(
            layers.softmax_with_cross_entropy(nsp_logits, nsp_labels)
        )
        return mlm_loss + nsp_loss
