"""Closed-form operation counts, from shapes alone.

Model FLOPs are what the forward and backward passes of the algorithm
require; recomputation, optimizer arithmetic, casts and whatever else the
compiled program does are not counted (XLA's ``cost_analysis`` counts
those and counts a Pallas custom call as 0, so it is not used)."""


def bert_pretrain_flops_per_step(*, hidden, layers, vocab, trunk_params,
                                 batch, seq, masked):
    """Training FLOPs of one BERT MLM+NSP step (copied from ``bench.py``
    ``_flops_per_step``).

    ``trunk_params`` counts every parameter used once per token: all but
    the embedding tables (gathers, no matmul) and the MLM head.  A matmul
    parameter costs 6 FLOPs per use (2 forward, 4 backward).  Attention
    scores and context are 2*S*h MACs per token per layer forward, so
    12*L*S*h FLOPs per token forward and backward.  The MLM head (tied
    decoder v*h, transform h*h, biases) runs on the ``masked`` positions
    of each sequence only."""
    head = vocab * hidden + hidden * hidden + hidden + vocab
    per_token = 6.0 * trunk_params + 12.0 * layers * hidden * seq
    return batch * seq * per_token + batch * masked * 6.0 * head


def bert_trunk_params(param_shapes):
    """Parameters of the trunk by ``bench.py``'s rule: every tensor whose
    name holds none of ``position``, ``token_type``, ``word``, ``mlm``."""
    total = 0
    for name, shape in param_shapes.items():
        if not any(s in name for s in ("position", "token_type", "word",
                                       "mlm")):
            n = 1
            for d in shape:
                n *= int(d)
            total += n
    return total


def attention_flops(*, batch, heads, seq, head_dim, causal, backward):
    """FLOPs of exact attention: QK^T and PV are 2*S*S*d MACs per head
    forward; backward costs twice the forward (dQ, dK, dV, dP).  A causal
    mask halves the work an algorithm needs."""
    fwd = 4.0 * batch * heads * seq * seq * head_dim
    if causal:
        fwd *= 0.5
    return fwd * (3.0 if backward else 1.0)
