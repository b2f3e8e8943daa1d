"""BERT pretraining through the program's `models.BertForPretraining`
(cut down from `chip_smoke.py`'s builders, which ran on the chip in PR 22).
"""

import math

import numpy as np

from chipbench import flops
from chipbench.common import say
from chipbench.references import bert as reference

# The program's float32 forward (flash kernels, fused projections) and
# the plain reference sum in different orders: they agree to ~1e-5 on a
# loss near 11.  bfloat16 products would miss by 1e-2 or more.
REFERENCE_LOSS_ATOL = 2e-3
# The timed step runs bf16 AMP with dropout 0.1, the reference neither,
# and the reference is taken on 4 of the batch's 48 sequences (or 192):
# most of what this number reads is those 4 rows' own scatter.  Over 17
# chip runs the two differed by 0.01 to 0.11 (PR 25), over 17 more seeds
# by 0.008 to 0.184 (root mean square 0.082; PR 34), and a limit of 0.25
# would fail one sound run in 400.  It has no upper reading: a model
# that predicts nothing reads 0.04 to 0.3 (the first loss is 0.27 above
# chance).  Until the gradient-level check replaces it (PERF.md section
# 7) the limit stands four times the root mean square above nought and
# says only that the timed step's loss is a loss.
TRAIN_FIRST_LOSS_ATOL = 0.35


def build(config, seed):
    from paddle_tpu import models
    from paddle_tpu.fluid import dygraph

    cfg = models.BertConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        hidden_dropout_prob=config["hidden_dropout_prob"],
        attention_probs_dropout_prob=config["attention_probs_dropout_prob"],
        initializer_range=config["initializer_range"])
    with dygraph.guard():
        np.random.seed(seed % 2 ** 32)
        return models.BertForPretraining(cfg)


def loss_fn(m, batch):
    logits, nsp_logits = m(
        batch["input_ids"], batch["token_type_ids"], batch["position_ids"],
        masked_positions=batch["masked_positions"])
    return m.loss(logits, nsp_logits, batch["mlm_labels"],
                  batch["mlm_weights"], batch["nsp_labels"])


def host_batch(config, job, rng, rows=None):
    """One host batch of random tokens.  Ids and labels are drawn below
    the published vocabulary, so padding rows are never asked for."""
    b, s, p = rows or job["global_batch"], job["seq_len"], job["masked"]
    v = config.get("assumed", {}).get("published_vocab_size",
                                      config["vocab_size"])
    return {
        "input_ids": rng.randint(0, v, (b, s)).astype(np.int32),
        "token_type_ids": np.zeros((b, s), np.int32),
        "position_ids": np.tile(np.arange(s, dtype=np.int32), (b, 1)),
        "masked_positions": np.stack([
            np.sort(rng.choice(s, size=p, replace=False))
            for _ in range(b)]).astype(np.int32),
        "mlm_labels": rng.randint(0, v, (b, p)).astype(np.int32),
        "mlm_weights": np.ones((b, p), np.float32),
        "nsp_labels": rng.randint(0, 2, (b, 1)).astype(np.int32),
    }


def tokens_per_step(job):
    return job["global_batch"] * job["seq_len"]


def flops_per_step(config, job, param_shapes):
    return flops.bert_pretrain_flops_per_step(
        hidden=config["hidden_size"], layers=config["num_hidden_layers"],
        vocab=config["vocab_size"],
        trunk_params=flops.bert_trunk_params(param_shapes),
        batch=job["global_batch"], seq=job["seq_len"], masked=job["masked"])


def chance_loss(config):
    """MLM + NSP loss of a model that knows nothing."""
    return math.log(config["vocab_size"]) + math.log(2.0)


def reference_check(model, config, job, batch):
    """The program's deterministic float32 forward and loss against the
    plain reference, on the first ``reference_rows`` sequences of
    ``batch`` and the initial weights.  Returns both losses; the runner
    holds them together within `REFERENCE_LOSS_ATOL`, and the timed
    step's first loss (bf16, dropout) to the reference's within
    `TRAIN_FIRST_LOSS_ATOL`.  Run it before the first
    training step, which donates the parameters' buffers."""
    import jax

    from paddle_tpu.fluid import dygraph, framework

    rows = job.get("reference_rows", 4)
    sample = {k: v[:rows] for k, v in batch.items()}
    params = {k: v.data for k, v in model.state_dict().items()}

    @jax.jit
    def program_loss(params, sample):
        with dygraph.guard():
            tracer = framework._dygraph_tracer
            tracer.train_mode = tracer._has_grad = False    # no dropout
            for var in model.state_dict().values():
                tracer.register_var(var)
            b = {k: dygraph.to_variable(v) for k, v in sample.items()}
            logits, nsp_logits = model.functional_call(
                params, b["input_ids"], b["token_type_ids"],
                b["position_ids"], masked_positions=b["masked_positions"])
            return model.loss(logits, nsp_logits, b["mlm_labels"],
                              b["mlm_weights"], b["nsp_labels"]).data

    layer = jax.jit(reference.encoder_layer, static_argnums=2)

    def plain(p, b):
        return reference.pretrain_loss(
            p, b, layers=config["num_hidden_layers"],
            heads=config["num_attention_heads"], layer_fn=layer)
    with jax.default_matmul_precision("highest"):
        got = float(program_loss(params, sample))
        want = float(plain(params, sample))
    say("reference", rows=rows, program_loss=got, reference_loss=want,
        abs_diff=abs(got - want), atol=REFERENCE_LOSS_ATOL)
    return got, want
