"""BERT step-time ablation: where the non-MXU time goes.

Runs the flagship pretrain step with components toggled off one at a
time and reports marginal step times — the profile-backed accounting
behind PERF.md's MFU-ceiling analysis.

Usage (on the machine with the chip): PYTHONPATH=. python benchmarks/bert_ablation.py
"""

import sys
import time

import numpy as np


def run_case(name, dropout, P, B=32, S=512, amp="bf16", opt_name="adamw"):
    import jax

    from paddle_tpu import distributed as dist
    from paddle_tpu import models
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.fluid.optimizer import AdamWOptimizer, SGDOptimizer

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        B, S, P = 4, 64, 8

    cfg = models.BertConfig(
        vocab_size=30528, hidden_size=768, num_hidden_layers=12,
        num_attention_heads=12, intermediate_size=3072,
        max_position_embeddings=512,
        hidden_dropout_prob=dropout, attention_probs_dropout_prob=dropout,
    ) if on_tpu else models.BertConfig.tiny()

    with dygraph.guard():
        model = models.BertForPretraining(cfg)
        opt = (AdamWOptimizer(learning_rate=1e-4, weight_decay=0.01)
               if opt_name == "adamw" else SGDOptimizer(learning_rate=1e-3))
        step = dist.ShardedTrainStep(
            model, opt, _loss_fn(P), dist.auto_mesh(1), zero_stage=0,
            amp=amp)
        state = step.init()
        rng = np.random.RandomState(0)
        batch = {
            "input_ids": rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32),
            "token_type_ids": np.zeros((B, S), np.int32),
            "position_ids": np.tile(np.arange(S, dtype=np.int32), (B, 1)),
            "masked_positions": np.stack([
                np.sort(rng.choice(S, P, replace=False)) for _ in range(B)
            ]).astype(np.int32) if P else None,
            "mlm_labels": rng.randint(
                0, cfg.vocab_size, (B, P or S)).astype(np.int32),
            "mlm_weights": np.ones((B, P or S), np.float32),
            "nsp_labels": rng.randint(0, 2, (B, 1)).astype(np.int32),
        }
        if P is None:
            batch.pop("masked_positions")
        for _ in range(2):
            state, loss = step(state, batch)
        float(loss)
        batch = step.place_batch(batch)

        import bench as bench_mod

        ks, kl = (10, 30) if on_tpu else (1, 3)
        dt, _worst, state = bench_mod._marginal_step_time(
            step, state, [batch], ks, kl, reps=2)
    print("%-36s %8.2f ms/step  (%.0f tokens/s)"
          % (name, dt * 1e3, B * S / dt), file=sys.stderr)
    return dt


def _loss_fn(P):
    def loss_fn(m, batch):
        logits, nsp_logits = m(
            batch["input_ids"], batch["token_type_ids"],
            batch["position_ids"],
            masked_positions=batch.get("masked_positions"),
        )
        return m.loss(logits, nsp_logits, batch["mlm_labels"],
                      batch["mlm_weights"], batch["nsp_labels"])
    return loss_fn


def main():
    base = run_case("base (drop .1, P=80, bf16, adamw)", 0.1, 80)
    run_case("no dropout", 0.0, 80)
    run_case("full-vocab head (P=None)", 0.1, None)
    run_case("fp32 (no amp)", 0.1, 80, amp=None)
    run_case("sgd optimizer", 0.1, 80, opt_name="sgd")
    print("base step: %.2f ms" % (base * 1e3), file=sys.stderr)


if __name__ == "__main__":
    main()
