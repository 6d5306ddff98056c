"""Cheetah distributed LM training: dp x sp x tp over one mesh.

On a v4-8: dp=2, sp=2, tp=2. Ring attention handles the seq axis, Megatron
param shardings the model axis; XLA inserts all collectives.

    python main.py --dp 2 --sp 2 --tp 2 --steps 100
"""

import argparse

import numpy as np

from fedml_tpu.parallel.trainer import DistTrainConfig, DistributedLMTrainer


def data_iter(vocab, B, T, seed=0):
    rng = np.random.default_rng(seed)
    while True:
        start = rng.integers(0, vocab, (B, 1))
        seq = (start + np.arange(T + 1)) % vocab
        yield seq[:, :-1].astype(np.int32), seq[:, 1:].astype(np.int32)


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--sp", type=int, default=2)
    p.add_argument("--tp", type=int, default=2)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--dim", type=int, default=512)
    p.add_argument("--layers", type=int, default=8)
    p.add_argument("--seq_len", type=int, default=2048)
    p.add_argument("--batch", type=int, default=8)
    # memory levers (their effect is not measured on this chip): per-block
    # remat and chunked cross-entropy
    p.add_argument("--no_remat", action="store_true")
    p.add_argument("--ce_chunk", type=int, default=256,
                   help="0 = full-logit CE; else sequence-chunk size "
                        "(seq_len must be divisible by it)")
    p.add_argument("--mu_dtype", default=None, choices=[None, "bfloat16"],
                   help="AdamW first-moment dtype; bfloat16 halves mu's "
                        "HBM footprint and optimizer-stage traffic")
    p.add_argument("--remat_policy", default="full", choices=["full", "dots"],
                   help="'dots' saves matmul outputs and recomputes only "
                        "elementwise ops in bwd (less recompute, more "
                        "activation HBM than 'full')")
    a = p.parse_args()
    if a.ce_chunk and a.seq_len % a.ce_chunk:
        # fall back rather than crash on the first step: chunked CE needs
        # seq_len % chunk == 0
        print(f"seq_len {a.seq_len} not divisible by ce_chunk {a.ce_chunk}; "
              "using full-logit CE")
        a.ce_chunk = 0

    trainer = DistributedLMTrainer(
        DistTrainConfig(dp=a.dp, tp=a.tp, sp=a.sp, lr=3e-4,
                        use_remat=not a.no_remat, ce_chunk=a.ce_chunk,
                        mu_dtype=a.mu_dtype, remat_policy=a.remat_policy),
        vocab_size=32000, dim=a.dim, num_heads=8, num_layers=a.layers,
        max_len=a.seq_len,
    )
    trainer.train(data_iter(32000, a.batch, a.seq_len), steps=a.steps)
