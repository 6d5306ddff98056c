"""Operations a step needs, counted from shapes. Forward + backward is three
times the forward matmul work; recomputation (remat) is not counted.

Each function takes the configuration and the traffic as read from their
files and returns FLOPs per unit of work (a token, a sample)."""

from __future__ import annotations


def lm_train_flops_per_token(config: dict, traffic: dict) -> float:
    """Decoder-only LM: 6 x matmul parameters of the blocks and the untied
    head, plus causal attention (QK^T and PV, half of the full T x T)."""
    d, layers = config["n_embd"], config["n_layer"]
    seq = traffic["seq_len"]
    block = 3 * d * d + d * d + 2 * config["mlp_ratio"] * d * d
    matmul_params = layers * block + d * config["vocab_size"]
    attention = 6 * seq * d * layers  # 3 x (2 matmuls x 2 x T/2 x d)
    return 6.0 * matmul_params + attention


def resnet18_forward_macs_per_sample(config: dict) -> int:
    """ResNet-18 as models/resnet.py builds it for small inputs: a 3x3 stem,
    four stages of two basic blocks (64..512 channels, stride 2 entering
    stages 2-4, a 1x1 projection where the shape changes) and a dense head."""
    size, cin = config["image_size"], config["image_channels"]
    macs = size * size * 9 * cin * 64
    prev = 64
    for stage, ch in enumerate((64, 128, 256, 512)):
        if stage:
            size //= 2
        hw = size * size
        macs += hw * 9 * prev * ch + 3 * hw * 9 * ch * ch
        if prev != ch:
            macs += hw * prev * ch
        prev = ch
    return macs + 512 * config["num_classes"]


def resnet18_train_flops_per_sample(config: dict, traffic: dict) -> float:
    return 6.0 * resnet18_forward_macs_per_sample(config)
