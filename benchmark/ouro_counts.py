"""The work a training step of the ``ouro_2_6b`` configuration needs, counted
from the configuration and the traffic alone: what the mathematics asks for,
whatever implements it. Nothing of the program is imported. ``readers/mfu.py``
takes ``train_flops_per_token``; ``readers/kernel_roofline.py`` the functions
that return ``{"ops": FLOPs a step, "hbm_bytes": bytes a step}`` over all
layer applications and passes of one step.

A looped decoder's FLOPs are not 6 x parameters x tokens: a token meets each
layer's parameters, the head's and the gate's ``total_ut_steps`` times, the
embedding's once (a look-up: no FLOPs). Remat ``full`` runs a layer's forward
a second time inside the backward pass; the step's MFU leaves that out, as
``flops.py`` does, and a kernel's roofline counts it, as ``kernel_counts.py``
does. The head's second product under the objective's checkpoint is
recomputation too, and is left out of the MFU."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(config: dict):
    return (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            len(config["layer_types"]), config["total_ut_steps"])


def parameters(config: dict) -> int:
    """Trained parameters: the layers (four projections, SwiGLU, four norms
    each), embedding, head, final norm, and the exit gate with its bias."""
    d, heads, kv_heads, head_dim, layers, _ = _dims(config)
    attn = 2 * d * heads * head_dim + 2 * d * kv_heads * head_dim
    layer = attn + 3 * d * config["intermediate_size"] + 4 * d
    return layers * layer + 2 * config["vocab_size"] * d + d + d + 1


def layer_applications(config: dict) -> int:
    """How often a step's forward pass applies a layer: layers x passes."""
    _, _, _, _, layers, passes = _dims(config)
    return layers * passes


def forward_flops_per_token(config: dict, traffic: dict) -> float:
    """Every pass: 2 x the matmul parameters of each layer (projections,
    SwiGLU), causal attention (QK^T and PV over half of T), the gate's dot
    product and the untied head's product over the whole vocabulary."""
    d, heads, kv_heads, head_dim, _, passes = _dims(config)
    attn = 2 * d * heads * head_dim + 2 * d * kv_heads * head_dim
    layer = 2.0 * (attn + 3 * d * config["intermediate_size"])
    core = 2 * traffic["seq_len"] * heads * head_dim  # 2 x 2 x T/2
    return (layer_applications(config) * (layer + core)
            + passes * 2.0 * (d * config["vocab_size"] + d))


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Forward + backward = 3 x the forward; remat is not counted."""
    return 3.0 * forward_flops_per_token(config, traffic)


def _forward_passes(config: dict) -> int:
    return 2 if config["remat"] == "full" else 1


def _attention_core(config: dict, traffic: dict):
    """(B x H x T^2 x Dh, bytes of a query-side operand, of a key-side one,
    layer applications)."""
    _, heads, kv_heads, head_dim, _, _ = _dims(config)
    batch, seq = traffic["batch"], traffic["seq_len"]
    row = batch * seq * head_dim * DTYPE_BYTES[config["compute_dtype"]]
    return (batch * heads * seq * seq * head_dim, heads * row, kv_heads * row,
            layer_applications(config))


def causal_attention_fwd(config: dict, traffic: dict) -> dict:
    """As ``kernel_counts.causal_attention_fwd``: two products over the kept
    half; q read and o written at the query heads, k and v read at the KV
    heads; over every layer application, run twice under remat ``full``."""
    square, q_bytes, kv_bytes, applications = _attention_core(config, traffic)
    passes = applications * _forward_passes(config)
    return {"ops": 2 * square * passes,
            "hbm_bytes": (2 * q_bytes + 2 * kv_bytes) * passes}


def causal_attention_bwd(config: dict, traffic: dict) -> dict:
    """As ``kernel_counts.causal_attention_bwd``: five products over the kept
    half; q, o, do read and dq written at the query heads, k, v read and dk,
    dv written at the KV heads; over every layer application."""
    square, q_bytes, kv_bytes, applications = _attention_core(config, traffic)
    return {"ops": 5 * square * applications,
            "hbm_bytes": (4 * q_bytes + 4 * kv_bytes) * applications}
