"""The work a training step of the ``lfm2_24b_a2b`` configuration needs,
counted from the configuration and the traffic alone: what the mathematics
asks for on this chip's share, whatever implements it. Nothing of the
program is imported. ``readers/mfu.py`` takes ``train_flops_per_token``;
``readers/kernel_roofline.py`` the functions that return ``{"ops": FLOPs a
step, "hbm_bytes": bytes a step}`` over all layers and passes of one step.

A token's expected share of the routed experts: it picks
``num_experts_per_tok`` of ``router_width`` experts, of which ``num_experts``
are held, so ``k x held / width`` assignments a token land here (uniform
routing; the program's counter ``fedml_moe_assignments_total`` says what
did). Remat ``full`` runs a layer's forward a second time inside the
backward pass; the step's MFU leaves that out, as ``flops.py`` does, and a
kernel's roofline counts it, as ``kernel_counts.py`` does."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(config: dict):
    heads = config["num_attention_heads"]
    head_dim = config.get("head_dim") or config["hidden_size"] // heads
    kinds = config["layer_types"]
    return (config["hidden_size"], heads, config["num_key_value_heads"],
            head_dim, kinds.count("conv"), kinds.count("full_attention"),
            len(kinds) - config["num_dense_layers"])


def held_assignments_per_token(config: dict) -> float:
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["router_width"])


def parameters(config: dict) -> int:
    """Trained parameters held on this chip (the selection biases are not)."""
    d, heads, kv_heads, head_dim, convs, attns, expert_layers = _dims(config)
    layers = len(config["layer_types"])
    conv = d * 3 * d + d * config["conv_L_cache"] + d * d
    attn = 2 * d * heads * head_dim + 2 * d * kv_heads * head_dim + 2 * head_dim
    dense = 3 * d * config["intermediate_size"]
    experts = (d * config["router_width"]
               + config["num_experts"] * 3 * d * config["moe_intermediate_size"])
    return (config["vocab_size"] * d + d + layers * 2 * d + convs * conv
            + attns * attn + config["num_dense_layers"] * dense
            + expert_layers * experts)


def forward_flops_per_token(config: dict, traffic: dict) -> float:
    """2 x the matmul parameters a token meets (projections, dense SwiGLU,
    router, its expected share of the held experts, the tied head over the
    vocabulary held) plus causal attention (QK^T and PV over half of T)."""
    d, heads, kv_heads, head_dim, convs, attns, expert_layers = _dims(config)
    conv = d * 3 * d + d * d
    attn = 2 * d * heads * head_dim + 2 * d * kv_heads * head_dim
    dense = 3 * d * config["intermediate_size"]
    experts = (d * config["router_width"] + held_assignments_per_token(config)
               * 3 * d * config["moe_intermediate_size"])
    matmul = (convs * conv + attns * attn + config["num_dense_layers"] * dense
              + expert_layers * experts + d * config["vocab_size"])
    core = attns * 2 * traffic["seq_len"] * heads * head_dim  # 2 x 2 x T/2
    return 2.0 * matmul + core


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Forward + backward = 3 x the forward; remat is not counted."""
    return 3.0 * forward_flops_per_token(config, traffic)


def _forward_passes(config: dict) -> int:
    return 2 if config["remat"] == "full" else 1


def moe_experts(config: dict, traffic: dict) -> dict:
    """The grouped products of the held experts: three (D x F) products over
    the rows routed here, 2 x 3 x D x F FLOPs a row a forward pass and twice
    that backward. Bytes: the held experts' weights read once a pass (bf16)
    and their gradients written once (float32); the rows' x read, both
    hidden halves and y written, and as much again each backward."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    _, _, _, _, _, _, expert_layers = _dims(config)
    item = DTYPE_BYTES[config["compute_dtype"]]
    rows = (traffic["batch"] * traffic["seq_len"]
            * held_assignments_per_token(config))
    passes = _forward_passes(config) + 2
    weights = config["num_experts"] * 3 * d * f
    per_pass = weights * item + rows * (2 * d + 3 * f) * item
    return {"ops": expert_layers * passes * rows * 6 * d * f,
            "hbm_bytes": expert_layers * (
                passes * per_pass
                + weights * DTYPE_BYTES[config["param_dtype"]])}


def short_conv_core(config: dict, traffic: dict) -> dict:
    """What lies between a conv layer's two projections: ``z = b * x``, the
    taps, ``c * y``: 2 + 2 L FLOPs an element forward, about three times
    that backward. Bytes: b, c, x read and the result written, an element
    each, a forward pass; backward b, c, x and the gradient read, three
    gradients written."""
    d, _, _, _, convs, _, _ = _dims(config)
    item = DTYPE_BYTES[config["compute_dtype"]]
    elements = traffic["batch"] * traffic["seq_len"] * d
    fwd = _forward_passes(config)
    flops = 2 + 2 * config["conv_L_cache"]
    return {"ops": convs * elements * flops * (fwd + 3),
            "hbm_bytes": convs * elements * item * (4 * fwd + 7)}


def _attention_core(config: dict, traffic: dict):
    """(B x H x T^2 x Dh, bytes of a query-side operand, of a key-side one,
    layers): k and v have ``num_key_value_heads`` heads."""
    _, heads, kv_heads, head_dim, _, attns, _ = _dims(config)
    batch, seq = traffic["batch"], traffic["seq_len"]
    row = batch * seq * head_dim * DTYPE_BYTES[config["compute_dtype"]]
    return (batch * heads * seq * seq * head_dim, heads * row, kv_heads * row,
            attns)


def causal_attention_fwd(config: dict, traffic: dict) -> dict:
    """As ``kernel_counts.causal_attention_fwd``: two products over the kept
    half; q read and o written at the query heads, k and v read at the KV
    heads (what a grouped kernel would need; a repeat to the query heads is
    the implementation's cost)."""
    square, q_bytes, kv_bytes, layers = _attention_core(config, traffic)
    passes = layers * _forward_passes(config)
    return {"ops": 2 * square * passes,
            "hbm_bytes": (2 * q_bytes + 2 * kv_bytes) * passes}


def causal_attention_bwd(config: dict, traffic: dict) -> dict:
    """As ``kernel_counts.causal_attention_bwd``: five products over the kept
    half; q, o, do read and dq written at the query heads, k, v read and dk,
    dv written at the KV heads."""
    square, q_bytes, kv_bytes, layers = _attention_core(config, traffic)
    return {"ops": 5 * square * layers,
            "hbm_bytes": (4 * q_bytes + 4 * kv_bytes) * layers}
