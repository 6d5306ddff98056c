"""The work a training step of the ``nemotron3_nano_30b_a3b`` configuration
needs, counted from the configuration and the traffic alone: what the
mathematics asks for on this chip's share, whatever implements it. Nothing of
the program is imported. ``readers/mfu.py`` takes ``train_flops_per_token``;
``readers/kernel_roofline.py`` the functions that return ``{"ops": FLOPs a
step, "hbm_bytes": bytes a step}`` over all blocks and passes of one step.

The blocks are ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``*``
attention, ``E`` an expert block. ``n_routed_experts`` experts are held of a
router ``router_width`` wide (the key is absent in the published config: all
of them are), so ``k x held / width`` assignments a token land here (uniform
routing; the program's counter ``fedml_moe_assignments_total`` says what
did). Remat ``full`` runs a block's forward a second time inside the backward
pass; the step's MFU leaves that out, as ``flops.py`` does, and a kernel's
roofline counts it, as ``kernel_counts.py`` does."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _blocks(config: dict):
    pattern = config["hybrid_override_pattern"]
    return pattern.count("M"), pattern.count("*"), pattern.count("E")


def _mamba_dims(config: dict):
    """(heads, head width, state width, inner, conv_dim)."""
    heads, p, n = (config["mamba_num_heads"], config["mamba_head_dim"],
                   config["ssm_state_size"])
    inner = heads * p
    return heads, p, n, inner, inner + 2 * config["n_groups"] * n


def _router_width(config: dict) -> int:
    return config.get("router_width", config["n_routed_experts"])


def held_assignments_per_token(config: dict) -> float:
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / _router_width(config))


def _matmul_parameters(config: dict):
    """(a Mamba block's two projections, an attention block's four, one
    routed expert, the shared expert, the router)."""
    d = config["hidden_size"]
    heads, _, _, inner, conv_dim = _mamba_dims(config)
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return (d * (inner + conv_dim + heads) + inner * d, 2 * d * q + 2 * d * kv,
            2 * d * config["moe_intermediate_size"],
            2 * d * config["moe_shared_expert_intermediate_size"],
            d * _router_width(config))


def parameters(config: dict) -> int:
    """Trained parameters held on this chip (the selection biases are not):
    on the published keys, the whole model's."""
    d = config["hidden_size"]
    mambas, attns, moes = _blocks(config)
    heads, _, _, inner, conv_dim = _mamba_dims(config)
    mamba, attn, expert, shared, router = _matmul_parameters(config)
    # taps and bias of the convolution; A_log, D, dt_bias; the gated norm
    mamba += conv_dim * (config["conv_kernel"] + 1) + 3 * heads + inner
    moe = config["n_routed_experts"] * expert + shared + router
    return (mambas * (mamba + d) + attns * (attn + d) + moes * (moe + d)
            + 2 * config["vocab_size"] * d + d)


def _ssd_flops_per_token(config: dict) -> int:
    """The recurrence, a head a position: the state decayed and updated
    (3 P N + P), read out (2 P N) and the skip (2 P)."""
    heads, p, n, _, _ = _mamba_dims(config)
    return heads * (5 * p * n + 3 * p)


def forward_flops_per_token(config: dict, traffic: dict) -> float:
    """2 x the matmul parameters a token meets (projections, the shared
    expert, router, its expected share of the held experts, the untied head
    over the vocabulary held), the scan's recurrence, and causal attention
    (QK^T and PV over half of T)."""
    mambas, attns, moes = _blocks(config)
    mamba, attn, expert, shared, router = _matmul_parameters(config)
    moe = held_assignments_per_token(config) * expert + shared + router
    matmul = (mambas * mamba + attns * attn + moes * moe
              + config["hidden_size"] * config["vocab_size"])
    core = (attns * 2 * traffic["seq_len"] * config["num_attention_heads"]
            * config["head_dim"])  # 2 products x 2 x T / 2
    return 2.0 * matmul + mambas * _ssd_flops_per_token(config) + core


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Forward + backward = 3 x the forward; remat is not counted."""
    return 3.0 * forward_flops_per_token(config, traffic)


def _forward_passes(config: dict) -> int:
    return 2 if config["remat"] == "full" else 1


def ssd_core(config: dict, traffic: dict) -> dict:
    """What lies between a Mamba block's convolution and its gated norm: the
    step's softplus, the scan and the skip. FLOPs: the recurrence's, a
    forward pass, and twice that backward. Bytes: x, B, C, dt read and y
    written a forward pass; backward those and y's gradient read, four
    gradients written."""
    mambas, _, _ = _blocks(config)
    heads, _, _, inner, conv_dim = _mamba_dims(config)
    item = DTYPE_BYTES[config["compute_dtype"]]
    tokens = traffic["batch"] * traffic["seq_len"]
    fwd = _forward_passes(config)
    read = conv_dim + heads
    return {"ops": mambas * tokens * _ssd_flops_per_token(config) * (fwd + 2),
            "hbm_bytes": mambas * tokens * item * (
                fwd * (read + inner) + 2 * read + inner)}


def moe_experts(config: dict, traffic: dict) -> dict:
    """The grouped products of the held experts: two (D x F) products over
    the rows routed here, 2 x 2 x D x F FLOPs a row a forward pass and twice
    that backward. Bytes: the held experts' weights read once a pass (bf16)
    and their gradients written once (float32); the rows' x read, the hidden
    written and read, y written, and as much again each backward."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    _, _, moes = _blocks(config)
    item = DTYPE_BYTES[config["compute_dtype"]]
    rows = (traffic["batch"] * traffic["seq_len"]
            * held_assignments_per_token(config))
    passes = _forward_passes(config) + 2
    weights = config["n_routed_experts"] * 2 * d * f
    per_pass = weights * item + rows * (2 * d + 2 * f) * item
    return {"ops": moes * passes * rows * 4 * d * f,
            "hbm_bytes": moes * (passes * per_pass
                                 + weights * DTYPE_BYTES[config["param_dtype"]])}


def _attention_core(config: dict, traffic: dict):
    """(B x H x T^2 x Dh, bytes of a query-side operand, of a key-side one,
    blocks): k and v have ``num_key_value_heads`` heads."""
    heads, head_dim = config["num_attention_heads"], config["head_dim"]
    batch, seq = traffic["batch"], traffic["seq_len"]
    row = batch * seq * head_dim * DTYPE_BYTES[config["compute_dtype"]]
    return (batch * heads * seq * seq * head_dim, heads * row,
            config["num_key_value_heads"] * row, _blocks(config)[1])


def causal_attention_fwd(config: dict, traffic: dict) -> dict:
    """As ``kernel_counts.causal_attention_fwd``: two products over the kept
    half; q read and o written at the query heads, k and v read at the KV
    heads (what a grouped kernel would need; a repeat to the query heads is
    the implementation's cost)."""
    square, q_bytes, kv_bytes, blocks = _attention_core(config, traffic)
    passes = blocks * _forward_passes(config)
    return {"ops": 2 * square * passes,
            "hbm_bytes": (2 * q_bytes + 2 * kv_bytes) * passes}


def causal_attention_bwd(config: dict, traffic: dict) -> dict:
    """As ``kernel_counts.causal_attention_bwd``: five products over the kept
    half; q, o, do read and dq written at the query heads, k, v read and dk,
    dv written at the KV heads."""
    square, q_bytes, kv_bytes, blocks = _attention_core(config, traffic)
    return {"ops": 5 * square * blocks,
            "hbm_bytes": (4 * q_bytes + 4 * kv_bytes) * blocks}
