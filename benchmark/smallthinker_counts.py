"""The work a training step of the ``smallthinker_21b_a3b`` configuration
needs, counted from the configuration and the traffic alone: what the
mathematics asks for on this chip's share, whatever implements it. Nothing of
the program is imported. ``readers/mfu.py`` takes ``train_flops_per_token``;
``readers/kernel_roofline.py`` the functions that return ``{"ops": FLOPs a
step, "hbm_bytes": bytes a step}`` over all layers and passes of one step.

Every layer is attention over routed experts. A layer whose
``sliding_window_layout`` entry is 1 sees the causal band of
``sliding_window_size`` keys, ``0 <= i - j < window``: its attention's work
is the band's query-key pairs, not the triangle's (``pairs``).
``moe_num_primary_experts`` experts are held of a router ``router_width``
wide (the key is absent in the published config: all of them are), so ``k x
held / width`` assignments a token land here (uniform routing; the program's
counter ``fedml_moe_assignments_total`` says what did). Remat ``full`` runs a
layer's forward a second time inside the backward pass; the step's MFU
leaves that out, as ``flops.py`` does, and a kernel's roofline counts it, as
``kernel_counts.py`` does."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def pairs(seq: int, window=None) -> int:
    """Query-key pairs ``(i, j)``, ``0 <= i - j < window`` (None: the causal
    triangle), over a sequence of ``seq`` positions."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def _windows(config: dict) -> list:
    """Each layer's window, None for a global one."""
    return [config["sliding_window_size"] if w else None
            for w in config["sliding_window_layout"]]


def _router_width(config: dict) -> int:
    return config.get("router_width", config["moe_num_primary_experts"])


def held_assignments_per_token(config: dict) -> float:
    return (config["moe_num_active_primary_experts"]
            * config["moe_num_primary_experts"] / _router_width(config))


def _matmul_parameters(config: dict):
    """(a layer's four attention projections, one ReGLU expert, the
    router)."""
    d = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return (2 * d * q + 2 * d * kv, 3 * d * config["moe_ffn_hidden_size"],
            d * _router_width(config))


def parameters(config: dict) -> int:
    """Trained parameters held on this chip: each layer's projections,
    router, held experts and two norms, the embedding and the untied head
    over the vocabulary held, the final norm. On the published keys, the
    whole model's."""
    d = config["hidden_size"]
    attn, expert, router = _matmul_parameters(config)
    layer = attn + router + config["moe_num_primary_experts"] * expert + 2 * d
    return (config["num_hidden_layers"] * layer
            + 2 * config["vocab_size"] * d + d)


def attention_core_flops_per_token(config: dict, traffic: dict,
                                   windowed_only: bool = False) -> float:
    """QK^T and PV, 2 x 2 x Dh a pair a head, over each layer's pairs (the
    band's or the triangle's), a token's share."""
    seq = traffic["seq_len"]
    heads, head_dim = config["num_attention_heads"], config["head_dim"]
    return sum(4 * pairs(seq, w) * heads * head_dim / seq
               for w in _windows(config)
               if w is not None or not windowed_only)


def forward_flops_per_token(config: dict, traffic: dict) -> float:
    """2 x the matmul parameters a token meets (projections, router, its
    expected share of the held experts, the untied head over the vocabulary
    held) and the attention core over each layer's pairs."""
    attn, expert, router = _matmul_parameters(config)
    layer = attn + router + held_assignments_per_token(config) * expert
    matmul = (config["num_hidden_layers"] * layer
              + config["hidden_size"] * config["vocab_size"])
    return 2.0 * matmul + attention_core_flops_per_token(config, traffic)


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Forward + backward = 3 x the forward; remat is not counted."""
    return 3.0 * forward_flops_per_token(config, traffic)


def _forward_passes(config: dict) -> int:
    return 2 if config["remat"] == "full" else 1


def _attention(config: dict, traffic: dict, windowed_only: bool):
    """(B x H x Dh x the pairs of the layers counted, bytes of a query-side
    operand, of a key-side one, the layers counted): k and v have
    ``num_key_value_heads`` heads."""
    heads, head_dim = config["num_attention_heads"], config["head_dim"]
    batch, seq = traffic["batch"], traffic["seq_len"]
    layers = [w for w in _windows(config) if w is not None or not windowed_only]
    row = batch * seq * head_dim * DTYPE_BYTES[config["compute_dtype"]]
    kept = batch * heads * head_dim * sum(pairs(seq, w) for w in layers)
    return kept, heads * row, config["num_key_value_heads"] * row, len(layers)


def _fwd(config, traffic, windowed_only):
    """As ``kernel_counts.causal_attention_fwd``, over the pairs each layer
    keeps: two products; q read and o written at the query heads, k and v
    read at the KV heads; run twice under remat ``full``."""
    kept, q_bytes, kv_bytes, layers = _attention(config, traffic, windowed_only)
    passes = _forward_passes(config)
    return {"ops": 4 * kept * passes,
            "hbm_bytes": (2 * q_bytes + 2 * kv_bytes) * layers * passes}


def _bwd(config, traffic, windowed_only):
    """As ``kernel_counts.causal_attention_bwd``, over the pairs each layer
    keeps: five products; q, o, do read and dq written at the query heads,
    k, v read and dk, dv written at the KV heads."""
    kept, q_bytes, kv_bytes, layers = _attention(config, traffic, windowed_only)
    return {"ops": 10 * kept,
            "hbm_bytes": (4 * q_bytes + 4 * kv_bytes) * layers}


def causal_attention_fwd(config: dict, traffic: dict) -> dict:
    """The attention core's forward over every layer."""
    return _fwd(config, traffic, False)


def causal_attention_bwd(config: dict, traffic: dict) -> dict:
    """The attention core's backward over every layer."""
    return _bwd(config, traffic, False)


def window_attention_fwd(config: dict, traffic: dict) -> dict:
    """The forward over the windowed layers alone: the band's pairs."""
    return _fwd(config, traffic, True)


def window_attention_bwd(config: dict, traffic: dict) -> dict:
    """The backward over the windowed layers alone."""
    return _bwd(config, traffic, True)


def moe_experts(config: dict, traffic: dict) -> dict:
    """The grouped products of the held ReGLU experts: three (D x F)
    products over the rows routed here, 3 x 2 x D x F FLOPs a row a forward
    pass and twice that backward. Bytes: the held experts' weights read once
    a pass (bf16) and their gradients written once (float32); the rows' x
    read, the hidden written and read, y written, and as much again each
    backward. The rows are a uniform router's
    (``held_assignments_per_token``): the seeded weights route 12.3-12.8%
    of the assignments to the held experts, against 12.5 uniform (PERF.md
    section 6)."""
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    item = DTYPE_BYTES[config["compute_dtype"]]
    rows = (traffic["batch"] * traffic["seq_len"]
            * held_assignments_per_token(config))
    passes = _forward_passes(config) + 2
    weights = config["moe_num_primary_experts"] * 3 * d * f
    per_pass = weights * item + rows * (2 * d + 2 * f) * item
    layers = config["num_hidden_layers"]
    return {"ops": layers * passes * rows * 6 * d * f,
            "hbm_bytes": layers * (passes * per_pass
                                   + weights * DTYPE_BYTES[config["param_dtype"]])}
