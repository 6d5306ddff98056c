"""Plain reference for the Nemotron-H training step (``nemotron_h``:
NVIDIA-Nemotron-3-Nano-30B-A3B), in straightforward jax.numpy: loss, gradients
and AdamW. float32 at matmul precision "highest" by default; ``compute``
lowers every product's operands one precision (the control). Nothing here
imports the program; what it shares with ``reference/lm.py`` and
``reference/lfm2.py`` (seeded keys, batches, the lowered matmul, AdamW, the
blocked attention, the search that balances a selection bias) it takes from
there. The weights are made here from the seed as a flat dict ``name ->
tensor`` (block ``i``'s leaves are ``L<i>.<leaf>``), the selection biases
beside them; the runner copies both into the program.

**The layer equations**, from the catalog's ``config`` and the Hugging Face
implementation of ``nemotron_h`` as remembered (there is no network here):
what could not be confirmed is listed under ``assumed`` in the
configuration's file.

- ``RMSNorm(x; g) = x / sqrt(mean(x^2) + layer_norm_epsilon) * g``, float32.
  No bias in any projection.
- Embedding: ``h = E[tokens]``; no position table, no scaling.
- Block ``i`` of kind ``hybrid_override_pattern[i]``: ``h = h +
  Mixer_i(RMSNorm(h; g_i))``: one mixer a block.
- ``M``, Mamba-2. ``inner = mamba_num_heads x mamba_head_dim``, ``conv_dim =
  inner + 2 n_groups ssm_state_size``. ``[z, xBC, dt] = split(u W_in)``,
  ``W_in`` (D, inner + conv_dim + heads). ``xBC = silu(conv(xBC) + b)``:
  depthwise, causal, ``conv_kernel`` taps, ``xBC_{<0} = 0``. ``[x, B, C] =
  split(xBC)``, x as (heads, P), B and C as (n_groups, N). ``dt = softplus(dt
  + dt_bias)``, ``A = -exp(A_log)``, a scalar a head. Head ``h`` of group
  ``h // (heads / n_groups)``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
  B_t`` (P x N, ``S_{-1} = 0``), ``y_t = S_t C_t + D_h x_t``. Then ``y =
  RMSNorm_groups(y * silu(z)) * w``, the norm over each of ``n_groups`` runs
  of inner / n_groups channels (the gate before the norm), and ``Mixer = y
  W_out``.
- ``*``, attention: ``q = u W_q`` as (T, H, Dh), ``k = u W_k``, ``v = u W_v``
  as (T, Hkv, Dh); causal ``softmax(q k^T / sqrt(Dh)) v``, each KV head
  serving H / Hkv consecutive query heads; ``Mixer = concat W_o``. No
  rotary embedding, no QK-norm.
- ``E``, experts: ``s = sigmoid(u W_g)`` over all ``router_width`` experts,
  float32; ``sel = top_k(s + beta)`` (beta: the selection bias, for the
  choice only, no gradient; ``n_group = topk_group = 1``: no group limit);
  ``w = s[sel] / (sum s[sel] + 1e-6)`` (``norm_topk_prob``) x
  ``routed_scaling_factor``; routed expert ``e``: ``W2_e relu(W1_e u)^2``,
  width ``moe_intermediate_size``; the shared expert, every token, the same
  form at ``moe_shared_expert_intermediate_size``. ``Mixer = sum_{e in sel}
  w_e expert_e(u) + shared(u)``. beta is held constant through the steps.
- Output: ``logits = RMSNorm(h_L; g_f) W_head`` (untied); the loss is the
  mean token cross-entropy.
- AdamW; step ``t`` (from 1) runs at ``lr * min(1, t / warmup_steps)``.

**The scan is the recurrence itself**, one position at a time in float32
(``lax.scan`` over t, in blocks of ``chunk_size`` steps under
``jax.checkpoint`` so that its backward pass keeps one state a block): never
the chunked algorithm the program runs. ``state_reset`` plants the fault of
a scan that drops the state it carries: ``S`` is zeroed at every multiple of
``chunk_size``.

**Departures, each because the configuration is a chip's share** (the
configuration's ``deployment``): the expert sum runs over ``sel`` within the
experts held (``n_routed_experts`` of them from ``experts_held_offset``; the
choice and the normaliser still run over all ``router_width``), every held
expert applied to every token and masked by the choice; the shared expert is
whole; the selection bias is what the seeded router needs to spread its load
evenly, found once at set-up on a batch of the pool that no checked step
sees (``reference/lfm2.py balanced``); the vocabulary is the slice held
(ids, logits and loss over ``vocab_size`` rows of E and columns of W_head);
the blocks are ``hybrid_override_pattern`` as cut. Tap ``j`` of the
convolution weighs the position ``j`` back. Attention is computed a block
of queries at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import lfm2, lm

make_batches = lm.make_batches
EXPERT_LEAVES = ("ew1", "ew2")  # (held, ., .): a norm per expert (among lfm2's)
ROUTER_BELOW = lfm2.ROUTER_BELOW


def shape_of(cfg: dict) -> tuple:
    """The configuration's shape as a hashable (a static argument)."""
    return (cfg["vocab_size"], cfg["hidden_size"],
            cfg["hybrid_override_pattern"], cfg["mamba_num_heads"],
            cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["n_groups"],
            cfg["conv_kernel"], cfg["chunk_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"], cfg["router_width"],
            cfg["n_routed_experts"], cfg["experts_held_offset"],
            cfg["num_experts_per_tok"], float(cfg["routed_scaling_factor"]),
            cfg["layer_norm_epsilon"],
            (cfg["time_step_min"], cfg["time_step_max"],
             cfg["time_step_floor"]))


def leaf_shapes(shape: tuple) -> dict:
    """name -> (shape, how it is seeded) of every trained leaf, in order."""
    (V, D, pattern, Hm, P, N, G, taps, _, H, Hkv, Dh, F, Fs, width, held,
     *_) = shape
    inner = Hm * P
    conv_dim = inner + 2 * G * N
    out = {"embed": ((V, D), "normal"), "final_norm": ((D,), "ones"),
           "head": ((D, V), "normal")}
    for i, kind in enumerate(pattern):
        p = f"L{i}."
        out[p + "norm"] = ((D,), "ones")
        if kind == "M":
            out[p + "in_proj"] = ((D, inner + conv_dim + Hm), "normal")
            out[p + "conv_w"] = ((conv_dim, taps), "conv")
            out[p + "conv_b"] = ((conv_dim,), "conv")
            out[p + "dt_bias"] = ((Hm,), "dt_bias")
            out[p + "A_log"] = ((Hm,), "A_log")
            out[p + "D"] = ((Hm,), "ones")
            out[p + "gnorm"] = ((inner,), "ones")
            out[p + "out_proj"] = ((inner, D), "normal")
        elif kind == "*":
            out[p + "wq"] = ((D, H * Dh), "normal")
            out[p + "wk"] = ((D, Hkv * Dh), "normal")
            out[p + "wv"] = ((D, Hkv * Dh), "normal")
            out[p + "wo"] = ((H * Dh, D), "normal")
        elif kind == "E":
            out[p + "gate"] = ((D, width), "normal")
            out[p + "ew1"] = ((held, D, F), "normal")
            out[p + "ew2"] = ((held, F, D), "normal")
            out[p + "sw1"] = ((D, Fs), "normal")
            out[p + "sw2"] = ((Fs, D), "normal")
        else:
            raise ValueError(f"hybrid_override_pattern holds {kind!r}; this "
                             "reference has M, * and E")
    return out


def _seeded_leaf(key, dims, how: str, std, shape: tuple):
    taps, (dt_min, dt_max, dt_floor) = shape[7], shape[-1]
    if how == "normal":
        return std * jax.random.normal(key, dims, jnp.float32)
    if how == "ones":
        return jnp.ones(dims, jnp.float32)
    if how == "conv":  # torch's conv1d default: uniform +- 1 / sqrt(taps)
        bound = taps ** -0.5
        return jax.random.uniform(key, dims, jnp.float32, -bound, bound)
    if how == "A_log":
        return jnp.log(jax.random.uniform(key, dims, jnp.float32, 1.0, 16.0))
    # dt_bias: softplus's inverse of a step log-uniform over [min, max]
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        key, dims, jnp.float32, np.log(dt_min), np.log(dt_max))), dt_floor)
    return dt + jnp.log(-jnp.expm1(-dt))


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, shape: tuple, std):
    weights = {
        name: _seeded_leaf(jax.random.fold_in(key, i), dims, how, std, shape)
        for i, (name, (dims, how)) in enumerate(leaf_shapes(shape).items())}
    biases = {
        f"L{i}.expert_bias": std * jax.random.normal(
            jax.random.fold_in(key, 10_000 + i), (shape[14],), jnp.float32)
        for i, kind in enumerate(shape[2]) if kind == "E"}
    return weights, biases


def init_weights(seed: int, cfg: dict):
    """(weights, the selection biases' seeded start), made in one jitted call
    on the device."""
    return _init(lm.seed_key(seed), shape_of(cfg), jnp.float32(cfg["init_std"]))


def seeded(seed: int, cfg: dict, traffic: dict):
    """(weights, selection biases) as a run starts from them: the seeded
    weights, and the biases balanced on the last batch of the seed's pool
    (the checked steps see the first ones)."""
    weights, start = init_weights(seed, cfg)
    tokens = make_batches(seed, cfg, traffic)[-1][:, :-1]
    return weights, balanced_biases(weights, start, jnp.asarray(tokens),
                                    shape_of(cfg))


@functools.partial(jax.jit, static_argnums=(3,))
def balanced_biases(weights, start, tokens, shape: tuple):
    """The selection biases that spread this router's choices evenly over
    its experts on ``tokens``, block by block in one float32 forward pass
    (``reference/lfm2.py balanced``, where the reason is written)."""
    return forward(weights, start, tokens, shape, balance=True)[1]["biases"]


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _scan(x, dt, A, B, C, block: int, state_reset: bool):
    """The recurrence. x: (b, T, H, P); dt: (b, T, H); A: (H,); B, C: (b, T,
    H, N), float32. Returns ``S_t C_t``, (b, T, H, P). Elementwise products
    and sums only: nothing here is a matmul whose precision could be
    lowered behind the reference's back."""
    b, T, H, P = x.shape
    block = min(block, T)
    fresh = (jnp.arange(T) % block == 0) & state_reset

    def step(S, at_t):
        x_t, dt_t, B_t, C_t, fresh_t = at_t
        S = jnp.where(fresh_t, 0.0, S)
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, jnp.sum(S * C_t[:, :, None, :], -1)

    @jax.checkpoint
    def steps(S, at_block):
        return jax.lax.scan(step, S, at_block)

    by_block = [jnp.moveaxis(a, 1, 0).reshape(T // block, block, *a.shape[:1],
                                              *a.shape[2:])
                for a in (x, dt, B, C)] + [fresh.reshape(T // block, block)]
    _, y = jax.lax.scan(steps, jnp.zeros((b, H, P, B.shape[-1]), jnp.float32),
                        by_block)
    return jnp.moveaxis(y.reshape(T, b, H, P), 0, 1)


def _mamba(u, p, shape, compute, state_reset):
    _, _, _, H, P, N, G, taps, chunk, *_ = shape
    eps = shape[19]
    b, T, _ = u.shape
    inner, bc = H * P, G * N
    z, xBC, dt = jnp.split(lm._mm("btd,de->bte", u, p["in_proj"], compute),
                           [inner, 2 * inner + 2 * bc], -1)
    xBC = jax.nn.silu(p["conv_b"] + sum(
        jnp.pad(xBC, ((0, 0), (j, 0), (0, 0)))[:, :T] * p["conv_w"][:, j]
        for j in range(taps)))
    x, B, C = jnp.split(xBC, [inner, inner + bc], -1)
    x = x.reshape(b, T, H, P)
    # the scan's products are its x, B and C: what the control lowers
    to_heads = lambda a: jnp.repeat(  # noqa: E731
        lm._lower(a, compute).reshape(b, T, G, N), H // G, axis=2)
    y = _scan(lm._lower(x, compute), jax.nn.softplus(dt + p["dt_bias"]),
              -jnp.exp(p["A_log"]), to_heads(B), to_heads(C), chunk,
              state_reset)
    y = (y + p["D"][:, None] * x).reshape(b, T, inner) * jax.nn.silu(z)
    y = _rms(y.reshape(b, T, G, inner // G), 1.0, eps).reshape(b, T, inner)
    return lm._mm("bte,ed->btd", y * p["gnorm"], p["out_proj"], compute)


def _attention(u, p, shape, compute):
    H, Hkv, Dh = shape[9:12]
    b, T, _ = u.shape
    q = lm._mm("btd,de->bte", u, p["wq"], compute).reshape(b, T, H, Dh)
    k = lm._mm("btd,de->bte", u, p["wk"], compute).reshape(b, T, Hkv, Dh)
    v = lm._mm("btd,de->bte", u, p["wv"], compute).reshape(b, T, Hkv, Dh)
    return lm._mm("bte,ed->btd", lfm2._attention(q, k, v, compute), p["wo"],
                  compute)


def _relu2_mlp(u, w1, w2, compute):
    mid = jnp.square(jax.nn.relu(lm._mm("nd,df->nf", u, w1, compute)))
    return lm._mm("nf,fd->nd", mid, w2, compute)


def _experts(u, p, bias, shape, compute, capacity_drop, balance=False,
             shared=True):
    """The held experts' part of the expert block, and the shared expert.
    u: (N, D). Returns (Mixer (N, D), the choice (N, k) over all the
    router's experts, the bias used: ``bias``, or with ``balance`` the one
    that evens the load)."""
    held, offset, k, scaling = shape[15:19]
    s = jax.nn.sigmoid(lm._mm("nd,de->ne", u, p["gate"], ROUTER_BELOW[compute]))
    bias = jax.lax.stop_gradient(lfm2.balanced(s, bias, k) if balance else bias)
    _, sel = jax.lax.top_k(s + bias, k)
    chosen = jax.nn.one_hot(sel, s.shape[-1], dtype=jnp.float32).sum(1)
    w = s * chosen
    w = scaling * w / (w.sum(-1, keepdims=True) + 1e-6)
    if capacity_drop:
        # the planted fault: an expert takes its first C tokens, C = 1.0 x
        # the mean load, as a capacity-factor layer would, and drops the rest
        capacity = u.shape[0] * k // s.shape[-1]
        w = w * (jnp.cumsum(chosen, 0) * chosen <= capacity)
    out = _relu2_mlp(u, p["sw1"], p["sw2"], compute) if shared else 0.0
    for e in range(held):
        out = out + w[:, offset + e, None] * _relu2_mlp(
            u, p["ew1"][e], p["ew2"][e], compute)
    return out, sel, bias


def forward(w, biases, x, shape: tuple, compute: str = "f32",
            capacity_drop: bool = False, state_reset: bool = False,
            balance: bool = False):
    """tokens x: (b, T) -> (logits (b, T, V), {"choices": [each expert
    block's choice], "biases": {name: the bias each used}})."""
    D, pattern, eps = shape[1], shape[2], shape[19]
    b, T = x.shape
    h = w["embed"][x]
    choices, used = [], {}

    def block(h, p, bias, kind):
        u = _rms(h, p["norm"], eps)
        if kind == "M":
            return h + _mamba(u, p, shape, compute, state_reset), None
        if kind == "*":
            return h + _attention(u, p, shape, compute), None
        out, *routed = _experts(u.reshape(b * T, D), p, bias, shape, compute,
                                capacity_drop, balance)
        return h + out.reshape(b, T, D), routed

    for i, kind in enumerate(pattern):
        pre = f"L{i}."
        p = {name[len(pre):]: a for name, a in w.items() if name.startswith(pre)}
        h, routed = jax.checkpoint(block, static_argnums=(3,))(
            h, p, biases.get(pre + "expert_bias"), kind)
        if routed is not None:
            choices.append(routed[0])
            used[pre + "expert_bias"] = routed[1]
    logits = lm._mm("btd,dv->btv", _rms(h, w["final_norm"], eps), w["head"],
                    compute)
    return logits, {"choices": choices, "biases": used}


def loss_sum(w, biases, tokens, shape, compute, capacity_drop, state_reset):
    """Sum of token cross-entropies over the rows given. tokens: (b, T+1)."""
    logits, _ = forward(w, biases, tokens[:, :-1], shape, compute,
                        capacity_drop, state_reset)
    logz = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logz, tokens[:, 1:, None], -1).sum()


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def loss_and_grad(w, biases, tokens, shape, compute: str, capacity_drop: bool,
                  state_reset: bool, rows: int):
    """Mean loss and its gradient over the whole batch, ``rows`` sequences
    at a time so that float32 activations fit beside the weights."""
    blocks = tokens.reshape(-1, rows, tokens.shape[-1])
    n_tok = tokens.shape[0] * (tokens.shape[1] - 1)

    def one(acc, tb):
        return jax.tree.map(jnp.add, acc, jax.value_and_grad(loss_sum)(
            w, biases, tb, shape, compute, capacity_drop, state_reset)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, w))
    (l, g), _ = jax.lax.scan(one, zero, blocks)
    return l / n_tok, jax.tree.map(lambda a: a / n_tok, g)


leaf_norms = lfm2.leaf_norms  # an expert tensor's norm a held expert: EXPERT_LEAVES


def readings(seed: int, cfg: dict, traffic: dict, compute: str = "f32",
             drop_half_batch: bool = False, capacity_drop: bool = False,
             state_reset: bool = False, rows: int = 1) -> dict:
    """Follow the first ``check_steps`` steps from the seed: each step's
    loss, the first gradient's norm per leaf, the norm of the parameters'
    change over those steps per leaf. ``drop_half_batch`` plants a step that
    trains on half of its tokens (half of its rows; of a batch of one row,
    that row's first half), ``capacity_drop`` an expert block that drops
    what is over its mean load, ``state_reset`` a scan that forgets its
    state at every chunk's start."""
    o = cfg["optimizer"]
    shape = shape_of(cfg)
    w, biases = seeded(seed, cfg, traffic)
    batches = make_batches(seed, cfg, traffic)[:traffic["check_steps"]]
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, grad1 = [], None
    for t, tokens in enumerate(batches, start=1):
        if drop_half_batch:
            tokens = (tokens[: len(tokens) // 2] if len(tokens) > 1
                      else tokens[:, : tokens.shape[1] // 2 + 1])
        loss, g = loss_and_grad(w, biases, jnp.asarray(tokens), shape, compute,
                                capacity_drop, state_reset,
                                min(rows, len(tokens)))
        losses.append(float(loss))
        if grad1 is None:
            grad1 = leaf_norms(g)
        lr = o["lr"] * min(1.0, t / o["warmup_steps"])
        w, m, v = lm.adamw(w, g, m, v, jnp.float32(t), lr, o["b1"], o["b2"],
                           o["eps"], o["weight_decay"])
        del g
    del m, v
    change = leaf_norms(jax.tree.map(jnp.subtract, w, init_weights(seed, cfg)[0]))
    return {"loss": losses, "grad1": grad1, "change": change}
