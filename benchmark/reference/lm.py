"""Plain reference for the LM step: a pre-LayerNorm decoder (learned
positions, causal attention, tanh-GELU MLP, untied head), mean token
cross-entropy, gradients and AdamW, in straightforward jax.numpy. float32 at
matmul precision "highest" by default; ``compute`` lowers every matmul's
operands to bfloat16 or to float8 (e4m3), which is what the control runs.

Nothing here imports the program. The weights are made here from the seed, in
this module's own layout (per-layer tensors stacked on a leading axis), and
the runner copies them into the program."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STACKED = ("ln1_g", "ln1_b", "qkv", "proj", "ln2_g", "ln2_b",
           "fc_w", "fc_b", "out_w", "out_b")
AS_RUN_LN_EPS = 1e-6  # flax's default, which the program runs (see `assumed`)


def seed_key(seed: int):
    """A key from any whole number up to 2**63 (the driver's are large)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _init(key, vocab, d, layers, positions, ratio, std):
    ks = jax.random.split(key, 7)
    n = lambda k, *shape: std * jax.random.normal(k, shape, jnp.float32)  # noqa: E731
    ones = lambda *s: jnp.ones(s, jnp.float32)  # noqa: E731
    zeros = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
    return {
        "wte": n(ks[0], vocab, d), "wpe": n(ks[1], positions, d),
        "ln1_g": ones(layers, d), "ln1_b": zeros(layers, d),
        "qkv": n(ks[2], layers, d, 3 * d), "proj": n(ks[3], layers, d, d),
        "ln2_g": ones(layers, d), "ln2_b": zeros(layers, d),
        "fc_w": n(ks[4], layers, d, ratio * d), "fc_b": zeros(layers, ratio * d),
        "out_w": n(ks[5], layers, ratio * d, d), "out_b": zeros(layers, d),
        "lnf_g": ones(d), "lnf_b": zeros(d), "head": n(ks[6], d, vocab),
    }


def init_weights(seed: int, cfg: dict) -> dict:
    """All weights in one jitted call on the device, float32."""
    return _init(seed_key(seed), cfg["vocab_size"], cfg["n_embd"],
                 cfg["n_layer"], cfg["n_positions"], cfg["mlp_ratio"],
                 jnp.float32(cfg["init_std"]))


def make_batches(seed: int, cfg: dict, traffic: dict) -> np.ndarray:
    """(pool, B, T+1) token ids from the seed: every row differs."""
    rng = np.random.default_rng([int(seed), 1])
    return rng.integers(
        0, cfg["vocab_size"],
        (traffic["token_pool_batches"], traffic["batch"],
         traffic["seq_len"] + 1), dtype=np.int32)


def _lower(x, compute):
    """An operand as the lower precision holds it, back in float32: the
    products are then exact and the sum is float32's, as on the MXU."""
    if compute == "f32":
        return x
    if compute == "fp8":
        x = x.astype(jnp.float8_e4m3fn)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(spec, a, b, compute):
    return jnp.einsum(spec, _lower(a, compute), _lower(b, compute),
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


def _ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + AS_RUN_LN_EPS) * g + b


def loss_sum(w, tokens, heads: int, compute: str):
    """Sum of token cross-entropies over the rows given. tokens: (b, T+1)."""
    x, y = tokens[:, :-1], tokens[:, 1:]
    T = x.shape[1]
    h = w["wte"][x] + w["wpe"][:T][None]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def block(h, p):
        a = _ln(h, p["ln1_g"], p["ln1_b"])
        qkv = _mm("btd,de->bte", a, p["qkv"], compute)
        q, k, v = (t.reshape(t.shape[0], T, heads, -1)
                   for t in jnp.split(qkv, 3, -1))
        s = _mm("bqhd,bkhd->bhqk", q, k, compute) / np.sqrt(q.shape[-1])
        s = jnp.where(causal, s, -jnp.inf)
        o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, compute)
        h = h + _mm("btd,de->bte", o.reshape(h.shape), p["proj"], compute)
        a = _ln(h, p["ln2_g"], p["ln2_b"])
        f = jax.nn.gelu(_mm("btd,de->bte", a, p["fc_w"], compute) + p["fc_b"],
                        approximate=True)
        return h + _mm("bte,ed->btd", f, p["out_w"], compute) + p["out_b"], None

    h, _ = jax.lax.scan(jax.checkpoint(block), h, {k: w[k] for k in STACKED})
    logits = _mm("btd,dv->btv", _ln(h, w["lnf_g"], w["lnf_b"]), w["head"],
                 compute)
    logz = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logz, y[..., None], -1).sum()


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def loss_and_grad(w, tokens, heads: int, compute: str, rows: int):
    """Mean loss and its gradient over the whole batch, ``rows`` sequences at
    a time so that float32 activations fit beside the weights."""
    blocks = tokens.reshape(-1, rows, tokens.shape[-1])
    n_tok = tokens.shape[0] * (tokens.shape[1] - 1)

    def one(acc, tb):
        l, g = jax.value_and_grad(loss_sum)(w, tb, heads, compute)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, w))
    (l, g), _ = jax.lax.scan(one, zero, blocks)
    return l / n_tok, jax.tree.map(lambda a: a / n_tok, g)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def adamw(w, g, m, v, t, lr, b1, b2, eps, wd):
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    w = jax.tree.map(
        lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * w),
        w, m, v)
    return w, m, v


def leaf_norms(tree: dict) -> dict:
    """name -> l2 norm; a stacked tensor gives one norm per layer,
    ``name/i``."""
    out = {}
    for name, a in tree.items():
        if name in STACKED:
            per = np.asarray(jnp.sqrt(jnp.sum(
                a.astype(jnp.float32) ** 2, axis=tuple(range(1, a.ndim)))))
            out.update({f"{name}/{i}": float(x) for i, x in enumerate(per)})
        else:
            out[name] = float(jnp.sqrt(jnp.sum(a.astype(jnp.float32) ** 2)))
    return out


def readings(seed: int, cfg: dict, traffic: dict, compute: str = "f32",
             drop_half_batch: bool = False, rows: int = 1) -> dict:
    """Follow the first ``check_steps`` steps from the seed. Returns each
    step's loss, the first gradient's norm per leaf and the norm of the
    parameters' change over those steps per leaf. ``drop_half_batch`` plants
    the fault of a step that trains on half of its rows."""
    o = cfg["optimizer"]
    steps = traffic["check_steps"]
    w = init_weights(seed, cfg)
    batches = make_batches(seed, cfg, traffic)[:steps]
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, grad1 = [], None
    for t, tokens in enumerate(batches, start=1):
        if drop_half_batch:
            tokens = tokens[: len(tokens) // 2]
        loss, g = loss_and_grad(w, jnp.asarray(tokens), cfg["n_head"],
                                compute, min(rows, len(tokens)))
        losses.append(float(loss))
        if grad1 is None:
            grad1 = leaf_norms(g)
        w, m, v = adamw(w, g, m, v, jnp.float32(t), o["lr"], o["b1"], o["b2"],
                        o["eps"], o["weight_decay"])
        del g
    del m, v
    change = leaf_norms(jax.tree.map(jnp.subtract, w, init_weights(seed, cfg)))
    return {"loss": losses, "grad1": grad1, "change": change}
