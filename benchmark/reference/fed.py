"""Plain reference for the federated round: seeded cohort selection, each
client's local SGD over its own batches of a ResNet-18 with GroupNorm, the
sample-weighted mean of the clients' updates and the server's update of the
global model (FedAvg), in straightforward jax.numpy. float32 at precision
"highest" by default; ``compute`` lowers every convolution's and matmul's
operands to bfloat16 or float8 (e4m3), which is what the control runs.

Nothing here imports the program. Data and weights are made here from the
seed; the runner hands the same arrays to the program."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STAGES = (64, 128, 256, 512)
HI = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def make_data(seed: int, cfg: dict):
    """(x, y, index): standard-normal images, uniform labels, and for each
    client the rows it holds (a seeded balanced split: ``homo``)."""
    n, size, ch = cfg["train_examples"], cfg["image_size"], cfg["image_channels"]
    rng = np.random.default_rng([int(seed), 2])
    x = rng.standard_normal((n, size, size, ch), dtype=np.float32)
    y = rng.integers(0, cfg["num_classes"], n, dtype=np.int32)
    rows = rng.permutation(n).reshape(cfg["client_num_in_total"],
                                      cfg["examples_per_client"])
    return x, y, {c: np.sort(r) for c, r in enumerate(rows)}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init(key, channels: int, classes: int):
    keys = iter(jax.random.split(key, 32))

    def conv(kh, cin, cout):
        std = np.sqrt(2.0 / (kh * kh * cin))
        return std * jax.random.normal(next(keys), (kh, kh, cin, cout), jnp.float32)

    def norm(c):
        return jnp.ones((c,), jnp.float32), jnp.zeros((c,), jnp.float32)

    w = {"stem": conv(3, channels, 64), "blocks": []}
    prev = 64
    for ch in STAGES:
        for j in range(2):
            cin = prev if j == 0 else ch
            b = {"c1": conv(3, cin, ch), "c2": conv(3, ch, ch)}
            b["g1s"], b["g1b"] = norm(ch)
            b["g2s"], b["g2b"] = norm(ch)
            if cin != ch:
                b["pc"] = conv(1, cin, ch)
                b["pgs"], b["pgb"] = norm(ch)
            w["blocks"].append(b)
        prev = ch
    w["fc_w"] = jax.random.normal(next(keys), (512, classes), jnp.float32) / np.sqrt(512.0)
    w["fc_b"] = jnp.zeros((classes,), jnp.float32)
    return w


def init_weights(seed: int, cfg: dict) -> dict:
    """All weights in one jitted call on the device, float32."""
    return _init(seed_key(seed), cfg["image_channels"], cfg["num_classes"])


def cohort(seed: int, round_idx: int, cfg: dict, per_round: int) -> np.ndarray:
    """The round's clients: a draw without replacement from a generator
    seeded by (seed, round)."""
    rng = np.random.default_rng([int(seed), int(round_idx)])
    return rng.choice(cfg["client_num_in_total"], per_round, replace=False)


def client_batches(seed: int, round_idx: int, cid: int, rows: np.ndarray,
                   batch: int) -> np.ndarray:
    """(steps, batch) rows of one client's local epoch: its examples in the
    order of a permutation seeded by (seed, round, client)."""
    perm = np.random.default_rng(
        [int(seed), int(round_idx), int(cid)]).permutation(len(rows))
    return rows[perm].reshape(-1, batch)


def _lower(x, compute):
    """An operand as the lower precision holds it, back in float32: the
    products are then exact and the sum is float32's, as on the MXU."""
    if compute == "f32":
        return x
    if compute == "fp8":
        x = x.astype(jnp.float8_e4m3fn)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _conv(x, k, stride, compute):
    return jax.lax.conv_general_dilated(
        _lower(x, compute), _lower(k, compute), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
        preferred_element_type=jnp.float32)


def _group_norm(x, scale, bias, group: int, eps: float):
    b, h, w, c = x.shape
    g = x.reshape(b, h, w, c // group, group)
    mu = g.mean((1, 2, 4), keepdims=True)
    var = ((g - mu) ** 2).mean((1, 2, 4), keepdims=True)
    return ((g - mu) * jax.lax.rsqrt(var + eps)).reshape(x.shape) * scale + bias


def forward(w, x, group: int, eps: float, compute: str):
    gn = functools.partial(_group_norm, group=group, eps=eps)
    h = _conv(x, w["stem"], 1, compute)
    for i, b in enumerate(w["blocks"]):
        stride = 2 if i in (2, 4, 6) else 1
        y = jax.nn.relu(gn(_conv(h, b["c1"], stride, compute), b["g1s"], b["g1b"]))
        y = gn(_conv(y, b["c2"], 1, compute), b["g2s"], b["g2b"])
        if "pc" in b:
            h = gn(_conv(h, b["pc"], stride, compute), b["pgs"], b["pgb"])
        h = jax.nn.relu(y + h)
    pooled = h.mean((1, 2))
    return jnp.dot(_lower(pooled, compute), _lower(w["fc_w"], compute),
                   precision=HI, preferred_element_type=jnp.float32) + w["fc_b"]


def batch_loss(w, xb, yb, group, eps, compute):
    logz = jax.nn.log_softmax(forward(w, xb, group, eps, compute), -1)
    return -jnp.take_along_axis(logz, yb[:, None], -1).mean()


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def run_round(w, x_all, y_all, idx, lr, group, eps, compute, chunk):
    """One FedAvg round. idx: (clients, steps, batch) rows of x_all. Returns
    the new global weights and the mean over clients of each client's mean
    step loss. Clients run ``chunk`` at a time."""

    def client(rows):
        def step(p, r):
            loss, g = jax.value_and_grad(batch_loss)(
                p, x_all[r], y_all[r], group, eps, compute)
            return jax.tree.map(lambda a, b: a - lr * b, p, g), loss

        p, losses = jax.lax.scan(step, w, rows)
        return jax.tree.map(jnp.subtract, p, w), losses.mean()

    deltas, losses = jax.lax.map(client, idx, batch_size=chunk)
    # every client holds the same number of examples: the sample-weighted
    # mean is weighted by idx.shape[1] * idx.shape[2] for each
    new = jax.tree.map(lambda a, d: a + d.mean(0), w, deltas)
    return new, losses.mean()


def leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda ls: [jnp.sqrt(jnp.sum(a ** 2)) for a in ls])(
        [a for _, a in flat])
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path):
            float(n) for (path, _), n in zip(flat, norms)}


def readings(seed: int, cfg: dict, traffic: dict, compute: str = "f32",
             drop_half_cohort: bool = False, chunk: int = 8) -> dict:
    """Follow the first ``check_steps`` rounds from the seed. Returns each
    round's training loss, the norm per leaf of the first round's aggregate
    update (the gradient as the server's optimizer gets it) and the norm per
    leaf of the parameters' change over those rounds. ``drop_half_cohort``
    plants the fault of a round that leaves half of its clients out and takes
    the mean over the rest."""
    x, y, index = make_data(seed, cfg)
    x, y = jnp.asarray(x), jnp.asarray(y)
    w0 = init_weights(seed, cfg)
    w, losses, grad1 = w0, [], None
    for r in range(traffic["check_steps"]):
        ids = cohort(seed, r, cfg, traffic["client_num_per_round"])
        if drop_half_cohort:
            ids = ids[: len(ids) // 2]
        idx = np.stack([client_batches(seed, r, c, index[int(c)],
                                       cfg["batch_size"]) for c in ids])
        w, loss = run_round(w, x, y, jnp.asarray(idx, jnp.int32),
                            jnp.float32(cfg["learning_rate"]),
                            cfg["group_norm_group_size"],
                            cfg["group_norm_epsilon"], compute,
                            min(chunk, len(ids)))
        losses.append(float(loss))
        if grad1 is None:
            grad1 = leaf_norms(jax.tree.map(jnp.subtract, w, w0))
    change = leaf_norms(jax.tree.map(jnp.subtract, w, w0))
    return {"loss": losses, "grad1": grad1, "change": change}
