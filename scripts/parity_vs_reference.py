"""Cross-framework parity: the jitted engine vs the reference torch hot loop.

The strongest accuracy-parity evidence available in a zero-egress image
(round-2 review weak #4): run the reference framework's FedAvg semantics —
replicated here in torch, on this machine's CPU — and the fedml_tpu jitted
engine on *identical* data, *identical* client sampling, *identical*
per-client batch permutations, *identical* initial weights, and assert the
per-round train-loss curves and the final global parameters agree to f32
tolerance.

Reference semantics replicated on the torch side:
- client sampling: the engine's pure per-round sampler
  (``fedml_tpu.simulation.sampling.sample_clients`` — a
  ``default_rng([seed, round])`` no-replacement draw; the reference's
  global ``np.random.seed(round_idx)`` stream survives as
  ``reference_client_sampling`` for the cross-silo server, but the
  simulation engines no longer consume it)
- local training: ``simulation/sp/fedavg/my_model_trainer_classification.py:15``
  (plain SGD, mean-reduction CE on logits, fixed batch order, ``epochs`` passes)
- aggregation: ``fedavg_api.py:156-171`` (sample-count weighted mean over the
  full weight set)

Determinism bridge: both sides consume the engine's per-client shuffle
streams ``np.random.default_rng([seed, round, client_id])`` (the engine's
``FedSimulator._client_perms``; the reference's DataLoader shuffle is an
unseeded torch generator, so batch ORDER is the one free variable — pinning
it to the same deterministic stream on both sides is what makes bitwise-level
comparison possible). The torch models mirror the flax modules exactly
(flatten in NHWC order) so initial weights transfer by transpose alone.

Usage: python scripts/parity_vs_reference.py
Writes results/parity_vs_reference.json.
"""

from __future__ import annotations

import copy
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BS = 16


# --- synthetic data (identical arrays feed both frameworks) ---------------

def make_synth(n_clients, sizes, feat_shape, n_classes, seed,
               test_per_client=24):
    rng = np.random.default_rng(seed)
    total = sum(sizes)
    # class-dependent means so the loss visibly falls
    y = rng.integers(0, n_classes, size=total).astype(np.int64)
    centers = rng.normal(0.0, 1.0, size=(n_classes,) + tuple(feat_shape))
    x = (centers[y] + rng.normal(0.0, 1.0, size=(total,) + tuple(feat_shape))
         ).astype(np.float32)
    idx_map, start = {}, 0
    for c, n in enumerate(sizes):
        idx_map[c] = list(range(start, start + n))
        start += n
    # per-client local TEST splits (same generative process) so the
    # _local_test_on_all_clients comparison exercises distinct local sets
    n_test = test_per_client * n_clients
    ty = rng.integers(0, n_classes, size=n_test).astype(np.int64)
    tx = (centers[ty]
          + rng.normal(0.0, 1.0, size=(n_test,) + tuple(feat_shape))
          ).astype(np.float32)
    test_idx_map = {
        c: list(range(c * test_per_client, (c + 1) * test_per_client))
        for c in range(n_clients)
    }
    return x, y, idx_map, tx, ty, test_idx_map


# --- engine side ----------------------------------------------------------

def run_engine(model_name, x, y, idx_map, n_classes, per_round, rounds,
               epochs, lr, seed, tx, ty, test_idx_map):
    import jax

    # Parity is about ALGORITHM semantics, so pin true-f32 math: on TPU the
    # default matmul/conv precision decomposes f32 into bf16 passes, which
    # drifts past the tolerance over rounds (measured: cnn 0.057 loss diff
    # at default vs ~1e-4 at highest). CPU is unaffected.
    jax.config.update("jax_default_matmul_precision", "highest")

    import fedml_tpu
    from fedml_tpu.data.federated import ArrayPair, build_federated_data
    from fedml_tpu.simulation import build_simulator

    fed = build_federated_data(
        ArrayPair(x, y.astype(np.int32)), ArrayPair(tx, ty.astype(np.int32)),
        idx_map, n_classes, test_idx_map=test_idx_map,
    )
    args = fedml_tpu.init(config=dict(
        dataset="synthetic_parity", model=model_name,
        client_num_in_total=len(idx_map), client_num_per_round=per_round,
        comm_round=rounds, learning_rate=lr, epochs=epochs, batch_size=BS,
        frequency_of_the_test=1, random_seed=seed,
        cohort_schedule="even", local_test_on_all_clients=True,
    ))
    sim, apply_fn = build_simulator(args, fed_data=fed)
    # real copies, not views: the round step donates the params buffers
    init_params = jax.tree.map(lambda a: np.array(a, copy=True), sim.params)
    hist = sim.run(apply_fn=apply_fn, log_fn=None)
    final_params = jax.tree.map(np.asarray, sim.params)
    losses = [h["train_loss"] for h in hist]
    local_metrics = [
        {k: h[k] for k in ("local_train_acc", "local_train_loss",
                           "local_test_acc", "local_test_loss")}
        for h in hist
    ]
    return init_params, final_params, losses, local_metrics


# --- reference-semantics torch side --------------------------------------

def _torch_models(model_name, flax_params, n_classes, feat_shape):
    """Build the torch mirror and load the flax initial weights into it."""
    import torch
    import torch.nn as nn

    p = flax_params["params"]
    if model_name == "lr":
        d = int(np.prod(feat_shape))

        class LR(nn.Module):
            def __init__(self):
                super().__init__()
                self.linear = nn.Linear(d, n_classes)

            def forward(self, x):
                return self.linear(x.flatten(1))

        m = LR()
        with torch.no_grad():
            m.linear.weight.copy_(torch.from_numpy(np.asarray(p["linear"]["kernel"]).T))
            m.linear.bias.copy_(torch.from_numpy(np.asarray(p["linear"]["bias"])))
        return m

    if model_name == "cnn_fedavg":
        # mirror of models/cnn.py CNNOriginalFedAvg; flattens in NHWC order so
        # flax dense kernels transfer by plain transpose
        class CNN(nn.Module):
            def __init__(self):
                super().__init__()
                self.c1 = nn.Conv2d(feat_shape[-1], 32, 5, padding=2)
                self.c2 = nn.Conv2d(32, 64, 5, padding=2)
                self.d1 = nn.Linear(64 * (feat_shape[0] // 4) * (feat_shape[1] // 4), 512)
                self.d2 = nn.Linear(512, n_classes)
                self.pool = nn.MaxPool2d(2, 2)

            def forward(self, x):
                x = x.permute(0, 3, 1, 2)  # NHWC input -> NCHW convs
                x = self.pool(torch.relu(self.c1(x)))
                x = self.pool(torch.relu(self.c2(x)))
                x = x.permute(0, 2, 3, 1).flatten(1)  # NHWC flatten = flax
                return self.d2(torch.relu(self.d1(x)))

        m = CNN()
        with torch.no_grad():
            for tmod, fkey in ((m.c1, "Conv_0"), (m.c2, "Conv_1")):
                k = np.asarray(p[fkey]["kernel"])  # (H, W, Cin, Cout)
                tmod.weight.copy_(torch.from_numpy(k.transpose(3, 2, 0, 1).copy()))
                tmod.bias.copy_(torch.from_numpy(np.asarray(p[fkey]["bias"])))
            for tmod, fkey in ((m.d1, "Dense_0"), (m.d2, "Dense_1")):
                k = np.asarray(p[fkey]["kernel"])  # (in, out)
                tmod.weight.copy_(torch.from_numpy(k.T.copy()))
                tmod.bias.copy_(torch.from_numpy(np.asarray(p[fkey]["bias"])))
        return m

    raise ValueError(model_name)


def run_torch_reference(model_name, flax_init, x, y, idx_map, n_classes,
                        per_round, rounds, epochs, lr, seed, feat_shape,
                        tx, ty, test_idx_map):
    import torch
    import torch.nn as nn

    torch.manual_seed(0)
    model = _torch_models(model_name, flax_init, n_classes, feat_shape)
    criterion = nn.CrossEntropyLoss()
    n_total = len(idx_map)
    w_global = copy.deepcopy(model.state_dict())
    losses_per_round = []
    local_metrics_per_round = []

    def local_test_on_all_clients():
        """fedavg_api.py:188-246 + my_model_trainer_classification.local_test
        (sum-of-per-sample-loss accumulation): weighted aggregates over
        every client's local train and test split under w_global."""
        model.load_state_dict(w_global)
        model.eval()
        sum_crit = nn.CrossEntropyLoss(reduction="sum")
        out = {}
        for split, data, split_map in (
            ("train", (x, y), idx_map), ("test", (tx, ty), test_idx_map)
        ):
            n_corr = n_samp = loss_sum = 0.0
            with torch.no_grad():
                for cid in range(n_total):
                    rows = np.asarray(split_map[int(cid)])
                    bx = torch.from_numpy(data[0][rows])
                    by = torch.from_numpy(data[1][rows])
                    logits = model(bx)
                    loss_sum += float(sum_crit(logits, by).item())
                    n_corr += float((logits.argmax(-1) == by).sum().item())
                    n_samp += len(rows)
            key = "local_train" if split == "train" else "local_test"
            out[f"{key}_acc"] = n_corr / n_samp
            out[f"{key}_loss"] = loss_sum / n_samp
        return out

    for round_idx in range(rounds):
        # lockstep with the engine's pure per-round sampler (the engine
        # moved off the reference's global np.random.seed(round_idx) stream;
        # parity means drawing the SAME cohorts the engine draws)
        from fedml_tpu.simulation.sampling import sample_clients

        cohort = np.asarray(
            sample_clients(seed, round_idx, n_total, per_round))
        w_locals, client_losses = [], []
        for cid in cohort:
            model.load_state_dict(copy.deepcopy(w_global))
            model.train()
            opt = torch.optim.SGD(model.parameters(), lr=lr)
            rows = np.asarray(idx_map[int(cid)])
            # the engine's deterministic local-epoch shuffle
            perm = np.random.default_rng(
                [seed, round_idx, int(cid)]).permutation(len(rows))
            order = rows[perm]
            nb = len(order) // BS
            batch_losses = []
            for _ in range(epochs):
                for b in range(nb):
                    sel = order[b * BS:(b + 1) * BS]
                    bx = torch.from_numpy(x[sel])
                    by = torch.from_numpy(y[sel])
                    model.zero_grad()
                    loss = criterion(model(bx), by)
                    loss.backward()
                    opt.step()
                    batch_losses.append(loss.item())
            client_losses.append(float(np.mean(batch_losses)))
            w_locals.append((len(rows), copy.deepcopy(model.state_dict())))
        # fedavg_api.py:156-171 sample-weighted aggregation
        training_num = sum(n for n, _ in w_locals)
        agg = {}
        for k in w_locals[0][1]:
            agg[k] = sum((n / training_num) * w[k] for n, w in w_locals)
        w_global = agg
        losses_per_round.append(float(np.mean(client_losses)))
        local_metrics_per_round.append(local_test_on_all_clients())

    model.load_state_dict(w_global)
    return model, losses_per_round, local_metrics_per_round


def _flax_to_flat(model_name, flax_params):
    """Flax params -> {torch_key: np.ndarray} for comparison."""
    p = flax_params["params"]
    if model_name == "lr":
        return {"linear.weight": np.asarray(p["linear"]["kernel"]).T,
                "linear.bias": np.asarray(p["linear"]["bias"])}
    out = {}
    for tkey, fkey in (("c1", "Conv_0"), ("c2", "Conv_1")):
        out[f"{tkey}.weight"] = np.asarray(
            p[fkey]["kernel"]).transpose(3, 2, 0, 1)
        out[f"{tkey}.bias"] = np.asarray(p[fkey]["bias"])
    for tkey, fkey in (("d1", "Dense_0"), ("d2", "Dense_1")):
        out[f"{tkey}.weight"] = np.asarray(p[fkey]["kernel"]).T
        out[f"{tkey}.bias"] = np.asarray(p[fkey]["bias"])
    return out


def run_parity(model_name, feat_shape, n_classes, sizes, per_round, rounds,
               epochs, lr, seed=3):
    x, y, idx_map, tx, ty, test_idx_map = make_synth(
        len(sizes), sizes, feat_shape, n_classes, seed)
    flax_init, flax_final, engine_losses, engine_local = run_engine(
        model_name, x, y, idx_map, n_classes, per_round, rounds, epochs, lr,
        seed, tx, ty, test_idx_map)
    torch_model, torch_losses, torch_local = run_torch_reference(
        model_name, flax_init, x, y, idx_map, n_classes, per_round, rounds,
        epochs, lr, seed, feat_shape, tx, ty, test_idx_map)

    loss_diffs = [abs(a - b) for a, b in zip(engine_losses, torch_losses)]
    # per-round _local_test_on_all_clients METRIC VALUES must match too —
    # the reference's reported numbers, not just the final params
    local_keys = ("local_train_acc", "local_train_loss",
                  "local_test_acc", "local_test_loss")
    local_diffs = [
        abs(e[k] - t[k])
        for e, t in zip(engine_local, torch_local) for k in local_keys
    ]
    flat = _flax_to_flat(model_name, flax_final)
    sd = torch_model.state_dict()
    param_diff = max(
        float(np.max(np.abs(flat[k] - sd[k].numpy()))) for k in flat
    )
    return {
        "model": model_name,
        "rounds": rounds,
        "engine_losses": engine_losses,
        "reference_losses": torch_losses,
        "engine_local_metrics": engine_local,
        "reference_local_metrics": torch_local,
        "max_abs_loss_diff": max(loss_diffs),
        "max_abs_local_metric_diff": max(local_diffs),
        "max_abs_param_diff": param_diff,
        "loss_tol": 2e-3,
        "param_tol": 2e-3,
        "pass": (max(loss_diffs) < 2e-3 and param_diff < 2e-3
                 and max(local_diffs) < 2e-3),
    }


def main():
    import jax

    results = {
        "engine_backend": (
            f"{jax.default_backend()} (jax_default_matmul_precision=highest "
            "is pinned by the harness: TPU's default precision decomposes "
            "f32 matmuls/convs into bf16 passes — a hardware numeric mode, "
            "not an algorithm-semantics difference, and it drifts the CNN "
            "case past tolerance over rounds)"),
        "basis": (
            "reference FedAvg semantics (engine sample_clients cohorts, "
            "trainer my_model_trainer_classification.py:15, aggregation "
            "fedavg_api.py:156-171) replicated in torch on this CPU vs the "
            "fedml_tpu jitted engine; identical data/init/sampling/batch "
            "permutations, f32 both sides"
        ),
        "cases": [
            run_parity("lr", (32,), 5, sizes=[64, 48, 32, 64, 48, 32, 64, 64],
                       per_round=4, rounds=6, epochs=2, lr=0.1),
            run_parity("cnn_fedavg", (28, 28, 1), 10,
                       sizes=[32, 32, 48, 32, 48, 32],
                       per_round=3, rounds=4, epochs=1, lr=0.05),
        ],
    }
    results["pass"] = all(c["pass"] for c in results["cases"])
    out = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "results", "parity_vs_reference.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(json.dumps(results, indent=2))
    if not results["pass"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
