"""FedNAS bilevel search: alpha steps on a val split, genotype retrain.

round-2 review missing #2: the reference alternates weight steps with
architecture-alpha steps through an Architect (architect.py:541,
train_search.py:435) and retrains the derived genotype. These tests run the
bilevel search federated, check the alphas actually move (they are NOT
ordinary FedAvg params any more), and check search-then-retrain beats a
random-genotype control on the same budget.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fedml_tpu.algorithms.fednas import (
    FedNASConfig,
    alpha_mask,
    get_fednas_algorithm,
    run_fednas_search,
)
from fedml_tpu.data.federated import ArrayPair, build_federated_data
from fedml_tpu.models.darts import (
    OP_NAMES,
    DARTSSearchNet,
    DerivedNet,
    derive_genotype,
    genotype_to_cells,
)
from fedml_tpu.simulation.fed_sim import FedSimulator, SimConfig

H = 16


def _shape_dataset(n, seed):
    """Binary shapes with EQUAL total energy: class 1 = 3x3 plus sign,
    class 0 = 3x3 diagonal. Global average pooling of the raw image cannot
    separate them — conv ops can, so search should prefer convs."""
    rng = np.random.default_rng(seed)
    x = rng.normal(0.0, 0.3, size=(n, H, H, 1)).astype(np.float32)
    y = rng.integers(0, 2, size=n).astype(np.int32)
    for i in range(n):
        r, c = rng.integers(2, H - 3, size=2)
        if y[i]:
            x[i, r, c - 1:c + 2, 0] += 2.0  # plus sign
            x[i, r - 1:r + 2, c, 0] += 2.0
            x[i, r, c, 0] -= 2.0
        else:
            for d in (-1, 0, 1):  # diagonal + anti-diagonal (same energy)
                x[i, r + d, c + d, 0] += 2.0
                x[i, r + d, c - d, 0] += 2.0
            x[i, r, c, 0] -= 2.0
    return x, y


def _fed(n_clients=4, per_client=64, seed=0):
    x, y = _shape_dataset(n_clients * per_client + 128, seed)
    idx_map = {c: list(range(c * per_client, (c + 1) * per_client))
               for c in range(n_clients)}
    test = ArrayPair(x[-128:], y[-128:])
    return build_federated_data(
        ArrayPair(x[:n_clients * per_client], y[:n_clients * per_client]),
        test, idx_map, 2), test


def _accuracy(model, variables, test):
    logits = model.apply(variables, jnp.asarray(test.x), train=False)
    return float((jnp.argmax(logits, -1) == jnp.asarray(test.y)).mean())


def _retrain(genotype_cells, fed, test, rounds=6, seed=0):
    from fedml_tpu.algorithms import LocalTrainConfig, get_algorithm

    model = DerivedNet(genotype=genotype_cells, num_classes=2, channels=8)
    variables = model.init(jax.random.PRNGKey(seed),
                           jnp.zeros((1, H, H, 1), jnp.float32), train=False)

    def apply_fn(v, x, train=False, rngs=None, mutable=False):
        return model.apply(v, x, train=train)

    alg = get_algorithm("FedAvg", apply_fn,
                        LocalTrainConfig(lr=0.05, epochs=1, momentum=0.9))
    sim = FedSimulator(fed, alg, variables,
                       SimConfig(comm_round=rounds, client_num_in_total=4,
                                 client_num_per_round=4, batch_size=16,
                                 frequency_of_the_test=1000, seed=seed))
    sim.run(apply_fn=None, log_fn=None)
    return model, sim.params


def test_bilevel_search_moves_alphas_and_learns():
    fed, test = _fed()
    model = DARTSSearchNet(num_classes=2, channels=8, n_cells=2)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, H, H, 1), jnp.float32), train=False)

    def apply_fn(v, x, train=False, rngs=None, mutable=False):
        return model.apply(v, x, train=train)

    hist, final, genotype = run_fednas_search(
        fed, variables, apply_fn,
        SimConfig(comm_round=8, client_num_in_total=4, client_num_per_round=4,
                  batch_size=16, frequency_of_the_test=1000, seed=0),
        FedNASConfig(lr=0.05, arch_lr=3e-3, epochs=1),
    )
    # alphas moved away from their zero init (bilevel step is live)
    amask = alpha_mask(final)
    moved = [float(jnp.abs(a).max())
             for a, m in zip(jax.tree.leaves(final), jax.tree.leaves(amask))
             if m]
    assert len(moved) == 4  # 2 cells x 2 mixed ops
    assert max(moved) > 1e-3
    assert hist[-1]["train_loss"] < hist[0]["train_loss"]
    assert len(genotype) == 4 and all(g["op"] in OP_NAMES for g in genotype)


def test_search_then_retrain_beats_random_genotype():
    fed, test = _fed()
    model = DARTSSearchNet(num_classes=2, channels=8, n_cells=2)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, H, H, 1), jnp.float32), train=False)

    def apply_fn(v, x, train=False, rngs=None, mutable=False):
        return model.apply(v, x, train=train)

    _, final, genotype = run_fednas_search(
        fed, variables, apply_fn,
        SimConfig(comm_round=8, client_num_in_total=4, client_num_per_round=4,
                  batch_size=16, frequency_of_the_test=1000, seed=0),
        FedNASConfig(lr=0.05, arch_lr=3e-3, epochs=1),
    )
    searched = genotype_to_cells(genotype, n_cells=2)

    # random-genotype control: first sample that differs from the searched one
    rng = np.random.default_rng(7)
    while True:
        random_cells = tuple(
            tuple(rng.choice(OP_NAMES) for _ in range(2)) for _ in range(2))
        if random_cells != searched:
            break

    m_s, v_s = _retrain(searched, fed, test)
    m_r, v_r = _retrain(random_cells, fed, test)
    acc_s, acc_r = _accuracy(m_s, v_s, test), _accuracy(m_r, v_r, test)
    assert acc_s > acc_r, (searched, random_cells, acc_s, acc_r)
