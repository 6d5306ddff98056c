"""VFL, SplitNN, TurboAggregate, FedGKT, FedGAN, FedNAS, FedSeg."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.simulation import build_simulator
from fedml_tpu.simulation.fed_sim import SimConfig


def test_vertical_fl_learns():
    from fedml_tpu.algorithms.vertical_fl import VFLSimulator

    rng = np.random.default_rng(0)
    n, d = 600, 10
    w_true = rng.normal(size=(d, 3))
    x = rng.normal(size=(n + 200, d)).astype(np.float32)
    y = np.argmax(x @ w_true + 0.1 * rng.normal(size=(n + 200, 3)), axis=1)
    sim = VFLSimulator(x[:n], y[:n], x[n:], y[n:], n_parties=3, n_classes=3,
                       lr=0.5, batch_size=64)
    hist = sim.run(epochs=8)
    assert hist[-1]["test_acc"] > 0.8, hist[-1]


def test_split_nn_learns():
    from fedml_tpu.algorithms.split_nn import SplitNNSimulator
    from fedml_tpu import data as data_mod

    args = fedml_tpu.init(config=dict(
        dataset="mnist", debug_small_data=True, client_num_in_total=4,
        partition_method="homo", random_seed=0))
    fed, _ = data_mod.load(args)
    import flax.linen as nn

    class Body(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = x.reshape((x.shape[0], -1))
            return nn.relu(nn.Dense(64)(x))

    class Head(nn.Module):
        @nn.compact
        def __call__(self, h):
            return nn.Dense(10)(h)

    body, head = Body(), Head()
    x0 = jnp.zeros((1, 28, 28, 1))
    cp = body.init(jax.random.PRNGKey(0), x0)
    sp = head.init(jax.random.PRNGKey(1), body.apply(cp, x0))
    sim = SplitNNSimulator(body.apply, head.apply, cp, sp, lr=0.2)
    pk = fed.pack_clients([0, 1, 2, 3], batch_size=16, num_batches=4)
    first = sim.run_epoch(pk.x, pk.y, pk.mask)
    for _ in range(3):
        last = sim.run_epoch(pk.x, pk.y, pk.mask)
    assert last["train_loss"] < first["train_loss"]
    test = fed.test_data_global
    preds = jnp.argmax(sim.predict(test.x[:200]), -1)
    assert float((preds == jnp.asarray(test.y[:200])).mean()) > 0.5


def test_turbo_aggregate_matches_fedavg_closely():
    from fedml_tpu.algorithms import LocalTrainConfig, make_local_update
    from fedml_tpu.algorithms.turbo_aggregate import TurboAggregateSimulator
    from fedml_tpu import data as data_mod, models as models_mod

    args = fedml_tpu.init(config=dict(
        dataset="mnist", model="lr", debug_small_data=True,
        client_num_in_total=4, client_num_per_round=4, comm_round=3,
        learning_rate=0.1, batch_size=8, frequency_of_the_test=1, random_seed=0))
    fed, output_dim = data_mod.load(args)
    model = models_mod.create(args, output_dim)
    variables = models_mod.init_params(
        model, jax.random.PRNGKey(0), models_mod.sample_input_for(args, fed))

    def apply_fn(v, x, train=False, rngs=None):
        return model.apply(v, x, train=train)

    lu = make_local_update(apply_fn, LocalTrainConfig(lr=0.1, epochs=1))
    sim = TurboAggregateSimulator(
        fed, lu, variables,
        SimConfig(comm_round=3, client_num_in_total=4, client_num_per_round=4,
                  batch_size=8, frequency_of_the_test=1),
        privacy_guarantee=1, q_bits=14)
    hist = sim.run(apply_fn, log_fn=None)
    assert hist[0]["train_loss"] > hist[-1]["train_loss"]
    assert hist[-1]["test_acc"] > 0.5


def test_fedgkt_learns():
    from fedml_tpu.algorithms.fedgkt import FedGKTSimulator
    from fedml_tpu.models import GKTClientNet, GKTServerNet
    from fedml_tpu import data as data_mod

    args = fedml_tpu.init(config=dict(
        dataset="cifar10", debug_small_data=True, client_num_in_total=3,
        partition_method="homo", random_seed=0))
    fed, _ = data_mod.load(args)
    cnet = GKTClientNet(num_classes=10)
    snet = GKTServerNet(num_classes=10)
    x0 = jnp.zeros((1, 32, 32, 3))
    cp = cnet.init(jax.random.PRNGKey(0), x0)
    h0, _ = cnet.apply(cp, x0)
    sp = snet.init(jax.random.PRNGKey(1), h0)
    sim = FedGKTSimulator(
        fed, cnet.apply, snet.apply, cp, sp,
        SimConfig(comm_round=3, client_num_in_total=3, client_num_per_round=3,
                  batch_size=16), lr=0.05)
    hist = sim.run(log_fn=None)
    assert hist[0]["client_loss"] > hist[-1]["client_loss"]
    acc = sim.evaluate(cnet.apply, snet.apply)
    assert np.isfinite(acc)


def test_fedgan_round_runs():
    from fedml_tpu.algorithms.fedgan import get_fedgan_algorithm
    from fedml_tpu.models import Discriminator, Generator
    from fedml_tpu.simulation.fed_sim import FedSimulator
    from fedml_tpu import data as data_mod

    args = fedml_tpu.init(config=dict(
        dataset="mnist", debug_small_data=True, client_num_in_total=3,
        partition_method="homo", random_seed=0))
    fed, _ = data_mod.load(args)
    gen, disc = Generator(latent_dim=16), Discriminator()
    gp = gen.init(jax.random.PRNGKey(0), jnp.zeros((1, 16)))
    dp = disc.init(jax.random.PRNGKey(1), jnp.zeros((1, 28, 28, 1)))
    alg = get_fedgan_algorithm(gen.apply, disc.apply, latent_dim=16, lr=1e-3)
    sim = FedSimulator(
        fed, alg, {"gen": gp, "disc": dp},
        SimConfig(comm_round=2, client_num_in_total=3, client_num_per_round=3,
                  batch_size=8, num_local_batches=2))
    hist = sim.run(apply_fn=None, log_fn=None)
    assert len(hist) == 2
    assert np.isfinite(hist[-1]["train_loss"])


def test_fednas_darts_search_runs():
    from fedml_tpu.models import derive_genotype

    args = fedml_tpu.init(config=dict(
        dataset="cifar10", model="darts", debug_small_data=True,
        client_num_in_total=3, client_num_per_round=3, comm_round=2,
        learning_rate=0.05, batch_size=8, frequency_of_the_test=2,
        random_seed=0))
    sim, apply_fn = build_simulator(args)
    hist = sim.run(apply_fn, log_fn=None)
    assert hist[0]["train_loss"] >= hist[-1]["train_loss"] or hist[-1]["train_loss"] < 3.0
    genotype = derive_genotype(sim.params)
    assert len(genotype) == 4  # 2 cells x 2 mixed ops
    assert all(g["op"] in ("conv3", "conv5", "avgpool", "identity") for g in genotype)


@pytest.mark.slow
def test_fedseg_transunet_learns():
    """TransUNet (reference app/fedcv/image_segmentation/model/transunet):
    CNN encoder + ViT bottleneck must train federated and segment."""
    args = fedml_tpu.init(config=dict(
        dataset="seg_synthetic", model="transunet", debug_small_data=True,
        client_num_in_total=2, client_num_per_round=2, comm_round=3,
        partition_method="homo", learning_rate=0.05, batch_size=8,
        frequency_of_the_test=3, random_seed=0))
    sim, apply_fn = build_simulator(args)
    hist = sim.run(apply_fn, log_fn=None)
    assert hist[0]["train_loss"] > hist[-1]["train_loss"]
    assert hist[-1]["test_acc"] > 0.9, hist[-1]


@pytest.mark.slow
def test_fedseg_deeplab_learns_and_beats_unet_control():
    """DeepLabV3+ (reference app/fedcv/image_segmentation/model/
    deeplabV3_plus.py) trains federated, learns, and — round-3 review #4 —
    earns its ASPP/decoder depth: same federated budget on the 4-class
    medical segmentation task, at least UNetLite's per-pixel accuracy.
    (slow: ~20 distinct conv shapes to compile on one CPU core; one
    combined test so the DeepLab compile is paid once)"""
    def run(model):
        args = fedml_tpu.init(config=dict(
            dataset="fets2021", model=model, debug_small_data=True,
            client_num_in_total=2, client_num_per_round=2, comm_round=4,
            partition_method="homo", learning_rate=0.05, batch_size=8,
            frequency_of_the_test=4, random_seed=0))
        sim, apply_fn = build_simulator(args)
        return sim.run(apply_fn, log_fn=None)

    h_unet = run("unet")
    h_dl = run("deeplabv3_plus")
    assert h_dl[0]["train_loss"] > h_dl[-1]["train_loss"]
    assert h_dl[-1]["test_acc"] >= h_unet[-1]["test_acc"] - 0.02, (
        h_dl[-1], h_unet[-1])
    assert h_dl[-1]["test_acc"] > 0.85, h_dl[-1]


def test_fedseg_unet_learns():
    args = fedml_tpu.init(config=dict(
        dataset="seg_synthetic", model="unet", debug_small_data=True,
        client_num_in_total=3, client_num_per_round=3, comm_round=3,
        partition_method="homo", learning_rate=0.1, batch_size=8,
        frequency_of_the_test=2, random_seed=0))
    sim, apply_fn = build_simulator(args)
    hist = sim.run(apply_fn, log_fn=None)
    assert hist[-1]["train_loss"] < hist[0]["train_loss"]
    # per-pixel accuracy should beat majority-class-ish quickly
    assert hist[-1]["test_acc"] > 0.9
