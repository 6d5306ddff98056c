"""L6 app-ecosystem task families (reference app/ tree):

- FedNLP: sequence tagging, span extraction, seq2seq (app/fednlp/*)
- FedGraphNN: node classification, link prediction, graph regression
  (app/fedgraphnn/*)

Each runs a few federated rounds through the shared simulator and must LEARN
(beat the task's chance level by a margin), not just execute.
"""

import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.simulation import build_simulator


def _run(config, rounds=4):
    base = dict(
        debug_small_data=True, client_num_in_total=4, client_num_per_round=4,
        comm_round=rounds, epochs=2, batch_size=16,
        frequency_of_the_test=rounds, random_seed=0,
        partition_method="homo",
    )
    base.update(config)
    args = fedml_tpu.init(config=base)
    sim, apply_fn = build_simulator(args)
    hist = sim.run(apply_fn, log_fn=None)
    return hist


def test_fednlp_seq_tagging_learns():
    hist = _run(dict(
        dataset="seq_tagging", model="transformer_tagger",
        vocab_size=128, max_seq_len=64, model_dim=64, model_layers=1,
        model_heads=4, learning_rate=0.01, client_optimizer="adam",
        epochs=4,
    ), rounds=10)
    # 9 tags -> chance ~0.11; the contextual rule is learnable fast
    assert hist[-1]["test_acc"] > 0.6, hist[-1]


def test_fednlp_span_extraction_learns():
    hist = _run(dict(
        dataset="span_extraction", model="span_extractor",
        vocab_size=256, max_seq_len=64, model_dim=64, model_layers=2,
        model_heads=4, learning_rate=0.003, client_optimizer="adam",
        batch_size=32, epochs=3,
    ), rounds=8)
    # chance = 1/seq_len ~ 0.016 per boundary; the bracketing delimiters
    # make both boundaries learnable (reaches ~0.97)
    assert hist[-1]["test_acc"] > 0.7, hist[-1]


def test_fednlp_seq2seq_learns():
    hist = _run(dict(
        dataset="seq2seq", model="seq2seq",
        vocab_size=64, src_seq_len=16, tgt_seq_len=8,
        model_dim=64, model_layers=2, model_heads=4,
        learning_rate=0.003, client_optimizer="adam", epochs=6,
    ), rounds=15)
    # per-token chance ~1/63; reversal needs encoder-decoder attention
    # (reaches 1.0)
    assert hist[-1]["test_acc"] > 0.8, hist[-1]


def test_fedgraphnn_node_classification_learns():
    hist = _run(dict(
        dataset="ego_networks_node_clf", model="gcn_node",
        learning_rate=0.003, client_optimizer="adam", epochs=6,
    ), rounds=15)
    # 2-class per-node, balanced-ish by construction -> chance ~0.5
    assert hist[-1]["test_acc"] > 0.6, hist[-1]


def test_fedgraphnn_link_prediction_learns():
    hist = _run(dict(
        dataset="ego_networks_link_pred", model="gcn_link",
        learning_rate=0.003, client_optimizer="adam", epochs=6,
    ), rounds=16)
    # pairwise 2-class; community structure + observed edges make links
    # recoverable above the ~0.66 majority (no-link) rate
    assert hist[-1]["test_acc"] > 0.7, hist[-1]


def test_fedgraphnn_graph_regression_learns():
    hist = _run(dict(
        dataset="moleculenet_reg", model="gcn_reg",
        learning_rate=0.003, client_optimizer="adam", epochs=3,
    ), rounds=8)
    # loss_kind=mse engages via the model name; test_loss is an MSE here.
    # Targets span [0, 4]; predicting the mean gives MSE ~1.3 — structure
    # must cut it well below that.
    assert hist[-1]["test_loss"] < 0.4, hist[-1]
    # and the within-0.5 hit rate ("accuracy") should be high
    assert hist[-1]["test_acc"] > 0.6, hist[-1]


@pytest.mark.slow
def test_medical_chest_xray_classification_learns():
    """Chest-x-ray classification (reference app/fedcv/
    medical_chest_xray_image_clf: DenseNet + CE over CheXpert/NIH-style
    data; synthetic opacity-pattern stand-in under zero egress)."""
    hist = _run(dict(
        dataset="chest_xray", model="densenet",
        learning_rate=0.003, client_optimizer="adam", epochs=2,
        batch_size=16,
    ), rounds=12)
    # 4 balanced classes -> chance 0.25
    assert hist[-1]["test_acc"] > 0.6, hist[-1]


@pytest.mark.slow
def test_medical_fets_segmentation_learns():
    """FeTS2021-style federated tumor segmentation (reference data/FeTS2021
    in SURVEY §2.2): 4-modality input, per-pixel 4-class labels."""
    hist = _run(dict(
        dataset="fets2021", model="unet",
        learning_rate=0.05, epochs=2,
    ), rounds=6)
    # background dominates (~90% pixels); segmentation must beat it
    assert hist[-1]["test_acc"] > 0.93, hist[-1]


def test_fedgraphnn_relation_prediction_learns():
    """Typed-edge relation prediction (reference app/fedgraphnn/
    subgraph_relation_pred: RGCN encoder + DistMult decoder)."""
    hist = _run(dict(
        dataset="subgraph_relation_pred", model="rgcn",
        learning_rate=0.003, client_optimizer="adam", epochs=6,
    ), rounds=16)
    # 5-way over all pairs; ~65% pairs are class 0 (no relation) so the
    # majority rate is ~0.65 — relation structure must push past it
    assert hist[-1]["test_acc"] > 0.75, hist[-1]


def test_fedgraphnn_recsys_rating_completion_learns():
    """Recsys user-item subgraph link prediction (reference
    app/fedgraphnn/recsys_subgraph_link_pred: MSE on rating logits)."""
    hist = _run(dict(
        dataset="recsys_subgraph_link_pred", model="gcn_recsys",
        learning_rate=0.01, client_optimizer="adam", epochs=6,
    ), rounds=20)
    # float labels => masked MSE; ratings span [1,5] (sd ~1.2 =>
    # mean-prediction MSE ~1.5) — completion must clearly beat the mean
    assert hist[-1]["test_loss"] < 0.8, hist[-1]


def test_all_reference_fedgraphnn_dirs_have_dataset_aliases():
    """Every task directory under the reference app/fedgraphnn tree must
    resolve through data.load (capability-parity check, round-3 review #5)."""
    from fedml_tpu import data as data_mod

    for name in ("moleculenet", "moleculenet_reg", "ego_networks_node_clf",
                 "ego_networks_link_pred", "subgraph_link_pred",
                 "social_networks_graph_clf", "subgraph_relation_pred",
                 "recsys_subgraph_link_pred"):
        args = fedml_tpu.init(config=dict(
            dataset=name, model="gcn", debug_small_data=True,
            client_num_in_total=2, client_num_per_round=2, comm_round=1,
            partition_method="homo", batch_size=8, random_seed=0))
        fed, class_num = data_mod.load(args)
        assert class_num >= 1 and len(fed.train_data_local_dict) == 2, name


def test_regression_float_labels_survive_packing():
    """Float regression targets must not be truncated to ints anywhere in
    the packing path (ADVICE r1: native pack int32 cast)."""
    args = fedml_tpu.init(config=dict(
        dataset="moleculenet_reg", model="gcn_reg", debug_small_data=True,
        client_num_in_total=3, client_num_per_round=3, comm_round=1,
        partition_method="hetero", partition_alpha=0.5, random_seed=0,
        batch_size=8,
    ))
    from fedml_tpu import data as data_mod

    fed, _ = data_mod.load(args)
    ys = np.concatenate([p.y for p in fed.train_data_local_dict.values()])
    assert ys.dtype == np.float32
    assert not np.allclose(ys, np.round(ys)), "float targets were truncated"
