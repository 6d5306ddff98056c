"""Real-format loader tests: LEAF JSON + TFF h5 fixtures (committed in-repo).

Fixtures are generated by scripts/make_leaf_fixtures.py in the exact on-disk
formats the reference consumes; these tests exercise the real parse paths and
the natural per-user client partitions (round-1 review #4).
"""

import os

import numpy as np
import pytest

from fedml_tpu.data import leaf, load_partition_data
from fedml_tpu.data.leaf import (
    CHAR_BOS,
    CHAR_EOS,
    CHAR_OOV,
    CHAR_PAD,
    SHAKESPEARE_SEQ_LEN,
    SHAKESPEARE_VOCAB_SIZE,
    shakespeare_snippet_to_sequences,
    stackoverflow_sentence_to_ids,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def test_leaf_json_mnist_natural_partition():
    fed = load_partition_data(
        "mnist", os.path.join(FIXTURES, "leaf_mnist"),
        partition_method="hetero", partition_alpha=0.5, client_num=999,
    )
    # natural partition: 3 users from the json, NOT the requested 999
    assert fed.client_num == 3
    assert fed.class_num == 10
    for c in range(3):
        pair = fed.train_data_local_dict[c]
        assert pair.x.shape[1:] == (28, 28, 1)
        assert pair.x.dtype == np.float32 and pair.y.dtype == np.int32
        # global-index map gathers this client's exact rows
        np.testing.assert_array_equal(
            fed.train_data_global.x[fed._global_index[c]], pair.x
        )
    assert fed.train_data_num == sum(fed.train_data_local_num_dict.values())
    # per-user test split parsed too
    assert all(len(fed.test_data_local_dict[c]) > 0 for c in range(3))


def test_shakespeare_snippet_encoding_reference_semantics():
    """bos + char ids + eos, padded to a multiple of 81, 81-token windows
    (reference data/fed_shakespeare/utils.py:preprocess)."""
    text = "dhl"
    (seq,) = shakespeare_snippet_to_sequences(text)
    assert len(seq) == SHAKESPEARE_SEQ_LEN + 1
    # 'd','h','l' are the first three chars of the TFF vocab -> ids 1,2,3
    assert seq[:5] == [CHAR_BOS, 1, 2, 3, CHAR_EOS]
    assert set(seq[5:]) == {CHAR_PAD}
    # out-of-vocab char maps to the OOV bucket
    (seq2,) = shakespeare_snippet_to_sequences("~")
    assert seq2[1] == CHAR_OOV
    # long snippet splits into multiple windows with no dropped chars
    long = "x" * 200
    wins = shakespeare_snippet_to_sequences(long)
    assert len(wins) == 3  # 202 tokens -> ceil to 243 = 3 windows
    flat = [t for w in wins for t in w]
    assert flat.count(6) == 200  # 'x' is the 6th vocab char


def test_fed_shakespeare_h5_loader():
    fed = load_partition_data(
        "fed_shakespeare", FIXTURES,
        partition_method="hetero", partition_alpha=0.5, client_num=50,
    )
    assert fed.client_num == 2  # natural per-speaker partition
    assert fed.class_num == SHAKESPEARE_VOCAB_SIZE == 90
    pair = fed.train_data_local_dict[0]
    assert pair.x.shape[1] == SHAKESPEARE_SEQ_LEN
    # per-token LM targets: y is x shifted by one inside each window
    np.testing.assert_array_equal(pair.x[:, 1:], pair.y[:, :-1])


def test_femnist_h5_loader():
    fed = load_partition_data(
        "femnist", FIXTURES,
        partition_method="hetero", partition_alpha=0.5, client_num=50,
    )
    assert fed.client_num == 2
    assert fed.class_num == 62
    pair = fed.train_data_local_dict[0]
    assert pair.x.shape == (8, 28, 28, 1)
    assert pair.y.shape == (8,)


def test_stackoverflow_tokenizer_reference_semantics():
    """truncate to 20 words, eos if short, bos prepended, pad to 21
    (reference data/stackoverflow_nwp/utils.py:tokenizer)."""
    word_dict = {"how": 1, "do": 2, "i": 3}
    V = len(word_dict)
    ids = stackoverflow_sentence_to_ids("how do i frobnicate", word_dict)
    assert len(ids) == 21
    bos, eos, oov = V + 1, V + 2, V + 3
    assert ids[:6] == [bos, 1, 2, 3, oov, eos]
    assert set(ids[6:]) == {0}
    # 25-word sentence: truncated at 20, no eos
    long_ids = stackoverflow_sentence_to_ids("how " * 25, word_dict)
    assert len(long_ids) == 21
    assert long_ids[0] == bos and long_ids[1:] == [1] * 20


def test_stackoverflow_h5_loader():
    fed = load_partition_data(
        "stackoverflow_nwp", FIXTURES,
        partition_method="hetero", partition_alpha=0.5, client_num=50,
    )
    assert fed.client_num == 2
    word_count = sum(1 for _ in open(os.path.join(FIXTURES, "stackoverflow.word_count")))
    assert fed.class_num == word_count + 4
    pair = fed.train_data_local_dict[0]
    assert pair.x.shape[1] == 20


def test_digits_real_dataset_learns():
    """Real data end-to-end: sklearn's bundled handwritten digits, federated
    across 10 clients, LR FedAvg reaches >= 85% test acc — genuine real-data
    learning evidence in a zero-egress image."""
    import fedml_tpu
    from fedml_tpu.simulation import build_simulator

    args = fedml_tpu.init(config=dict(
        dataset="digits", model="lr", partition_method="homo",
        client_num_in_total=10, client_num_per_round=10, comm_round=30,
        learning_rate=0.3, epochs=1, batch_size=32,
        frequency_of_the_test=29, random_seed=0,
    ))
    sim, apply_fn = build_simulator(args)
    hist = sim.run(apply_fn, log_fn=None)
    assert hist[-1]["test_acc"] >= 0.85, hist[-1]


def test_natural_partition_reconciles_args_end_to_end():
    """A LEAF cache with 3 users + requested client_num_in_total=999 must
    clamp cohort sampling to the natural population (no KeyError, no silent
    subset)."""
    import fedml_tpu
    from fedml_tpu.simulation import build_simulator

    args = fedml_tpu.init(config=dict(
        dataset="mnist", model="lr",
        data_cache_dir=os.path.join(FIXTURES, "leaf_mnist"),
        client_num_in_total=999, client_num_per_round=999, comm_round=2,
        learning_rate=0.1, epochs=1, batch_size=4,
        frequency_of_the_test=1, random_seed=0,
    ))
    sim, apply_fn = build_simulator(args)
    assert args.client_num_in_total == 3
    assert args.client_num_per_round == 3
    hist = sim.run(apply_fn, log_fn=None)
    assert len(hist) == 2 and np.isfinite(hist[-1]["train_loss"])
