"""Torch/HF checkpoint import: logit equality + federated fine-tune.

round-2 review missing #3: the reference's FedNLP path fine-tunes pretrained
HF BERT (app/fednlp/.../bert_model.py). Here a REAL HuggingFace
BertForSequenceClassification (config-constructed — zero egress) is saved
as a torch state_dict file, imported into the flax BERT, and the logits are
asserted equal to the torch forward; then a federated fine-tune run starts
from the imported weights and learns.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fedml_tpu.models.bert import BertConfig, BertForSequenceClassification
from fedml_tpu.utils.torch_import import (
    convert_state_dict,
    import_bert_classifier,
    linear_kernel,
    load_torch_state_dict,
)

CFG = BertConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                 num_attention_heads=2, intermediate_size=64,
                 max_position_embeddings=16, type_vocab_size=2, num_labels=3)


def _hf_model():
    import transformers

    hf_cfg = transformers.BertConfig(
        vocab_size=CFG.vocab_size, hidden_size=CFG.hidden_size,
        num_hidden_layers=CFG.num_hidden_layers,
        num_attention_heads=CFG.num_attention_heads,
        intermediate_size=CFG.intermediate_size,
        max_position_embeddings=CFG.max_position_embeddings,
        type_vocab_size=CFG.type_vocab_size, num_labels=CFG.num_labels,
        hidden_act="gelu",
    )
    model = transformers.BertForSequenceClassification(hf_cfg)
    model.eval()
    return model


def test_hf_bert_checkpoint_logit_equality(tmp_path):
    import torch

    torch.manual_seed(0)
    hf = _hf_model()
    ckpt = str(tmp_path / "bert_tiny.pt")
    torch.save(hf.state_dict(), ckpt)

    variables = import_bert_classifier(ckpt, CFG)
    flax_model = BertForSequenceClassification(CFG)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, CFG.vocab_size, size=(4, 12)).astype(np.int32)
    mask = np.ones((4, 12), np.float32)
    mask[2, 8:] = 0.0  # one padded sequence exercises the attention bias
    with torch.no_grad():
        torch_logits = hf(
            input_ids=torch.from_numpy(ids.astype(np.int64)),
            attention_mask=torch.from_numpy(mask.astype(np.int64)),
        ).logits.numpy()
    flax_logits = np.asarray(flax_model.apply(
        variables, jnp.asarray(ids), attention_mask=jnp.asarray(mask),
        train=False))
    np.testing.assert_allclose(flax_logits, torch_logits, atol=2e-5)


def test_import_shape_check_fails_loudly(tmp_path):
    import torch

    hf = _hf_model()
    sd = hf.state_dict()
    sd["classifier.weight"] = torch.zeros(5, 7)  # wrong shape
    with pytest.raises(ValueError, match="shape mismatch"):
        import_bert_classifier(
            {k: v.numpy() for k, v in sd.items()}, CFG)


def test_import_rejects_unmapped_and_missing_keys():
    with pytest.raises(ValueError, match="no mapping"):
        convert_state_dict({"surprise.weight": np.zeros((2, 2))},
                           mapping={}, expected_shapes=None)
    # a checkpoint that leaves flax leaves unpopulated is also rejected —
    # even when the mapping table covers them (e.g. encoder-only BERT)
    with pytest.raises(ValueError, match="not populated"):
        convert_state_dict(
            {"a.weight": np.zeros((2, 3))},
            mapping={"a.weight": (("a", "kernel"), linear_kernel),
                     "b.bias": (("b", "bias"), np.asarray)},
            expected_shapes={("a", "kernel"): (3, 2), ("b", "bias"): (4,)},
        )


def test_federated_finetune_from_imported_weights(tmp_path):
    """The reference fednlp flow: pretrained checkpoint -> federated
    fine-tune. Labels here are a function of the first token, so the tiny
    randomly-initialized 'pretrained' net must genuinely learn."""
    import torch

    from fedml_tpu.algorithms import LocalTrainConfig, get_algorithm
    from fedml_tpu.data.federated import ArrayPair, build_federated_data
    from fedml_tpu.simulation.fed_sim import FedSimulator, SimConfig

    torch.manual_seed(1)
    hf = _hf_model()
    ckpt = str(tmp_path / "pretrained.pt")
    torch.save(hf.state_dict(), ckpt)
    variables = import_bert_classifier(load_torch_state_dict(ckpt), CFG)

    rng = np.random.default_rng(0)
    n = 256
    x = rng.integers(0, CFG.vocab_size, size=(n, 12)).astype(np.int32)
    y = (x[:, 0] % CFG.num_labels).astype(np.int32)
    idx_map = {c: list(range(c * 64, (c + 1) * 64)) for c in range(4)}
    fed = build_federated_data(ArrayPair(x, y), ArrayPair(x[:64], y[:64]),
                               idx_map, CFG.num_labels)

    model = BertForSequenceClassification(CFG)

    def apply_fn(v, xx, train=False, rngs=None, mutable=False):
        return model.apply(v, xx, train=False)  # dropout off for the test

    alg = get_algorithm("FedAvg", apply_fn,
                        LocalTrainConfig(lr=1e-3, epochs=1,
                                         client_optimizer="adam"))
    sim = FedSimulator(fed, alg, variables,
                       SimConfig(comm_round=6, client_num_in_total=4,
                                 client_num_per_round=4, batch_size=16,
                                 frequency_of_the_test=1000, seed=0))
    hist = sim.run(apply_fn=None, log_fn=None)
    assert hist[-1]["train_loss"] < hist[0]["train_loss"], hist
    assert hist[-1]["train_acc"] > 0.75 > hist[0]["train_acc"], hist
