"""Model zoo + factory: ``fedml_tpu.models.create(args, output_dim)``.

Parity: reference ``python/fedml/model/model_hub.py:20-94`` — dispatch on
``(args.model, args.dataset)``. Returns an (un-initialized) Flax module;
``init_params(model, rng, sample_input)`` produces the param pytree.

Implemented: lr, cnn (CNN_DropOut), cnn_fedavg, resnet18_gn, resnet56/20,
rnn (per-dataset LSTM variants), rnn_fedavg, mobilenet (v1), mobilenet_v3,
efficientnet, vgg11, vit, transformer_lm, darts (FedNAS search net), unet
(FedSeg), GAN generator/discriminator, GKT client/server pair.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from .cnn import CNNDropOut, CNNOriginalFedAvg
from .linear import LogisticRegression
from .resnet import CifarResNet, ResNet18
from .rnn import RNNOriginalFedAvg, RNNStackOverFlow
from .mobilenet import MobileNetV1
from .mobilenet_v3 import EfficientNet, EfficientNetLite, MobileNetV3Small, VGG
from .transformer import (
    Seq2SeqTransformer,
    TransformerClassifier,
    TransformerLM,
    TransformerSpanExtractor,
    TransformerTagger,
    ViT,
)
from .gan import Discriminator, Generator
from .gkt import GKTClientNet, GKTServerNet
from .darts import DARTSSearchNet, derive_genotype
from .unet import UNetLite
from .yolo import YoloLiteDetector
from .gcn import (
    BipartiteGCNRecommender,
    GCNGraphClassifier,
    GCNGraphRegressor,
    GCNLinkPredictor,
    GCNNodeClassifier,
    RGCNRelationPredictor,
)
from .mobile import (
    MobileLeNet5,
    MobileResNet18,
    build_mobile_model_file,
    load_mobile_model_file,
)

__all__ = [
    "create", "init_params", "sample_input_for",
    "LogisticRegression", "CNNDropOut", "CNNOriginalFedAvg",
    "CifarResNet", "ResNet18", "RNNOriginalFedAvg", "RNNStackOverFlow",
    "MobileNetV1", "MobileNetV3Small", "EfficientNet", "EfficientNetLite", "VGG",
    "TransformerLM", "TransformerClassifier", "ViT",
    "TransformerTagger", "TransformerSpanExtractor", "Seq2SeqTransformer",
    "Generator", "Discriminator", "GKTClientNet", "GKTServerNet",
    "DARTSSearchNet", "derive_genotype", "UNetLite", "YoloLiteDetector", "GCNGraphClassifier",
    "GCNNodeClassifier", "GCNLinkPredictor", "GCNGraphRegressor",
    "MobileLeNet5", "MobileResNet18", "build_mobile_model_file",
    "load_mobile_model_file",
]


def create(args, output_dim: int):
    """Reference ``fedml.model.create`` (model_hub.py:20)."""
    model_name = getattr(args, "model", "lr")
    dataset = getattr(args, "dataset", "mnist")
    dtype = jnp.bfloat16 if getattr(args, "use_bf16", False) else jnp.float32

    if model_name == "lr":
        return LogisticRegression(num_classes=output_dim, dtype=dtype)
    if model_name == "cnn":
        return CNNDropOut(num_classes=output_dim, only_digits=(dataset == "mnist"), dtype=dtype)
    if model_name == "cnn_fedavg":
        return CNNOriginalFedAvg(num_classes=output_dim, dtype=dtype)
    if model_name == "resnet18_gn":
        return ResNet18(num_classes=output_dim, norm_kind="group", dtype=dtype)
    if model_name in ("resnet56", "resnet20", "resnet8"):
        # 6n+2 CIFAR family; resnet8 (n=1) exists for fast BN-path tests
        depth = int(model_name.replace("resnet", ""))
        # 'batch' matches the reference flagship resnet56 (model/cv/resnet.py:303);
        # batch_stats thread through training via make_local_update and are
        # federated-averaged like every other key (fedavg_api.py:163-170).
        norm = getattr(args, "norm", "group")
        # conv_impl: "xla" (default) | "im2col" | "pallas" — the multi-weight
        # conv paths (ops/conv.py) for per-lane-weight execution experiments;
        # XLA's conv is the default (the three are not compared on this
        # chip)
        conv_impl = getattr(args, "conv_impl", None) or "xla"
        return CifarResNet(depth=depth, num_classes=output_dim,
                           norm_kind=norm, dtype=dtype, conv_impl=conv_impl)
    if model_name == "mobilenet":
        return MobileNetV1(num_classes=output_dim, dtype=dtype)
    if model_name == "mobilenet_v3":
        return MobileNetV3Small(num_classes=output_dim, dtype=dtype)
    if model_name == "efficientnet":
        return EfficientNetLite(num_classes=output_dim, dtype=dtype)
    if model_name.startswith("efficientnet-"):
        # compound-scaling family (reference model/cv/efficientnet)
        from .mobilenet_v3 import EFFICIENTNET_PARAMS

        variant = model_name.split("-", 1)[1]
        if variant not in EFFICIENTNET_PARAMS:
            raise ValueError(
                f"unknown efficientnet variant '{variant}' "
                f"(have {sorted(EFFICIENTNET_PARAMS)})")
        return EfficientNet(num_classes=output_dim, variant=variant,
                            dtype=dtype)
    if model_name == "vgg11":
        return VGG(num_classes=output_dim, dtype=dtype)
    if model_name in ("densenet", "densenet121"):
        # medical chest-x-ray backbone (reference app/fedcv/
        # medical_chest_xray_image_clf/model/densenet.py)
        from .densenet import DenseNet

        if model_name == "densenet121":
            return DenseNet(num_classes=output_dim, growth=32,
                            block_config=(6, 12, 24, 16), dtype=dtype)
        return DenseNet(num_classes=output_dim, dtype=dtype)
    if model_name == "darts":
        return DARTSSearchNet(num_classes=output_dim, dtype=dtype)
    if model_name == "unet":
        return UNetLite(num_classes=output_dim, dtype=dtype)
    if model_name in ("deeplabv3_plus", "deeplab"):
        # DeepLabV3+ (reference app/fedcv/image_segmentation/model/
        # deeplabV3_plus.py) — ASPP + low-level fusion decoder
        from .deeplab import DeepLabV3Plus

        return DeepLabV3Plus(num_classes=output_dim, dtype=dtype)
    if model_name == "transunet":
        # TransUNet (reference app/fedcv/image_segmentation/model/
        # transunet/transunet.py) — CNN encoder + ViT bottleneck + decoder
        from .transunet import TransUNet

        return TransUNet(num_classes=output_dim, dtype=dtype)
    if model_name == "yolo_lite":
        # multi-scale anchor detector (reference app/fedcv YOLOv5 class)
        return YoloLiteDetector(num_classes=output_dim, dtype=dtype)
    if model_name in ("gcn", "graph"):
        return GCNGraphClassifier(
            num_classes=output_dim,
            num_nodes=int(getattr(args, "graph_num_nodes", 16) or 16),
            dtype=dtype,
        )
    if model_name == "gcn_node":
        return GCNNodeClassifier(
            num_classes=output_dim,
            num_nodes=int(getattr(args, "graph_num_nodes", 16) or 16),
            dtype=dtype,
        )
    if model_name == "rgcn":
        # relation-type prediction over typed edges (reference
        # app/fedgraphnn/subgraph_relation_pred RGCN+DistMult); dataset
        # class_num = num_relations + 1 (class 0 = no relation)
        return RGCNRelationPredictor(
            num_relations=max(output_dim - 1, 1),
            num_nodes=int(getattr(args, "graph_num_nodes", 16) or 16),
            dtype=dtype,
        )
    if model_name in ("gcn_recsys", "recsys_link_pred"):
        # user-item rating completion (reference
        # app/fedgraphnn/recsys_subgraph_link_pred, MSE on rating logits)
        return BipartiteGCNRecommender(
            num_users=int(getattr(args, "graph_num_users", 8) or 8),
            num_items=int(getattr(args, "graph_num_items", 8) or 8),
            dtype=dtype,
        )
    if model_name == "gcn_link":
        return GCNLinkPredictor(
            num_nodes=int(getattr(args, "graph_num_nodes", 16) or 16),
            dtype=dtype,
        )
    if model_name == "gcn_reg":
        return GCNGraphRegressor(
            num_nodes=int(getattr(args, "graph_num_nodes", 16) or 16),
            dtype=dtype,
        )
    if model_name in ("rnn", "rnn_fedavg"):
        if "stackoverflow" in dataset:
            return RNNStackOverFlow(dtype=dtype)
        return RNNOriginalFedAvg(vocab_size=output_dim, dtype=dtype)
    if model_name == "transformer_lm":
        return TransformerLM(vocab_size=output_dim, dtype=dtype)
    if model_name in ("transformer_classifier", "bert_tiny"):
        vocab = int(getattr(args, "vocab_size", 2000) or 2000)
        return TransformerClassifier(
            num_classes=output_dim, vocab_size=vocab,
            max_len=int(getattr(args, "max_seq_len", 512) or 512), dtype=dtype,
        )
    if model_name == "vit":
        return ViT(num_classes=output_dim, dtype=dtype)
    dim = int(getattr(args, "model_dim", 256) or 256)
    layers = int(getattr(args, "model_layers", 4) or 4)
    heads = int(getattr(args, "model_heads", 8) or 8)
    if model_name in ("transformer_tagger", "bert_tagger"):
        vocab = int(getattr(args, "vocab_size", 2000) or 2000)
        return TransformerTagger(
            num_tags=output_dim, vocab_size=vocab, dim=dim,
            num_layers=layers, num_heads=heads,
            max_len=int(getattr(args, "max_seq_len", 512) or 512), dtype=dtype,
        )
    if model_name in ("span_extractor", "bert_qa"):
        vocab = int(getattr(args, "vocab_size", 2000) or 2000)
        return TransformerSpanExtractor(
            vocab_size=vocab, dim=dim, num_layers=layers, num_heads=heads,
            max_len=int(getattr(args, "max_seq_len", 512) or 512), dtype=dtype,
        )
    if model_name in ("seq2seq", "bart_tiny"):
        vocab = int(getattr(args, "vocab_size", 2000) or 2000)
        return Seq2SeqTransformer(
            vocab_size=vocab, dim=dim, num_heads=heads,
            # encoder+decoder stacks double the depth: seq2seq deliberately
            # defaults shallower; graftcheck: disable=config-drift
            num_layers=int(getattr(args, "model_layers", 3) or 3),
            src_len=int(getattr(args, "src_seq_len", 64) or 64),
            tgt_len=int(getattr(args, "tgt_seq_len", 32) or 32),
            dtype=dtype,
        )
    raise ValueError(f"unknown model '{model_name}'")


def sample_input_for(args, fed_or_shape: Any) -> jax.Array:
    """A (1, ...) sample batch for module init, derived from the dataset."""
    if hasattr(fed_or_shape, "train_data_global"):
        x = fed_or_shape.train_data_global.x[:1]
        return jnp.asarray(x)
    return jnp.zeros((1,) + tuple(fed_or_shape), jnp.float32)


def init_params(model, rng: jax.Array, sample_input: jax.Array):
    """Initialize a param pytree. Returns the full variables dict; for
    stateless models this is ``{'params': ...}``."""
    variables = model.init(rng, sample_input, train=False)
    return variables
