"""Generate tiny committed fixtures in the reference's on-disk formats.

Run once; outputs live in tests/fixtures/ and are committed so the loader
tests always exercise the real-format parse paths (round-1 review #4). Contents
are synthetic; only the FORMATS are real:

- LEAF JSON (reference data/MNIST/data_loader.py:32 read_data)
- TFF h5 fed_shakespeare (data/fed_shakespeare/data_loader.py)
- TFF h5 FederatedEMNIST (data/FederatedEMNIST/data_loader.py)
- TFF h5 stackoverflow_nwp + word_count file (data/stackoverflow_nwp/)
"""

from __future__ import annotations

import json
import os

import h5py
import numpy as np

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "tests", "fixtures")


def make_leaf_mnist() -> None:
    rng = np.random.default_rng(0)
    base = os.path.join(OUT, "leaf_mnist")
    for split, n_lo, n_hi in (("train", 6, 12), ("test", 2, 4)):
        users, num_samples, user_data = [], [], {}
        for u in range(3):
            uid = f"f_{u:05d}"
            n = int(rng.integers(n_lo, n_hi))
            users.append(uid)
            num_samples.append(n)
            user_data[uid] = {
                "x": rng.random((n, 784)).round(4).tolist(),
                "y": rng.integers(0, 10, n).tolist(),
            }
        d = os.path.join(base, split)
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "all_data_0.json"), "w") as f:
            json.dump(
                {"users": users, "num_samples": num_samples, "user_data": user_data}, f
            )


SNIPPETS = {
    "THE_FOOL": [
        "Have more than thou showest, speak less than thou knowest.",
        "Lend less than thou owest.",
    ],
    "KENT": ["This is nothing, fool."],
}


def make_fed_shakespeare() -> None:
    for split in ("train", "test"):
        path = os.path.join(OUT, f"shakespeare_{split}.h5")
        with h5py.File(path, "w") as h5:
            g = h5.create_group("examples.md")
            for client, snippets in SNIPPETS.items():
                cg = g.create_group(client)
                sel = snippets if split == "train" else snippets[:1]
                cg.create_dataset(
                    "snippets", data=np.array([s.encode() for s in sel])
                )


def make_femnist() -> None:
    rng = np.random.default_rng(1)
    for split, n in (("train", 8), ("test", 3)):
        path = os.path.join(OUT, f"fed_emnist_{split}.h5")
        with h5py.File(path, "w") as h5:
            g = h5.create_group("examples.md")
            for u in range(2):
                cg = g.create_group(f"f{u:04d}_00")
                cg.create_dataset(
                    "pixels", data=rng.random((n, 28, 28)).astype(np.float32)
                )
                cg.create_dataset("label", data=rng.integers(0, 62, n))


SO_SENTENCES = {
    "user_a": ["how do i sort a list in python", "what is a pointer"],
    "user_b": ["why does my code segfault"],
}
SO_WORDS = ("a i in is what how do my why list sort python pointer code does "
            "segfault the to of and").split()


def make_stackoverflow() -> None:
    with open(os.path.join(OUT, "stackoverflow.word_count"), "w") as f:
        for i, w in enumerate(SO_WORDS):
            f.write(f"{w} {1000 - i}\n")
    for split in ("train", "test"):
        path = os.path.join(OUT, f"stackoverflow_{split}.h5")
        with h5py.File(path, "w") as h5:
            g = h5.create_group("examples.md")
            for client, sents in SO_SENTENCES.items():
                cg = g.create_group(client)
                sel = sents if split == "train" else sents[:1]
                cg.create_dataset("tokens", data=np.array([s.encode() for s in sel]))


if __name__ == "__main__":
    os.makedirs(OUT, exist_ok=True)
    make_leaf_mnist()
    make_fed_shakespeare()
    make_femnist()
    make_stackoverflow()
    print("fixtures written to", OUT)
