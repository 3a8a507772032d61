"""The NCF flagship's plain reference: pure GMF with an item bias,
``item_emb @ user_emb[u] + out_b + item_bias`` (no MLP tower)."""

from __future__ import annotations

import numpy as np

from benchmark.reference import bf16_round


class Served:
    def __init__(self, model: dict, lower_precision: bool = False):
        p = model["params"]
        if "out_w" in p:
            raise ValueError("this reference covers the pure-GMF model only")
        n_items = len(model["item_vocab"])
        self.user_emb = np.asarray(p["user_emb"], np.float32)
        self.item_emb = np.asarray(p["item_emb"], np.float32)[:n_items]
        self.offset = np.float32(np.asarray(p["out_b"])[0]) + np.asarray(
            p["item_bias"], np.float32
        )[:n_items]
        self.user_index = {k: i for i, k in enumerate(model["user_vocab"])}
        self.items = list(model["item_vocab"])
        self.item_index = {k: i for i, k in enumerate(self.items)}
        self.finite = bool(
            np.isfinite(self.user_emb).all() and np.isfinite(self.item_emb).all()
        )
        if lower_precision:
            self.user_emb = bf16_round(self.user_emb)
            self.item_emb = bf16_round(self.item_emb)

    def scores(self, user: str) -> np.ndarray:
        return self.item_emb @ self.user_emb[self.user_index[user]] + self.offset


served = Served
