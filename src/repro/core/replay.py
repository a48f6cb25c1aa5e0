"""Replay buffer for the simulated online protocol (Algorithm 1).

Partial feedback only: each record is the chosen action's outcome. Stored
as growable numpy arrays (host side — this is the control plane, not the
accelerator data path)."""
from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


class ReplayBuffer:
    def __init__(self, emb_dim: int, feat_dim: int):
        self.emb_dim = emb_dim
        self.feat_dim = feat_dim
        self._chunks: list[Dict[str, np.ndarray]] = []
        self._cached: Dict[str, np.ndarray] | None = None

    def add_batch(self, x_emb, x_feat, domain, action, reward, gate_label,
                  gate_mask=None) -> None:
        n = len(action)
        chunk = {
            "x_emb": np.asarray(x_emb, np.float32).reshape(n, self.emb_dim),
            "x_feat": np.asarray(x_feat, np.float32).reshape(n, self.feat_dim),
            "domain": np.asarray(domain, np.int32).reshape(n),
            "action": np.asarray(action, np.int32).reshape(n),
            "reward": np.asarray(reward, np.float32).reshape(n),
            "gate_label": np.asarray(gate_label, np.float32).reshape(n),
            "gate_mask": (np.ones(n, np.float32) if gate_mask is None
                          else np.asarray(gate_mask, np.float32).reshape(n)),
        }
        self._chunks.append(chunk)
        self._cached = None

    def __len__(self) -> int:
        return sum(len(c["action"]) for c in self._chunks)

    def data(self) -> Dict[str, np.ndarray]:
        if self._cached is None:
            if not self._chunks:
                raise ValueError("empty buffer")
            self._cached = {
                k: np.concatenate([c[k] for c in self._chunks])
                for k in self._chunks[0]
            }
        return self._cached

    def minibatches(self, rng: np.random.Generator, batch_size: int, *,
                    drop_tail: bool = False
                    ) -> Iterator[Dict[str, np.ndarray]]:
        """Shuffled minibatches covering EVERY stored sample exactly once
        per epoch: full batches plus the short shuffle tail (``n %
        batch_size`` samples; the whole buffer when ``n < batch_size``).
        Dropping the tail silently skipped SGD on early protocol slices
        and small serving pools, and under-trained on up to
        ``batch_size - 1`` samples per epoch forever after. Each distinct
        tail size costs one extra trace of the jitted train step on this
        host reference path — pass ``drop_tail=True`` to keep only full
        batches (fixed shapes) when that matters; a buffer smaller than
        one batch always yields its single short batch."""
        data = self.data()
        n = len(self)
        order = rng.permutation(n)
        for i in range(0, n, batch_size):
            idx = order[i:i + batch_size]
            if drop_tail and i > 0 and len(idx) < batch_size:
                return
            yield {k: v[idx] for k, v in data.items()}
