"""Open-loop traffic for one cell, made from the traffic file and ``--seed``.

The task streams are the paper's Table II datasets (Reservoir, Sec. V):
embeddings on the unit sphere grouped into classes, sub-centres and
captures, emitted i.i.d. (``low`` correlation) or as video-like random walks
(``high``).  The generator below is a copy of the program's
``repro.data.synthetic.make_stream`` without the labels, kept here so that the
workload cannot change with the program.

Arrivals are a Poisson process conditioned on its count: exactly
``rate * seconds`` tasks, at sorted uniform times in the window.  Every seed
then offers the same amount of work, in another order and at other instants.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    name: str
    dim: int = 64
    n_classes: int = 10
    subs_per_class: int = 8
    correlation: str = "low"      # 'low' | 'moderate' | 'high'
    granularity: str = "medium"   # 'coarse' | 'medium' | 'fine'
    sub_spread: float = 0.55
    item_noise: float = 0.30
    walk_noise: float = 0.06
    run_length: int = 30
    seed: int = 1234

    def sub_centres(self) -> np.ndarray:
        rng = np.random.default_rng(self.seed)
        cls = _normalize(rng.standard_normal((self.n_classes, self.dim)))
        subs = cls[:, None, :] + self.sub_spread * _unit_noise(
            rng, (self.n_classes, self.subs_per_class, self.dim))
        return _normalize(subs.reshape(-1, self.dim))


DATASETS: Dict[str, DatasetSpec] = {
    "mnist": DatasetSpec("mnist", correlation="low", granularity="medium",
                         n_classes=10, subs_per_class=12, item_noise=0.42),
    "pandaset": DatasetSpec("pandaset", correlation="low", granularity="fine",
                            n_classes=12, subs_per_class=10,
                            sub_spread=0.45, item_noise=0.40),
    "stanford_ar": DatasetSpec("stanford_ar", correlation="moderate",
                               granularity="medium", n_classes=8,
                               subs_per_class=6, item_noise=0.22),
    "cctv1": DatasetSpec("cctv1", correlation="high", granularity="coarse",
                         n_classes=6, subs_per_class=6, item_noise=0.30),
    "cctv2": DatasetSpec("cctv2", correlation="high", granularity="fine",
                         n_classes=6, subs_per_class=6,
                         sub_spread=0.45, item_noise=0.30),
}


def _normalize(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float32)
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def _unit_noise(rng: np.random.Generator, shape) -> np.ndarray:
    return rng.standard_normal(shape) / np.sqrt(shape[-1])


def make_stream(spec: DatasetSpec, n: int, seed: int) -> np.ndarray:
    """(n, dim) unit task embeddings in stream order."""
    rng = np.random.default_rng(seed ^ spec.seed)
    subs = spec.sub_centres()
    xs = np.empty((n, spec.dim), np.float32)
    i = 0
    while i < n:
        sub = subs[rng.integers(len(subs))]
        if spec.correlation == "low":
            xs[i] = sub + spec.item_noise * _unit_noise(rng, (spec.dim,))
            i += 1
        elif spec.correlation == "moderate":
            burst = int(rng.geometric(1.0 / max(2, spec.run_length // 5)))
            for _ in range(min(burst, n - i)):
                xs[i] = sub + spec.item_noise * _unit_noise(rng, (spec.dim,))
                i += 1
        else:
            run = int(rng.geometric(1.0 / spec.run_length))
            cur = sub + spec.item_noise * _unit_noise(rng, (spec.dim,))
            for _ in range(min(run, n - i)):
                xs[i] = cur
                cur = cur + spec.walk_noise * _unit_noise(rng, (spec.dim,))
                i += 1
    return _normalize(xs)


def derive_seed(seed: int, *labels) -> int:
    """A 63-bit seed for one named part of a run (weights, history, ...)."""
    words = [int(seed) % (1 << 64)] + [int.from_bytes(str(x).encode(), "little")
                                       % (1 << 64) for x in labels]
    state = np.random.SeedSequence(words).generate_state(2, np.uint32)
    return (int(state[0]) << 31) ^ int(state[1])


def load_traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def prompt_tokens(emb: np.ndarray, n: int, vocab: int) -> np.ndarray:
    """A task's prompt, derived from its embedding as ``launch/serve.py``
    does: the first ``n`` coordinates' magnitudes, scaled, modulo the
    vocabulary."""
    return ((np.abs(emb[:n]) * 1e4).astype(np.int64) % vocab).astype(np.int32)


def image_index(emb: np.ndarray, pool: int) -> int:
    """The image a task annotates, picked from the pool by its embedding."""
    return int(np.abs(emb[-1]) * 1e4) % pool if pool else -1


@dataclasses.dataclass
class Segment:
    """Tasks of one part of a run: embeddings, prompts and due times."""

    emb: np.ndarray          # (n, dim) unit rows
    tokens: np.ndarray       # (n, prompt) int32
    image: np.ndarray        # (n,) int32, -1 without images
    due: np.ndarray          # (n,) seconds from the segment's start


def stream(traffic: dict, n: int, seed: int, part: str) -> np.ndarray:
    """The ``part`` stream of a run, from the run's seed."""
    return make_stream(DATASETS[traffic["stream"]], n,
                       derive_seed(seed, part, "stream"))


def segment(traffic: dict, n: int, seconds: float, seed: int, part: str,
            prompt: int, vocab: int, pool: int) -> Segment:
    emb = stream(traffic, n, seed, part)
    rng = np.random.default_rng(derive_seed(seed, part, "arrivals"))
    due = np.sort(rng.uniform(0.0, seconds, n))
    tokens = np.stack([prompt_tokens(e, prompt, vocab) for e in emb]) \
        if n else np.zeros((0, prompt), np.int32)
    image = np.asarray([image_index(e, pool) for e in emb], np.int32)
    return Segment(emb, tokens, image, due)


def history(traffic: dict, seed: int) -> np.ndarray:
    """Earlier tasks of the same stream that fill the stores at set-up."""
    n = int(traffic.get("history", 0))
    return stream(traffic, n, seed, "history") if n else \
        np.zeros((0, DATASETS[traffic["stream"]].dim), np.float32)


def window_counts(traffic: dict, seconds: float) -> Tuple[int, int, float]:
    """(window tasks, warm-up tasks, warm-up seconds) at the fixed rate."""
    rate = float(traffic["rate_per_s"])
    n = int(round(rate * seconds))
    n_warm = int(traffic.get("warmup_tasks", 0))
    return n, n_warm, n_warm / rate
