"""The served model behind the replicas, built through the program's API.

The configuration file's ``reference`` key names the model reference
(``bench/reference/<reference>.py``, loaded by ``reference.load``; its
contract is in ``bench/reference/__init__.py``).  ``repro.configs.get_arch``
gives the architecture, set to the configuration file's numbers by the
reference's ``program_fields`` with ``dataclasses.replace`` and checked
against the fields it expects; ``repro.models.build_model`` builds it, and
its jitted ``prefill`` serves a replica's miss group as one call.  The
weights are the benchmark's own (the reference's ``make_weights``), made on
the device from the seed and checked against the layout of the program's
``init``.

Rows of a group are padded to the next of the configuration's batch sizes;
the function returns the argmax token of each real row.  How the program
forms groups (batcher, ``max_batch``, flush window) is the program's; that
the group is one model call is fixed here, so a change to how the model is
called for a group needs a service executor inside the program first.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


def arch_config(cfg: dict):
    """The program's ArchConfig for a configuration file, checked against
    the file's numbers."""
    from repro.configs import get_arch

    ref = reference.load(cfg["reference"])
    set_, _ = ref.program_fields(cfg["model"])
    return _held(dataclasses.replace(get_arch(cfg["arch"]), **set_), ref,
                 cfg["model"], cfg["arch"])


def tiny_config(cfg: dict):
    """-> (cfg, arch): a configuration file at the size the CPU tests run,
    as its reference's ``tiny`` gives it, and the program's ArchConfig for
    it, checked against the tiny block's numbers as ``arch_config`` checks
    the full size."""
    from repro.configs import get_arch

    ref = reference.load(cfg["reference"])
    model, fields = ref.tiny(cfg["model"])
    arch = dataclasses.replace(get_arch(cfg["arch"]), **fields)
    return ({**copy.deepcopy(cfg), "model": model},
            _held(arch, ref, model, cfg["arch"]))


def _held(arch, ref, model: dict, name: str):
    """``arch`` if it holds every field that ``ref.program_fields(model)``
    sets or expects, else a ``ValueError`` naming those it differs in."""
    set_, expect = ref.program_fields(model)
    bad = {k: (getattr(arch, k), v) for k, v in {**set_, **expect}.items()
           if getattr(arch, k) != v}
    if bad:
        raise ValueError(f"program's {name} differs from the model "
                         f"block it is built for: {bad}")
    return arch


class Service:
    """Weights, the program's jitted prefill, and the replicas' execute_fn."""

    def __init__(self, cfg: dict, key, model_override=None):
        from repro.models import build_model

        self.cfg = cfg
        self.model_cfg = cfg["model"]
        self.arch = model_override or arch_config(cfg)
        self.model = build_model(self.arch)
        ref = reference.load(cfg["reference"])
        self.weights = ref.make_weights(self.model_cfg, key)
        want = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                           self.weights)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("the benchmark's weight layout differs from the "
                             "program's init")
        svc = cfg["service"]
        self.prompt = svc["prompt_tokens"]
        self.pool_size = svc["image_pool"]
        self.front = self.model_cfg["frontend_tokens"]
        self.batch_sizes = sorted(cfg["engine"]["batch_sizes"])
        self.images = None
        if self.pool_size:
            self.images = _image_pool(
                jax.random.fold_in(key, 1), self.pool_size, self.front,
                self.model_cfg["hidden_size"], jnp.dtype(self.arch.dtype))
        model, max_len = self.model, self.prompt + self.front + 8

        @jax.jit
        def serve(params, tokens, image_idx, images):
            batch = {"tokens": tokens}
            if images is not None:
                batch["patch_embeds"] = images[image_idx]
            logits, _ = model.prefill(params, batch, max_len)
            return jnp.argmax(logits[:, -1], axis=-1)

        self._serve = serve
        self.calls: List[tuple] = []   # (real rows, padded rows) per call

    def padded(self, n: int) -> int:
        for b in self.batch_sizes:
            if n <= b:
                return b
        raise ValueError(f"group of {n} exceeds the largest batch "
                         f"{self.batch_sizes[-1]}")

    def run(self, tokens: np.ndarray, image: np.ndarray) -> np.ndarray:
        """(n, prompt) tokens and (n,) image ids -> (n,) served tokens."""
        n = tokens.shape[0]
        b = self.padded(n)
        tok = np.zeros((b, self.prompt), np.int32)
        tok[:n] = tokens
        img = np.zeros((b,), np.int32)
        img[:n] = np.maximum(image, 0)
        out = self._serve(self.weights, tok, img, self.images)
        self.calls.append((n, b))
        return np.asarray(out)[:n]

    def execute(self, reqs) -> List[int]:
        """A replica's ``execute_fn``: one prefill call for the group."""
        tokens = np.stack([r.payload["tokens"] for r in reqs])
        image = np.asarray([r.payload["image"] for r in reqs], np.int32)
        return [int(t) for t in self.run(tokens, image)]

    def warm(self) -> None:
        for b in self.batch_sizes:
            self.run(np.zeros((b, self.prompt), np.int32),
                     np.zeros((b,), np.int32))
        self.calls.clear()


def _image_pool(key, n: int, front: int, d: int, dtype) -> jax.Array:
    """(n, front, d) patch embeddings in the type the model takes them."""
    return jax.jit(lambda k: jax.random.normal(k, (n, front, d), dtype),
                   static_argnums=())(key)
