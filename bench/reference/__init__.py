"""Plain references that ``correct`` holds the program to.

``store.py`` is the reference of the router and the reuse stores.  A model
reference is a module of this package that a configuration file names under
its ``reference`` key; the harness reaches it only through :func:`load`, so
a new architecture enters the benchmark as a new module here and a new
configuration file, with no edit to the harness or its tests.  Like
``decoder.py``, a model reference imports nothing of the program under
test.  It exports:

``shapes(model)``
    the weight layout: the leaf shapes of the program's ``init`` for a
    configuration's ``model`` block;
``make_weights(model, key)``
    weights from the seed, made on the device in one jitted call;
``last_logits(model, precision)``
    jitted ``(weights, tokens (B, S), images (B, F, d) or None) -> (B, V)``
    float32 logits of the last position, at
    ``jax.default_matmul_precision("highest")``; ``precision="fp8"`` is the
    control;
``prefill_flops(model, seq)``
    the FLOPs one prompt's prefill needs, as ``bench/flops.py`` defines
    them: what the work requires, not what a program happens to do;
``program_fields(model)``
    ``(set, expect)``, two plain dicts keyed by field names of the program's
    ``ArchConfig``: the service applies ``set`` with ``dataclasses.replace``
    to ``get_arch(cfg["arch"])`` and then requires every key of ``expect``;
``tiny(model)``
    ``(model, fields)``: the ``model`` block at the size the CPU tests run,
    every width this reference reads shrunk and the architecture's kinds of
    layer kept in their ratio (at least one whole period of its pattern),
    and the ``ArchConfig`` fields that ``dataclasses.replace`` sets on
    ``get_arch(cfg["arch"])`` to make the program that model, in float32;
    ``bench/service.py`` ``tiny_config`` applies them and holds the result
    to ``program_fields`` of the tiny block.  So a new configuration file is
    built, checked for layout and served at that size by the benchmark's
    tests with no edit to them.
"""
from __future__ import annotations

import importlib
import pkgutil
from types import ModuleType

CONTRACT = ("shapes", "make_weights", "last_logits", "prefill_flops",
            "program_fields", "tiny")


def load(name: str) -> ModuleType:
    """The model reference ``bench.reference.<name>``."""
    full = f"{__name__}.{name}"
    mod = None
    if name.isidentifier():
        try:
            mod = importlib.import_module(full)
        except ModuleNotFoundError as e:
            if e.name != full:       # the module exists but fails to import
                raise
    if mod is None:
        known = sorted(m.name for m in pkgutil.iter_modules(__path__))
        raise ValueError(f"no model reference {name!r}; known: {known}")
    missing = [f for f in CONTRACT if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"{full} is no model reference: it lacks {missing}")
    return mod
