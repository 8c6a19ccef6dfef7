"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed here and compiles for a described, unattached
``v5e:2x2`` topology: each test lowers one program of the served path at its
real size onto chip 0 and checks what interpret mode cannot — that Mosaic
accepts the kernel (block tiling, memory spaces, scoped VMEM) and that the
program fits the chip's memory.  Nothing runs, so nothing here is a timing.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test module.  Keep these tests in this one file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.lsh import LSHParams
from repro.core.reuse_store import DEFAULT_PAGE_SIZE, _auto_bucket_cap
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_query import fused_query
from repro.kernels.lsh_hash import lsh_hash_mix
from repro.kernels.sim_topk import reuse_top1
from repro.launch.serve import SERVE_LSH
from repro.models import build_model

HBM_BYTES = 16 * 2**30                    # one v5e chip
STORE_ROWS = 100_000                      # ReuseStore's default capacity
BATCH = 1024
# the 100k-entry store of chip_smoke.py's store phase
SMOKE_LSH = LSHParams(dim=64, num_tables=5, num_probes=8, num_buckets=16384,
                      family="hyperplane", seed=11)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", saved)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _store_shapes(p: LSHParams, sharding):
    """(slots, pages) device mirrors of a ReuseStore holding STORE_ROWS."""
    cap = _auto_bucket_cap(p, STORE_ROWS)
    pages = 1
    while pages * DEFAULT_PAGE_SIZE < STORE_ROWS:
        pages *= 2
    return (_sds((p.num_tables * p.num_buckets, cap), jnp.int32, sharding),
            _sds((pages, DEFAULT_PAGE_SIZE, p.dim), jnp.float32, sharding))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("family,num_buckets", [
    ("hyperplane", 16384),        # chip_smoke.py's store
    ("cross_polytope", 16384),
    ("cross_polytope", 256),      # launch/serve.py's LSH: bucket_cap 512
])
def test_fused_query_100k_store(one_chip, family, num_buckets):
    p = LSHParams(dim=64, num_tables=5, num_probes=8,
                  num_buckets=num_buckets, family=family)
    proj = ((p.num_tables, 1, p.dim, p.dim) if family == "cross_polytope"
            else (p.num_tables, p.bits, p.dim))
    slots, pages = _store_shapes(p, one_chip)
    compiled = fused_query.lower(
        _sds((BATCH, p.dim), jnp.float32, one_chip),
        _sds(proj, jnp.float32, one_chip), slots, pages,
        family=family, num_probes=p.num_probes, interpret=False,
        with_counts=True).compile()
    _assert_kernel(compiled)
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < HBM_BYTES


def test_gather_top1_staged_width(one_chip):
    """The staged search's top-1 (``ops.gathered_top1`` -> ``reuse_top1``)
    over the paged store at the widest deduplicated candidate row."""
    _, pages = _store_shapes(SMOKE_LSH, one_chip)
    width = (SMOKE_LSH.num_tables * SMOKE_LSH.num_probes
             * _auto_bucket_cap(SMOKE_LSH, STORE_ROWS))
    compiled = reuse_top1.lower(
        _sds((BATCH, SMOKE_LSH.dim), jnp.float32, one_chip), pages,
        _sds((BATCH, width), jnp.int32, one_chip), interpret=False).compile()
    _assert_kernel(compiled)


def test_lsh_hash_mix(one_chip):
    p = SERVE_LSH
    compiled = lsh_hash_mix.lower(
        _sds((BATCH, p.dim), jnp.float32, one_chip),
        # two rotations per table exercise the resident bucket accumulator
        _sds((p.num_tables, 2, p.dim, p.dim), jnp.float32, one_chip),
        num_buckets=p.num_buckets, interpret=False).compile()
    _assert_kernel(compiled)


def test_flash_attention_qwen3_widths(one_chip):
    cfg = get_arch("qwen3-1.7b")
    hd = cfg.resolved_head_dim
    q = _sds((1, 1024, cfg.n_heads, hd), jnp.bfloat16, one_chip)
    kv = _sds((1, 1024, cfg.n_kv_heads, hd), jnp.bfloat16, one_chip)
    compiled = flash_attention.lower(q, kv, kv, interpret=False).compile()
    _assert_kernel(compiled)


def test_qwen3_prefill_fits_one_chip(one_chip):
    """Full-width qwen3-1.7b prefill (B=8, S=64) as launch/serve.py runs it:
    f32 weights cast to bf16 inside the step.  Attention is jnp, so this
    program holds no Pallas kernel; what it checks is the memory fit."""
    cfg = get_arch("qwen3-1.7b")
    model = build_model(cfg)
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    tokens = _sds((8, 64), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda p, t: model.prefill(p, {"tokens": t}, 72)[0]
    ).lower(params, tokens).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert m.argument_size_in_bytes > 6e9      # 1.72B f32 parameters
    assert total < HBM_BYTES, total
