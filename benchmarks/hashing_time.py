"""Paper Table III: LSH hashing time per task vs number of tables.

Three arms per table count:

* scalar   — ``hash_batch`` on a single task (the paper's measurement),
* batched  — ``hash_batch`` amortised over a 256-task batch,
* fused    — the one-dispatch ``ops.lsh_buckets`` kernel (rotation matmul +
  cross-polytope vertex ids + bucket mixing folded into the kernel
  epilogue; ISSUE 7 satellite), same 256-task batch.
"""
from __future__ import annotations

import numpy as np

from repro.core.lsh import LSHParams, get_lsh
from repro.kernels import ops
from .common import Row, timeit


def run(dim: int = 64) -> list:
    rows: list = []
    rng = np.random.default_rng(0)
    x1 = rng.standard_normal((1, dim)).astype(np.float32)
    xb = rng.standard_normal((256, dim)).astype(np.float32)
    for t in (1, 5, 10):
        lsh = get_lsh(LSHParams(dim=dim, num_tables=t, num_probes=8, seed=2))
        nb = lsh.params.num_buckets
        us = timeit(lambda: np.asarray(lsh.hash_batch(x1)))
        us_b = timeit(lambda: np.asarray(lsh.hash_batch(xb)))
        us_k = timeit(lambda: np.asarray(ops.lsh_buckets(xb, lsh.rotations, nb)))
        rows.append((f"hash_time/tables={t}", us,
                     f"ms_per_task={us / 1e3:.3f};paper_ms={ {1: 0.4, 5: 1.7, 10: 3.3}[t] };"
                     f"batched_us_per_task={us_b / 256:.1f};"
                     f"fused_kernel_us_per_task={us_k / 256:.1f}"))
    return rows
