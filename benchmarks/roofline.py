"""§Roofline: per (arch x shape) three-term roofline from dry-run artifacts.

Reads artifacts/dryrun/*.json (written by repro.launch.dryrun) and prints
the full baseline table: compute / memory / collective terms in seconds,
dominant bottleneck, MODEL_FLOPS/HLO_FLOPS usefulness ratio.
Hardware: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.

Also emits analytic rows for the one-dispatch fused reuse query
(ISSUE 7): per (store, batch) operating point, the hash-matmul compute
term vs the candidate-gather + top-1 memory term on v5e.
"""
from __future__ import annotations

import glob
import json
import os

ART_DIR = os.environ.get("DRYRUN_DIR", "artifacts/dryrun")

# TPU v5e single-chip constants (f32 MXU rate = half the bf16 peak)
V5E_F32_FLOPS = 98.5e12
V5E_HBM_BPS = 819e9


def _fused_query_rows() -> list:
    """Analytic fused-query roofline at the benchmark operating points.

    Work model per batch of B queries (T tables, P probes, bucket cap c,
    dim D): hash matmul 2*B*T*K*D^2 flops, slot-table gather B*T*P*c*4
    bytes, candidate gather + masked top-1 B*W*D*(4 bytes + 2 flops) with
    W = T*P*c.  On v5e the candidate gather dominates everything else by
    an order of magnitude -> the fused kernel is HBM-bound and the win
    over the staged path is the removed host round-trip, not flops.
    """
    rows = []
    T, P, K, D = 5, 8, 1, 64
    for n_store, cap in ((100_000, 25), (250_000, 62)):
        for batch in (1024, 10_000):
            w = T * P * cap
            hash_s = 2.0 * batch * T * K * D * D / V5E_F32_FLOPS
            table_s = batch * w * 4 / V5E_HBM_BPS
            gather_s = batch * w * D * 4 / V5E_HBM_BPS
            top1_s = 2.0 * batch * w * D / V5E_F32_FLOPS
            dom_s = max(hash_s, table_s + gather_s, top1_s)
            dominant = ("memory" if dom_s == table_s + gather_s else
                        "compute" if dom_s == top1_s else "hash")
            rows.append((
                f"roofline/fused_query/store{n_store}/batch{batch}",
                dom_s * 1e6,
                f"hash_s={hash_s:.2e};gather_s={table_s + gather_s:.2e};"
                f"top1_s={top1_s:.2e};dominant={dominant};"
                f"cand_width={w}"))
    return rows


def load_cells(mesh: str = "16x16") -> list:
    cells = []
    for path in sorted(glob.glob(os.path.join(ART_DIR, f"*__{mesh}.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def run() -> list:
    rows = _fused_query_rows()
    cells = load_cells("16x16")
    if not cells:
        rows.append(("roofline/missing", 0.0,
                     f"no dry-run artifacts under {ART_DIR}; run "
                     "`python -m repro.launch.dryrun --all --both-meshes` first"))
        return rows
    for c in cells:
        r = c.get("roofline", {})
        name = f"roofline/{c['arch']}/{c['shape']}"
        dom_s = max(r.get("compute_s", 0), r.get("memory_s", 0),
                    r.get("collective_s", 0))
        frac = r.get("compute_s", 0.0) / max(dom_s, 1e-12)
        rows.append((name, dom_s * 1e6,
                     f"compute_s={r.get('compute_s', 0):.4f};"
                     f"memory_s={r.get('memory_s', 0):.4f};"
                     f"collective_s={r.get('collective_s', 0):.4f};"
                     f"dominant={r.get('dominant')};"
                     f"roofline_frac={frac:.3f};"
                     f"useful_flops_frac={r.get('useful_flops_frac', 0):.3f}"))
    n_multi = len(load_cells("2x16x16"))
    rows.append(("roofline/multi_pod_proof", 0.0,
                 f"cells_compiled_2x16x16={n_multi}/40"))
    return rows
