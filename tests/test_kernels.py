"""Per-kernel validation: sweep shapes/dtypes, assert_allclose vs ref.py.

Kernels run in interpret mode on CPU (tests/test_tpu_compile.py compiles
the served ones for the v5e); the oracles are pure jnp.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, sim_topk
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_query import fused_query
from repro.kernels.lsh_hash import lsh_hash, lsh_hash_mix
from repro.kernels.sim_topk import reuse_top1, sim_top1

RNG = np.random.default_rng(42)


def randn(*shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


# ---------------------------------------------------------------- lsh_hash
class TestLshHash:
    @pytest.mark.parametrize("B,D,T,K", [(8, 64, 1, 1), (33, 128, 5, 2),
                                         (128, 256, 3, 1), (7, 32, 2, 3)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, B, D, T, K, dtype):
        x = randn(B, D, dtype=dtype)
        rot = randn(T, K, D, D)
        got = np.asarray(ops.lsh_hash_ids(x, rot))
        want = np.asarray(ref.lsh_hash_ref(x, rot))
        # bf16 rounding may flip near-tie argmaxes on a few rows
        agree = (got == want).mean()
        assert agree >= (1.0 if dtype == jnp.float32 else 0.98), agree

    def test_bucket_mixing_matches_core(self):
        from repro.core.lsh import LSHParams, get_lsh

        p = LSHParams(dim=64, num_tables=4, rotations_per_table=2,
                      num_buckets=256, seed=3)
        lsh = get_lsh(p)
        x = randn(16, 64)
        got = np.asarray(ops.lsh_buckets(x, lsh.rotations, p.num_buckets))
        want = np.asarray(lsh.hash_batch(x))
        assert (got == want).all()

    def test_block_size_invariance(self):
        x, rot = randn(50, 64), randn(2, 1, 64, 64)
        a = np.asarray(lsh_hash(x, rot, block_b=8, interpret=True))
        b = np.asarray(lsh_hash(x, rot, block_b=64, interpret=True))
        assert (a == b).all()

    @pytest.mark.parametrize("T,K,NB", [(1, 1, 64), (4, 2, 256), (3, 3, 100)])
    def test_mix_epilogue_matches_host_mixing(self, T, K, NB):
        """lsh_hash_mix (in-kernel mixing) == lsh_hash + host modular steps."""
        x, rot = randn(20, 32), randn(T, K, 32, 32)
        vids = np.asarray(lsh_hash(x, rot, interpret=True))
        radix = 2 * 32
        want = np.zeros(vids.shape[:-1], np.int32)
        for kk in range(K):
            want = (want * radix + vids[..., kk]) % NB
        got = np.asarray(lsh_hash_mix(x, rot, num_buckets=NB, interpret=True))
        assert (got == want).all()

    def test_mix_epilogue_block_invariance(self):
        x, rot = randn(50, 32), randn(2, 2, 32, 32)
        a = np.asarray(lsh_hash_mix(x, rot, num_buckets=128, block_b=8,
                                    interpret=True))
        b = np.asarray(lsh_hash_mix(x, rot, num_buckets=128, block_b=64,
                                    interpret=True))
        assert (a == b).all()


# ---------------------------------------------------------------- sim_top1
class TestSimTop1:
    @pytest.mark.parametrize("Q,N,D", [(8, 64, 32), (128, 1000, 64),
                                       (5, 4096, 128), (64, 200, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, Q, N, D, dtype):
        q = randn(Q, D, dtype=dtype)
        s = randn(N, D, dtype=dtype)
        qn = q / jnp.linalg.norm(q.astype(jnp.float32), axis=-1, keepdims=True).astype(dtype)
        sn = s / jnp.linalg.norm(s.astype(jnp.float32), axis=-1, keepdims=True).astype(dtype)
        val, idx = ops.nearest_neighbor(qn, sn)
        wv, wi = ref.sim_top1_ref(qn, sn)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(val), np.asarray(wv), atol=tol)
        if dtype == jnp.float32:
            assert (np.asarray(idx) == np.asarray(wi)).all()

    def test_n_valid_masking(self):
        # kernel assumes unit-normalised rows (the reuse store normalises on
        # insert); ref normalises internally, so normalise here for parity
        q = randn(16, 64)
        s = randn(512, 64)
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
        s = s / jnp.linalg.norm(s, axis=-1, keepdims=True)
        val, idx = ops.nearest_neighbor(q, s, n_valid=jnp.int32(100))
        assert (np.asarray(idx) < 100).all()
        wv, wi = ref.sim_top1_ref(q, s, valid_n=100)
        assert (np.asarray(idx) == np.asarray(wi)).all()
        np.testing.assert_allclose(np.asarray(val), np.asarray(wv), atol=1e-5)

    def test_block_invariance(self):
        q, s = randn(32, 64), randn(700, 64)
        v1, i1 = sim_top1(q, s, block_q=8, block_n=128, interpret=True)
        v2, i2 = sim_top1(q, s, block_q=32, block_n=512, interpret=True)
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-6)
        assert (np.asarray(i1) == np.asarray(i2)).all()


# ------------------------------------------- staged top-1 (gathered_top1)
class TestGatherTop1:
    def _unit(self, *shape):
        x = randn(*shape)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    @pytest.mark.parametrize("Q,N,C,D", [(8, 64, 16, 32), (33, 1000, 200, 64),
                                         (128, 4096, 700, 128), (5, 50, 7, 256)])
    def test_matches_ref(self, Q, N, C, D):
        q = self._unit(Q, D)
        s = self._unit(N, D)
        ids = jnp.asarray(RNG.integers(-1, N, (Q, C)), jnp.int32)
        val, idx = ops.gathered_top1(q, s, ids)
        wv, wi = ref.gather_top1_ref(q, s, ids)
        fin = np.isfinite(np.asarray(wv))
        np.testing.assert_allclose(np.asarray(val)[fin], np.asarray(wv)[fin],
                                   atol=1e-5)
        assert (np.asarray(idx) == np.asarray(wi)).all()

    def test_no_candidates_row(self):
        q, s = self._unit(4, 32), self._unit(64, 32)
        ids = jnp.full((4, 10), -1, jnp.int32)
        val, idx = ops.gathered_top1(q, s, ids)
        assert (np.asarray(idx) == -1).all()
        assert np.isneginf(np.asarray(val)).all()

    def test_empty_store(self):
        q = self._unit(3, 32)
        val, idx = ops.gathered_top1(q, jnp.zeros((0, 32), jnp.float32),
                                     jnp.zeros((3, 4), jnp.int32))
        assert (np.asarray(idx) == -1).all()

    def test_block_invariance(self):
        q, s = self._unit(40, 64), self._unit(500, 64)
        ids = jnp.asarray(RNG.integers(-1, 500, (40, 130)), jnp.int32)
        v1, i1 = reuse_top1(q, s, ids, block_q=8, block_c=32, interpret=True)
        v2, i2 = reuse_top1(q, s, ids, block_q=64, block_c=256,
                            interpret=True)
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-6)
        assert (np.asarray(i1) == np.asarray(i2)).all()

    @pytest.mark.parametrize("P,S,D,C", [(8, 32, 32, 40), (3, 128, 64, 200),
                                         (16, 8, 16, 25)])
    def test_paged_store_matches_flat(self, P, S, D, C):
        """(P, S, D) paged buffer == the same rows flattened to (P*S, D)."""
        flat = self._unit(P * S, D)
        paged = flat.reshape(P, S, D)
        q = self._unit(12, D)
        ids = jnp.asarray(RNG.integers(-1, P * S, (12, C)), jnp.int32)
        fv, fi = ops.gathered_top1(q, flat, ids)
        pv, pi = ops.gathered_top1(q, paged, ids)
        np.testing.assert_allclose(np.asarray(pv), np.asarray(fv), atol=1e-6)
        assert (np.asarray(pi) == np.asarray(fi)).all()

    def test_paged_oracle_lockstep(self):
        """ref.gather_top1_ref accepts the paged layout and agrees with the
        kernel through the (page, offset) decomposition."""
        P, S, D, C = 5, 64, 32, 90
        paged = self._unit(P * S, D).reshape(P, S, D)
        q = self._unit(9, D)
        ids = jnp.asarray(RNG.integers(-1, P * S, (9, C)), jnp.int32)
        val, idx = ops.gathered_top1(q, paged, ids)
        wv, wi = ref.gather_top1_ref(q, paged, ids)
        fin = np.isfinite(np.asarray(wv))
        np.testing.assert_allclose(np.asarray(val)[fin], np.asarray(wv)[fin],
                                   atol=1e-5)
        assert (np.asarray(idx) == np.asarray(wi)).all()

    def test_paged_block_invariance(self):
        P, S, D = 4, 64, 32
        paged = self._unit(P * S, D).reshape(P, S, D)
        q = self._unit(24, D)
        ids = jnp.asarray(RNG.integers(-1, P * S, (24, 70)), jnp.int32)
        v1, i1 = reuse_top1(q, paged, ids, block_q=8, block_c=32,
                            interpret=True)
        v2, i2 = reuse_top1(q, paged, ids, block_q=32, block_c=128,
                            interpret=True)
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-6)
        assert (np.asarray(i1) == np.asarray(i2)).all()

    def test_paged_empty_store(self):
        q = self._unit(3, 32)
        val, idx = ops.gathered_top1(q, jnp.zeros((0, 16, 32), jnp.float32),
                                     jnp.zeros((3, 4), jnp.int32))
        assert (np.asarray(idx) == -1).all()

    def test_agrees_with_sim_top1_when_all_candidates(self):
        """Full candidate list == brute-force streaming top-1."""
        q, s = self._unit(16, 64), self._unit(256, 64)
        ids = jnp.broadcast_to(jnp.arange(256, dtype=jnp.int32), (16, 256))
        gv, gi = ops.gathered_top1(q, s, ids)
        bv, bi = ops.nearest_neighbor(q, s)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(bv), atol=1e-5)
        assert (np.asarray(gi) == np.asarray(bi)).all()


# ------------------------------------------------------------- reuse_top1
class TestReuseQueryTop1:
    """Sweeps for the one-dispatch query path: the lexicographic top-1
    kernel (reuse_top1) and the full fused pipeline (fused_query)."""

    def _unit(self, *shape):
        x = randn(*shape)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    @pytest.mark.parametrize("Q,N,C,D", [(8, 64, 16, 32), (33, 1000, 200, 64),
                                         (128, 4096, 700, 128), (5, 50, 7, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, Q, N, C, D, dtype):
        q = self._unit(Q, D).astype(dtype)
        s = self._unit(N, D).astype(dtype)
        ids = jnp.asarray(RNG.integers(-1, N, (Q, C)), jnp.int32)
        val, idx = reuse_top1(q, s, ids, interpret=True)
        wv, wi = ref.reuse_top1_ref(q, s, ids)
        fin = np.isfinite(np.asarray(wv))
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(val)[fin], np.asarray(wv)[fin],
                                   atol=tol)
        if dtype == jnp.float32:
            assert (np.asarray(idx) == np.asarray(wi)).all()

    def test_lowest_id_wins_ties_regardless_of_order(self):
        """Duplicate embeddings at different ids: the lowest id must win no
        matter where it sits in the (unsorted, duplicated) candidate list."""
        s = np.array(self._unit(64, 32))
        s[40] = s[3]
        s[57] = s[3]
        q = jnp.asarray(s[3:4])
        for order in ([40, 7, 3, 57, -1, 3], [57, 40, 3, 3, 7, -1],
                      [3, 57, 40, -1, -1, 7]):
            ids = jnp.asarray([order], jnp.int32)
            _, idx = reuse_top1(q, jnp.asarray(s), ids, interpret=True)
            assert int(idx[0]) == 3, order
            _, wi = ref.reuse_top1_ref(q, jnp.asarray(s), ids)
            assert int(wi[0]) == 3, order

    def test_tie_break_across_candidate_tiles(self):
        """The winning (lowest) id sits in a *later* candidate tile than an
        equal-similarity duplicate: the cross-tile lexicographic merge must
        still pick it (a plain strictly-greater merge would not)."""
        s = np.array(self._unit(256, 32))
        s[200] = s[5]
        q = jnp.asarray(s[5:6])
        ids = np.full((1, 256), -1, np.int32)
        ids[0, 0] = 200            # tile 0 (block_c=128): high id first
        ids[0, 200] = 5            # tile 1: the lower equal-sim id
        _, idx = reuse_top1(q, jnp.asarray(s), jnp.asarray(ids),
                            block_c=128, interpret=True)
        assert int(idx[0]) == 5

    def test_query_chunks_match_reference(self, monkeypatch):
        """A gather budget of one 8-row chunk splits the batch into several
        kernel launches; the result must not depend on the split."""
        Q, N, C, D = 37, 300, 130, 48
        monkeypatch.setattr(sim_topk, "GATHER_BUDGET_BYTES", 8 * 256 * D * 4)
        q, s = self._unit(Q, D), self._unit(N, D)
        ids = jnp.asarray(RNG.integers(-1, N, (Q, C)), jnp.int32)
        val, idx = reuse_top1(q, s, ids, block_q=8, block_c=128,
                              interpret=True)
        wv, wi = ref.reuse_top1_ref(q, s, ids)
        fin = np.isfinite(np.asarray(wv))
        np.testing.assert_allclose(np.asarray(val)[fin], np.asarray(wv)[fin],
                                   atol=1e-5)
        assert (np.asarray(idx) == np.asarray(wi)).all()

    @pytest.mark.parametrize("P,S,D,C", [(8, 32, 32, 40), (3, 128, 64, 200)])
    def test_paged_store_matches_flat(self, P, S, D, C):
        flat = self._unit(P * S, D)
        paged = flat.reshape(P, S, D)
        q = self._unit(12, D)
        ids = jnp.asarray(RNG.integers(-1, P * S, (12, C)), jnp.int32)
        fv, fi = reuse_top1(q, flat, ids, interpret=True)
        pv, pi = reuse_top1(q, paged, ids, interpret=True)
        np.testing.assert_allclose(np.asarray(pv), np.asarray(fv), atol=1e-6)
        assert (np.asarray(pi) == np.asarray(fi)).all()

    def test_no_candidates_row(self):
        q, s = self._unit(4, 32), self._unit(64, 32)
        ids = jnp.full((4, 10), -1, jnp.int32)
        val, idx = reuse_top1(q, s, ids, interpret=True)
        assert (np.asarray(idx) == -1).all()
        assert np.isneginf(np.asarray(val)).all()

    def test_block_invariance(self):
        q, s = self._unit(40, 64), self._unit(500, 64)
        ids = jnp.asarray(RNG.integers(-1, 500, (40, 130)), jnp.int32)
        v1, i1 = reuse_top1(q, s, ids, block_q=8, block_c=128,
                            interpret=True)
        v2, i2 = reuse_top1(q, s, ids, block_q=64, block_c=256,
                            interpret=True)
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-6)
        assert (np.asarray(i1) == np.asarray(i2)).all()

    @pytest.mark.parametrize("family,T,P_probe,NB,cap", [
        ("cross_polytope", 3, 4, 64, 4),
        ("cross_polytope", 2, 6, 128, 8),
        ("hyperplane", 4, 8, 64, 4),
    ])
    def test_pipeline_matches_staged_oracle(self, family, T, P_probe, NB, cap):
        """fused_query == probe_batch + table gather + reuse_top1_ref +
        sorted-unique candidate counts, end to end."""
        from repro.core.lsh import LSHParams, get_lsh

        D, B, pages_n, page_s = 16, 24, 4, 8
        lsh = get_lsh(LSHParams(dim=D, num_tables=T, num_probes=P_probe,
                                num_buckets=NB, family=family, seed=11))
        n_rows = pages_n * page_s
        pages = self._unit(n_rows, D).reshape(pages_n, page_s, D)
        slots = RNG.integers(-1, n_rows, (T * NB, cap)).astype(np.int32)
        embs = self._unit(B, D)
        proj = lsh.rotations if family == "cross_polytope" else lsh.planes
        val, idx, counts = fused_query(
            jnp.asarray(embs), proj, jnp.asarray(slots), jnp.asarray(pages),
            family=family, num_probes=P_probe, interpret=True,
            with_counts=True)
        # the with_counts=False variant hands the raw candidate matrix back
        # for host-side counting — same results, bit-identical counts
        val2, idx2, cand2 = fused_query(
            jnp.asarray(embs), proj, jnp.asarray(slots), jnp.asarray(pages),
            family=family, num_probes=P_probe, interpret=True,
            with_counts=False)
        assert (np.asarray(idx2) == np.asarray(idx)).all()
        assert (np.asarray(val2) == np.asarray(val)).all()
        assert (ops.unique_counts(np.asarray(cand2))
                == np.asarray(counts)).all()
        probes = np.asarray(lsh.probe_batch(np.asarray(embs)))  # (B, T, P)
        t_idx = np.arange(T)[None, :, None]
        raw = slots.reshape(T, NB, cap)[t_idx, probes].reshape(B, -1)
        wv, wi = ref.reuse_top1_ref(jnp.asarray(embs), jnp.asarray(pages),
                                    jnp.asarray(raw))
        want_counts = [len({i for i in row if i >= 0}) for row in raw]
        fin = np.isfinite(np.asarray(wv))
        np.testing.assert_allclose(np.asarray(val)[fin], np.asarray(wv)[fin],
                                   atol=1e-5)
        assert (np.asarray(idx) == np.asarray(wi)).all()
        assert np.asarray(counts).tolist() == want_counts


# --------------------------------------------------------- flash attention
class TestFlashAttention:
    @pytest.mark.parametrize("B,S,H,KV,D", [
        (1, 32, 4, 4, 32),     # MHA
        (2, 64, 8, 2, 64),     # GQA
        (1, 128, 8, 1, 128),   # MQA
        (2, 48, 4, 4, 16),     # odd seq vs block
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_matches_ref(self, B, S, H, KV, D, dtype):
        q = randn(B, S, H, D, dtype=dtype)
        k = randn(B, S, KV, D, dtype=dtype)
        v = randn(B, S, KV, D, dtype=dtype)
        got = ops.flash_attention(q, k, v, block_q=16, block_k=16)
        want = ref.flash_attention_ref(q, k, v)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol)

    @pytest.mark.parametrize("kwargs", [
        {"causal": False},
        {"causal": True, "window": 16},
        {"causal": True, "softcap": 50.0},
        {"causal": True, "window": 24, "softcap": 30.0},
        {"causal": True, "scale": 0.0625},
    ])
    def test_variants(self, kwargs):
        q, k, v = randn(2, 64, 8, 32), randn(2, 64, 4, 32), randn(2, 64, 4, 32)
        got = ops.flash_attention(q, k, v, block_q=16, block_k=16, **kwargs)
        want = ref.flash_attention_ref(q, k, v, **kwargs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_block_invariance(self):
        q, k, v = randn(1, 64, 4, 32), randn(1, 64, 4, 32), randn(1, 64, 4, 32)
        a = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True)
        b = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    def test_matches_model_attention_math(self):
        """Kernel == the jnp attention path used by the models."""
        from repro.models.attention import attn_core

        class Cfg:
            attn_logit_softcap = None
            query_pre_attn_scalar = None

        q, k, v = randn(2, 32, 8, 32), randn(2, 32, 4, 32), randn(2, 32, 4, 32)
        got = ops.flash_attention(q, k, v, block_q=16, block_k=16)
        want = attn_core(q, k, v, cfg=Cfg(), causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -------------------------------------------------------- decode attention
class TestDecodeAttention:
    @pytest.mark.parametrize("B,T,H,KV,D", [
        (1, 64, 4, 4, 32), (2, 96, 8, 2, 64), (4, 128, 8, 1, 128),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, B, T, H, KV, D, dtype):
        q = randn(B, H, D, dtype=dtype)
        k = randn(B, T, KV, D, dtype=dtype)
        v = randn(B, T, KV, D, dtype=dtype)
        kv_len = jnp.asarray(RNG.integers(1, T + 1, B), jnp.int32)
        got = ops.decode_attention(q, k, v, kv_len, block_k=32)
        want = ref.decode_attention_ref(q, k, v, kv_len)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol)

    def test_full_cache_equals_flash_last_row(self):
        """decode(q_last) == flash(full seq) at the last position."""
        B, S, H, KV, D = 1, 48, 4, 2, 32
        q = randn(B, S, H, D)
        k = randn(B, S, KV, D)
        v = randn(B, S, KV, D)
        full = ref.flash_attention_ref(q, k, v, causal=True)
        got = ops.decode_attention(q[:, -1], k, v, jnp.asarray([S], jnp.int32),
                                   block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, -1]),
                                   atol=2e-5)
