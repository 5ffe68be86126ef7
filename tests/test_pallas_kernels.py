"""Pallas kernel path: blocked encoding + one-hot MXU kernels.

Runs in Pallas interpreter mode on the CPU test mesh (the same code compiles
to Mosaic on TPU). Mirrors the reference's kernel verification strategy —
numeric agreement with an oracle (SURVEY.md section 4) — plus cross-kernel
fingerprint equality between the XLA and Pallas implementations of the
distributed ops (`/root/reference/scratch.cpp:26-76`).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from distributed_sddmm_tpu.common import MatMode
from distributed_sddmm_tpu.ops.blocked import CHUNK, build_blocked, unpack_meta
from distributed_sddmm_tpu.ops.kernels import XlaKernel, get_kernel
from distributed_sddmm_tpu.ops.pallas_kernels import BlockedTile, PallasKernel
from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
from distributed_sddmm_tpu.utils.coo import HostCOO
from distributed_sddmm_tpu.utils import oracle


def sddmm_oracle(rows, cols, vals, A, B):
    S = HostCOO(rows, cols, vals, A.shape[0], B.shape[0])
    return oracle.sddmm(S, A.astype(np.float64), B.astype(np.float64))


def spmm_oracle(rows, cols, vals, B, out_rows):
    S = HostCOO(rows, cols, vals, out_rows, B.shape[0])
    return oracle.spmm_a(S, B.astype(np.float64))


def _tile_setup(Mr=700, Nc=500, nnz=3000, seed=0, group=1):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, Mr, nnz).astype(np.int64)
    cols = rng.integers(0, Nc, nnz).astype(np.int64)
    bucket = np.zeros(nnz, dtype=np.int64)
    meta = build_blocked(1, bucket, rows, cols, Mr, Nc, group=group)
    blk = BlockedTile(
        lr=jnp.array(meta.lr[0]),
        lc=jnp.array(meta.lc[0]),
        meta=jnp.array(meta.meta[0]),
        bm=meta.bm, bn=meta.bn,
        gr_blocks=meta.gr_blocks, gc_blocks=meta.gc_blocks,
        group=meta.group,
    )
    max_nnz = meta.n_chunks * CHUNK
    vals = np.zeros(max_nnz, np.float32)
    vals[meta.host_to_chunk] = rng.standard_normal(nnz).astype(np.float32)
    return rows, cols, meta, blk, vals, rng


class TestBlockedMeta:
    def test_chunk_invariants(self):
        rows, cols, meta, _, _, _ = _tile_setup()
        # Every nonzero lands in the right block.
        gr, gc, first, last = unpack_meta(meta.meta[0])
        ch = meta.host_to_chunk // CHUNK
        assert np.all(gr[ch] == rows // meta.bm)
        assert np.all(gc[ch] == cols // meta.bn)
        # Every gr group has exactly one first and one last flag, and real
        # (flagged-or-populated) chunks are sorted by (gr, gc) — the
        # accumulator zero/flush contract of the kernels.
        assert first.sum() == meta.gr_blocks
        assert last.sum() == meta.gr_blocks
        real = np.zeros(gr.shape, dtype=bool)
        real[np.unique(ch)] = True
        real |= (first | last).astype(bool)
        key = gr[real] * meta.gc_blocks + gc[real]
        assert np.all(np.diff(key) >= 0)
        # global_rows/global_cols reproduce the original coordinates.
        grows = meta.global_rows().reshape(-1)
        gcols = meta.global_cols().reshape(-1)
        assert np.all(grows[meta.host_to_chunk] == rows)
        assert np.all(gcols[meta.host_to_chunk] == cols)
        # Pad lanes are marked and zeroed.
        pads = meta.pad_lane.reshape(-1)
        assert pads.sum() == meta.n_chunks * CHUNK - rows.size
        assert np.all(grows[pads] == 0)

    def test_meta_word_gr_no_sign_extension(self):
        # gr occupies the int32 sign-adjacent bits; unpack must mask, not
        # arithmetic-shift (regression: gr=16384 came back as -16384).
        from distributed_sddmm_tpu.ops.blocked import pack_meta

        w = pack_meta(
            np.array([16384]), np.array([7]), np.array([1]), np.array([0])
        )
        gr, gc, first, last = unpack_meta(w)
        assert (gr[0], gc[0], first[0], last[0]) == (16384, 7, 1, 0)

    def test_pad_chunks_pin_last_output_window(self):
        # Buckets shorter than the shared C get trailing pad chunks; their
        # meta must keep the output window on the LAST row block (an
        # unwritten remapped window would flush stale VMEM over block 0).
        rng = np.random.default_rng(2)
        nnz = 4000
        rows = rng.integers(0, 1500, nnz).astype(np.int64)
        cols = rng.integers(0, 1500, nnz).astype(np.int64)
        bucket = (np.arange(nnz) < 100).astype(np.int64)  # very uneven
        meta = build_blocked(2, bucket, rows, cols, 1500, 1500)
        gr, gc, first, last = unpack_meta(meta.meta)
        n_chunks_b1 = int(
            (~meta.pad_lane[1].all(axis=1)).sum()
        )  # chunks with any real lane
        assert n_chunks_b1 < meta.n_chunks  # pads exist for this test
        trailing = gr[1, np.where(last[1])[0].max() + 1 :]
        assert np.all(trailing == meta.gr_blocks - 1)

    @pytest.mark.parametrize("group", [2, 4, 8])
    def test_group_alignment(self, group):
        # With chunk grouping, a kernel grid step (group consecutive chunks)
        # must never straddle a row-block window: C is a multiple of the
        # group and every step's chunks share one gr.
        rows, cols, meta, _, _, _ = _tile_setup(group=group)
        assert meta.group == group
        assert meta.n_chunks % group == 0
        gr, gc, first, last = unpack_meta(meta.meta[0])
        steps = gr.reshape(-1, group)
        assert np.all(steps == steps[:, :1])
        # Flag counts survive the deficit padding (one zero + one flush per
        # gr group; the flush may sit on a trailing pad chunk by design).
        assert first.sum() == meta.gr_blocks
        assert last.sum() == meta.gr_blocks
        # Coordinates still round-trip.
        assert np.all(meta.global_rows().reshape(-1)[meta.host_to_chunk] == rows)
        assert np.all(meta.global_cols().reshape(-1)[meta.host_to_chunk] == cols)

    def test_every_gr_flushed_for_empty_rows(self):
        # Matrix with nonzeros only in the top rows: lower row blocks must
        # still get first/last chunks so the output is zeroed.
        rng = np.random.default_rng(1)
        rows = rng.integers(0, 100, 500).astype(np.int64)
        cols = rng.integers(0, 2000, 500).astype(np.int64)
        meta = build_blocked(1, np.zeros(500, np.int64), rows, cols, 4000, 2000)
        _, _, first, last = unpack_meta(meta.meta[0])
        assert first.sum() == meta.gr_blocks
        assert last.sum() == meta.gr_blocks


class TestPallasTileKernels:
    # Slow-marked rows are single-axis redundancies: every axis keeps a
    # fast representative — grouping×form×batch interactions stay via
    # (4,bt,True)/(4,nt,True)/(8,bt,True), ungrouped bases via
    # (1,bt,False)/(1,nt,False), bf16 via both its rows.
    @pytest.mark.parametrize(
        "precision,tol,group,form,batch",
        [
            ("f32", 1e-5, 1, "bt", False),
            ("bf16", 3e-2, 1, "bt", False),
            pytest.param("f32", 1e-5, 4, "bt", False,
                         marks=pytest.mark.slow),
            ("f32", 1e-5, 1, "nt", False),
            pytest.param("f32", 1e-5, 4, "nt", False,
                         marks=pytest.mark.slow),
            pytest.param("f32", 1e-5, 1, "bt", True,
                         marks=pytest.mark.slow),
            ("f32", 1e-5, 4, "bt", True),
            ("f32", 1e-5, 4, "nt", True),
            ("f32", 1e-5, 8, "bt", True),
            ("bf16", 3e-2, 4, "bt", True),
        ],
    )
    def test_against_oracle(self, precision, tol, group, form, batch):
        rows, cols, meta, blk, vals, rng = _tile_setup(group=group)
        Mr, Nc, R = 700, 500, 32
        A = rng.standard_normal((Mr, R)).astype(np.float32)
        B = rng.standard_normal((Nc, R)).astype(np.float32)
        k = PallasKernel(precision=precision, interpret=True,
                         scatter_form=form, batch_step=batch)
        vj, Aj, Bj = jnp.array(vals), jnp.array(A), jnp.array(B)

        host_vals = vals[meta.host_to_chunk]
        ref_mid = sddmm_oracle(rows, cols, host_vals, A, B)
        mid = np.asarray(k.sddmm_tile(blk, vj, Aj, Bj))
        scale = np.abs(ref_mid).max() + 1
        np.testing.assert_allclose(
            mid[meta.host_to_chunk] / scale, ref_mid / scale, atol=tol
        )
        # Pad lanes stay exactly zero.
        assert np.all(mid[meta.pad_lane.reshape(-1)] == 0)

        ref_out = spmm_oracle(rows, cols, host_vals, B, Mr)
        out = np.asarray(k.spmm_tile(blk, vj, Bj, Mr))
        scale = np.abs(ref_out).max() + 1
        np.testing.assert_allclose(out / scale, ref_out / scale, atol=tol)

        fo, fm = k.fused_tile(blk, vj, Aj, Bj)
        ref_fo = spmm_oracle(rows, cols, ref_mid, B, Mr)
        scale = np.abs(ref_fo).max() + 1
        np.testing.assert_allclose(np.asarray(fo) / scale, ref_fo / scale, atol=tol)
        np.testing.assert_allclose(
            np.asarray(fm)[meta.host_to_chunk] / (np.abs(ref_mid).max() + 1),
            ref_mid / (np.abs(ref_mid).max() + 1),
            atol=tol,
        )

    @pytest.mark.parametrize("max_chunks", [4, 12])
    @pytest.mark.parametrize("group,batch", [(1, False), (4, True)])
    def test_segmented_launches_match_one_launch(self, monkeypatch, group,
                                                 batch, max_chunks):
        """A chunk list longer than MAX_PREFETCH_CHUNKS (SMEM) runs as
        several launches; row-block groups cut by a segment boundary add
        their partial sums, and the result is the single launch's."""
        from distributed_sddmm_tpu.ops import pallas_kernels

        rows, cols, meta, blk, vals, rng = _tile_setup(group=group, nnz=6000)
        A = jnp.array(rng.standard_normal((700, 16)).astype(np.float32))
        B = jnp.array(rng.standard_normal((500, 16)).astype(np.float32))
        k = PallasKernel(precision="f32", interpret=True, batch_step=batch)
        vj = jnp.array(vals)

        def run():
            fo, fm = k.fused_tile(blk, vj, A, B)
            return [np.asarray(x) for x in (
                fo, fm, k.spmm_tile(blk, vj, B, 700),
                k.sddmm_tile(blk, vj, A, B))]

        one = run()
        monkeypatch.setattr(pallas_kernels, "MAX_PREFETCH_CHUNKS", max_chunks)
        pallas_kernels._tile_call.clear_cache()
        try:
            assert meta.n_chunks > 2 * max_chunks
            for got, want in zip(run(), one):
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
        finally:
            pallas_kernels._tile_call.clear_cache()

    def test_flat_protocol_refused_off_interpret(self):
        """On the TPU a tile set without blocked metadata raises instead of
        running XLA under the Pallas kernel's name."""
        k = PallasKernel(precision="bf16", interpret=False)
        rows = jnp.array([0, 1], jnp.int32)
        with pytest.raises(RuntimeError, match="no blocked"):
            k.sddmm(rows, rows, jnp.ones(2), jnp.ones((2, 4)),
                    jnp.ones((2, 4)))
        with pytest.raises(RuntimeError, match="no blocked"):
            k.spmm(rows, rows, jnp.ones(2), jnp.ones((2, 4)), 2)

    def test_flat_protocol_fallback(self):
        # In interpret mode (CPU test meshes) flat calls route to XLA.
        k = PallasKernel(interpret=True)
        rows = jnp.array([0, 1, 1], jnp.int32)
        cols = jnp.array([0, 0, 2], jnp.int32)
        vals = jnp.array([1.0, 2.0, 3.0])
        A = jnp.ones((2, 4))
        B = jnp.ones((3, 4))
        ref = XlaKernel()
        np.testing.assert_allclose(
            k.sddmm(rows, cols, vals, A, B), ref.sddmm(rows, cols, vals, A, B)
        )
        np.testing.assert_allclose(
            k.spmm(rows, cols, vals, B, 2), ref.spmm(rows, cols, vals, B, 2)
        )

    def test_factory(self):
        assert get_kernel("pallas").name.startswith("pallas")


class TestPallasDistributed:
    """XLA and Pallas kernels must produce identical fingerprints through
    the full distributed 1.5D dense-shift programs."""

    # Off-diagonal (c, fusion) combos are slow-marked: the c axis and
    # the fusion axis each keep a fast representative on the diagonal,
    # and kernel identity is per-axis, not per-cross-product.
    @pytest.mark.parametrize(
        "c,fusion",
        [
            (1, 1),
            pytest.param(1, 2, marks=pytest.mark.slow),
            pytest.param(2, 1, marks=pytest.mark.slow),
            (2, 2),
        ],
    )
    def test_fingerprints_match_xla(self, c, fusion):
        S = HostCOO.erdos_renyi(260, 220, 5, seed=3, values="normal")
        algs = [
            DenseShift15D(S, R=16, c=c, fusion_approach=fusion, kernel=XlaKernel()),
            DenseShift15D(
                S, R=16, c=c, fusion_approach=fusion,
                kernel=PallasKernel(precision="f32", interpret=True),
            ),
        ]
        prints = []
        for alg in algs:
            A = alg.dummy_initialize(MatMode.A)
            B = alg.dummy_initialize(MatMode.B)
            out, mid = alg.fused_spmm(A, B, alg.like_s_values(1.0))
            prints.append(
                (
                    alg.fingerprint(alg.host_a(out)),
                    alg.fingerprint(alg.gather_s_values(mid)),
                    alg.fingerprint(alg.host_b(alg.spmm_b(A, B, alg.like_st_values(1.0)))),
                )
            )
        np.testing.assert_allclose(prints[0], prints[1], rtol=1e-5)


class TestPallasAllAlgorithms:
    """Every strategy (including the tile-rotating and fiber-replicated
    ones) runs its ops through the blocked Pallas kernels with fingerprints
    identical to the XLA path — the scratch.cpp protocol across kernels."""

    # The (c=2, p=8) rows are slow-marked: each algorithm keeps its
    # fast pallas-vs-xla identity representative at (1, 4), and
    # replication's interaction with the blocked kernels stays covered
    # fast by TestPallasDistributed's c=2 row.
    @pytest.mark.parametrize(
        "alg_name,c,p",
        [
            ("15d_sparse", 1, 4),
            pytest.param("15d_sparse", 2, 8, marks=pytest.mark.slow),
            ("25d_dense_replicate", 1, 4),
            pytest.param("25d_dense_replicate", 2, 8,
                         marks=pytest.mark.slow),
            ("25d_sparse_replicate", 1, 4),
            pytest.param("25d_sparse_replicate", 2, 8,
                         marks=pytest.mark.slow),
        ],
    )
    def test_fingerprints_match_xla(self, alg_name, c, p):
        import jax

        from distributed_sddmm_tpu.common import KernelMode
        from distributed_sddmm_tpu.bench.harness import make_algorithm

        S = HostCOO.erdos_renyi(280, 260, 5, seed=4, values="normal")
        devices = jax.devices()[:p]
        prints = []
        for kern in (
            XlaKernel(),
            PallasKernel(precision="f32", interpret=True),
        ):
            alg = make_algorithm(alg_name, S, R=16, c=c, kernel=kern,
                                 devices=devices)
            A = alg.dummy_initialize(MatMode.A)
            B = alg.dummy_initialize(MatMode.B)
            A_s, B_s = alg.initial_shift(A, B, KernelMode.SDDMM_A)
            mid = alg.sddmm_a(A_s, B_s, alg.like_s_values(1.0))
            zero, B_s2 = alg.initial_shift(
                alg.like_a_matrix(0.0), B, KernelMode.SPMM_A
            )
            out = alg.spmm_a(zero, B_s2, alg.like_s_values(1.0))
            out, _ = alg.de_shift(out, None, KernelMode.SPMM_A)
            A_s3, zb = alg.initial_shift(
                A, alg.like_b_matrix(0.0), KernelMode.SPMM_B
            )
            outb = alg.spmm_b(A_s3, zb, alg.like_st_values(1.0))
            _, outb = alg.de_shift(None, outb, KernelMode.SPMM_B)
            A_s4, B_s4 = alg.initial_shift(A, B, KernelMode.SDDMM_B)
            mid_b = alg.sddmm_b(A_s4, B_s4, alg.like_st_values(1.0))
            prints.append(
                (
                    alg.fingerprint(alg.gather_s_values(mid)),
                    alg.fingerprint(alg.host_a(out)),
                    alg.fingerprint(alg.host_b(outb)),
                    alg.fingerprint(alg.gather_st_values(mid_b)),
                )
            )
        np.testing.assert_allclose(prints[0], prints[1], rtol=1e-5)
