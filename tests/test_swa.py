"""Sliding-window attention (MiMo-V2-Flash's window layers): the causal
window mask, the sink and the value operand of the fused attention
program against the float64 oracle, the attention epilogue cut into
SMEM segments, and ``models/swa.py`` against the benchmark's plain
reference, whole and as the sum of its KV groups' shares."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import sparse_ref
from benchmark.apps import swa_ref
from distributed_sddmm_tpu import masks
from distributed_sddmm_tpu.common import MatMode
from distributed_sddmm_tpu.models.swa import (
    SlidingWindowAttention, stack_heads,
)
from distributed_sddmm_tpu.ops import pallas_kernels
from distributed_sddmm_tpu.ops.blocked import CHUNK, build_blocked
from distributed_sddmm_tpu.ops.pallas_kernels import BlockedTile, PallasKernel
from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
from distributed_sddmm_tpu.utils import oracle

# The tiny layer: T tokens, a window of W keys, H query heads a group,
# q/k and v widths, rotary dims (int(24 * 0.334) = 8, as 64 of 192).
T, W, H, DQ, DV, HID, ROT = 256, 8, 2, 24, 16, 64, 8


def _pallas():
    return PallasKernel(precision="f32", interpret=True)


# --------------------------------------------------------------------- #
# The causal window
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("n,w", [(64, 1), (64, 8), (300, 128), (5, 8)])
def test_causal_window_count_causality_edges(n, w):
    S = masks.causal_window(n, w)
    k = min(n, w)
    assert S.nnz == n * k - k * (k - 1) // 2
    assert np.all(S.cols <= S.rows) and np.all(S.rows - S.cols < w)
    assert np.all(S.vals == 1.0) and S.M == S.N == n
    deg = np.bincount(S.rows, minlength=n)
    np.testing.assert_array_equal(deg, np.minimum(np.arange(n) + 1, w))
    assert len(set(zip(S.rows.tolist(), S.cols.tolist()))) == S.nnz


def test_causal_window_at_the_cell_size_and_spec():
    """4,186,176 nonzeros at 32,768 tokens and a 128-key window."""
    n, w = 32768, 128
    assert n * w - w * (w - 1) // 2 == 4_186_176
    S = masks.from_spec("causal:16", 100)
    want = masks.causal_window(100, 16)
    assert np.array_equal(S.rows, want.rows)
    assert np.array_equal(S.cols, want.cols)
    assert masks.from_spec("causal:w=4", 10).nnz == 10 * 4 - 6
    with pytest.raises(ValueError):
        masks.causal_window(10, 0)


def test_benchmark_window_matches_the_program_mask():
    from benchmark.matrices import window

    got = window.make({"log_m": 8, "window": 17}, None)
    want = masks.causal_window(256, 17)
    assert np.array_equal(got["rows"], want.rows)
    assert np.array_equal(got["cols"], want.cols)


def test_stack_heads_repeats_the_mask():
    S = masks.causal_window(10, 3)
    St = stack_heads(S, 4)
    assert (St.M, St.N, St.nnz) == (40, 10, 4 * S.nnz)
    for h in range(4):
        sel = (St.rows >= 10 * h) & (St.rows < 10 * (h + 1))
        assert np.array_equal(St.rows[sel] - 10 * h, S.rows)
        assert np.array_equal(St.cols[sel], S.cols)


# --------------------------------------------------------------------- #
# Sinks and a value operand through the fused attention program
# --------------------------------------------------------------------- #


def _case(n_dev, c, kern, R=24, R_v=None, sink=False, seed=0):
    """A causal-window mask with masked entries and one fully masked row,
    the strategy over ``n_dev`` CPU devices, and the float64 oracle."""
    rng = np.random.default_rng(seed)
    S0 = stack_heads(masks.causal_window(96, 8), 2)
    vals = np.ones(S0.nnz)
    vals[rng.random(S0.nnz) < 0.1] = 0.0
    vals[S0.rows == 5] = 0.0
    S = S0.with_values(vals)
    A = rng.standard_normal((S.M, R))
    B = rng.standard_normal((S.N, R))
    V = None if R_v is None else rng.standard_normal((S.N, R_v))
    s = rng.uniform(-1.0, 3.0, S.M) if sink else None
    alg = DenseShift15D(S, R=R, c=c, kernel=kern,
                        devices=jax.devices()[:n_dev])
    args = dict(V=None if V is None else _put_rows(alg, V, alg.N_pad,
                                                   alg.b_sharding()),
                sink=None if s is None else _put_rows(
                    alg, s[:, None], alg.M_pad, alg.a_sharding()))
    out, probs = alg.fused_attention(
        alg.put_a(A.astype(np.float32)), alg.put_b(B.astype(np.float32)),
        alg.scatter_s_values(vals.astype(np.float32)), **args)
    want = oracle.fused_attention_a(S, A, B, V, s)
    return alg, S, (out, probs), want


def _put_rows(alg, x, n_pad, sharding):
    buf = np.zeros((n_pad, x.shape[1]), np.float32)
    buf[: x.shape[0]] = x
    return jax.device_put(buf, sharding)


@pytest.mark.parametrize("form", ["flat", "blocked"])
def test_sink_in_both_tile_forms_against_the_oracle(form):
    kern = None if form == "flat" else _pallas()
    alg, S, (out, probs), (want_out, want_probs) = _case(1, 1, kern,
                                                         sink=True)
    assert alg._use_blocked(alg.S_tiles) == (form == "blocked")
    got_p = alg.gather_s_values(probs)
    np.testing.assert_allclose(got_p, want_probs, atol=1e-6)
    np.testing.assert_allclose(alg.host_a(out), want_out, atol=1e-5)
    # The sink takes a share of every live row's mass: rows sum below 1.
    sums = np.zeros(S.M)
    np.add.at(sums, S.rows, got_p)
    live = np.zeros(S.M, bool)
    live[S.rows[S.vals != 0]] = True
    assert np.all(sums[live] < 1.0) and np.all(sums[~live] == 0.0)


@pytest.mark.parametrize("n_dev,c", [(1, 1), (4, 1), (4, 2)])
@pytest.mark.parametrize("form", ["flat", "blocked"])
def test_value_operand_of_its_own_width(n_dev, c, form):
    """V (width 16) differs from the keys (width 24), with sinks, on one
    and four devices, with and without replication (c = 2)."""
    kern = None if form == "flat" else _pallas()
    alg, S, (out, probs), (want_out, want_probs) = _case(
        n_dev, c, kern, R=24, R_v=16, sink=True, seed=1)
    assert np.asarray(out).shape[-1] == 16
    np.testing.assert_allclose(alg.gather_s_values(probs), want_probs,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(out)[: S.M], want_out, atol=1e-5)


def test_value_operand_without_sink_and_b_mode_refused():
    alg, S, (out, _), (want_out, _) = _case(4, 2, None, R=24, R_v=16,
                                            seed=2)
    np.testing.assert_allclose(np.asarray(out)[: S.M], want_out, atol=1e-5)
    A = alg.like_a_matrix(1.0)
    with pytest.raises(NotImplementedError, match="A mode"):
        alg.fused_attention(A, A, alg.like_st_values(1.0), MatMode.B, V=A)
    with pytest.raises(NotImplementedError, match="A mode"):
        alg.attention_program(alg.like_st_values(1.0), MatMode.B, sink=True)


def test_attention_program_takes_operands_as_arguments():
    """The raw accessor's program is the dispatched one, its tile
    operands passed in (not baked into a caller's program)."""
    alg, S, (out, probs), _ = _case(1, 1, None, R_v=16, sink=True, seed=3)
    f, operands = alg.attention_program(alg.like_s_values(1.0), v=True,
                                        sink=True)
    assert operands[-1].shape == alg.like_s_values(1.0).shape
    A, B = alg.like_a_matrix(0.5), alg.like_b_matrix(0.25)
    V = jnp.ones((alg.N_pad, 16), jnp.float32)
    s = jnp.zeros((alg.M_pad, 1), jnp.float32)
    got = jax.jit(f)(A, B, V, s, *operands)
    want = alg.fused_attention(A, B, alg.like_s_values(1.0), V=V, sink=s)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


# --------------------------------------------------------------------- #
# The epilogue in SMEM segments
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("max_chunks", [4, 12])
@pytest.mark.parametrize("group", [1, 4])
def test_attn_call_segmented_matches_one_launch(monkeypatch, group,
                                                max_chunks):
    """An epilogue chunk list longer than MAX_PREFETCH_CHUNKS runs as
    several launches: ``attn_reduce`` segments merge their row stats,
    ``attn_norm`` segments each write their own chunks, and both equal
    one launch."""
    rng = np.random.default_rng(group)
    S = stack_heads(masks.causal_window(300, 16), 3)
    meta = build_blocked(1, np.zeros(S.nnz, np.int64), S.rows, S.cols, S.M,
                         S.N, group=group, block_rows=256, block_cols=256)
    blk = BlockedTile(lr=jnp.array(meta.lr[0]), lc=jnp.array(meta.lc[0]),
                      meta=jnp.array(meta.meta[0]), bm=meta.bm, bn=meta.bn,
                      gr_blocks=meta.gr_blocks, gc_blocks=meta.gc_blocks,
                      group=meta.group)
    gate = np.zeros(meta.n_chunks * CHUNK, np.float32)
    gate[meta.host_to_chunk] = (rng.random(S.nnz) > 0.1)
    z = np.zeros_like(gate)
    z[meta.host_to_chunk] = 4 * rng.standard_normal(S.nnz)
    gate, z = jnp.array(gate), jnp.array(z)
    k = _pallas()

    def run():
        m, d = k.attn_stats_tile_t(blk, gate, z)
        p = k.attn_norm_tile_t(blk, gate, z, m, d, jnp.float32)
        return [np.asarray(x) for x in (m, d, p)]

    one = run()
    monkeypatch.setattr(pallas_kernels, "MAX_PREFETCH_CHUNKS", max_chunks)
    pallas_kernels._attn_call.clear_cache()
    try:
        assert pallas_kernels.launches(meta.n_chunks, group) > 2
        m, d, p = run()
    finally:
        pallas_kernels._attn_call.clear_cache()
    live = one[1][:, 0] > 0
    np.testing.assert_array_equal(m[live], one[0][live])
    np.testing.assert_allclose(d, one[1], rtol=1e-6)
    np.testing.assert_allclose(p, one[2], rtol=1e-6, atol=1e-7)
    want = oracle.masked_softmax(
        S.with_values(np.asarray(gate)[meta.host_to_chunk]),
        np.asarray(z)[meta.host_to_chunk])
    np.testing.assert_allclose(p[meta.host_to_chunk], want, atol=1e-6)


def test_attention_gauges_count_epilogue_launches(monkeypatch):
    monkeypatch.setattr(pallas_kernels, "MAX_PREFETCH_CHUNKS", 8)
    S = stack_heads(masks.causal_window(128, 8), 2)
    alg = DenseShift15D(S, R=8, kernel=_pallas(), devices=jax.devices()[:1])
    g = alg.metrics.gauges()
    n = g["fusedAttn"]["n_chunks"]
    assert g["fusedAttn"]["attn_segments"] == pallas_kernels.launches(
        n, alg.S_tiles.blk_geom[4]) > 1
    assert g["swaLayer"]["attn_segments"] == g["fusedAttn"]["attn_segments"]
    assert "attn_segments" not in g["fusedSpMM"]


# --------------------------------------------------------------------- #
# models/swa.py against the plain reference
# --------------------------------------------------------------------- #


def _dims(heads=H, kv_heads=1):
    return {"hidden": HID, "heads": heads, "kv_heads": kv_heads, "d_qk": DQ,
            "d_v": DV, "rotary_dims": ROT, "rope_theta": 10000.0,
            "value_scale": 0.707}


def _params(key, heads, kv_heads):
    kq, kk, kv, ko, ks = jax.random.split(key, 5)

    def u(k, shape):
        b = shape[0] ** -0.5
        return jax.random.uniform(k, shape, jnp.float32, -b, b)

    return {"wq": u(kq, (HID, heads * DQ)), "wk": u(kk, (HID, kv_heads * DQ)),
            "wv": u(kv, (HID, kv_heads * DV)), "wo": u(ko, (heads * DV, HID)),
            "sinks": jax.random.uniform(ks, (heads,), jnp.float32, 2.0, 6.0)}


def _model(kern=None, n_dev=1, c=1):
    return SlidingWindowAttention(
        masks.causal_window(T, W), H, HID, DQ, DV, ROT, 10000.0, 0.707,
        kernel=kern, devices=jax.devices()[:n_dev], c=c)


@pytest.mark.parametrize("form,n_dev,c", [("flat", 1, 1),
                                          ("blocked", 1, 1),
                                          ("flat", 4, 2)])
def test_model_matches_the_reference(form, n_dev, c):
    model = _model(None if form == "flat" else _pallas(), n_dev, c)
    x = jax.random.normal(jax.random.key(1), (T, HID), jnp.float32)
    model.params = _params(jax.random.key(2), H, 1)
    out, attn = model.forward(x, return_attention=True)
    assert out.shape == (T, HID) and attn.shape == (T, H * DV)
    S = masks.causal_window(T, W)
    ell = sparse_ref.Ell(S.rows, S.cols, T)
    want_out, want_attn = swa_ref.forward(ell, x, model.params, _dims())
    for got, want in ((out, want_out), (attn, want_attn)):
        fro, widest = sparse_ref.gaps(got, want)
        assert fro < 1e-5 and widest < 1e-5, (fro, widest)
    # One compiled program a forward, under the model's span.
    calls = model.d_ops.metrics.calls_view()
    assert calls == {"swaLayer": 1}
    # Each planted departure reads far off the sound program.
    for fault in ("no_sink", "no_rope"):
        bad, _ = swa_ref.forward(ell, x, model.params, _dims(), fault=fault)
        assert sparse_ref.gaps(bad, want_out)[0] > 1e-2, fault
    acausal = sparse_ref.Ell(S.cols, S.rows, T)
    bad, _ = swa_ref.forward(acausal, x, model.params, _dims())
    assert sparse_ref.gaps(bad, want_out)[0] > 1e-2


def test_kv_group_shares_add_up_to_the_whole_layer():
    """Two KV groups of two query heads each, each group's share run by
    its own model (its heads' q, k, v slices, its rows of Wo, its sinks):
    the partial outputs add up to the uncut reference layer's output."""
    G = 2
    full = _params(jax.random.key(3), H * G, G)
    x = jax.random.normal(jax.random.key(4), (T, HID), jnp.float32)
    total = 0.0
    for g in range(G):
        model = _model()
        q = slice(g * H * DQ, (g + 1) * H * DQ)
        kv = slice(g * DQ, (g + 1) * DQ)
        model.params = {
            "wq": full["wq"][:, q], "wk": full["wk"][:, kv],
            "wv": full["wv"][:, g * DV:(g + 1) * DV],
            "wo": full["wo"][g * H * DV:(g + 1) * H * DV],
            "sinks": full["sinks"][g * H:(g + 1) * H],
        }
        total = total + model.forward(x)
    S = masks.causal_window(T, W)
    want, _ = swa_ref.forward(sparse_ref.Ell(S.rows, S.cols, T), x, full,
                              _dims(H * G, G))
    fro, widest = sparse_ref.gaps(total, want)
    assert fro < 1e-5 and widest < 1e-5, (fro, widest)
