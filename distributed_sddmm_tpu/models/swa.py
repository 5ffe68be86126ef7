"""Sliding-window attention sublayer of a decoder: one KV group of
MiMo-V2-Flash's window layers (grouped query heads, a value width of its
own, partial RoPE, a causal window and a learned sink logit a head).

For query head ``h`` of the group and token ``i``, over the keys ``j`` of
the mask's row ``i`` (a causal window: ``[i - w + 1, i]``)::

    q_h = RoPE(x Wq_h) / sqrt(d_qk)      k = RoPE(x Wk)
    v   = value_scale * (x Wv)           l_ij = q_h[i] . k[j]
    m_i = max(max_j l_ij, s_h)
    o_h[i] = sum_j exp(l_ij - m_i) v[j] / (sum_j exp(l_ij - m_i)
                                           + exp(s_h - m_i))
    out = concat_h(o_h) Wo

RoPE rotates the first ``rotary_dims`` of each q and k head by the
rotate-half convention at positions ``0 .. T-1``; the sink ``s_h`` joins
the denominator and carries no value. ``x`` stands for the normed hidden
state (the norm belongs to the block, not to this sublayer).

The group's query heads are the rows of ONE stacked mask: row ``h*T + t``
of a ``(heads*T) x T`` matrix that repeats the ``T x T`` mask once a
head, so Q is ``[heads*T, d_qk]`` and one fused-attention program of the
dense-shift strategy (SDDMM, the masked-softmax epilogue with the sink a
row, SpMM over V) serves the whole group. The projections, RoPE, the
attention and ``Wo`` compile as one program per forward.

Under tensor parallelism by KV group each chip holds one such group and
its slice of ``Wo``: its output is that chip's partial sum, before the
all-reduce over the group's chips.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from distributed_sddmm_tpu.common import MatMode
from distributed_sddmm_tpu.obs import trace as obs_trace
from distributed_sddmm_tpu.utils.coo import HostCOO


def stack_heads(mask: HostCOO, heads: int) -> HostCOO:
    """``mask`` (``T x T``) repeated for ``heads`` query heads: row
    ``h*T + t`` of a ``(heads*T) x T`` mask is row ``t`` of ``mask``."""
    T, nnz = mask.M, mask.nnz
    off = np.repeat(np.arange(heads, dtype=np.int64) * T, nnz)
    return HostCOO(
        rows=np.tile(mask.rows, heads) + off, cols=np.tile(mask.cols, heads),
        vals=np.tile(mask.vals, heads), M=heads * T, N=mask.N,
    )


def rope_tables(T: int, rotary_dims: int, theta: float) -> tuple:
    """``(cos, sin)``, float32 ``[T, rotary_dims]``, computed in float64:
    frequencies ``theta ** (-2i / rotary_dims)`` for the rotate-half
    convention (each frequency on dims ``i`` and ``i + rotary_dims/2``)."""
    inv = theta ** (-np.arange(0, rotary_dims, 2, dtype=np.float64)
                    / rotary_dims)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def apply_rope(x, cos, sin):
    """Rotate-half RoPE on the first ``cos.shape[-1]`` dims of ``x``'s
    last axis; ``cos``/``sin`` broadcast against them."""
    r = cos.shape[-1]
    xr, rest = x[..., :r], x[..., r:]
    rot = jnp.concatenate([-xr[..., r // 2:], xr[..., :r // 2]], axis=-1)
    return jnp.concatenate([xr * cos + rot * sin, rest], axis=-1)


def _pad_rows(x, n):
    return jnp.pad(x, ((0, n - x.shape[0]), (0, 0))) if x.shape[0] < n else x


class SlidingWindowAttention:
    """One KV group's attention sublayer over ``mask`` (``T x T``, unit
    values) with ``group`` query heads, run by a dense-shift strategy
    over the stacked mask (``make_algorithm(algorithm, ...,
    attention=True)``).

    ``params``: ``wq [hidden, group*d_qk]``, ``wk [hidden, d_qk]``,
    ``wv [hidden, d_v]``, ``wo [group*d_v, hidden]``, ``sinks [group]``;
    seeded uniform(±1/sqrt(in)) weights and zero sinks until set.
    :meth:`forward` dispatches one program, ``swaLayer``, under a
    ``swa:forward`` span."""

    def __init__(self, mask: HostCOO, group: int, hidden: int, d_qk: int,
                 d_v: int, rotary_dims: int, rope_theta: float = 10000.0,
                 value_scale: float = 1.0, algorithm: str = "15d_fusion2",
                 c: int = 1, kernel=None, devices=None, seed: int = 0):
        from distributed_sddmm_tpu.bench.harness import make_algorithm

        if mask.M != mask.N:
            raise ValueError("the window mask is square: T queries, T keys")
        if rotary_dims % 2 or not 0 < rotary_dims <= d_qk:
            raise ValueError(f"rotary_dims {rotary_dims}: even, in (0, "
                             f"{d_qk}]")
        self.T, self.group = mask.M, group
        self.hidden, self.d_qk, self.d_v = hidden, d_qk, d_v
        self.value_scale = float(value_scale)
        self.d_ops = make_algorithm(algorithm, stack_heads(mask, group), d_qk,
                                    c, kernel=kernel, devices=devices,
                                    attention=True)
        cos, sin = rope_tables(self.T, rotary_dims, rope_theta)
        self.cos, self.sin = jnp.asarray(cos), jnp.asarray(sin)
        shapes = {"wq": (hidden, group * d_qk), "wk": (hidden, d_qk),
                  "wv": (hidden, d_v), "wo": (group * d_v, hidden)}
        keys = jax.random.split(jax.random.key(seed), len(shapes))
        self.params = {
            name: jax.random.uniform(k, shape, jnp.float32,
                                     -shape[0] ** -0.5, shape[0] ** -0.5)
            for k, (name, shape) in zip(keys, shapes.items())
        }
        self.params["sinks"] = jnp.zeros((group,), jnp.float32)
        self._prog = None

    def _layer_program(self):
        """``(prog, operands)``: ``prog(x, params, cos, sin, *operands)
        -> (out [T, hidden], attn [T, group*d_v])``, one jitted program
        with the tile operands as arguments."""
        if self._prog is not None:
            return self._prog
        from distributed_sddmm_tpu import programs

        d = self.d_ops
        attn, operands = d.attention_program(
            d.like_s_values(1.0), MatMode.A, v=True, sink=True)
        T, H, dq, dv = self.T, self.group, self.d_qk, self.d_v
        scale = self.value_scale

        def layer(x, p, cos, sin, *operands):
            with jax.named_scope("swaLayer"):
                q = apply_rope((x @ p["wq"]).reshape(T, H, dq),
                               cos[:, None], sin[:, None]) * dq ** -0.5
                k = apply_rope(x @ p["wk"], cos, sin)
                v = scale * (x @ p["wv"])
                sink = jnp.repeat(p["sinks"], T)[:, None]
                o, _ = attn(
                    _pad_rows(q.transpose(1, 0, 2).reshape(H * T, dq),
                              d.M_pad),
                    _pad_rows(k, d.N_pad), _pad_rows(v, d.N_pad),
                    _pad_rows(sink, d.M_pad), *operands)
                o = o[:H * T].reshape(H, T, dv).transpose(1, 0, 2)
                o = o.reshape(T, H * dv)
                return o @ p["wo"], o

        prog = programs.chained_program(d, f"swaLayer-v{scale:g}",
                                        jax.jit(layer))
        self._prog = prog, operands
        return self._prog

    def forward(self, x, return_attention: bool = False):
        """The sublayer's output ``[T, hidden]`` for ``x`` ``[T, hidden]``
        (with ``return_attention``, also the heads' outputs before
        ``Wo``, ``[T, group*d_v]``)."""
        with obs_trace.span("swa:forward", tokens=self.T, heads=self.group):
            prog, operands = self._layer_program()
            out, o = self.d_ops._timed(
                "swaLayer", prog, x, self.params, self.cos, self.sin,
                *operands, _comm_op="fusedAttn")
        return (out, o) if return_attention else out
