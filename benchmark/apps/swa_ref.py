"""Plain reference of MiMo-V2-Flash's sliding-window attention sublayer
(https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash, ``config.json``: the
``swa_*`` keys), in ``jax.numpy`` float32 over ``sparse_ref``'s ELL
layout of the ``T x T`` window mask. It imports nothing of the program.

With ``H`` query heads over ``G`` KV heads (query head ``h`` reads KV
head ``h // (H // G)``), per head and token ``i``, over the mask's keys
``j`` of row ``i``::

    q_h = RoPE(x Wq_h) / sqrt(d_qk)      k = RoPE(x Wk_g)
    v   = value_scale * (x Wv_g)         l_ij = q_h[i] . k[j]
    m_i = max(max_j l_ij, s_h)
    o_h[i] = sum_j exp(l_ij - m_i) v[j] / (sum_j exp(l_ij - m_i)
                                           + exp(s_h - m_i))
    out = concat_h(o_h) Wo

RoPE: rotate-half on the first ``int(d_qk * partial_rotary_factor)``
dims, frequencies ``swa_rope_theta ** (-2i / dims)``, positions
``0 .. T-1``, tables computed in float64. ``REFERENCE_FAULTS`` of
``apps/swa.py`` plant a departure here: ``no_sink`` (the sink left out of
the denominator), ``acausal`` (each token attends to the window after it
in place of the one before), ``no_rope`` (no rotation).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import sparse_ref


def dims(cfg: dict) -> dict:
    """The sublayer's sizes from the configuration's ``swa_*`` keys."""
    dq = cfg["swa_head_dim"]
    return {"hidden": cfg["hidden_size"],
            "heads": cfg["swa_num_attention_heads"],
            "kv_heads": cfg["swa_num_key_value_heads"],
            "d_qk": dq, "d_v": cfg["swa_v_head_dim"],
            "rotary_dims": int(dq * cfg["partial_rotary_factor"]),
            "rope_theta": float(cfg["swa_rope_theta"]),
            "value_scale": float(cfg["attention_value_scale"])}


def rope_tables(T: int, rotary_dims: int, theta: float) -> tuple:
    """``(cos, sin)`` float32 ``[T, rotary_dims]``, computed in float64."""
    inv = theta ** (-np.arange(0, rotary_dims, 2, dtype=np.float64)
                    / rotary_dims)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return (jnp.asarray(np.cos(ang).astype(np.float32)),
            jnp.asarray(np.sin(ang).astype(np.float32)))


def rope(x, cos, sin):
    """Rotate-half RoPE on the first ``cos.shape[-1]`` dims of ``x``."""
    r = cos.shape[-1]
    xr = x[:, :r]
    rot = jnp.concatenate([-xr[:, r // 2:], xr[:, :r // 2]], axis=-1)
    return jnp.concatenate([xr * cos + rot * sin, x[:, r:]], axis=-1)


@jax.jit
def _softmax(logits, ones, sink):
    """Masked softmax over the ELL slots with a sink logit: a masked max,
    then the denominator with ``exp(sink - m)``."""
    live = ones > 0
    m = jnp.maximum(jnp.max(jnp.where(live, logits, -jnp.inf), axis=1),
                    sink)
    e = jnp.where(live, jnp.exp(logits - m[:, None]), 0.0)
    return e / (jnp.sum(e, axis=1) + jnp.exp(sink - m))[:, None]


@jax.jit
def _softmax_no_sink(logits, ones):
    live = ones > 0
    m = jnp.max(jnp.where(live, logits, -jnp.inf), axis=1)
    e = jnp.where(live, jnp.exp(logits - m[:, None]), 0.0)
    return e / jnp.sum(e, axis=1)[:, None]


def forward(ell, x, params: dict, d: dict, quant=sparse_ref.identity,
            keep=None, fault=None) -> tuple:
    """``(out [T, hidden], attn [T, H*d_v])``: the sublayer's output and
    the heads' outputs before ``Wo``. ``params``: ``wq [hidden, H*d_qk]``,
    ``wk [hidden, G*d_qk]``, ``wv [hidden, G*d_v]``, ``wo [H*d_v,
    hidden]``, ``sinks [H]``; ``ell`` the window mask's ELL layout (its
    rows the queries); ``keep`` (0/1 a nonzero) leaves nonzeros out;
    ``fault`` plants one of ``no_sink``, ``no_rope`` (``acausal`` is the
    caller's: a transposed mask)."""
    with jax.default_matmul_precision("highest"):
        return _forward(ell, x, params, d, quant, keep, fault)


def _forward(ell, x, params, d, quant, keep, fault):
    T, H, G = x.shape[0], d["heads"], d["kv_heads"]
    dq, dv = d["d_qk"], d["d_v"]
    cos, sin = rope_tables(T, d["rotary_dims"], d["rope_theta"])
    turn = ((lambda y: y) if fault == "no_rope"
            else (lambda y: rope(y, cos, sin)))
    ones = ell.to_ell(jnp.ones(ell.slot_of.shape, jnp.float32)
                      if keep is None else keep)
    kv = []
    for g in range(G):
        k = turn(sparse_ref.project(x, params["wk"][:, g * dq:(g + 1) * dq],
                                    quant))
        v = d["value_scale"] * sparse_ref.project(
            x, params["wv"][:, g * dv:(g + 1) * dv], quant)
        kv.append((k, v))
    heads = []
    for h in range(H):
        k, v = kv[h // (H // G)]
        q = turn(sparse_ref.project(x, params["wq"][:, h * dq:(h + 1) * dq],
                                    quant)) * dq ** -0.5
        logits = ell.sddmm(ones, q, k, quant)
        if fault == "no_sink":
            p = _softmax_no_sink(logits, ones)
        else:
            sink = jnp.full((ell.n_pad,), params["sinks"][h], jnp.float32)
            p = _softmax(logits, ones, sink)
        heads.append(ell.spmm(p, v, quant))
    attn = jnp.concatenate(heads, axis=-1)
    return sparse_ref.project(attn, params["wo"], quant), attn
