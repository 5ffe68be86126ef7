"""One KV group of MiMo-V2-Flash's sliding-window attention sublayer over
one prefill, through the program's ``models.swa.SlidingWindowAttention``
(built by ``make_algorithm`` with ``get_kernel("auto")`` over the group's
stacked window mask).

A unit of work is one forward over the ``T``-token sequence: the four
projections, partial RoPE, the fused attention (SDDMM, the masked
softmax with a sink a head, SpMM over V) and ``Wo``, one compiled
program. The benchmark makes the hidden states ``x ~ N(0, 1)`` (as after
the block's norm), the weights uniform(±1/sqrt(in)) and the sinks
uniform in [2, 6] on the device from the seed.

Correctness: one forward of the window, drawn from the seed among its
first ones, against the plain reference (``swa_ref.py``): the layer's
output ``out`` ``[T, hidden]`` and the heads' outputs before ``Wo``,
``attn`` ``[T, H*d_v]``, each as a relative Frobenius gap and a widest
gap of one answer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import faults, program, sparse_ref
from benchmark.apps import swa_ref

#: The sinks' range: at these logit scales the sink holds a real share
#: of a row's mass, so leaving it out reads far above the limits.
SINK_RANGE = (2.0, 6.0)


def tiny(cfg: dict) -> dict:
    return dict(cfg, hidden_size=64, swa_num_attention_heads=2,
                swa_head_dim=24, swa_v_head_dim=16)


def work(cfg: dict, nnz: int, M: int, N: int) -> dict:
    """Per forward over ``M = T`` tokens with the ``T x T`` mask's ``nnz``:
    model FLOPs (the four projections and ``2 nnz_s (d_qk + d_v)`` of
    attention, ``nnz_s`` the stacked mask's nonzeros, ``H`` times the
    mask's) and each sparse op's FLOPs and least bytes and its kernel.
    Least bytes: the mask's rows, cols and values (12 bytes a nonzero),
    each operand read once and each output written once, 4 bytes an
    element; the logits are an operand of both epilogue kernels, the
    merged row stats (max and denominator a row) an output of
    ``attn_reduce`` and an input of ``attn_norm``. The epilogue's FLOPs
    are 3 a nonzero (a max or difference, an exponential, a sum or a
    division) and count in no model FLOPs."""
    d = swa_ref.dims(cfg)
    H, G, hid, dq, dv = (d["heads"], d["kv_heads"], d["hidden"], d["d_qk"],
                         d["d_v"])
    T, nnz_s, rows = M, H * nnz, H * M
    proj = 2 * T * hid * (H * dq + G * dq + G * dv) + 2 * T * H * dv * hid
    ops = [
        (2 * nnz_s * dq, 12 * nnz_s + 4 * (rows + G * N) * dq + 4 * nnz_s),
        (3 * nnz_s, 12 * nnz_s + 4 * nnz_s + 2 * 4 * rows),
        (3 * nnz_s, 12 * nnz_s + 4 * nnz_s + 2 * 4 * rows + 4 * nnz_s),
        (2 * nnz_s * dv, 12 * nnz_s + 4 * G * N * dv + 4 * rows * dv),
    ]
    return {"model_flops": proj + 2 * nnz_s * (dq + dv), "sparse_ops": ops,
            "sparse_kernels": ["sddmm", "attn_reduce", "attn_norm", "spmm"]}


def _altered():
    """Each forward's output has one element moved."""
    from distributed_sddmm_tpu.models.swa import SlidingWindowAttention

    fwd = SlidingWindowAttention.forward

    def altered(self, x, return_attention=False):
        out = fwd(self, x, return_attention)
        if return_attention:
            return faults.alter(out[0]), out[1]
        return faults.alter(out)
    return faults.patched(SlidingWindowAttention, "forward", altered)


#: The faults of ``faults.py`` that this app can have, with its patches.
FAULTS = {"half": None, "altered": _altered}
#: Departures from the layer's mathematics that ``reference(...,
#: fault=<name>)`` plants in the plain reference (``swa_ref.py``).
REFERENCE_FAULTS = ("no_sink", "acausal", "no_rope")


def _inputs(key, T: int, d: dict) -> tuple:
    """Hidden states and the group's weights in one jitted call."""
    H, G, hid, dq, dv = (d["heads"], d["kv_heads"], d["hidden"], d["d_qk"],
                         d["d_v"])
    shapes = {"wq": (hid, H * dq), "wk": (hid, G * dq), "wv": (hid, G * dv),
              "wo": (H * dv, hid)}

    def make(key):
        kx, ks, *kw = jax.random.split(key, 2 + len(shapes))
        x = jax.random.normal(kx, (T, hid), jnp.float32)
        p = {n: jax.random.uniform(k, s, jnp.float32, -s[0] ** -0.5,
                                   s[0] ** -0.5)
             for k, (n, s) in zip(kw, shapes.items())}
        p["sinks"] = jax.random.uniform(ks, (H,), jnp.float32, *SINK_RANGE)
        return x, p

    return jax.jit(make)(key)


class Run:
    def __init__(self, cfg: dict, mat: dict, key, devices, check: dict):
        from distributed_sddmm_tpu.models.swa import SlidingWindowAttention
        from distributed_sddmm_tpu.ops.kernels import get_kernel
        from distributed_sddmm_tpu.utils.coo import HostCOO

        self.cfg, self.mat = cfg, mat
        self.d = d = swa_ref.dims(cfg)
        if d["kv_heads"] != 1:
            raise ValueError("one KV group a chip: swa_num_key_value_heads 1")
        S = HostCOO(mat["rows"], mat["cols"], mat["vals"], mat["M"], mat["N"])
        self.model = SlidingWindowAttention(
            S, d["heads"], d["hidden"], d["d_qk"], d["d_v"], d["rotary_dims"],
            d["rope_theta"], d["value_scale"], algorithm=cfg["algorithm"],
            c=cfg["c"], kernel=get_kernel("auto"), devices=devices)
        self.kernel = program.kernel_line(self.model.d_ops)
        self.x, self.params = _inputs(key, mat["M"], d)
        self.model.params = self.params
        self.out = None
        self.unit()
        self.out = None

    def unit(self) -> None:
        self.out = None  # free the last output before the next is made
        self.out = self.model.forward(self.x, return_attention=True)
        jax.block_until_ready(self.out)

    def keep(self) -> None:
        self.kept = self.out

    def release(self) -> None:
        self.model = self.out = None

    def outputs(self) -> dict:
        out, attn = self.kept
        return {"out": (out,), "attn": (attn,)}

    def reference(self, quant, keep=None, fault=None) -> dict:
        if fault not in (None, *REFERENCE_FAULTS):
            raise ValueError(f"unknown reference fault {fault!r}")
        rows, cols = self.mat["rows"], self.mat["cols"]
        if fault == "acausal":
            rows, cols = cols, rows
        ell = sparse_ref.Ell(rows, cols, self.mat["M"])
        out, attn = swa_ref.forward(ell, self.x, self.params, self.d, quant,
                                    keep, fault)
        return {"out": (out,), "attn": (attn,)}
