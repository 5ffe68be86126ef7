"""GAT inference (`gat.hpp:25-113`): the program's ``GAT.forward`` over
the benchmark's layer list, weights and node features.

A unit of work is one forward pass of every layer. Per layer and head:
projection ``X @ W``, SDDMM of the projection with itself at the
adjacency pattern, LeakyReLU, SpMM aggregation, ReLU; the heads'
outputs are concatenated. The benchmark makes the weights and the node
features on the device from the seed and sets them into the model.

Correctness: the output of one forward of the window, drawn from the
seed among its first ones, against the plain reference (``gat_ref.py``),
as one relative Frobenius gap.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import faults, program, sparse_ref
from benchmark.apps import gat_ref


def tiny(cfg: dict) -> dict:
    return dict(cfg, input_features=16, features_per_head=16, heads=[2, 2, 3])


def work(cfg: dict, nnz: int, M: int, N: int) -> dict:
    """Per forward: model FLOPs (projections and sparse ops) and each
    sparse op's FLOPs and least bytes (4 bytes an element) and the
    kernel that runs it."""
    ops, kernels, flops = [], [], 0
    for fin, f, heads in gat_ref.layer_dims(cfg):
        flops += heads * 2 * M * fin * f
        sddmm = (2 * nnz * f, 12 * nnz + 4 * (M + N) * f + 4 * nnz)
        spmm = (2 * nnz * f, 12 * nnz + 4 * N * f + 4 * M * f)
        ops += [sddmm, spmm] * heads
        kernels += ["sddmm", "spmm"] * heads
    return {"model_flops": flops + sum(f for f, _ in ops), "sparse_ops": ops,
            "sparse_kernels": kernels}


def _altered():
    """Each forward's output has one element moved."""
    from distributed_sddmm_tpu.models.gat import GAT

    fwd = GAT.forward
    return faults.patched(GAT, "forward",
                          lambda self, X=None: faults.alter(fwd(self, X)))


#: The faults of ``faults.py`` that this app can have, with its patches.
FAULTS = {"half": None, "altered": _altered}


def _inputs(key, cfg, shape_x, sharding):
    """Node features and every head's weights in one jitted call."""
    dims = gat_ref.layer_dims(cfg)

    def make(key):
        kx, kw = jax.random.split(key)
        X = jax.random.uniform(kx, shape_x, jnp.float32, -1.0, 1.0)
        keys = jax.random.split(kw, sum(h for _, _, h in dims))
        W, i = [], 0
        for fin, f, heads in dims:
            b = 1.0 / fin ** 0.5
            W.append([jax.random.uniform(keys[i + h], (fin, f), jnp.float32,
                                         -b, b) for h in range(heads)])
            i += heads
        return X, W

    replicated = jax.sharding.NamedSharding(sharding.mesh,
                                            jax.sharding.PartitionSpec())
    return jax.jit(make, out_shardings=(sharding, replicated))(key)


class Run:
    def __init__(self, cfg: dict, mat: dict, key, devices, check: dict):
        from distributed_sddmm_tpu.common import MatMode
        from distributed_sddmm_tpu.models.gat import GAT, GATLayer

        self.cfg, self.mat = cfg, mat
        dims = gat_ref.layer_dims(cfg)
        alg = program.build_strategy(cfg, mat, dims[0][0], devices)
        self.kernel = program.kernel_line(alg)
        X, W = _inputs(key, cfg, alg.dense_shape(MatMode.A),
                       alg.a_sharding())
        self.X, self.W = X, W
        layers = [GATLayer(fin, f, h) for fin, f, h in dims]
        self.gat = GAT(layers, alg, leaky_relu_alpha=cfg["leaky_relu_alpha"])
        for layer, ws in zip(self.gat.layers, W):
            layer.weights = list(ws)
        self.out = None
        self.unit()
        self.out = None

    def unit(self) -> None:
        self.out = None  # free the last output before the next is made
        self.out = self.gat.forward(self.X)
        jax.block_until_ready(self.out)

    def keep(self) -> None:
        self.kept = self.out

    def release(self) -> None:
        self.kept = self.kept[: self.mat["M"]]
        self.gat = self.out = None

    def outputs(self) -> dict:
        return {"out": (self.kept,)}

    def reference(self, quant, keep=None) -> dict:
        M = self.mat["M"]
        ell = sparse_ref.Ell(self.mat["rows"], self.mat["cols"], M)
        return {"out": (gat_ref.forward(ell, self.X[:M], self.W,
                                        self.cfg["leaky_relu_alpha"],
                                        quant, keep),)}
