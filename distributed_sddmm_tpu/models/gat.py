"""Multi-head graph attention network forward pass.

TPU-native redesign of the reference's ``GAT`` / ``GATLayer``
(`/root/reference/gat.hpp:25-113`): per layer and head,

1. local projection ``A_h = X @ W``  (`gat.hpp:88`)
2. distributed SDDMM at the adjacency pattern -> attention logits
   (`gat.hpp:93`)
3. LeakyReLU on the edge values (`gat.hpp:97`)
4. distributed SpMM aggregation (`gat.hpp:100`)
5. ReLU into the head's output column block (`gat.hpp:103`)

Deviations, by design:

* Weights are randomly initialized (scaled-uniform) instead of the
  reference's all-zeros constants (`gat.hpp:76`), which make a forward pass
  vacuous.
* The aggregation is a fresh ``h = S_att @ A_h``; the reference accumulated
  into the buffer still holding the projected features at c=1 (an
  accidental residual connection, an inconsistency for c>1 —
  `gat.hpp:94,100` with `15D_dense_shift.hpp:346`), which we do not
  reproduce.
* Per-layer R changes (the reference's ``setRValue`` mid-flight,
  `gat.hpp:84`) simply retrace the strategy's cached jitted programs per
  distinct shape.
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

import jax
import jax.numpy as jnp

from distributed_sddmm_tpu.common import KernelMode, MatMode
from distributed_sddmm_tpu.obs import trace as obs_trace
from distributed_sddmm_tpu.obs import watchdog as obs_watchdog
from distributed_sddmm_tpu.parallel.base import DistributedSparse
from distributed_sddmm_tpu.resilience import guards


@dataclasses.dataclass
class GATLayer:
    """Layer spec (reference `gat.hpp:25-40`); weights filled by GAT."""

    input_features: int
    features_per_head: int
    num_heads: int
    weights: list = dataclasses.field(default_factory=list)

    @property
    def output_features(self) -> int:
        return self.features_per_head * self.num_heads


def _supports_programs(d_ops: DistributedSparse) -> bool:
    """True when the strategy exposes the raw sddmm/spmm program
    accessors and needs no pre/post skew — then one whole layer (all
    heads: project -> SDDMM -> LeakyReLU -> SpMM -> ReLU -> concat)
    compiles as ONE program."""
    return (
        hasattr(d_ops, "sddmm_program")
        and hasattr(d_ops, "spmm_program")
        and type(d_ops).initial_shift is DistributedSparse.initial_shift
        and type(d_ops).de_shift is DistributedSparse.de_shift
    )


class GAT:
    """``use_programs``: ``"auto"`` (default) compiles each layer's full
    multi-head computation into one jitted program when the strategy
    supports it (the 1.5D dense-shift strategies); per-op counters then
    show ``gatLayer`` once per layer instead of 4 dispatches per head —
    the same dispatch-elimination treatment the headline bench gets from
    ``fused_program``."""

    def __init__(
        self,
        layers: list[GATLayer],
        d_ops: DistributedSparse,
        leaky_relu_alpha: float = 0.2,
        seed: int = 0,
        use_programs: str | bool = "auto",
    ):
        if d_ops.M != d_ops.N:
            raise ValueError("GAT requires a square adjacency matrix")
        if not layers:
            raise ValueError("need at least one layer")
        for i in range(1, len(layers)):
            if layers[i].input_features != layers[i - 1].output_features:
                raise ValueError(
                    f"layer {i} input_features {layers[i].input_features} != "
                    f"layer {i - 1} output {layers[i - 1].output_features}"
                )
        self.d_ops = d_ops
        self.layers = layers
        self.leaky_relu_alpha = leaky_relu_alpha
        if use_programs == "auto":
            self._use_programs = _supports_programs(d_ops)
        else:
            self._use_programs = bool(use_programs) and _supports_programs(d_ops)
        self._layer_programs: dict = {}

        key = jax.random.key(seed)
        for layer in layers:
            layer.weights = []  # never reuse weights from a prior GAT instance
            for _ in range(layer.num_heads):
                key, sub = jax.random.split(key)
                bound = 1.0 / math.sqrt(layer.input_features)
                layer.weights.append(
                    jax.random.uniform(
                        sub,
                        (layer.input_features, layer.features_per_head),
                        d_ops.dtype,
                        minval=-bound,
                        maxval=bound,
                    )
                )

    def compute_self_attention_head(self, X: jax.Array, i: int, j: int) -> jax.Array:
        """One head: projection -> SDDMM -> LeakyReLU -> SpMM -> ReLU
        (reference ``computeSelfAttentionHead``, `gat.hpp:83-104`)."""
        d = self.d_ops
        layer = self.layers[i]
        alpha = self.leaky_relu_alpha

        d.set_r_value(layer.input_features)
        A = d.dense_project(X, layer.weights[j], MatMode.A)
        # GAT mandates M == N, where every strategy's A and B canonical
        # layouts coincide — the B-role projection is the same array.
        B = A

        ones = d.like_s_values(1.0)
        A_s, B_s = d.initial_shift(A, B, KernelMode.SDDMM_A)
        logits = d.sddmm_a(A_s, B_s, ones)
        att = jnp.maximum(logits, 0) + jnp.minimum(logits, 0) * alpha  # gat.hpp:97

        # SDDMM_A and SPMM_A share a shift-mode group in every strategy, so
        # the already-shifted B_s serves the aggregation too — no second
        # collective.
        h = d.spmm_a(d.like_a_matrix(0.0), B_s, att)
        h, _ = d.de_shift(h, None, KernelMode.SPMM_A)
        return jnp.maximum(h, 0)  # gat.hpp:103

    def _layer_program(self, i: int):
        """``(prog, operands)``: ONE jitted program for layer ``i``,
        ``prog(X, weights, *operands)``, with the tile operands passed as
        arguments (see ``DenseShift15D.fused_program``). It runs every
        head's projection,
        SDDMM logits, LeakyReLU, SpMM aggregation and ReLU, plus the head
        concat — the raw-program composition of
        :meth:`compute_self_attention_head` (same math, one dispatch).

        Square layers (``input_features == output_features``) **donate**
        the carried activation ``X``: the forward loop rebinds it every
        layer and never reads the old buffer again, so XLA reuses it for
        the output instead of allocating. Donation is shape-gated —
        non-square layers would only earn a "donated buffer unusable"
        warning — and follows ``models.als.donation_enabled`` (off under
        the resilience retry rung; ``DSDDMM_DONATE=0``). Models over a
        store-bound strategy also resolve the compiled layer through
        the persistent program store under the strategy's fingerprint
        + config."""
        from distributed_sddmm_tpu.models.als import donation_enabled

        d = self.d_ops
        layer = self.layers[i]
        donate = (
            donation_enabled()
            and layer.input_features == layer.output_features
        )
        key = (i, donate)
        if key in self._layer_programs:
            return self._layer_programs[key]
        alpha = self.leaky_relu_alpha
        mode = MatMode.A

        d.set_r_value(layer.input_features)
        sddmm, struct = d.sddmm_program(mode)
        spmm, _ = d.spmm_program(mode)  # the same tile set's structure
        operands = (d.like_s_values(1.0), struct)

        def head(X, w, ones, struct):
            A = d._skew_cols(
                jnp.einsum("...r,rk->...k", d._unskew_cols(X, mode), w), mode
            )
            logits = sddmm(A, A, ones, *struct)  # A==B: GAT mandates M == N
            att = jnp.maximum(logits, 0) + jnp.minimum(logits, 0) * alpha
            return jnp.maximum(spmm(A, att, *struct), 0)

        def layer_fn(X, weights, ones, struct):
            with jax.named_scope("gatLayer"):
                heads = [head(X, w, ones, struct) for w in weights]
                return d._skew_cols(
                    jnp.concatenate(
                        [d._unskew_cols(h, mode) for h in heads], axis=-1
                    ),
                    mode,
                )

        d.set_r_value(layer.output_features)
        prog = jax.jit(
            layer_fn, out_shardings=d.a_sharding(),
            donate_argnums=(0,) if donate else (),
        )
        from distributed_sddmm_tpu import programs

        # alpha is baked into the traced body as a Python constant —
        # neither avals nor the models code hash see a ctor override.
        prog = programs.chained_program(
            d, f"gatLayer-{i}-a{alpha:g}-{'don' if donate else 'nodon'}",
            prog,
        )
        self._layer_programs[key] = prog, operands
        return prog, operands

    def forward(self, X: jax.Array | None = None) -> jax.Array:
        """Full forward pass (`gat.hpp:106-112`).

        ``X`` is node features in A-layout with R = layers[0].input_features;
        defaults to a deterministic dummy fill. Runs under a
        ``gat:forward`` span.
        """
        with obs_trace.span("gat:forward", layers=len(self.layers)):
            return self._forward(X)

    def _forward(self, X: jax.Array | None) -> jax.Array:
        d = self.d_ops
        if X is None:
            d.set_r_value(self.layers[0].input_features)
            X = d.dummy_initialize(MatMode.A) * (1.0 / (d.M * self.layers[0].input_features))
        elif self._use_programs:
            from distributed_sddmm_tpu.models.als import donation_enabled

            layer0 = self.layers[0]
            if (donation_enabled()
                    and layer0.input_features == layer0.output_features):
                # A donating first layer would consume the CALLER'S
                # buffer; the copy keeps donation an internal detail.
                X = jnp.copy(X)
        guarding = guards.enabled()
        wd = obs_watchdog.active()
        for i, layer in enumerate(self.layers):
            t_layer = time.perf_counter()
            if self._use_programs:
                # The whole-layer program dispatches through _timed, whose
                # resilient path already guards (and repairs) the output —
                # a second per-layer sentinel here would double the
                # reduction + host sync on the hot path. The layer runs
                # one fused SDDMM+SpMM pair per head; _pairs scales the
                # comm/FLOP charge accordingly.
                prog, operands = self._layer_program(i)
                d.set_r_value(layer.output_features)
                X = d._timed(
                    "gatLayer", prog, X, layer.weights, *operands,
                    _pairs=float(layer.num_heads),
                )
            else:
                with obs_trace.span(
                    "gat:layer", layer=i, heads=layer.num_heads,
                ):
                    heads = [
                        self.compute_self_attention_head(X, i, j)
                        for j in range(layer.num_heads)
                    ]
                    X = d.concat_heads(heads, MatMode.A)
                if guarding:
                    # Per-head path: dense_project/concat_heads dispatch
                    # outside _timed, so the layer output needs its own
                    # sentinel — poisoned activations raise (naming the
                    # layer) or nan_to_num-repair per DSDDMM_GUARD_MODE,
                    # never silently feed layer i+1.
                    X = guards.guard_output(f"gat:layer{i}", X)
            if wd is not None:
                # Whole-layer cadence: per-head dispatches are watched
                # individually in _timed, but a layer whose heads each
                # slow a little only crosses the spike bar in aggregate.
                # Keyed per layer index (like the guard sentinel): layer
                # costs are legitimately heterogeneous (width/head-count
                # differ), and one shared EWMA would flag the expensive
                # layer of a healthy network on every forward pass.
                # Strict-mode alarms propagate out of forward() by
                # design: unlike ALS (damped restart, serial oracle),
                # GAT inference has no cheaper rung to degrade to, so
                # the ladder's last rung — a loud typed NumericalFault —
                # is the correct response.
                wd.observe(f"gat:layer{i}", time.perf_counter() - t_layer)
        return X

    def node_embeddings(self, X: jax.Array | None = None) -> np.ndarray:
        """Run the forward pass and return the final-layer embeddings
        (M, output_features) in global row AND column order on the host
        — the serving gather source (``serve/workloads.py::GATNodeScore``
        caches this once per weight refresh). The canonical device
        layout may be column-skewed on the dense-shift strategies; this
        is the one place that unskews it for host consumers."""
        d = self.d_ops
        out = self.forward(X)
        d.set_r_value(self.layers[-1].output_features)
        out = d._unskew_cols(out, MatMode.A)
        return d.host_a(out)

    # ------------------------------------------------------------------ #
    # Parameter checkpoints
    # ------------------------------------------------------------------ #

    def save_checkpoint(self, store, step: int = 0) -> None:
        """Persist every head's projection weights atomically."""
        arrays = {
            f"w_{i}_{j}": np.asarray(w)
            for i, layer in enumerate(self.layers)
            for j, w in enumerate(layer.weights)
        }
        store.save(
            step, arrays,
            meta={"kind": "gat",
                  "heads": [layer.num_heads for layer in self.layers]},
        )

    def load_checkpoint(self, store) -> bool:
        """Restore weights from the newest valid checkpoint; False when
        none exists (or the store belongs to another app/shape)."""
        loaded = store.load_latest()
        if loaded is None:
            return False
        _, arrays, meta = loaded
        if meta and meta.get("kind") not in (None, "gat"):
            return False
        want = {
            f"w_{i}_{j}"
            for i, layer in enumerate(self.layers)
            for j in range(layer.num_heads)
        }
        if not want.issubset(arrays):
            return False
        for i, layer in enumerate(self.layers):
            layer.weights = [
                jnp.asarray(arrays[f"w_{i}_{j}"], dtype=self.d_ops.dtype)
                for j in range(layer.num_heads)
            ]
        return True

    @classmethod
    def from_plan(
        cls, S, layers: list[GATLayer], plan=None, devices=None,
        plan_mode: str = "model", **kw,
    ) -> "GAT":
        """Build GAT on an autotune-selected strategy (R fingerprinted at
        the first layer's input width). The selected plan is kept on
        ``self.plan``; on the dense-shift strategies the plan route lands
        every layer on the one-program-per-layer path automatically."""
        from distributed_sddmm_tpu.autotune import Problem, get_plan

        R = layers[0].input_features
        if plan is None:
            plan = get_plan(
                Problem.from_coo(S, R), devices, S=S, mode=plan_mode
            )
        alg = plan.instantiate(S, R=R, devices=devices)
        model = cls(layers, alg, **kw)
        model.plan = plan
        return model
