"""The main path compiled for a v5e chip, and chip_smoke.py on the CPU.

The first three tests compile, at chip_smoke.py's real sizes (R-mat
logM=18, nnz/row=32), what its phases run on the chip: the fused pair's
Pallas program (R=128), one ALS CG step (R=128) and one layer of the
reference GAT (256 features per head, 6 heads). The TPU compiler runs here
against a described v5e topology, so what it refuses (VMEM, SMEM, tiling,
device memory) fails here and costs no chip time. The strategy is built on
the host CPU and retargeted at one described device; its tile arrays enter
the compiled programs as arguments, as the models pass them.

One KV group of MiMo-V2-Flash's sliding-window attention layer
compiles at its published widths over 2,048 tokens, its attention
epilogue in several SMEM segments.

Then each strategy's SDDMM and SpMM rings compile on four described
chips at a small size, and each kernel's launch compiles alone. The last
two run chip_smoke.py's phases at logM=8 on the CPU mesh, and check that
it refuses to report anything without a TPU.
"""

import json

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import chip_smoke
from distributed_sddmm_tpu.common import MatMode
from distributed_sddmm_tpu.ops.pallas_kernels import PallasKernel
from distributed_sddmm_tpu.parallel.mesh import make_grid

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def smoke_alg(topo):
    """(S, alg, cpu): the smoke's 1.5D dense-shift strategy at full size
    with the bf16 Mosaic kernel, retargeted at one v5e device; ``cpu``
    holds host-built dense operands whose shapes and specs the compiles
    take. Tile operands stay the host-built arrays and are described the
    same way."""
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    kernel = PallasKernel(precision="bf16", interpret=False)
    S, alg = chip_smoke.build(chip_smoke.LOG_M, kernel, jax.devices()[:1])
    cpu = {
        "A": alg.dummy_initialize(MatMode.A),
        "B": alg.dummy_initialize(MatMode.B),
    }
    g = alg.grid
    alg.grid = make_grid(g.nr, g.nc, g.nh, adjacency=g.adjacency,
                         devices=[topo.devices[0]])
    alg._programs.clear()
    yield S, alg, cpu
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(alg, x, spec=None):
    """``x``'s shape and dtype with its partition spec on the described
    devices (pytrees of arrays map leaf by leaf)."""
    def one(x):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(
                alg.grid.mesh, x.sharding.spec if spec is None else spec))

    return jax.tree.map(one, x)


def _check_compiled(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert need < V5E_HBM_BYTES, need


def test_fused_pair_compiles_for_v5e(smoke_alg, monkeypatch):
    """The pair at the geometry the density rule sizes for it, its chunk
    list cut into several SMEM segments as a longer list would be."""
    from distributed_sddmm_tpu.ops import pallas_kernels

    _, alg, cpu = smoke_alg
    C = alg.S_tiles.blk_lr.shape[-2]
    assert alg.S_tiles.blk_geom[:2] != (512, 512)
    monkeypatch.setattr(pallas_kernels, "MAX_PREFETCH_CHUNKS", C // 3)
    pallas_kernels._tile_call.clear_cache()
    alg._programs.clear()
    try:
        prog, operands = alg.fused_program(alg.like_s_values(1.0))
        args = _sds(alg, (cpu["A"], cpu["B"], *operands))
        _check_compiled(prog.lower(*args).compile())
    finally:
        pallas_kernels._tile_call.clear_cache()


def test_als_cg_step_compiles_for_v5e(smoke_alg):
    from distributed_sddmm_tpu.models.als import DistributedALS, _batch_dot

    S, alg, cpu = smoke_alg
    als = DistributedALS(alg, artificial_groundtruth=False,
                         ground_truth_vals=np.zeros(S.nnz, np.float32))
    prog, operands = als._cg_iter_program(MatMode.A, als.ridge_lambda)
    A, B = cpu["A"], cpu["B"]
    args = _sds(alg, (A, B, A, A, _batch_dot(A, A), *operands))
    _check_compiled(prog.lower(*args).compile())


def test_gat_layer_compiles_for_v5e(smoke_alg):
    from distributed_sddmm_tpu.bench.harness import _gat_layers
    from distributed_sddmm_tpu.models.gat import GAT

    _, alg, _ = smoke_alg
    gat = GAT(_gat_layers(chip_smoke.GAT_FEATURES_PER_HEAD), alg)
    i = len(gat.layers) - 1
    layer = gat.layers[i]
    assert (layer.features_per_head, layer.num_heads) == (256, 6)
    prog, operands = gat._layer_program(i)
    alg.set_r_value(layer.input_features)
    X = jax.ShapeDtypeStruct(alg.dense_shape(MatMode.A), alg.dtype,
                             sharding=alg.a_sharding())
    args = (X, _sds(alg, layer.weights, P()), *_sds(alg, operands))
    _check_compiled(prog.lower(*args).compile())


def test_swa_layer_compiles_for_v5e(topo, monkeypatch):
    """One KV group of MiMo-V2-Flash's window layer at its published
    widths (hidden 4096, 8 query heads, q/k 192, v 128, window 128) over
    2,048 tokens, the attention epilogue's chunk list cut into several
    SMEM segments as the 32,768-token prefill's is: the whole layer,
    projections, RoPE, the fused attention with V and sinks, and Wo, is
    one program of Mosaic kernels that fits a v5e."""
    import re

    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from distributed_sddmm_tpu import masks
    from distributed_sddmm_tpu.models.swa import SlidingWindowAttention
    from distributed_sddmm_tpu.ops import pallas_kernels

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    T = 2048
    model = SlidingWindowAttention(
        masks.causal_window(T, 128), 8, 4096, 192, 128, 64, 10000.0, 0.707,
        kernel=PallasKernel(precision="bf16", interpret=False),
        devices=jax.devices()[:1])
    alg = model.d_ops
    C = alg.S_tiles.blk_lr.shape[-2]
    monkeypatch.setattr(pallas_kernels, "MAX_PREFETCH_CHUNKS", C // 3)
    g = alg.grid
    alg.grid = make_grid(g.nr, g.nc, g.nh, adjacency=g.adjacency,
                         devices=[topo.devices[0]])
    alg._programs.clear()
    pallas_kernels._attn_call.clear_cache()
    pallas_kernels._tile_call.clear_cache()
    try:
        assert pallas_kernels.launches(C, alg.S_tiles.blk_geom[4]) > 2
        prog, operands = model._layer_program()
        x = jax.ShapeDtypeStruct((T, 4096), jnp.float32,
                                 sharding=NamedSharding(alg.grid.mesh, P()))
        args = (x, _sds(alg, model.params, P()), _sds(alg, model.cos, P()),
                _sds(alg, model.sin, P()), *_sds(alg, operands))
        compiled = prog.lower(*args).compile()
    finally:
        pallas_kernels._attn_call.clear_cache()
        pallas_kernels._tile_call.clear_cache()
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
    _check_compiled(compiled)
    calls = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", compiled.as_text())
    kinds = [c.split(".")[0] for c in calls]
    for op in ("sddmm", "spmm", "attn_reduce", "attn_norm"):
        assert kinds.count(pallas_kernels.kernel_name(op)) >= (
            3 if op.startswith("attn") else 1), kinds


@pytest.fixture(scope="module")
def one_chip_mesh(topo):
    """A one-device mesh on the described v5e, JAX's persistent cache off
    around its compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield Mesh([topo.devices[0]], ("x",))
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "op", ["fused", "sddmm", "spmm", "attn_reduce", "attn_norm"])
def test_compiled_launch_is_named_by_kind(one_chip_mesh, op):
    """Each kind's launch, compiled for a described v5e at a small size,
    is a custom call named by its kernel (what a device trace's ``XLA
    Ops`` line shows), with ``tpu_custom_call`` as its target."""
    import re

    import jax.numpy as jnp

    from distributed_sddmm_tpu.ops import pallas_kernels as pk

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(one_chip_mesh, P()))

    C, bm, G = 16, 512, 4
    meta, lr = sds((C,), jnp.int32), sds((C, pk.CHUNK), jnp.int32)
    sv = sds((C, pk.CHUNK), jnp.float32)
    if op.startswith("attn"):
        md = sds((4 * bm, 1), jnp.float32)
        stats = op == "attn_norm"
        fn = jax.jit(lambda meta, lr, gv, zv, m, d: pk._attn_call(
            meta, lr, gv, zv, m if stats else None, d if stats else None,
            op=op, bm=bm, gr_blocks=4, group=G, interpret=False))
        args = (meta, lr, sv, sv, md, md)
    else:
        dense = sds((128, 4 * bm), jnp.bfloat16)
        geom = (bm, bm, 4, 4, G, False, "bt", False, False)
        fn = jax.jit(lambda meta, lr, lc, sv, at, bt: pk._geom_call(
            geom, op, meta, lr, lc, sv, at, bt))
        args = (meta, lr, lr, sv, dense, dense)
    text = fn.lower(*args).compile().as_text()
    calls = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert calls and all(c.split(".")[0] == pk.kernel_name(op)
                         for c in calls), calls


@pytest.mark.parametrize("bm,bn,R", [
    (1024, 1024, 128), (1024, 2048, 128), (512, 1024, 256),
    (4096, 1024, 128),
])
def test_batched_launch_fits_its_count(one_chip_mesh, monkeypatch, bm, bn,
                                       R):
    """The step-batched fused launch compiles with its scoped VMEM limit
    set to ``tile_vmem_bytes``, at the cells' geometries and at one whose
    per-chunk count stays under Mosaic's 16 MiB default while the batched
    body needs more."""
    import jax.numpy as jnp

    from distributed_sddmm_tpu.ops import blocked
    from distributed_sddmm_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(blocked, "DEFAULT_SCOPED_VMEM", 0)
    monkeypatch.setattr(blocked, "TILE_VMEM_LIMIT", 0)
    pk._tile_call.clear_cache()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(one_chip_mesh, P()))

    C, G = 16, 4
    lr, sv = sds((C, pk.CHUNK), jnp.int32), sds((C, pk.CHUNK), jnp.float32)
    geom = (bm, bn, 2, 2, G, False, "bt", True, False)
    fn = jax.jit(lambda meta, lr, lc, sv, at, bt: pk._geom_call(
        geom, "fused", meta, lr, lc, sv, at, bt))
    try:
        fn.lower(sds((C,), jnp.int32), lr, lr, sv,
                 sds((R, 2 * bm), jnp.bfloat16),
                 sds((R, 2 * bn), jnp.bfloat16)).compile()
    finally:
        pk._tile_call.clear_cache()


@pytest.fixture(scope="module")
def four_chips(topo):
    """The described v5e's four devices, JAX's persistent cache off
    around their compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)[:4]
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name,c", [
    ("15d_fusion1", 2),
    ("15d_sparse", 1),
    ("25d_dense_replicate", 1),
    ("25d_sparse_replicate", 1),
])
def test_strategy_ring_compiles_for_v5e(four_chips, name, c):
    """Each strategy's SDDMM and SpMM ring programs, on the blocked
    bf16 Mosaic kernel and four described v5e chips at a small size:
    every launch in the compiled program is named by its kernel. The
    CPU suite reaches these rings' blocked form in interpret mode only."""
    import re

    from distributed_sddmm_tpu.bench.harness import make_algorithm
    from distributed_sddmm_tpu.ops.pallas_kernels import kernel_name
    from distributed_sddmm_tpu.utils.coo import HostCOO

    S = HostCOO.erdos_renyi(512, 512, 8, seed=5, values="normal")
    alg = make_algorithm(
        name, S, 512, c, devices=jax.devices()[:4],
        kernel=PallasKernel(precision="bf16", interpret=False),
    )
    assert alg._use_blocked(alg.S_tiles) and alg._use_blocked(alg.ST_tiles)
    A = alg.dummy_initialize(MatMode.A)
    B = alg.dummy_initialize(MatMode.B)
    vals = alg.like_s_values(1.0)
    g = alg.grid
    alg.grid = make_grid(g.nr, g.nc, g.nh, adjacency=g.adjacency,
                         devices=four_chips)
    alg._programs.clear()
    # The public ops hand their program and operands to _timed; keep them
    # instead of running.
    alg._timed = lambda op_name, prog, *args, **kw: (prog, args)
    for op, call in (("sddmm", alg.sddmm_a), ("spmm", alg.spmm_a)):
        prog, args = call(A, B, vals)
        text = prog.lower(*_sds(alg, args)).compile().as_text()
        calls = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                           r"\"tpu_custom_call\"", text)
        assert calls and all(k.split(".")[0] == kernel_name(op)
                             for k in calls), (op, calls)


def test_smoke_phases_on_cpu_mesh(tmp_path, monkeypatch):
    """Every phase of chip_smoke.py, its checks included, at logM=8 with
    the Pallas kernel in interpret mode (f32, so the oracle tolerance is
    the f32 one)."""
    monkeypatch.setenv("DSDDMM_PLAN_CACHE", str(tmp_path / "plans"))
    kernel = PallasKernel(precision="f32", interpret=True)
    S, alg = chip_smoke.build(8, kernel, jax.devices()[:1])
    fused = chip_smoke.phase_fused(S, alg, rtol=1e-4)
    assert fused["blocked"] and fused["interpret"] is True
    als = chip_smoke.phase_als(S, alg, cg_iters=3)
    assert als["residuals"][1] < als["residuals"][0]
    gat = chip_smoke.phase_gat(alg)
    assert gat["shape"][-1] == 256 * 6
    serve = chip_smoke.phase_serve(8, str(tmp_path), duration_s=2.0,
                                   rate_hz=12.0)
    assert serve["oracle_failures"] == 0 and serve["completed"] >= 16
    multi = chip_smoke.phase_multichip(S, jax.devices()[:4], kernel,
                                       rtol=1e-4)
    assert len(multi["configs"]) == 5
    for cfg in multi["configs"].values():
        assert all(s["devices"] == 4 for s in cfg["spread"].values())


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert json.loads(out.splitlines()[0])["device"]["platform"] == "cpu"
