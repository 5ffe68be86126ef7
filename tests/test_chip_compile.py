"""The main path compiled for a v5e chip, and chip_smoke.py on the CPU.

The first three tests compile, at chip_smoke.py's real sizes (R-mat
logM=18, nnz/row=32), what its phases run on the chip: the fused pair's
Pallas program (R=128), one ALS CG step (R=128) and one layer of the
reference GAT (256 features per head, 6 heads). The TPU compiler runs here
against a described v5e topology, so what it refuses (VMEM, SMEM, tiling,
device memory) fails here and costs no chip time. The strategy is built on
the host CPU and retargeted at one described device; its tile arrays enter
the compiled programs as arguments, as the models pass them.

The last two run chip_smoke.py's phases at logM=8 on the CPU mesh, and
check that it refuses to report anything without a TPU.
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


def test_fused_pair_compiles_for_v5e(smoke_alg):
    _, alg, cpu = smoke_alg
    assert alg.S_tiles.blk_lr.shape[-2] > 2**17  # several SMEM segments
    prog, operands = alg.fused_program(alg.like_s_values(1.0))
    args = _sds(alg, (cpu["A"], cpu["B"], *operands))
    _check_compiled(prog.lower(*args).compile())


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
