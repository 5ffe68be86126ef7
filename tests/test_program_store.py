"""The persistent AOT program store (programs/store.py): round-trip,
corrupt/stale-entry eviction (the plan cache's corruption discipline,
applied to serialized executables), backend gating, the StoredProgram
wrapper, strategy binding, and the serve engine's disk-warmed cold start."""

import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sddmm_tpu import programs
from distributed_sddmm_tpu.common import MatMode
from distributed_sddmm_tpu.programs import store as store_mod
from distributed_sddmm_tpu.utils.coo import HostCOO


def _jit():
    return jax.jit(lambda x: x * 2.0 + 1.0)


def _compiled(x):
    return _jit().lower(x).compile()


X = None


def _x():
    global X
    if X is None:
        X = jnp.ones((4, 4), jnp.float32)
    return X


def test_save_load_roundtrip(tmp_path):
    store = programs.ProgramStore(tmp_path)
    assert store.save("plan:fp:op:sig:cpu:c", _compiled(_x()))
    prog = store.load("plan:fp:op:sig:cpu:c")
    assert prog is not None
    assert float(np.asarray(prog(_x())).sum()) == 48.0
    assert store.stats()["hits"] == 1
    rows = store.index()
    assert [r["key"] for r in rows] == ["plan:fp:op:sig:cpu:c"]


def test_absent_key_is_miss_without_droppings(tmp_path):
    store = programs.ProgramStore(tmp_path)
    assert store.load("plan:none:op:sig:cpu:c") is None
    assert store.stats() == {"hits": 0, "misses": 1, "live_compiles": 0}


def test_truncated_entry_evicts_and_recompiles(tmp_path):
    store = programs.ProgramStore(tmp_path)
    key = "plan:fp:op:sig:cpu:c"
    store.save(key, _compiled(_x()))
    path = store._path(key)
    path.write_bytes(path.read_bytes()[: path.stat().st_size // 2])
    assert store.load(key) is None
    assert not path.exists()  # evicted, not left to fail forever
    # ...and the slot heals: get_or_compile lands a fresh entry.
    prog, src = store.get_or_compile(key, lambda: _compiled(_x()))
    assert src == "live"
    assert store.load(key) is not None


def test_schema_version_bump_evicts(tmp_path, monkeypatch):
    store = programs.ProgramStore(tmp_path)
    key = "plan:fp:op:sig:cpu:c"
    store.save(key, _compiled(_x()))
    monkeypatch.setattr(store_mod, "SCHEMA_VERSION",
                        store_mod.SCHEMA_VERSION + 1)
    assert store.load(key) is None
    assert not store._path(key).exists()


def test_renamed_entry_not_served_under_foreign_key(tmp_path):
    """A copied/renamed entry must not answer for a different key — the
    stored record pins its own (wrong-code_hash case: the code hash is a
    key segment, so a stale generation's entry IS a foreign key)."""
    store = programs.ProgramStore(tmp_path)
    old = "plan:fp:op:sig:cpu:oldcode"
    new = "plan:fp:op:sig:cpu:newcode"
    store.save(old, _compiled(_x()))
    store._path(new).write_bytes(store._path(old).read_bytes())
    assert store.load(new) is None
    assert not store._path(new).exists()  # foreign entry evicted
    assert store.load(old) is not None  # the original is untouched


def test_wrong_backend_is_miss_without_eviction(tmp_path):
    store = programs.ProgramStore(tmp_path)
    key = "plan:fp:op:sig:tpu:c"
    store.save(key, _compiled(_x()), backend="tpu")
    assert store.load(key) is None  # live backend is cpu
    assert store._path(key).exists()  # another platform's entry survives
    # ...and the caller falls through to a live compile.
    prog, src = store.get_or_compile(key, lambda: _compiled(_x()))
    assert src == "live"
    assert float(np.asarray(prog(_x())).sum()) == 48.0


def test_garbled_payload_evicts_on_deserialize_failure(tmp_path):
    store = programs.ProgramStore(tmp_path)
    key = "plan:fp:op:sig:cpu:c"
    store.save(key, _compiled(_x()))
    entry = pickle.loads(store._path(key).read_bytes())
    ser, in_tree, out_tree = entry["payload"]
    entry["payload"] = (b"\x00garbage", in_tree, out_tree)
    store._path(key).write_bytes(pickle.dumps(entry))
    assert store.load(key) is None
    assert not store._path(key).exists()


def test_corrupt_index_is_rebuilt_from_entries(tmp_path):
    store = programs.ProgramStore(tmp_path)
    store.save("plan:fp:a:s:cpu:c", _compiled(_x()))
    store.save("plan:fp:b:s:cpu:c", _compiled(_x()))
    store.index_path.write_text("{not json")
    rows = store.index()
    assert sorted(r["key"] for r in rows) == [
        "plan:fp:a:s:cpu:c", "plan:fp:b:s:cpu:c",
    ]


def test_get_or_compile_counts_disk_vs_live(tmp_path):
    store = programs.ProgramStore(tmp_path)
    key = "plan:fp:op:sig:cpu:c"
    _p, src = store.get_or_compile(key, lambda: _compiled(_x()))
    assert src == "live"
    _p, src = store.get_or_compile(key, lambda: _compiled(_x()))
    assert src == "disk"
    assert store.stats() == {"hits": 1, "misses": 1, "live_compiles": 1}


# --------------------------------------------------------------------- #
# StoredProgram wrapper
# --------------------------------------------------------------------- #


def test_stored_program_resolves_once_per_signature(tmp_path):
    store = programs.ProgramStore(tmp_path)
    sp = programs.StoredProgram(
        _jit(), lambda sig: f"plan:fp:op:{sig}:cpu:c", store
    )
    out = sp(_x())
    assert float(np.asarray(out).sum()) == 48.0
    for _ in range(3):
        sp(_x())
    assert store.stats()["live_compiles"] == 1
    # A second wrapper (fresh process analog) hits disk.
    sp2 = programs.StoredProgram(
        _jit(), lambda sig: f"plan:fp:op:{sig}:cpu:c", store
    )
    out2 = sp2(_x())
    assert np.array_equal(np.asarray(out), np.asarray(out2))
    assert store.stats()["hits"] == 1


def test_stored_program_inlines_under_trace(tmp_path):
    """Inside an outer jit the wrapper must step aside (tracers have no
    buffers) — the cgStep/gatLayer chains compose strategy programs this
    way."""
    store = programs.ProgramStore(tmp_path)
    sp = programs.StoredProgram(
        _jit(), lambda sig: f"plan:fp:op:{sig}:cpu:c", store
    )

    @jax.jit
    def outer(x):
        return sp(x) + 1.0

    assert float(np.asarray(outer(_x())).sum()) == 64.0
    assert store.stats()["live_compiles"] == 0  # never resolved via store


def test_stored_falls_back_to_plain_jit_without_store():
    fn = _jit()
    assert programs.stored(fn, lambda sig: "k", store=None) is fn


# --------------------------------------------------------------------- #
# Strategy binding (Plan.instantiate's integration)
# --------------------------------------------------------------------- #


def _plan(S, tmp_path):
    from distributed_sddmm_tpu.autotune import Problem, get_plan
    from distributed_sddmm_tpu.autotune.cache import PlanCache

    return get_plan(Problem.from_coo(S, 8), mode="model",
                    cache=PlanCache(tmp_path / "plans"))


def test_plan_instantiate_binds_store_and_warm_starts(tmp_path):
    S = HostCOO.erdos_renyi(64, 48, 5, seed=1, values="normal")
    store = programs.ProgramStore(tmp_path / "programs")
    plan = _plan(S, tmp_path)

    alg = plan.instantiate(S, R=8, program_store=store)
    A = alg.dummy_initialize(MatMode.A)
    B = alg.dummy_initialize(MatMode.B)
    ones = alg.like_s_values(1.0)
    out1 = np.asarray(alg.fused_spmm(A, B, ones, MatMode.A)[0])
    assert store.stats()["live_compiles"] >= 1

    live_before = store.stats()["live_compiles"]
    alg2 = plan.instantiate(S, R=8, program_store=store)
    out2 = np.asarray(alg2.fused_spmm(A, B, ones, MatMode.A)[0])
    assert store.stats()["live_compiles"] == live_before  # all from disk
    assert store.stats()["hits"] >= 1
    assert np.array_equal(out1, out2)


def test_chained_keys_invalidate_on_models_code_generation(tmp_path,
                                                           monkeypatch):
    """The cgStep/gatLayer chains bake models/ math into the executable;
    their store keys must change when the models/ sources do (the plan
    fingerprint's code_hash deliberately covers only ops/ + parallel/)."""
    from distributed_sddmm_tpu.autotune import fingerprint as fp

    S = HostCOO.erdos_renyi(48, 32, 4, seed=1, values="normal")
    store = programs.ProgramStore(tmp_path)
    plan = _plan(S, tmp_path)
    alg = plan.instantiate(S, R=8, program_store=store)

    jit_fn = lambda x: x  # noqa: E731 — key inspection only
    key_before = programs.chained_program(
        alg, "cgStep-A-1e-06-don", jit_fn
    )._key_fn("sig0")
    monkeypatch.setattr(fp, "models_code_hash", lambda: "ffffffffffff")
    key_after = programs.chained_program(
        alg, "cgStep-A-1e-06-don", jit_fn
    )._key_fn("sig0")
    assert key_before != key_after
    assert "ffffffffffff" in key_after


def test_chained_keys_share_matrix_content_separate_ring_build(tmp_path):
    """Two same-shape matrices (identical coarse fingerprint) share one
    chained-program key — the tile operands are arguments — while the
    two ring builds (overlap/sequential), whose structure avals cannot
    see, get distinct keys."""
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D

    S1 = HostCOO.erdos_renyi(48, 32, 4, seed=1, values="normal")
    S2 = HostCOO.erdos_renyi(48, 32, 4, seed=9, values="normal")
    assert (S1.M, S1.N) == (S2.M, S2.N)
    store = programs.ProgramStore(tmp_path)
    jit_fn = lambda x: x  # noqa: E731 — key inspection only

    def key_for(S, overlap):
        alg = DenseShift15D(S, R=8, c=1, fusion_approach=2, overlap=overlap)
        programs.bind_strategy(alg, "samefingerprint", store=store)
        return programs.chained_program(alg, "cgStep", jit_fn)._key_fn("s")

    assert key_for(S1, False) == key_for(S2, False)  # content
    assert key_for(S1, False) != key_for(S1, True)   # ring build


def test_chained_program_passes_over_unbound_strategy(tmp_path):
    """An unbound strategy's chain is the plain jit, byte for byte."""
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D

    S = HostCOO.erdos_renyi(48, 32, 4, seed=1, values="normal")
    alg = DenseShift15D(S, R=8, c=1, fusion_approach=2)
    jit_fn = lambda x: x  # noqa: E731
    assert programs.chained_program(alg, "cgStep", jit_fn) is jit_fn


def test_inject_program_reaches_dispatch_under_fusion_keys():
    """inject_program must install under the SAME cache key _program
    looks up — including the PR 6 fusion segment — or injected offline
    executables are silently unreachable (jit fallback)."""
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D

    S = HostCOO.erdos_renyi(48, 32, 4, seed=1, values="normal")
    for overlap in (False, True):
        alg = DenseShift15D(S, R=8, c=1, fusion_approach=2, overlap=overlap)
        sentinel_calls = []
        real = alg._program("sddmm", use_st=False)

        def loaded(*args, _real=real):
            sentinel_calls.append(1)
            return _real(*args)

        alg.inject_program("sddmm", False, loaded)
        A = alg.dummy_initialize(MatMode.A)
        B = alg.dummy_initialize(MatMode.B)
        alg.sddmm_a(A, B, alg.like_s_values(1.0))
        assert sentinel_calls, f"injected program unreachable (overlap={overlap})"


def test_unbound_strategy_untouched_by_store(tmp_path):
    """Without a binder the strategies run exactly the pre-PR 6 path —
    plain jits, nothing written anywhere."""
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D

    S = HostCOO.erdos_renyi(48, 32, 4, seed=1, values="normal")
    alg = DenseShift15D(S, R=8, c=1, fusion_approach=2)
    assert alg._program_binder is None
    A = alg.dummy_initialize(MatMode.A)
    B = alg.dummy_initialize(MatMode.B)
    alg.fused_spmm(A, B, alg.like_s_values(1.0), MatMode.A)
    assert not (tmp_path / "entries").exists()


# --------------------------------------------------------------------- #
# Serve engine: warmed cold start performs zero live compiles
# --------------------------------------------------------------------- #


def _engine(store):
    from distributed_sddmm_tpu.models.als import DistributedALS
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
    from distributed_sddmm_tpu.serve import ALSFoldInTopK, ServingEngine

    S = HostCOO.erdos_renyi(48, 32, 5, seed=2, values="normal")
    alg = DenseShift15D(S, R=8, c=1, fusion_approach=2)
    model = DistributedALS(alg, S_host=S)
    model.initialize_embeddings()
    workload = ALSFoldInTopK(model, k=3, item_buckets=(4, 8))
    return workload, ServingEngine(
        workload, max_batch=2, max_depth=8, max_wait_ms=2.0,
        program_store=store,
    )


def test_serve_cold_start_warms_from_disk(tmp_path):
    store = programs.ProgramStore(tmp_path)
    workload, e1 = _engine(store)
    warmed = e1.warmup()
    s1 = e1.stats()
    assert s1["live_compiles"] == warmed and s1["disk_hits"] == 0

    _, e2 = _engine(store)
    e2.warmup()
    s2 = e2.stats()
    assert s2["live_compiles"] == 0, "warmed cold start must not compile"
    assert s2["disk_hits"] == warmed

    rng = np.random.default_rng(0)
    payloads = [workload.sample_payload(rng) for _ in range(2)]
    r1 = e1.execute_now(payloads)
    r2 = e2.execute_now(payloads)
    for a, b in zip(r1, r2):
        assert np.array_equal(a["items"], b["items"])
        assert np.array_equal(a["scores"], b["scores"])


def test_serve_stats_expose_compile_attribution():
    _, engine = _engine(None)  # no store: builds count as live compiles
    warmed = engine.warmup()
    stats = engine.stats()
    assert stats["live_compiles"] == warmed
    assert stats["disk_hits"] == 0


# --------------------------------------------------------------------- #
# Runstore column: the cold-start compile count is indexed
# --------------------------------------------------------------------- #


def test_runstore_index_carries_live_compiles(tmp_path):
    from distributed_sddmm_tpu.obs.store import RunStore, build_run_doc

    rs = RunStore(tmp_path / "runstore")
    rec = {
        "run_id": "r-offline", "algorithm": "15d_fusion2", "app": "vanilla",
        "R": 8, "c": 1, "fused": True, "elapsed": 1.0,
        "overall_throughput": 1.0, "alg_info": {"m": 64, "n": 64,
                                                "nnz": 256, "p": 8},
        "program_store": {"program_store_hits": 2,
                          "program_store_misses": 1, "live_compiles": 1},
    }
    rs.ingest_prebuilt(build_run_doc(rec))
    rec2 = dict(rec, run_id="r-serve", program_store=None)
    rec2.pop("program_store")
    rec2["engine"] = {"live_compiles": 0, "disk_hits": 6}
    rs.ingest_prebuilt(build_run_doc(rec2))
    rows = {r["run_id"]: r for r in rs.index()}
    assert rows["r-offline"]["live_compiles"] == 1
    assert rows["r-serve"]["live_compiles"] == 0
