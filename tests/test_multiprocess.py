"""Two-process jax.distributed pod test (the multi-host path, for real).

The reference's multi-node story is ``mpirun -n p`` oversubscribed on one
host (SURVEY.md section 4); ours is the same idea with the actual multi-host
machinery: two OS processes Gloo-connected through
``jax.distributed.initialize`` (exactly what ``scripts/run_pod.py`` /
``dist/run.py`` wires on a TPU pod), each owning 2 of the global mesh's 4
CPU devices. The strategy code runs UNCHANGED: same ingest
(``parallel/sharding.put_sharded`` places each process's addressable
shards), same shard_map ring programs, same collectives — now crossing a
process boundary.

Strictness is keyed on a CAPABILITY PROBE, not an unconditional xfail:
each worker attempts a tiny cross-process global placement
(``dist.init.cross_process_probe``) and emits the verdict in its record.
A backend that rejects it (this container's jax 0.4.x CPU backend:
"Multiprocess computations aren't implemented on the CPU backend")
xfails with the probe's own error; a backend that supports it runs the
full assertion strict — the day the jax backend (or a real pod backend)
supports cross-process placement, this test starts gating for real with
no edit.
"""

import json
import subprocess
import sys
import pathlib

import numpy as np
import pytest

from distributed_sddmm_tpu.dist.elastic import free_port

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_two_process_pod_matches_single_process():
    port = free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, str(ROOT / "tests" / "_mp_worker.py"),
             str(pid), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=str(ROOT),
        )
        for pid in range(2)
    ]
    results = {}
    infra_failures = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=600)
            lines = [l for l in out.strip().splitlines() if l.strip()]
            probe_lines = []
            for l in lines:
                try:
                    rec = json.loads(l)
                except ValueError:
                    continue
                # Gloo's C++ log lines interleave on stdout, so a torn
                # one ("1") can parse as JSON: records are objects.
                if isinstance(rec, dict) and rec.get("probe"):
                    probe_lines.append(rec)
            if p.returncode != 0:
                if any(r.get("probe_ok") for r in probe_lines):
                    # The probe PASSED and the worker then crashed in
                    # the strategy code: a genuine pod-path regression,
                    # not environment noise — gate hard.
                    raise AssertionError(
                        f"worker crashed after a passing capability "
                        f"probe:\n{err[-2000:]}"
                    )
                # Died before (or at) the probe — Gloo init error,
                # coordinator port race: the environment territory the
                # old blanket xfail covered.
                infra_failures.append(err[-1500:])
                continue
            # Last parseable JSON line is the record (tolerant of any
            # trailing library chatter, like elastic._watch).
            for l in reversed(lines):
                try:
                    rec = json.loads(l)
                except ValueError:
                    continue
                if isinstance(rec, dict) and not rec.get("probe"):
                    results[rec["pid"]] = rec
                    break
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    if infra_failures:
        pytest.xfail(
            "pod worker died before the capability probe (environment "
            f"failure):\n{infra_failures[0]}"
        )
    assert set(results) == {0, 1}
    # Every worker's record must carry the probe verdict (satellite
    # contract: the capability is measured, not assumed).
    assert all("probe_ok" in rec for rec in results.values()), results
    if not all(rec["probe_ok"] for rec in results.values()):
        err = next(
            rec.get("probe_error") for rec in results.values()
            if not rec["probe_ok"]
        )
        pytest.xfail(
            f"backend lacks cross-process global placement: {err}"
        )

    fps = {pid: (rec["fp_out"], rec["fp_mid"])
           for pid, rec in results.items()}
    np.testing.assert_allclose(fps[0], fps[1], rtol=1e-6)

    # Single-process reference: same computation on 4 devices of the test
    # process's own CPU mesh.
    import jax
    import jax.numpy as jnp

    from distributed_sddmm_tpu.common import MatMode
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
    from distributed_sddmm_tpu.utils.coo import HostCOO

    S = HostCOO.erdos_renyi(96, 80, 4, seed=5, values="normal")
    alg = DenseShift15D(S, R=16, c=2, fusion_approach=2,
                        devices=jax.devices()[:4])
    A = alg.dummy_initialize(MatMode.A)
    B = alg.dummy_initialize(MatMode.B)
    out, mid = alg.fused_spmm(A, B, alg.like_s_values(1.0))
    expect = (float(jnp.sum(out * out)), float(jnp.sum(mid * mid)))
    np.testing.assert_allclose(fps[0], expect, rtol=1e-5)
