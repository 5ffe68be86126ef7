"""TPU application + heatmap benchmark runner (single chip).

Runs the reference's application-level benchmarks on real hardware and
appends records to APPS_TPU.jsonl:

* vanilla fused pairs (`bench_erdos_renyi.cpp` analog) for both kernels,
* ALS-CG and GAT apps (`benchmark_dist.cpp:88-100`),
* the R-sweep heatmap (`bench_heatmap.cpp:33-35`) for both kernels.

Timed loops end in host fetches (utils.platform.force_fetch). Each
invocation skips configs already recorded, so an interrupted run resumes
where it stopped.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from distributed_sddmm_tpu.bench.harness import benchmark_algorithm
from distributed_sddmm_tpu.ops import get_kernel
from distributed_sddmm_tpu.utils.coo import HostCOO

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / "APPS_TPU.jsonl"

# (app, algorithm, logM, npr, R, kernel, trials)
PLAN = [
    ("als", "15d_fusion2", 14, 32, 128, "pallas", 2),
    ("gat", "15d_fusion2", 14, 32, 64, "pallas", 2),
    ("als", "15d_fusion2", 14, 32, 128, "xla", 2),
    ("gat", "15d_fusion2", 14, 32, 64, "xla", 2),
    # heatmap R-sweep (subset of bench_heatmap.cpp's {64..448}: compile cost
    # on this backend bounds the grid; every recorded point is real)
    *[("vanilla", "15d_fusion2", 14, 32, R, k, 5)
      for R in (64, 128, 256, 448) for k in ("pallas", "xla")],
]


def done_keys() -> set:
    keys = set()
    if OUT.exists():
        for line in OUT.read_text().splitlines():
            try:
                r = json.loads(line)
                # R from the plan, not the record: GAT's per-layer
                # set_r_value mutates alg.R before the record is written.
                keys.add((r["app"], r["algorithm"], r["extra"]["logM"],
                          r["extra"]["npr"], r["extra"]["R_req"],
                          r["extra"]["kernel_req"]))
            except (json.JSONDecodeError, KeyError):
                continue
    return keys


from distributed_sddmm_tpu.bench.aot import APP_PROGRAM_KEYS as APP_AOT_KEYS  # noqa: E402

_MEMO: dict = {}


def _bench_module():
    """bench.py imported once (ROOT is on sys.path); its code hash cached —
    both would otherwise re-run per plan entry."""
    if "bench" not in _MEMO:
        import bench

        _MEMO["bench"] = bench
        _MEMO["hash"] = bench._bench_code_hash()
    return _MEMO["bench"], _MEMO["hash"]


def _aot_post_build(app: str, log_m: int, npr: int, R: int):
    """Returns a benchmark_algorithm post_build hook that injects
    offline-AOT-compiled strategy programs, or None when AOT is not
    validated / not applicable (xla kernel and GAT use the jit path).
    Precompiles in a CPU-pinned subprocess with negative caching."""
    import hashlib
    import subprocess

    if app not in APP_AOT_KEYS:
        return None
    bench, code_hash = _bench_module()
    if not bench._aot_validated("pallas_fused"):
        return None

    from distributed_sddmm_tpu.ops.blocked import knob_env_defaults

    h = hashlib.sha256()
    h.update(code_hash.encode())
    h.update(pathlib.Path(__file__).read_bytes())
    h.update((ROOT / "scripts" / "aot_compile_apps.py").read_bytes())
    h.update("_".join(f"{k}={os.environ.get(k, '')}"
                      for k in sorted(knob_env_defaults())).encode())
    d = ROOT / "artifacts" / "aot_bench" / (
        f"apps_{app}_{log_m}_{npr}_{R}_{h.hexdigest()[:10]}")
    if not (d / "meta.json").exists():
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=f"{ROOT}:{os.environ.get('PYTHONPATH', '')}")
        fail = None
        try:
            proc = subprocess.run(
                [sys.executable,
                 str(ROOT / "scripts" / "aot_compile_apps.py"),
                 app, str(log_m), str(npr), str(R), str(d)],
                env=env, capture_output=True, text=True, timeout=420)
            if proc.returncode != 0:
                fail = "\n".join((proc.stderr or "").strip().splitlines()[-5:])
        except subprocess.TimeoutExpired:
            fail = "timeout after 420s"
        if fail is not None:
            print(f"[apps] AOT precompile failed ({app}): {fail}",
                  file=sys.stderr)
            d.mkdir(parents=True, exist_ok=True)
            (d / "meta.json").write_text(json.dumps({"ok": False,
                                                     "error": fail}))
    try:
        if not json.loads((d / "meta.json").read_text()).get("ok"):
            return None
    except (OSError, json.JSONDecodeError):
        return None

    def hook(alg):
        import jax

        from distributed_sddmm_tpu.bench import aot

        if jax.device_count() != 1:
            return
        for op, use_st in APP_AOT_KEYS[app]:
            name = f"{op}_{'b' if use_st else 'a'}"
            try:
                loaded = aot.load_executable(d, name, 0, jax.devices()[0])
            except Exception as e:  # noqa: BLE001 — jit path covers it
                print(f"[apps] AOT load failed for {name} "
                      f"({type(e).__name__}); jit path", file=sys.stderr)
                continue
            alg.inject_program(op, use_st, loaded)

    return hook


def main() -> int:
    xla_only = os.environ.get("APPS_XLA_ONLY", "") not in ("", "0")
    # APPS_SUBSET splits the plan so the short application benches can
    # run before the longer heatmap sweep:
    # "apps" = ALS/GAT only, "heatmap" = vanilla R-sweep only, "all".
    subset = os.environ.get("APPS_SUBSET", "all")
    if subset not in ("apps", "heatmap", "all"):
        print(f"unknown APPS_SUBSET={subset!r} (want apps|heatmap|all)",
              file=sys.stderr)
        return 2
    done = done_keys()
    mats: dict = {}
    failures = 0
    for app, alg, log_m, npr, R, kern, trials in PLAN:
        if xla_only and kern != "xla":
            continue  # APPS_XLA_ONLY: run the XLA half only
        if subset == "apps" and app == "vanilla":
            continue
        if subset == "heatmap" and app != "vanilla":
            continue
        key = (app, alg, log_m, npr, R, kern)
        if key in done:
            print(f"skip (done): {key}", flush=True)
            continue
        if (log_m, npr) not in mats:
            mats[(log_m, npr)] = HostCOO.rmat(log_m=log_m, edge_factor=npr, seed=0)
        S = mats[(log_m, npr)]
        try:
            hook = None
            if kern == "pallas":
                try:
                    hook = _aot_post_build(app, log_m, npr, R)
                except Exception as e:  # noqa: BLE001 — advisory only:
                    # a broken AOT path (full disk, import failure) must
                    # degrade to the jit measurement, never abort it.
                    print(f"[apps] AOT setup failed ({type(e).__name__}: "
                          f"{e}); jit path", file=sys.stderr)
            rec = benchmark_algorithm(
                S, alg, str(OUT), fused=True, R=R, c=1, app=app,
                trials=trials, kernel=get_kernel(kern),
                extra_info={"extra": {"logM": log_m, "npr": npr,
                                      "R_req": R, "kernel_req": kern}},
                post_build=hook,
            )
            print(json.dumps({"app": app, "R": R, "kernel": kern,
                              "GFLOPs": round(rec["overall_throughput"], 2),
                              "elapsed": round(rec["elapsed"], 3)}), flush=True)
        except Exception as e:  # noqa: BLE001 — record and continue the sweep
            failures += 1
            print(f"FAIL {key}: {type(e).__name__}: {e}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
