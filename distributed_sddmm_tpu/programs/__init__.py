"""Unified persistent AOT compiled-program store (PR 6).

``programs.keys``   — the one key grammar every compiled-program cache
uses (plan-routed strategy programs, serve bucket ladder, bench AOT).
``programs.store``  — the store itself: serialized-executable entries
under ``artifacts/programs/``, flock'd index, corrupt-entry eviction,
graceful fall-through to live compile.
"""

from distributed_sddmm_tpu.programs.keys import (  # noqa: F401
    bench_aot_key,
    parse_bench_key,
    parse_key,
    parse_plan_key,
    parse_serve_key,
    plan_program_key,
    safe_stem,
    serve_program_key,
    sig_for_args,
)
from distributed_sddmm_tpu.programs.store import (  # noqa: F401
    DEFAULT_ROOT,
    SCHEMA_VERSION,
    ProgramStore,
    StoredProgram,
    active,
    bind_strategy,
    chained_program,
    cost_log_len,
    disable,
    enable,
    stored,
    strategy_config_tag,
    xla_cost_summary,
)
