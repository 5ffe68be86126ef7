"""Tracer contract: nesting, schema round-trip, no-op cost, comm truth.

Covers the observability layer's load-bearing promises:

* span nesting/ordering survives the emit-on-close format (children are
  written first; ``parent`` ids reconstruct the tree),
* every emitted line round-trips through the reader/validator
  (``tools/tracereport``),
* with JSONL off a span is its profiler annotation alone (no file, no
  lock, no clock read, a pinned cost); on or off it enters a
  ``jax.profiler.TraceAnnotation``,
* resilience events (fault fired, retry) land in the trace,
* counted comm volume on a real DenseShift15D run equals the analytic
  cost-model prediction — the measured-vs-modeled agreement the paper's
  accounting argument rests on.
"""

import json
import threading

import pytest

from distributed_sddmm_tpu.obs import metrics, trace
from distributed_sddmm_tpu.tools import tracereport


@pytest.fixture
def tracer(tmp_path):
    trace.disable()
    tr = trace.enable(tmp_path / "t.jsonl")
    yield tr
    trace.disable()


@pytest.fixture(autouse=True)
def _no_env_trace(monkeypatch):
    monkeypatch.delenv("DSDDMM_TRACE", raising=False)
    yield
    trace.disable()


def _records(tr):
    return [
        json.loads(l)
        for l in tr.path.read_text().splitlines() if l.strip()
    ]


class TestSpanNesting:
    def test_parent_ids_reconstruct_nesting(self, tracer):
        with trace.span("outer", level=0):
            with trace.span("inner_a"):
                pass
            with trace.span("inner_b"):
                with trace.span("leaf"):
                    pass
        trace.disable()
        recs = _records(tracer)
        spans = {r["name"]: r for r in recs if r["type"] == "span"}
        assert spans["inner_a"]["parent"] == spans["outer"]["id"]
        assert spans["inner_b"]["parent"] == spans["outer"]["id"]
        assert spans["leaf"]["parent"] == spans["inner_b"]["id"]
        assert spans["outer"]["parent"] is None

    def test_close_order_and_monotonic_bounds(self, tracer):
        with trace.span("outer"):
            with trace.span("inner"):
                pass
        trace.disable()
        names = [r["name"] for r in _records(tracer) if r["type"] == "span"]
        assert names == ["inner", "outer"]  # emit-on-close
        spans = {r["name"]: r for r in _records(tracer) if r["type"] == "span"}
        assert spans["inner"]["t0"] >= spans["outer"]["t0"]
        assert spans["inner"]["t1"] <= spans["outer"]["t1"]
        for s in spans.values():
            assert s["t1"] >= s["t0"] and s["dur_s"] >= 0

    def test_threads_nest_independently(self, tracer):
        def worker():
            with trace.span("worker_span"):
                pass

        with trace.span("main_span"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        trace.disable()
        spans = {r["name"]: r for r in _records(tracer) if r["type"] == "span"}
        # The worker thread has no enclosing span on ITS stack.
        assert spans["worker_span"]["parent"] is None
        assert spans["worker_span"]["tid"] != spans["main_span"]["tid"]

    def test_events_parent_to_current_span(self, tracer):
        with trace.span("outer"):
            trace.event("ping", k=1)
        trace.disable()
        recs = _records(tracer)
        ev = next(r for r in recs if r["type"] == "event")
        sp = next(r for r in recs if r["type"] == "span")
        assert ev["parent"] == sp["id"]
        assert ev["attrs"] == {"k": 1}


class TestSchemaRoundTrip:
    def test_reader_validates_every_line(self, tracer):
        with trace.span("op", R=16) as sp:
            sp.set(kernel_s=0.5)
            trace.event("note", x="y")
        trace.disable()
        loaded = tracereport.load_trace(tracer.path, strict=True)
        assert loaded["begin"]["run_id"] == tracer.run_id
        assert len(loaded["spans"]) == 1
        assert loaded["spans"][0]["attrs"]["kernel_s"] == 0.5
        assert loaded["errors"] == []

    def test_validator_rejects_malformed(self):
        assert tracereport.validate_record({"type": "nope"}) != []
        assert tracereport.validate_record([1, 2]) != []
        ok = {"type": "event", "name": "e", "id": 1, "tid": 2, "t": 0.1,
              "attrs": {}}
        assert tracereport.validate_record(ok) == []
        bad_span = {"type": "span", "name": "s", "id": 1, "tid": 2,
                    "t0": 2.0, "t1": 1.0, "dur_s": -1.0, "attrs": {}}
        assert any("monotonic" in e
                   for e in tracereport.validate_record(bad_span))

    def test_strict_load_raises_on_garbage(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text('{"type": "begin", "schema": 1, "run_id": "r", '
                     '"t0_epoch": 0}\nnot json\n')
        with pytest.raises(ValueError):
            tracereport.load_trace(p, strict=True)
        loose = tracereport.load_trace(p, strict=False)
        assert len(loose["errors"]) == 1


def _forbid_jsonl_work(monkeypatch):
    """Make every JSONL-side cost raise: a clock read, a tracer lock, a
    file opened by the tracer module."""
    def boom(*_a, **_k):
        raise AssertionError("JSONL-off span touched the JSONL machinery")

    monkeypatch.setattr(trace.clock, "now", boom)
    monkeypatch.setattr(trace.Tracer, "next_id", boom)
    monkeypatch.setattr(trace.Tracer, "emit", boom)
    monkeypatch.setattr(trace, "open", boom, raising=False)


class _RecordingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; logs enters and
    exits in order."""

    log: list = []

    def __init__(self, name, **kw):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler

    _RecordingAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _RecordingAnnotation)
    return _RecordingAnnotation.log


class TestDisabledTracer:
    def test_span_is_shared_noop(self, tmp_path, monkeypatch):
        """With JSONL off a span writes nothing: no file, no tracer lock,
        no clock read; only its profiler annotation remains."""
        trace.disable()
        assert not trace.enabled()
        _forbid_jsonl_work(monkeypatch)
        with trace.span("x", a=1) as sp:
            sp.set(k=2)  # must not raise
        trace.event("y", a=1)  # must not raise, must not create a file
        assert trace.run_id() is None and trace.trace_path() is None
        assert list(tmp_path.iterdir()) == []

    def test_span_enters_annotation_with_jsonl_off(self, annotations):
        trace.disable()
        with trace.span("outer"):
            with trace.span("outer:fetch", n=3):
                pass
        assert annotations == [("enter", "outer"), ("enter", "outer:fetch"),
                               ("exit", "outer:fetch"), ("exit", "outer")]

    def test_span_enters_annotation_with_jsonl_on(self, tracer, annotations):
        with trace.span("op", R=4):
            pass
        trace.disable()
        assert annotations == [("enter", "op"), ("exit", "op")]
        assert [r["name"] for r in _records(tracer)
                if r["type"] == "span"] == ["op"]

    def test_span_cost_with_jsonl_off(self, monkeypatch):
        """Three spans a dispatch: the JSONL-off span, annotation
        included, stays under 5 us (median of batches over 10k spans)."""
        import statistics
        import time

        trace.disable()
        _forbid_jsonl_work(monkeypatch)
        per_span = []
        for _ in range(10):
            t0 = time.perf_counter()
            for _ in range(1000):
                with trace.span("cgStep:fetch"):
                    pass
            per_span.append((time.perf_counter() - t0) / 1000)
        assert statistics.median(per_span) < 5e-6, per_span

    def test_leaf_names(self):
        assert trace.is_leaf("cgStep:dispatch") and trace.is_leaf("x:fetch")
        assert not trace.is_leaf("cgStep") and not trace.is_leaf("als:step")

    def test_env_activation(self, tmp_path, monkeypatch):
        trace.disable()
        monkeypatch.setenv("DSDDMM_TRACE", str(tmp_path / "env_dir"))
        # disable() marked env as checked; reset the latch as a fresh
        # process would see it.
        trace._env_checked = False
        assert trace.enabled()
        with trace.span("op"):
            pass
        path = trace.trace_path()
        trace.disable()
        assert path is not None and path.endswith(".jsonl")
        recs = [json.loads(l)
                for l in open(path).read().splitlines() if l.strip()]
        assert recs[0]["type"] == "begin"


class TestResilienceEventsInTrace:
    def test_fault_and_retry_events(self, tracer):
        from distributed_sddmm_tpu.common import MatMode
        from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
        from distributed_sddmm_tpu.resilience import (
            FaultPlan, FaultSpec, fault_plan,
        )
        from distributed_sddmm_tpu.utils.coo import HostCOO

        S = HostCOO.rmat(log_m=6, edge_factor=8, seed=0)
        plan = FaultPlan([
            FaultSpec(site="execute:fusedSpMM", kind="timeout", at=(0,)),
        ])
        with fault_plan(plan):
            alg = DenseShift15D(S, R=8, c=2)
            A = alg.dummy_initialize(MatMode.A)
            B = alg.dummy_initialize(MatMode.B)
            alg.fused_spmm(A, B, alg.like_s_values(1.0), MatMode.A)
        trace.disable()
        recs = _records(tracer)
        events = [r for r in recs if r["type"] == "event"]
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)
        assert by_name["fault_fired"][0]["attrs"]["kind"] == "timeout"
        assert by_name["retry"][0]["attrs"]["op"] == "fusedSpMM"
        assert "strategy" in by_name
        # The faulted dispatch's span carries the retry + overhead split.
        sp = next(r for r in recs
                  if r["type"] == "span" and r["name"] == "fusedSpMM")
        assert sp["attrs"]["retries"] == 1
        assert sp["attrs"]["overhead_s"] > 0
        assert sp["attrs"]["kernel_s"] > 0
        # Metrics agree with the trace.
        m = alg.metrics.to_dict()["fusedSpMM"]
        assert m["retries"] == 1 and m["overhead_s"] > 0


class TestCommAgreement:
    @pytest.mark.parametrize("fusion,c", [(2, 2), (1, 2), (2, 1)])
    def test_counted_words_match_costmodel(self, fusion, c):
        """Strategy layout math vs tools/costmodel.pair_words — two
        independent derivations of the fused pair's per-device volume
        (M=N=64 divides p=8, so padding is exact and they must agree
        to float precision)."""
        from distributed_sddmm_tpu.common import MatMode
        from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
        from distributed_sddmm_tpu.tools import costmodel
        from distributed_sddmm_tpu.utils.coo import HostCOO

        trace.disable()
        S = HostCOO.rmat(log_m=6, edge_factor=8, seed=0)
        alg = DenseShift15D(S, R=16, c=c, fusion_approach=fusion)
        A = alg.dummy_initialize(MatMode.A)
        B = alg.dummy_initialize(MatMode.B)
        alg.fused_spmm(A, B, alg.like_s_values(1.0), MatMode.A)
        counted = alg.metrics.to_dict()["fusedSpMM"]["comm_words"]
        want = costmodel.pair_words(
            alg.cost_model_name, alg.M_pad, alg.N_pad, alg.R,
            S.nnz, alg.p, alg.c,
        )
        assert counted == pytest.approx(want, rel=1e-12)
        # FLOPs follow the harness convention: 4*nnz*R per fused pair.
        assert alg.metrics.to_dict()["fusedSpMM"]["flops"] == pytest.approx(
            4.0 * S.nnz * alg.R
        )

    def test_b_mode_rectangular_swaps_operands(self):
        """A B-mode fused dispatch on a rectangular matrix runs on the
        transposed tiles (stationary = N-side block, A blocks ride the
        ring); the counted words must charge THAT layout, not A-mode's."""
        from distributed_sddmm_tpu.common import MatMode
        from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
        from distributed_sddmm_tpu.utils.coo import HostCOO

        trace.disable()
        S = HostCOO.erdos_renyi(96, 48, 4, seed=0)  # M != N
        alg = DenseShift15D(S, R=16, c=2)
        assert alg.localArows != alg.localBrows
        A = alg.dummy_initialize(MatMode.A)
        B = alg.dummy_initialize(MatMode.B)
        alg.fused_spmm(A, B, alg.like_st_values(1.0), MatMode.B)
        counted = alg.metrics.to_dict()["fusedSpMM"]["comm_words"]
        want_b = (
            (alg.c - 1) * alg.localBrows * alg.R
            + (alg.nr - 1) * alg.localArows * alg.R
        )
        want_a = (
            (alg.c - 1) * alg.localArows * alg.R
            + (alg.nr - 1) * alg.localBrows * alg.R
        )
        assert counted == pytest.approx(want_b)
        assert counted != pytest.approx(want_a)

    def test_report_model_column(self, tracer):
        from distributed_sddmm_tpu.common import MatMode
        from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
        from distributed_sddmm_tpu.utils.coo import HostCOO

        S = HostCOO.rmat(log_m=6, edge_factor=8, seed=0)
        alg = DenseShift15D(S, R=16, c=2)
        A = alg.dummy_initialize(MatMode.A)
        B = alg.dummy_initialize(MatMode.B)
        for _ in range(3):
            alg.fused_spmm(A, B, alg.like_s_values(1.0), MatMode.A)
        trace.disable()
        report = tracereport.aggregate(
            tracereport.load_trace(tracer.path, strict=True)
        )
        ph = report["phases"]["fusedSpMM"]
        assert ph["calls"] == 3
        assert ph["model_words"] == pytest.approx(ph["comm_words"])
        assert ph["model_ratio"] == pytest.approx(1.0)
        assert "strategy" in report and report["strategy"]["p"] == 8
        # The human renderer produces the per-phase table.
        text = tracereport.render(report)
        assert "fusedSpMM" in text and "kernel_s" in text
