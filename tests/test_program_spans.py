"""Program spans at the host-loop boundaries.

``DistributedSparse._timed`` opens ``<op>`` and, inside it, the leaves
``<op>:dispatch`` and ``<op>:fetch`` on every path, JSONL on or off and
per retry attempt; each span is a ``jax.profiler.TraceAnnotation`` (here
a recorder stands in for the class). A profiler capture does not change
the path ``_timed`` takes. The JSONL readers treat the leaves as host
phases of their op. (That every Pallas launch names its kernel in
compiled HLO is pinned in ``test_chip_compile.py``.)
"""

import json

import numpy as np
import pytest

from distributed_sddmm_tpu.common import MatMode
from distributed_sddmm_tpu.obs import profiler, regress, trace
from distributed_sddmm_tpu.parallel.base import DistributedSparse
from distributed_sddmm_tpu.tools import tracereport
from distributed_sddmm_tpu.utils.coo import HostCOO


class _Recorder:
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

    monkeypatch.delenv("DSDDMM_TRACE", raising=False)
    trace.disable()
    _Recorder.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    yield _Recorder.log
    trace.disable()


@pytest.fixture(scope="module")
def alg():
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D

    S = HostCOO.rmat(log_m=6, edge_factor=8, seed=0)
    return DenseShift15D(S, R=8, c=2)


def _pair(alg):
    A = alg.dummy_initialize(MatMode.A)
    B = alg.dummy_initialize(MatMode.B)
    return alg.fused_spmm(A, B, alg.like_s_values(1.0), MatMode.A)


def _op_spans(log, op):
    """The enters and exits of ``op`` and its leaves, in order."""
    return [e for e in log if e[1].split(":")[0] == op]


ONE_CALL = [("enter", "fusedSpMM"), ("enter", "fusedSpMM:dispatch"),
            ("exit", "fusedSpMM:dispatch"), ("enter", "fusedSpMM:fetch"),
            ("exit", "fusedSpMM:fetch"), ("exit", "fusedSpMM")]


def test_plain_path_opens_op_dispatch_fetch(alg, annotations):
    """JSONL off and no capture: the hot path still opens the three
    spans, nested, and still counts the call."""
    alg.reset_performance_timers()
    _pair(alg)
    _pair(alg)
    assert _op_spans(annotations, "fusedSpMM") == ONE_CALL * 2
    assert alg.metrics.to_dict()["fusedSpMM"]["calls"] == 2


def test_resilient_path_opens_leaves_per_attempt(alg, annotations):
    """A first attempt whose output is poisoned trips the guard and
    retries: one op span, a dispatch and a fetch leaf per attempt."""
    from distributed_sddmm_tpu.resilience import (
        FaultPlan, FaultSpec, fault_plan,
    )

    plan = FaultPlan([FaultSpec(site="output:fusedSpMM", kind="nan",
                                at=(0,))])
    with fault_plan(plan):
        _pair(alg)
    leaves = [("enter", "fusedSpMM:dispatch"), ("exit", "fusedSpMM:dispatch"),
              ("enter", "fusedSpMM:fetch"), ("exit", "fusedSpMM:fetch")]
    assert _op_spans(annotations, "fusedSpMM") == (
        [("enter", "fusedSpMM")] + leaves * 2 + [("exit", "fusedSpMM")])


def test_capture_does_not_change_the_path(alg, monkeypatch, tmp_path):
    """The traced path (strategy meta, overhead split) runs for JSONL or
    resilience only; a running profiler capture leaves ``_timed`` on its
    plain path."""
    monkeypatch.delenv("DSDDMM_TRACE", raising=False)
    trace.disable()
    traced = []
    real = DistributedSparse._emit_strategy_meta
    monkeypatch.setattr(DistributedSparse, "_emit_strategy_meta",
                        lambda self: traced.append(1) or real(self))
    _pair(alg)
    with profiler.capture(str(tmp_path / "prof")):
        assert profiler.active()
        _pair(alg)
    assert traced == []
    trace.enable(tmp_path / "t.jsonl")
    try:
        _pair(alg)
    finally:
        trace.disable()
    assert traced == [1]


def test_jsonl_leaves_parent_to_their_op(alg, tmp_path, monkeypatch):
    monkeypatch.delenv("DSDDMM_TRACE", raising=False)
    trace.disable()
    tr = trace.enable(tmp_path / "t.jsonl")
    try:
        _pair(alg)
    finally:
        trace.disable()
    spans = [json.loads(line) for line in tr.path.read_text().splitlines()]
    spans = {r["name"]: r for r in spans if r["type"] == "span"}
    op = spans["fusedSpMM"]
    for leaf in ("fusedSpMM:dispatch", "fusedSpMM:fetch"):
        assert spans[leaf]["parent"] == op["id"]
        assert op["t0"] <= spans[leaf]["t0"] <= spans[leaf]["t1"] <= op["t1"]
    assert spans["fusedSpMM:dispatch"]["t1"] <= spans["fusedSpMM:fetch"]["t0"]


def _span(name, dur, **attrs):
    return {"type": "span", "name": name, "id": 1, "parent": None, "tid": 1,
            "t0": 0.0, "t1": dur, "dur_s": dur, "attrs": attrs}


def test_report_and_gate_treat_leaves_as_host_phases():
    """``report-trace`` lists the leaves as phases without kernel time;
    a gate between a trace without them and one with them calls them
    neither missing nor a regression, either way round."""
    before = {"spans": [_span("cgStep", 0.2, kernel_s=0.2)], "events": [],
              "begin": None}
    after = {"spans": before["spans"] + [_span("cgStep:dispatch", 0.01),
                                         _span("cgStep:fetch", 0.19)],
             "events": [], "begin": None}
    rep = tracereport.aggregate(after)
    assert set(rep["phases"]) == {"cgStep", "cgStep:dispatch", "cgStep:fetch"}
    assert rep["phases"]["cgStep:fetch"]["kernel_s"] == 0.0
    assert rep["phases"]["cgStep:fetch"]["total_s"] == pytest.approx(0.19)
    assert rep["phases"]["cgStep"]["kernel_s"] == pytest.approx(0.2)
    docs = [{"phases": tracereport.aggregate(t)["phases"]}
            for t in (before, after)]
    for a, b in (docs, docs[::-1]):
        cmp = regress.compare(b, a)
        assert cmp["missing"] == [] and cmp["regressions"] == []


def test_als_step_fetches(annotations):
    """One alternating step at 10 CG iterations: per half-step one
    right-hand-side SpMM, one fused pair and ten ``cgStep`` programs,
    each fetched once: 24 ``:fetch`` spans under ``als:step``."""
    from distributed_sddmm_tpu.models.als import DistributedALS
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D

    S = HostCOO.rmat(log_m=6, edge_factor=8, seed=1)
    als = DistributedALS(DenseShift15D(S, R=8, c=1))
    als.initialize_embeddings()
    _Recorder.log.clear()
    als.run_cg(1, cg_iters=10)
    log = annotations
    assert log[0] == ("enter", "als:step") and log[-1] == ("exit", "als:step")
    enters = [n for e, n in log if e == "enter"]
    assert enters.count("als:half_step") == 2
    assert sum(n.endswith(":fetch") for n in enters) == 24
    assert enters.count("cgStep:dispatch") == 20


def test_gat_forward_span(annotations):
    from distributed_sddmm_tpu.models.gat import GAT, GATLayer
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D

    S = HostCOO.rmat(log_m=6, edge_factor=8, seed=1)
    layers = [GATLayer(8, 4, 2), GATLayer(8, 4, 2)]
    gat = GAT(layers, DenseShift15D(S, R=8, c=1))
    out = gat.forward()
    assert np.isfinite(np.asarray(out)).all()
    enters = [n for e, n in annotations if e == "enter"]
    assert enters[0] == "gat:forward" and annotations[-1] == (
        "exit", "gat:forward")
    assert enters.count("gatLayer") == enters.count("gatLayer:fetch") == 2
