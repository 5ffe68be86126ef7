"""Running one cell in-process at a tiny size, without the chip."""

import json
import os

from benchmark import calibrate, faults, harness
from benchmark.tests.conftest import ROOT


def run_cell(root, capsys, workload, seed=5, seconds=0.5, trace=0):
    """The result line of one run of ``workload`` under ``root``."""
    args = harness.parser().parse_args(
        ["--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)])
    capsys.readouterr()
    assert harness.run(args, root=root, require_tpu=False) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def apps_and_chips(root=ROOT) -> dict:
    """Every cell of ``root``'s ``BENCHMARK.json``: its app and the chips
    it asks for."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    out = {}
    for w in bench["workloads"]:
        with open(os.path.join(root, files[w["config"]])) as f:
            out[w["name"]] = (json.load(f)["app"], w["chips"])
    return out


def fault_cases(root=ROOT) -> list:
    """``(cell, fault)`` for every fault that the ``FAULTS`` of a cell's
    app declares; one chip has no exchange between chips to leave out."""
    return [(cell, f) for cell, (app, chips) in apps_and_chips(root).items()
            for f in harness.app_module(app, root).FAULTS
            if f != "no_exchange" or chips > 1]


def assert_fault_caught(root, capsys, cell, fault):
    """A run of ``cell`` under ``root`` with ``fault`` planted reports
    ``correct`` false, and some number over its limit says why; the
    run's result line."""
    app = harness.app_module(apps_and_chips(root)[cell][0], root)
    with faults.plant(fault, app):
        res = run_cell(root, capsys, cell)
    assert res["correct"] is False, (fault, res["checks"])
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in res["checks"].values())
    return res


def calibrate_readings(root, capsys, cell, units=1):
    """``calibrate.py --control --faults`` on two seeds: each number of
    the control and of every fault the app's cell can have reads over
    100 times the program's."""
    capsys.readouterr()
    assert calibrate.main(["--workload", cell, "--seeds", "3,4", "--units",
                           str(units), "--control", "--faults", "--cpu"],
                          root=root) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["seed"] for x in lines] == [3, 4]
    app = harness.Cell(cell, root).app
    want = {"fault.half", "fault.altered"} | {
        "fault." + f for f in getattr(app, "REFERENCE_FAULTS", ())}
    for x in lines:
        assert {k for k in x if k.startswith("fault.")} == want
        prog = max(x["program"].values())
        assert prog < 1e-5
        for kind in ("control", *sorted(want)):
            assert max(x[kind].values()) > 100 * prog, (kind, x)
    return lines


APPS_AND_CHIPS = apps_and_chips()
CELLS = tuple(APPS_AND_CHIPS)
