"""Running one cell in-process at a tiny size, without the chip."""

import json
import os

from benchmark import harness
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


def _apps_and_chips() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    out = {}
    for w in bench["workloads"]:
        with open(os.path.join(ROOT, files[w["config"]])) as f:
            out[w["name"]] = (json.load(f)["app"], w["chips"])
    return out


#: Every cell of ``BENCHMARK.json``: its app and the chips it asks for.
APPS_AND_CHIPS = _apps_and_chips()
CELLS = tuple(APPS_AND_CHIPS)
