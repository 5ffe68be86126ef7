"""Each configuration's matrix, found by its generator's name, is the
matrix the cells ran on before generators were found by name: the same
arrays, bit for bit, for the same seed. The digests were taken from the
earlier ``benchmark/rmat.py`` plus the harness's own ``values: uniform``
draw, at each configuration's generator settings with ``log_m`` 8 (and
``edge_factor`` 8) or 18 (``pair-rmat20-r128`` cut from 20)."""

import hashlib
import json
import os

import pytest

from benchmark import harness
from benchmark.tests.conftest import ROOT

SEEDS = (5, 2024, 2**31 + 12345)
#: ``(config, log_m, edge_factor)`` -> a digest per seed of ``SEEDS``.
DIGESTS = {
    ("als-rmat18-r128", 8, 8): ("3291b42e6a7b392eb971e3ad7418cc88",
                                "c3026bfd0cb64f544e608722ad9e3e40",
                                "e24cb3c44ffa3529de748a5f5bceb06b"),
    ("als-rmat18-r128", 18, 32): ("64f02df3e2f22bbb8f57083c6345e64a",
                                  "f5f7796cb5ed6e1eea67148c898f6d15",
                                  "18461aa527da0cdde2bec45953893b4c"),
    ("gat-ref-rmat18", 8, 8): ("3291b42e6a7b392eb971e3ad7418cc88",
                               "c3026bfd0cb64f544e608722ad9e3e40",
                               "e24cb3c44ffa3529de748a5f5bceb06b"),
    ("gat-ref-rmat18", 18, 32): ("64f02df3e2f22bbb8f57083c6345e64a",
                                 "f5f7796cb5ed6e1eea67148c898f6d15",
                                 "18461aa527da0cdde2bec45953893b4c"),
    ("pair-rmat20-r128", 8, 8): ("91b022b123e9f9cbe225f303847d6141",
                                 "490dea89a91c5f1cb127d3a3c22d5681",
                                 "77d073056ea6b65dc2849cfbb6de84b5"),
    ("pair-rmat20-r128", 18, 32): ("b323f3cbd3d710e0ea5a66d9010379c9",
                                   "5624762b8186375c5135c83ed8d6b540",
                                   "cc906843d9ef1abe33eb5c4f400da359"),
}
# The one-chip pair's matrix is the four-chip one's generator at 2^18 rows.
DIGESTS["pair-rmat18-r128", 8, 8] = DIGESTS["pair-rmat20-r128", 8, 8]
DIGESTS["pair-rmat18-r128", 18, 32] = DIGESTS["pair-rmat20-r128", 18, 32]


def digest(mat: dict) -> str:
    h = hashlib.sha256()
    for k in ("rows", "cols"):
        h.update(mat[k].astype("<i8").tobytes())
    h.update(mat["vals"].astype("<f8").tobytes())
    h.update(f"{mat['M']},{mat['N']}".encode())
    return h.hexdigest()[:32]


@pytest.mark.parametrize("config,log_m,edge_factor", sorted(DIGESTS))
def test_matrix_unchanged(config, log_m, edge_factor):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        spec = json.load(f)["matrix"]
    spec.update(log_m=log_m, edge_factor=edge_factor)
    got = tuple(digest(harness.make_matrix(spec, harness.seeds(s)[0]))
                for s in SEEDS)
    assert got == DIGESTS[config, log_m, edge_factor]


def test_unknown_generator_lists_the_known(capsys):
    with pytest.raises(SystemExit) as e:
        harness.make_matrix({"generator": "no-such-generator"},
                            harness.seeds(1)[0])
    msg = str(e.value)
    assert "'no-such-generator'" in msg and "'rmat'" in msg
