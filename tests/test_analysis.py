"""Analyzer framework + per-checker fixtures.

Per checker: a clean snippet (no finding), a violating snippet (one
``new`` finding), a tagged snippet (finding suppressed at the site) and
a baseline-suppressed run (finding suppressed by a written baseline).
Framework half: tag parsing (the ONE scanner that replaced the two
divergent per-lint regexes — the PR's bugfix satellite), baseline
round-trip and content-addressed fingerprints, walker exclusions, the
unknown-checker error.

Everything runs on throwaway trees that mimic the package layout so the
path-scoped checkers (serve/obs rules, allowlists) engage exactly as
they do on the real checkout.
"""

import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from distributed_sddmm_tpu import analysis
from distributed_sddmm_tpu.analysis import baseline as bl
from distributed_sddmm_tpu.analysis import core

PKG = "distributed_sddmm_tpu"


# --------------------------------------------------------------------- #
# Per-checker fixtures: (path, clean, violating, tagged)
# --------------------------------------------------------------------- #

CASES = {
    "bare-print": {
        "path": f"{PKG}/models/x.py",
        "clean": "def f():\n    return 1\n",
        "bad": "def f():\n    print('leak')\n",
        "tagged": "def f():\n    print('product')  # cli-output\n",
    },
    "monotonic-clock": {
        "path": f"{PKG}/obs/x.py",
        "clean": ("from distributed_sddmm_tpu.obs import clock\n"
                  "def f():\n    return clock.now()\n"),
        "bad": "import time\ndef f():\n    return time.monotonic()\n",
        "tagged": ("import time\n"
                   "def f():\n    return time.time()  # wall-clock-ok\n"),
    },
    "export-completeness": {
        "path": f"{PKG}/serve/x.py",
        # The checker reads the SCANNED tree's declarations: give the
        # fixture tree its own KNOWN_GLOBAL_COUNTERS.
        "extra": {
            f"{PKG}/obs/httpexp.py":
                "KNOWN_GLOBAL_COUNTERS = {'exec_retries': 'help'}\n"
                "from distributed_sddmm_tpu.obs.metrics import GLOBAL\n"
                "def bump():\n    GLOBAL.add('exec_retries')\n",
        },
        "clean": ("from distributed_sddmm_tpu.obs.metrics import GLOBAL\n"
                  "def f():\n    GLOBAL.add('exec_retries')\n"),
        "bad": ("from distributed_sddmm_tpu.obs.metrics import GLOBAL\n"
                "def f():\n    GLOBAL.add('no_such_counter_ever')\n"),
        "tagged": ("from distributed_sddmm_tpu.obs.metrics import GLOBAL\n"
                   "def f():\n"
                   "    GLOBAL.add('private_counter')  # not-exported\n"),
    },
    "atomic-write": {
        "path": f"{PKG}/tools/x.py",
        "clean": ("from distributed_sddmm_tpu.utils.atomic import "
                  "atomic_write_json\n"
                  "def f(p, doc):\n    atomic_write_json(p, doc)\n"),
        "bad": ("import json\n"
                "def f(p, doc):\n"
                "    with open(p, 'w') as fh:\n"
                "        json.dump(doc, fh)\n"),
        "tagged": ("def f(p, line):\n"
                   "    # non-atomic-ok: append stream\n"
                   "    with open(p, 'a') as fh:\n"
                   "        fh.write(line)\n"),
    },
    "env-knob": {
        "path": f"{PKG}/serve/y.py",
        "clean": ("import os\n"
                  "def f():\n"
                  "    return os.environ.get('DSDDMM_TRACE')\n"),
        "bad": ("import os\n"
                "def f():\n"
                "    return os.environ.get('DSDDMM_NOT_A_KNOB')\n"),
        "tagged": ("import os\n"
                   "def f():\n"
                   "    return os.environ.get('DSDDMM_SECRET')"
                   "  # env-ok\n"),
    },
    "lock-discipline": {
        "path": f"{PKG}/serve/z.py",
        "clean": ("import threading\n"
                  "_lock = threading.Lock()\n"
                  "_reg = {}\n"
                  "def f(k, v):\n"
                  "    with _lock:\n"
                  "        _reg[k] = v\n"),
        "bad": ("_reg = {}\n"
                "def f(k, v):\n"
                "    _reg[k] = v\n"),
        "tagged": ("_reg = {}\n"
                   "def f(k, v):\n"
                   "    _reg[k] = v  # lock: engine_lock\n"),
    },
    "key-grammar": {
        "path": f"{PKG}/autotune/x.py",
        "clean": ("from distributed_sddmm_tpu.programs.keys import "
                  "plan_program_key\n"
                  "def f(fp, sig):\n"
                  "    return plan_program_key(fp, 'op', sig, 'cpu', 'c0')\n"),
        "bad": ("def f(fp, op, sig):\n"
                "    return f'plan:{fp}:{op}:{sig}:cpu:c0'\n"),
        "tagged": ("def f(fp, op, sig):\n"
                   "    return f'bench:{fp}:{op}:{sig}:cpu'"
                   "  # key-grammar-ok\n"),
    },
    "trace-purity": {
        "path": f"{PKG}/ops/x.py",
        "clean": ("import jax\n"
                  "@jax.jit\n"
                  "def f(x):\n    return x + 1\n"),
        "bad": ("import jax\nimport time\n"
                "@jax.jit\n"
                "def f(x):\n    return x + time.time()\n"),
        "tagged": ("import jax\nimport time\n"
                   "@jax.jit\n"
                   "def f(x):\n"
                   "    return x + time.time()  # trace-impure-ok\n"),
    },
    "trace-propagation": {
        "path": f"{PKG}/fleet/x.py",
        "clean": ("from distributed_sddmm_tpu.obs.httpexp import "
                  "post_json\n"
                  "def f(port, body, hdr):\n"
                  "    return post_json('127.0.0.1', port, '/submit', "
                  "body, headers=hdr)\n"),
        "bad": ("from distributed_sddmm_tpu.obs.httpexp import "
                "post_json\n"
                "def f(port, body):\n"
                "    return post_json('127.0.0.1', port, '/submit', "
                "body)\n"),
        "tagged": ("from distributed_sddmm_tpu.obs.httpexp import "
                   "post_json\n"
                   "def f(port, body):\n"
                   "    return post_json('127.0.0.1', port, '/healthz', "
                   "body)  # no-trace-ctx\n"),
    },
    "raw-collective": {
        "path": f"{PKG}/parallel/x.py",
        "clean": ("from distributed_sddmm_tpu.parallel.loops import "
                  "abl_ppermute\n"
                  "def f(x, perm):\n"
                  "    return abl_ppermute(x, 'rows', perm, wire='bf16')\n"),
        "bad": ("from jax import lax\n"
                "def f(x, perm):\n"
                "    return lax.ppermute(x, 'rows', perm)\n"),
        "tagged": ("from jax import lax\n"
                   "def f(x, perm):\n"
                   "    return lax.ppermute(x, 'rows', perm)"
                   "  # raw-collective-ok\n"),
    },
}


def _run_on(tmp_path, checker, rel, src, extra=None):
    root = tmp_path / "tree"
    for r, s in {rel: src, **(extra or {})}.items():
        p = root / r
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(s)
    return analysis.run(root=root, checkers=[checker])


@pytest.mark.parametrize("checker", sorted(CASES))
def test_clean_snippet(tmp_path, checker):
    case = CASES[checker]
    findings = _run_on(tmp_path, checker, case["path"], case["clean"],
                       case.get("extra"))
    assert [f for f in findings if f.state == "new"] == [], findings


@pytest.mark.parametrize("checker", sorted(CASES))
def test_violating_snippet(tmp_path, checker):
    case = CASES[checker]
    findings = _run_on(tmp_path, checker, case["path"], case["bad"],
                       case.get("extra"))
    new = [f for f in findings if f.state == "new"]
    assert new, "checker failed to fire on its violating fixture"
    assert all(f.checker == checker for f in new)
    # Findings carry a real anchor: file:line into the seeded tree.
    assert new[0].path == case["path"] and new[0].line >= 1


@pytest.mark.parametrize("checker", sorted(CASES))
def test_tagged_snippet_suppressed(tmp_path, checker):
    case = CASES[checker]
    findings = _run_on(tmp_path, checker, case["path"], case["tagged"],
                       case.get("extra"))
    assert [f for f in findings if f.state == "new"] == [], findings
    tagged = [f for f in findings if f.state == "tagged"]
    assert tagged, "tag did not register as a suppression (vs no finding)"
    assert tagged[0].tag is not None


@pytest.mark.parametrize("checker", sorted(CASES))
def test_baseline_suppressed(tmp_path, checker):
    case = CASES[checker]
    root = tmp_path / "tree"
    p = root / case["path"]
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(case["bad"])

    first = analysis.run(root=root, checkers=[checker])
    assert any(f.state == "new" for f in first)
    path = tmp_path / "baseline.json"
    bl.write_baseline(path, first)

    second = analysis.run(root=root, checkers=[checker])
    result = analysis.apply_baseline(second, bl.load_baseline(path))
    assert [f for f in second if f.state == "new"] == []
    assert any(f.state == "baselined" for f in second)
    assert result["stale"] == []


# --------------------------------------------------------------------- #
# Framework: the one tag scanner (the unification bugfix)
# --------------------------------------------------------------------- #


def test_parse_tags_whole_vocabulary():
    """Every tag parses through the SAME function — the bare-print and
    export-completeness lints previously carried separate regexes for
    their tags, and this is the single scanner that replaced them."""
    for name in core.TAG_VOCABULARY:
        comment = (f"# lock: my_lock" if name == "lock"
                   else f"# {name} — because reasons")
        tags = core.parse_tags(comment)
        assert any(t.name == name for t in tags), name
    # lock is parametric
    (tag,) = core.parse_tags("# lock: _registry_lock")
    assert tag.name == "lock" and tag.arg == "_registry_lock"


def test_parse_tags_multiple_in_one_comment():
    tags = {t.name for t in core.parse_tags(
        "# cli-output and also not-exported"
    )}
    assert tags == {"cli-output", "not-exported"}


def test_scan_tags_skips_strings_and_docstrings():
    src = (
        'X = "# cli-output"\n'
        'def f():\n'
        '    """mentions # wall-clock-ok in prose"""\n'
        '    return 1  # cli-output\n'
    )
    tags = core.scan_tags(src)
    assert list(tags) == [4]  # only the real comment line


def test_tag_above_statement_suppresses(tmp_path):
    src = (
        "def f(p, line):\n"
        "    # non-atomic-ok: stream\n"
        "    with open(p, 'a') as fh:\n"
        "        fh.write(line)\n"
    )
    findings = _run_on(tmp_path, "atomic-write", f"{PKG}/tools/y.py", src)
    assert [f for f in findings if f.state == "new"] == []


def test_tag_on_multiline_statement_closing_line(tmp_path):
    src = (
        "def f(p, doc):\n"
        "    with open(\n"
        "        p, 'w',\n"
        "    ) as fh:  # non-atomic-ok: fixture\n"
        "        fh.write(doc)\n"
    )
    findings = _run_on(tmp_path, "atomic-write", f"{PKG}/tools/z.py", src)
    assert [f for f in findings if f.state == "new"] == []


# --------------------------------------------------------------------- #
# Framework: baseline round-trip
# --------------------------------------------------------------------- #


def test_baseline_roundtrip_and_fingerprint_stability(tmp_path):
    root = tmp_path / "tree"
    rel = f"{PKG}/models/m.py"
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text("def f():\n    print('x')\n")
    findings = analysis.run(root=root, checkers=["bare-print"])
    path = tmp_path / "b.json"
    doc = bl.write_baseline(path, findings)
    assert len(doc["findings"]) == 1
    assert bl.load_baseline(path) == doc

    # Content-addressed: lines ABOVE the finding shift it without
    # invalidating the entry...
    p.write_text("import os\n\n\ndef f():\n    print('x')\n")
    shifted = analysis.run(root=root, checkers=["bare-print"])
    result = analysis.apply_baseline(shifted, bl.load_baseline(path))
    assert [f for f in shifted if f.state == "new"] == []
    assert result["stale"] == []

    # ...but editing the flagged line itself invalidates it (the edit
    # is the moment the debt is repaid or consciously re-baselined).
    p.write_text("def f():\n    print('different')\n")
    edited = analysis.run(root=root, checkers=["bare-print"])
    result = analysis.apply_baseline(edited, bl.load_baseline(path))
    assert [f for f in edited if f.state == "new"] != []
    assert result["stale"], "the old entry should report as stale"


def test_baseline_ordinal_distinguishes_duplicates(tmp_path):
    """Baselining one ``print('x')`` must NOT cover an identical second
    one added later — fingerprints carry a per-duplicate ordinal."""
    root = tmp_path / "tree"
    rel = f"{PKG}/models/m.py"
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text("def f():\n    print('x')\n")
    first = analysis.run(root=root, checkers=["bare-print"])
    path = tmp_path / "b.json"
    bl.write_baseline(path, first)

    p.write_text("def f():\n    print('x')\n\ndef g():\n    print('x')\n")
    second = analysis.run(root=root, checkers=["bare-print"])
    analysis.apply_baseline(second, bl.load_baseline(path))
    states = sorted(f.state for f in second)
    assert states == ["baselined", "new"], states


def test_snippetless_findings_never_alias():
    """finish() findings anchor at a file with no snippet — the message
    keeps two distinct repo-wide facts from sharing one fingerprint."""
    a = core.Finding("export-completeness", "x.py", 1,
                     "stale declaration 'foo'")
    b = core.Finding("export-completeness", "x.py", 1,
                     "stale declaration 'bar'")
    fps = bl.fingerprints([a, b])
    assert fps[0] != fps[1]


def test_partial_run_never_touches_other_checkers_baseline(tmp_path):
    """A ``--checker X`` run must neither report other checkers'
    baseline entries as stale nor delete them on --write-baseline."""
    from distributed_sddmm_tpu.analysis import cli as analysis_cli

    root = tmp_path / "tree"
    p1 = root / PKG / "models" / "m.py"
    p1.parent.mkdir(parents=True, exist_ok=True)
    p1.write_text("def f():\n    print('x')\n")
    p2 = root / PKG / "serve" / "s.py"
    p2.parent.mkdir(parents=True, exist_ok=True)
    p2.write_text("_reg = {}\ndef f(k, v):\n    _reg[k] = v\n")
    path = tmp_path / "b.json"

    # Full baseline: both checkers' debt.
    code = analysis_cli.main([
        "lint", "--root", str(root), "--baseline", str(path),
        "--write-baseline",
    ])
    assert code == 0
    full = {e["checker"] for e in bl.load_baseline(path)["findings"]}
    assert full == {"bare-print", "lock-discipline"}

    # Partial run: the other checker's entry is out of scope, not stale.
    findings = analysis.run(root=root, checkers=["bare-print"])
    result = analysis.apply_baseline(
        findings, bl.load_baseline(path), checkers=["bare-print"]
    )
    assert result["stale"] == []
    assert [f for f in findings if f.state == "new"] == []

    # Partial --write-baseline: the unselected entry survives.
    code = analysis_cli.main([
        "lint", "--root", str(root), "--baseline", str(path),
        "--checker", "bare-print", "--write-baseline",
    ])
    assert code == 0
    kept = {e["checker"] for e in bl.load_baseline(path)["findings"]}
    assert kept == {"bare-print", "lock-discipline"}


def test_render_markdown_scope():
    from distributed_sddmm_tpu.utils import envreg

    runtime = envreg.render_markdown()
    test = envreg.render_markdown(scope="test")
    assert "DSDDMM_MP_INIT_TIMEOUT" not in runtime
    assert "DSDDMM_MP_INIT_TIMEOUT" in test
    assert "DSDDMM_TRACE" not in test


def test_baseline_schema_mismatch_raises(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"schema": 99, "findings": []}))
    with pytest.raises(ValueError):
        bl.load_baseline(p)


# --------------------------------------------------------------------- #
# Framework: walker, registry, errors
# --------------------------------------------------------------------- #


def test_walker_never_scans_artifacts(tmp_path):
    root = tmp_path / "tree"
    bad = "def f():\n    print('x')\n"
    for rel in (f"{PKG}/models/a.py",
                "artifacts/runstore/gen.py",
                f"{PKG}/artifacts/gen.py"):
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(bad)
    findings = analysis.run(root=root, checkers=["bare-print"])
    assert {f.path for f in findings} == {f"{PKG}/models/a.py"}


def test_unknown_checker_raises():
    with pytest.raises(KeyError):
        analysis.run(checkers=["no-such-checker"])


def test_syntax_error_becomes_parse_finding(tmp_path):
    root = tmp_path / "tree"
    p = root / PKG / "models" / "broken.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text("def f(:\n")
    findings = analysis.run(root=root, checkers=["bare-print"])
    assert [f.checker for f in findings] == ["parse"]


def test_registry_covers_the_six_disciplines():
    assert set(analysis.CHECKERS) == {
        "bare-print", "monotonic-clock", "export-completeness",
        "atomic-write", "env-knob", "lock-discipline", "key-grammar",
        "trace-purity", "raw-collective", "trace-propagation",
    }


# --------------------------------------------------------------------- #
# The committed tree itself (same gate the smoke runs, in-process)
# --------------------------------------------------------------------- #


def test_committed_tree_is_clean():
    findings = analysis.run_repo()
    new = [f.render() for f in findings if f.state == "new"]
    assert not new, "\n".join(new)
