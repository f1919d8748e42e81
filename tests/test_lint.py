"""tools/lint.py self-test (the reference's codestyle stack ships its own
docstring-checker unit test, /root/reference/codestyle/test_docstring_checker.py
— same idea here)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from lint import check_file, doc_files  # noqa: E402


def _lint_src(tmp_path, src):
    p = tmp_path / "mod.py"
    p.write_text(src)
    return {code for _, _, code, _ in check_file(str(p))}


def test_detects_unused_import(tmp_path):
    assert "E2" in _lint_src(tmp_path, "import os\nimport sys\n\nprint(sys.argv)\n")


def test_used_dotted_and_aliased_imports_ok(tmp_path):
    src = (
        "import jax.numpy as jnp\n"
        "from typing import Optional\n\n"
        "def f(x: Optional[int]):\n    return jnp.sin(x)\n"
    )
    assert _lint_src(tmp_path, src) == set()


def test_string_annotation_counts_as_use(tmp_path):
    src = (
        "from typing import Mapping\n\n"
        'def f(x: "Mapping[str, int]"):\n    return x\n'
    )
    assert _lint_src(tmp_path, src) == set()


def test_detects_bare_except_eval_tab_trailing_ws_mutable_default(tmp_path):
    src = (
        "def f(x=[]):\n"
        "\ttry:\n"
        "\t\treturn eval('x')   \n"
        "\texcept:\n"
        "\t\tpass\n"
    )
    codes = _lint_src(tmp_path, src)
    assert {"E3", "E4", "E5", "E7", "E8"} <= codes


def test_noqa_suppresses(tmp_path):
    assert _lint_src(tmp_path, "import os  # noqa\n") == set()


def test_syntax_error_reported(tmp_path):
    assert "E1" in _lint_src(tmp_path, "def broken(:\n")


def test_repo_is_clean():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py")],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout[-2000:]


def test_docstring_mention_does_not_mask_unused_import(tmp_path):
    src = '"""Helpers for os-level work."""\nimport os\n\nprint(1)\n'
    assert "E2" in _lint_src(tmp_path, src)


def test_mutable_default_call_and_lambda(tmp_path):
    assert "E8" in _lint_src(tmp_path, "def f(x=set()):\n    return x\n")
    assert "E8" in _lint_src(tmp_path, "g = lambda x=[]: x\n")
    assert "E8" in _lint_src(tmp_path, "def f(x=dict(a=1)):\n    return x\n")


def test_missing_module_docstring_in_package(tmp_path, monkeypatch):
    # hermetic: point lint.REPO at tmp_path instead of writing a temp
    # module into the real package (which races test_repo_is_clean and
    # leaks the file into the source tree on a hard kill)
    import lint as _lint

    pkg = tmp_path / "paddlefleetx_tpu"
    pkg.mkdir()
    p = pkg / "mod.py"
    p.write_text("x = 1\n")
    monkeypatch.setattr(_lint, "REPO", str(tmp_path))
    codes = {c for _, _, c, _ in check_file(str(p))}
    assert "E9" in codes
    # non-package files are exempt
    q = tmp_path / "m.py"
    q.write_text("x = 1\n")
    assert "E9" not in {c for _, _, c, _ in check_file(str(q))}


def test_metric_name_lint_undeclared_and_malformed(tmp_path):
    # undeclared name handed to a registry accessor
    src = 'reg.counter("pfx_made_up_total").inc()\n'
    assert "E10" in _lint_src(tmp_path, src)
    # schema violation (uppercase) at a registry call site
    src = 'reg.gauge("pfx_BAD_Name").set(1)\n'
    assert "E10" in _lint_src(tmp_path, src)
    # a metric-shaped string literal anywhere (e.g. a StatsView mapping)
    src = 'M = {"requests": "pfx_never_declared_total"}\n'
    assert "E10" in _lint_src(tmp_path, src)


def test_metric_name_lint_declared_names_pass(tmp_path):
    src = (
        'reg.counter("pfx_serving_requests_total").inc()\n'
        'reg.histogram("pfx_request_latency_seconds").observe(0.1)\n'
        '# exposition suffixes resolve to the declared base name\n'
        'x = "pfx_request_latency_seconds_bucket"\n'
        'y = "pfx_serving_requests_total"\n'
        'print(reg, x, y)\n'
    )
    assert "E10" not in _lint_src(tmp_path, src)


def test_metric_name_lint_declared_table_parses():
    # the AST parse of telemetry.METRICS finds the real table
    import lint as _lint

    _lint._declared_metrics = ...  # reset the cache
    names = _lint.declared_metrics()
    assert names and "pfx_serving_requests_total" in names
    assert all(n.startswith("pfx_") for n in names)


def test_metrics_docs_table_parses_and_agrees():
    """E11 happy path on the real repo: the docs table exists and the
    two-way agreement holds (the repo-clean test covers this too, but
    this one names the check)."""
    import lint as _lint

    documented, linenos = _lint.documented_metrics()
    assert documented, "docs/observability.md Metrics reference missing"
    assert documented == _lint.declared_metrics()
    assert all(n in linenos for n in documented)
    assert _lint.check_metrics_docs() == []


def test_metrics_docs_drift_is_detected(tmp_path, monkeypatch):
    """E11 both directions, hermetically: a declared-but-undocumented
    metric and a stale doc row each produce a finding; a missing table
    is itself a finding."""
    import lint as _lint

    pkg = tmp_path / "paddlefleetx_tpu" / "utils"
    pkg.mkdir(parents=True)
    (pkg / "telemetry.py").write_text(
        '"""t."""\nMETRICS = {\n'
        '    "pfx_a_total": ("counter", "a"),\n'  # noqa — fixture table
        '    "pfx_b_total": ("counter", "b"),\n'  # noqa — fixture table
        "}\n"
    )
    docs = tmp_path / "docs"
    docs.mkdir()
    doc = docs / "observability.md"
    doc.write_text(
        "# x\n\n### Metrics reference\n\n"
        "| metric | kind | meaning |\n|---|---|---|\n"
        "| `pfx_a_total` | counter | a |\n"
        "| `pfx_stale_total` | counter | gone |\n\n## next\n"  # noqa
    )
    monkeypatch.setattr(_lint, "REPO", str(tmp_path))
    _lint._declared_metrics = ...  # re-read from the tmp repo
    try:
        findings = _lint.check_metrics_docs()
        codes = {(code, msg.split("'")[1]) for _, _, code, msg in findings}
        assert ("E11", "pfx_b_total") in codes  # noqa — fixture name
        assert ("E11", "pfx_stale_total") in codes  # noqa — fixture name
        # stale rows point at their doc line
        stale = next(f for f in findings if "pfx_stale_total" in f[3])  # noqa
        assert stale[0].endswith("observability.md") and stale[1] > 1
        # a missing table is loud, not silently clean
        doc.write_text("# x\n\nno table here\n")
        missing = _lint.check_metrics_docs()
        assert len(missing) == 1 and "missing" in missing[0][3]
    finally:
        _lint._declared_metrics = ...  # drop the tmp-repo cache


def test_env_knob_docs_agree_on_the_real_repo():
    """E12 happy path: every PFX_* knob referenced in package source has
    a docs table row and no documented knob is stale (the repo-clean
    test covers this too; this one names the check)."""
    import lint as _lint

    knobs = _lint.source_env_knobs()
    assert "PFX_TRACE_SAMPLE" in knobs and "PFX_FAULT" in knobs
    documented, where = _lint.documented_env_knobs()
    assert set(knobs) <= documented
    assert _lint.check_env_knob_docs() == []


def test_env_knob_docs_drift_is_detected(tmp_path, monkeypatch):
    """E12 both directions, hermetically: an undocumented source knob
    and a stale doc row each produce a finding; prefix building blocks
    (trailing underscore) and prose mentions don't count."""
    import lint as _lint

    pkg = tmp_path / "paddlefleetx_tpu"
    pkg.mkdir(parents=True)
    (pkg / "knobs.py").write_text(
        '"""k."""\nimport os\n'
        'A = os.environ.get("PFX_REAL_KNOB")\n'
        'B = "PFX_PREFIX_"  # building block, not a knob\n'
    )
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "anydoc.md").write_text(
        "# d\n\nprose mention of `PFX_PROSE_ONLY` does not count\n\n"
        "| knob | default | meaning |\n|---|---|---|\n"
        "| `PFX_STALE_KNOB` | 1 | gone |\n"
    )
    monkeypatch.setattr(_lint, "REPO", str(tmp_path))
    findings = _lint.check_env_knob_docs()
    codes = {(code, msg.split("'")[1]) for _, _, code, msg in findings}
    assert ("E12", "PFX_REAL_KNOB") in codes
    assert ("E12", "PFX_STALE_KNOB") in codes
    assert len(findings) == 2  # PFX_PREFIX_ and PFX_PROSE_ONLY ignored
    # findings point at real locations
    src = next(f for f in findings if "PFX_REAL_KNOB" in f[3])
    assert src[0].endswith("knobs.py") and src[1] == 3
    stale = next(f for f in findings if "PFX_STALE_KNOB" in f[3])
    assert stale[0].endswith("anydoc.md") and stale[1] > 1
    # documenting the knob clears the source-side finding
    (docs / "anydoc.md").write_text(
        "| knob | default | meaning |\n|---|---|---|\n"
        "| `PFX_REAL_KNOB` | unset | real |\n"
    )
    assert _lint.check_env_knob_docs() == []


@pytest.mark.parametrize("doc", doc_files())
def test_documented_paths_exist_on_the_real_repo(doc):
    """E13, one case a document: every path README.md and docs/*.md name
    in a code span or a fenced block exists."""
    import lint as _lint

    assert _lint.check_doc_paths([doc]) == []


def test_documented_path_drift_is_detected(tmp_path, monkeypatch):
    """E13 hermetically: a retired script, a retired directory and a bare
    name that exists nowhere are findings; an existing file, a glob over
    an existing directory, shorthand for a file under a source directory,
    prose outside code and another project's path are not."""
    import lint as _lint

    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "serve.py").write_text("")
    (tmp_path / "tests").mkdir()
    (tmp_path / "docs").mkdir()
    (tmp_path / "README.md").write_text(
        "# r\n\nrun `tools/serve.py` (`serve.py` for short), `tests/test_*.py`\n"
        "and examples/gone.py in prose; the reference's `ppfleetx/tools/x.py`\n"
        "```\npython tasks/retired/run.py\n```\n"
    )
    (tmp_path / "docs" / "a.md").write_text(
        "see `tools/retired.py`, `retired.py` and `configs/<family>/x.yaml`\n"
    )
    monkeypatch.setattr(_lint, "REPO", str(tmp_path))
    assert _lint.doc_files() == ["README.md", os.path.join("docs", "a.md")]
    found = {
        (os.path.relpath(path, tmp_path), lineno, msg.split("'")[1])
        for path, lineno, code, msg in _lint.check_doc_paths()
        if code == "E13"
    }
    assert found == {
        ("README.md", 6, "tasks/retired/run.py"),
        (os.path.join("docs", "a.md"), 1, "tools/retired.py"),
        (os.path.join("docs", "a.md"), 1, "retired.py"),
        (os.path.join("docs", "a.md"), 1, "configs/<family>/x.yaml"),
    }
    # correcting the documents clears the findings
    (tmp_path / "configs").mkdir()
    (tmp_path / "README.md").write_text("`tools/serve.py`\n")
    (tmp_path / "docs" / "a.md").write_text("`configs/<family>/x.yaml`\n")
    assert _lint.check_doc_paths() == []


def test_an_environment_read_under_ops_or_models_is_a_finding(tmp_path, monkeypatch):
    """E14 hermetically: ``os.environ`` / ``os.getenv`` (or their import
    from ``os``) under paddlefleetx_tpu/ops/ and models/ are findings; the
    same module under utils/ (where the platform pin lives) is not."""
    import lint as _lint

    src = ('"""k."""\nimport os\n\n'
           'BLOCK = int(os.environ.get("SOME_BLOCK", "0"))\n'
           'MODE = os.getenv("SOME_MODE")\n')
    monkeypatch.setattr(_lint, "REPO", str(tmp_path))
    try:
        for sub, want in (("ops", 2), ("models/gpt", 2), ("utils", 0)):
            d = tmp_path / "paddlefleetx_tpu" / sub
            d.mkdir(parents=True)
            (d / "k.py").write_text(src)
            found = [f for f in check_file(str(d / "k.py")) if f[2] == "E14"]
            assert len(found) == want, (sub, found)
        k = tmp_path / "paddlefleetx_tpu" / "ops" / "k.py"
        k.write_text('"""k."""\nfrom os import environ\n\nprint(environ)\n')
        assert "E14" in {c for _, _, c, _ in check_file(str(k))}
    finally:
        _lint._declared_metrics = ...  # check_file cached the tmp repo's (no) METRICS table


def test_nothing_under_ops_or_models_reads_the_environment():
    """E14 on the real repo: a kernel's tile and schedule are chosen beside
    the kernel, from shapes (PR 45's parent read nine names there)."""
    import lint as _lint

    found = [f for d in _lint._NO_ENV_DIRS
             for p in _lint.iter_py_files([os.path.join(REPO, d)])
             for f in check_file(p) if f[2] == "E14"]
    assert found == []


def test_a_removed_name_is_a_finding(tmp_path, monkeypatch):
    """E15 hermetically: a removed environment name in a document, a removed
    ``Model`` key in a recipe and its argument in source are findings;
    the kernels that stay (``pfx_flash_bwd_dq``) and the functions whose
    names only contain one (``_flash_bwd``) are not."""
    import lint as _lint

    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "k.md").write_text("set `PFX_FLASH_BWD=fused` or `PFX_FLASH_BLOCK_K`\n")
    (tmp_path / "README.md").write_text("the kernels are `pfx_flash_bwd_dq` and `pfx_flash_bwd_dkv`\n")  # noqa: E10
    (tmp_path / "configs" / "gpt").mkdir(parents=True)
    (tmp_path / "configs" / "gpt" / "r.yaml").write_text("Model:\n  use_fused_ln: True\n")
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "t.py").write_text(
        "def _flash_bwd(x):\n    return x\n\n\nattention(q, k, v, flash_bwd='fused')\n")
    monkeypatch.setattr(_lint, "REPO", str(tmp_path))
    found = {(os.path.relpath(p, tmp_path), n, msg.split("'")[1])
             for p, n, code, msg in _lint.check_removed_names() if code == "E15"}
    assert found == {
        (os.path.join("docs", "k.md"), 1, "PFX_FLASH_BWD"),
        (os.path.join("docs", "k.md"), 1, "PFX_FLASH_BLOCK_K"),
        (os.path.join("configs", "gpt", "r.yaml"), 2, "use_fused_ln"),
        (os.path.join("tools", "t.py"), 5, "flash_bwd"),
    }


def test_no_removed_name_on_the_real_repo():
    """E15 on the real repo: source, recipes, documents and the Makefile name
    nothing PR 45 removed."""
    import lint as _lint

    assert _lint.check_removed_names() == []
