"""Repo lint gate — stdlib-only (no ruff/flake8 in the image).

The reference enforces code style via a pre-commit stack (pylint, cpplint,
clang-format, a docstring checker: /root/reference/codestyle/); this is the
TPU repo's equivalent, an AST + text checker covering the failure modes that
actually bite:

  E1  syntax error (file does not parse)
  E2  unused import (module scope; __init__.py re-export files exempt)
  E3  bare `except:`
  E4  tab characters in indentation
  E5  trailing whitespace
  E6  missing newline at end of file
  E7  `eval(` / `exec(` call (the reference's name-dispatch-by-eval is a
      design smell SURVEY.md §5.6 explicitly replaces with typed registries)
  E8  mutable default argument (def f(x=[]) / {} / set())
  E9  missing module docstring (package code under paddlefleetx_tpu/ only —
      the reference's docstring-checker analogue, codestyle/ SURVEY §4.3)
  E10 telemetry metric-name lint: every name passed to a registry
      `.counter(` / `.gauge(` / `.histogram(` call — and every string
      literal shaped like a metric name (`^pfx_[a-z0-9_]+$`, exposition
      suffixes _bucket/_sum/_count allowed) — must be declared in THE ONE
      `METRICS` table in paddlefleetx_tpu/utils/telemetry.py, so the
      /metrics namespace cannot fragment the way the per-module stats
      dicts once did (docs/observability.md)
  E11 metrics-docs agreement: every name in the `METRICS` table must
      have a row in the "### Metrics reference" table of
      docs/observability.md, and every row there must name a declared
      metric — the doc drifted from the table twice before this gate.
      (Repo-level check: runs once per invocation, not per file.)
  E12 env-knob docs agreement (two-way, like E11): every `PFX_*` env
      knob referenced in PACKAGE source (paddlefleetx_tpu/, tools/ —
      tests excluded: a test-only helper knob is not an operator
      surface) must appear in a docs knob TABLE row
      (any docs/*.md markdown table line carrying the backticked name),
      and every documented knob must still exist in source — an
      operator reading the tracing/telemetry/serving/fault knob tables
      sees every knob that exists and no knob that does not.
      (Repo-level check: runs once per invocation, not per file.)
  E13 documented paths exist: every repo-relative path that README.md
      or a docs/*.md writes in backticks or in a fenced block, and that
      starts with one of the repo's source directories (or is a bare
      `name.py`), must exist — the documents taught scripts for PRs
      after nothing read them.  A bare `name.py` may be shorthand for a
      file under those directories (`serve.py` for tools/serve.py).
      (Repo-level check: runs once per invocation, not per file.)

  E14 a kernel's tile and schedule are chosen beside the kernel: no
      `os.environ` / `os.getenv` under paddlefleetx_tpu/ops/ or
      paddlefleetx_tpu/models/ (the platform pin lives in
      utils/device.py).  A size or a code path there comes from static
      shapes or from what the kernel observes in its input; a test that
      wants another tile passes an argument.
  E15 removed names stay removed: none of `REMOVED_NAMES` (the
      environment names and `Model` keys PR 45 took out after measuring
      what they selected, with the arguments only they fed; PERF.md
      section 6) in
      source, configs/, docs/, README.md or the Makefile.
      (Repo-level check: runs once per invocation, not per file.)

Suppress a finding with `# noqa` on the offending line.
Usage: python tools/lint.py [paths...]   (default: the whole repo)
"""

import ast
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIRS = ["paddlefleetx_tpu", "tools", "tests", "examples", "tasks"]
DEFAULT_FILES = ["__graft_entry__.py"]


# E10: telemetry metric naming
_METRIC_RE = re.compile(r"^pfx_[a-z0-9_]+$")
_EXPOSITION_SUFFIX = re.compile(r"_(bucket|sum|count)$")
_TELEMETRY_FNS = {"counter", "gauge", "histogram"}
_declared_metrics = ...  # lazy cache; None = telemetry module unavailable


def declared_metrics():
    """Metric names declared in telemetry.METRICS, parsed from the AST
    (never imported: lint stays jax-free).  None when the module or its
    table is missing — the E10 check then degrades to regex-only."""
    global _declared_metrics
    if _declared_metrics is not ...:
        return _declared_metrics
    path = os.path.join(REPO, "paddlefleetx_tpu", "utils", "telemetry.py")
    names = None
    try:
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        for node in tree.body:
            targets = (
                node.targets if isinstance(node, ast.Assign)
                else [node.target] if isinstance(node, ast.AnnAssign) else []
            )
            if any(isinstance(t, ast.Name) and t.id == "METRICS" for t in targets):
                value = node.value
                if isinstance(value, ast.Dict):
                    names = {
                        k.value for k in value.keys
                        if isinstance(k, ast.Constant) and isinstance(k.value, str)
                    }
                break
    except (OSError, SyntaxError):
        names = None
    _declared_metrics = names
    return names


# E11: docs/observability.md "### Metrics reference" table
DOC_METRICS_HEADING = "### Metrics reference"


def documented_metrics(doc_path=None):
    """(names, line_numbers) documented in the Metrics reference table of
    docs/observability.md: rows matching ``| `pfx_...` | ...`` between
    the heading and the next heading.  (None, {}) when the doc or the
    heading is missing — E11 then reports the missing table itself."""
    path = doc_path or os.path.join(REPO, "docs", "observability.md")
    try:
        with open(path) as f:
            lines = f.read().split("\n")
    except OSError:
        return None, {}
    names, linenos = set(), {}
    in_table = False
    for i, ln in enumerate(lines, 1):
        if ln.strip() == DOC_METRICS_HEADING:
            in_table = True
            continue
        if in_table and ln.startswith("#"):
            break  # next heading ends the table's section
        if in_table:
            m = re.match(r"^\|\s*`(pfx_[a-z0-9_]+)`", ln)
            if m:
                names.add(m.group(1))
                linenos.setdefault(m.group(1), i)
    if not in_table:
        return None, {}
    return names, linenos


def check_metrics_docs():
    """E11 (repo-level, once per run): METRICS <-> docs/observability.md
    Metrics-reference agreement, both directions."""
    declared = declared_metrics()
    if declared is None:
        return []  # no table to check against (E10 degrades the same way)
    doc_path = os.path.join(REPO, "docs", "observability.md")
    tel_path = os.path.join(
        REPO, "paddlefleetx_tpu", "utils", "telemetry.py"
    )
    documented, linenos = documented_metrics(doc_path)
    if documented is None:
        return [(doc_path, 1, "E11",
                 f"missing '{DOC_METRICS_HEADING}' table documenting the "
                 "METRICS names")]
    findings = []
    for name in sorted(declared - documented):
        findings.append((
            tel_path, 1, "E11",
            f"metric '{name}' is declared in METRICS but has no row in "
            f"docs/observability.md '{DOC_METRICS_HEADING}'",
        ))
    for name in sorted(documented - declared):
        findings.append((
            doc_path, linenos.get(name, 1), "E11",
            f"documented metric '{name}' is not declared in "
            "telemetry.METRICS (stale doc row?)",
        ))
    return findings


# E12: env-knob docs agreement.  A knob is a FULL name (no trailing
# underscore: `f"PFX_RETRY_{field}"`-style prefixes are building blocks,
# not knobs); the docs side accepts any markdown table row in docs/*.md
# carrying the backticked name.
_ENV_KNOB_RE = re.compile(r"^PFX_[A-Z0-9]+(_[A-Z0-9]+)*$")
# source scope: operator-facing code only (tests set knobs too, but a
# test-only helper name is not an operator surface)
_ENV_KNOB_DIRS = ["paddlefleetx_tpu", "tools"]


def source_env_knobs():
    """name -> (file, lineno) of every PFX_* string literal in package
    source (first sighting wins)."""
    knobs = {}
    paths = [os.path.join(REPO, d) for d in _ENV_KNOB_DIRS]
    for path in iter_py_files(paths):
        try:
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
        except (OSError, SyntaxError):
            continue  # E1 reports it
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and _ENV_KNOB_RE.match(node.value)
                and node.value not in REMOVED_NAMES  # E15's own table
            ):
                knobs.setdefault(node.value, (path, node.lineno))
    return knobs


def documented_env_knobs():
    """(names, first-sighting {name: (file, lineno)}) for every
    backticked PFX_* name on a markdown TABLE row in docs/*.md."""
    names, where = set(), {}
    docs_dir = os.path.join(REPO, "docs")
    try:
        files = sorted(os.listdir(docs_dir))
    except OSError:
        return names, where
    row_re = re.compile(r"`(PFX_[A-Z0-9_]+)`")
    for fn in files:
        if not fn.endswith(".md"):
            continue
        path = os.path.join(docs_dir, fn)
        try:
            with open(path) as f:
                lines = f.read().split("\n")
        except OSError:
            continue
        for i, ln in enumerate(lines, 1):
            if not ln.lstrip().startswith("|"):
                continue  # knob TABLE rows only, not prose mentions
            for m in row_re.finditer(ln):
                name = m.group(1)
                if _ENV_KNOB_RE.match(name):
                    names.add(name)
                    where.setdefault(name, (path, i))
    return names, where


def check_env_knob_docs():
    """E12 (repo-level, once per run): PFX_* knobs in source <-> docs
    knob tables, both directions."""
    knobs = source_env_knobs()
    documented, where = documented_env_knobs()
    findings = []
    for name in sorted(set(knobs) - documented):
        path, lineno = knobs[name]
        findings.append((
            path, lineno, "E12",
            f"env knob '{name}' is referenced in source but has no row "
            "in any docs/*.md knob table — document it "
            "(tracing/telemetry/serving/fault docs)",
        ))
    for name in sorted(documented - set(knobs)):
        path, lineno = where[name]
        findings.append((
            path, lineno, "E12",
            f"documented env knob '{name}' is not referenced anywhere "
            "in source (stale doc row?)",
        ))
    return findings


# E13: documented paths exist.  ``benchmarks`` is a root no more: listed
# so that a document naming the retired tree is a finding.
_DOC_PATH_ROOTS = (
    "paddlefleetx_tpu", "tools", "tests", "benchmarks", "pfx_bench",  # noqa: E10 — a directory, not a metric
    "configs", "projects", "tasks", "examples",
)
_DOC_PATH_RE = re.compile(
    r"(?<![\w./~-])((?:%s)/[\w./*<>{}$\[\]-]*|[A-Za-z_][\w-]*\.py)(?![\w/])"
    % "|".join(_DOC_PATH_ROOTS)
)
_DOC_PATH_WILD = re.compile(r"[*<{$\[]")


def doc_files():
    """README.md and docs/*.md, repo-relative, sorted."""
    try:
        docs = sorted(
            os.path.join("docs", fn)
            for fn in os.listdir(os.path.join(REPO, "docs"))
            if fn.endswith(".md")
        )
    except OSError:
        docs = []
    return ["README.md"] + docs


def documented_paths(doc):
    """[(lineno, path)] for every path-shaped token inside a code span or
    a fenced block of ``doc`` (repo-relative): one under _DOC_PATH_ROOTS,
    or a bare ``name.py``."""
    with open(os.path.join(REPO, doc)) as f:
        lines = f.read().split("\n")
    found, fenced = [], False
    for i, ln in enumerate(lines, 1):
        if ln.lstrip().startswith("```"):
            fenced = not fenced
            continue
        spans = [ln] if fenced else re.findall(r"`([^`]+)`", ln)
        for span in spans:
            for m in _DOC_PATH_RE.finditer(span):
                found.append((i, m.group(1).rstrip(".,:;")))
    return found


def check_doc_paths(docs=None):
    """E13 (repo-level, once per run): the paths the documents name exist.
    A glob or placeholder (`tests/test_*.py`, `configs/<family>/`) is held
    to its directory; a bare `name.py` to the root or, as shorthand, to any
    file of that name under the source directories."""
    basenames = None
    findings = []
    for doc in doc_files() if docs is None else docs:
        for lineno, path in documented_paths(doc):
            if "/" not in path:
                if os.path.exists(os.path.join(REPO, path)):
                    continue
                if basenames is None:
                    basenames = {
                        os.path.basename(p) for p in iter_py_files(
                            [os.path.join(REPO, d) for d in _DOC_PATH_ROOTS])
                    }
                exists = path in basenames
            else:
                fixed = _DOC_PATH_WILD.split(path, 1)
                target = path if len(fixed) == 1 else os.path.dirname(fixed[0])
                exists = os.path.exists(os.path.join(REPO, target))
            if not exists:
                findings.append((
                    os.path.join(REPO, doc), lineno, "E13",
                    f"documented path '{path}' does not exist (retired or "
                    "renamed? correct the document)",
                ))
    return findings


# E14: directories whose code may not read the environment
_NO_ENV_DIRS = (os.path.join("paddlefleetx_tpu", "ops"), os.path.join("paddlefleetx_tpu", "models"))

# E15: what PR 45 removed (each was a second code path or a size that no
# recipe, workload or cell set; the chip pairs are in PERF.md section 6)
REMOVED_NAMES = (
    "PFX_FLASH_BWD", "PFX_FLASH_BLOCK", "PFX_FLASH_BLOCK_K", "PFX_DECODE_BLOCK",
    "PFX_KV_DTYPE", "PFX_KV_BLOCK", "PFX_TOPP_K", "PFX_DISPATCH_AHEAD",
    "PFX_SCHED_QUANTUM",
    "flash_block", "flash_bwd", "use_fused_ln", "scan_unroll",
    "recompute_names", "recompute_name_tuple",
)
_REMOVED_RE = re.compile(
    r"(?<![A-Za-z0-9_])(%s)(?![A-Za-z0-9_])" % "|".join(REMOVED_NAMES))
# the table above and its test's fixtures name them on purpose
_REMOVED_EXEMPT = (os.path.join("tools", "lint.py"), os.path.join("tests", "test_lint.py"))


def check_removed_names():
    """E15 (repo-level, once per run): no file of the source tree, the
    recipes or the documents names a removed thing."""
    paths = list(iter_py_files(
        [os.path.join(REPO, d) for d in DEFAULT_DIRS]
        + [os.path.join(REPO, f) for f in DEFAULT_FILES + ["chip_smoke.py"]]))
    paths += [os.path.join(REPO, d) for d in doc_files() + ["Makefile"]]
    for root, _, files in os.walk(os.path.join(REPO, "configs")):
        paths += [os.path.join(root, f) for f in sorted(files)]
    findings = []
    for path in paths:
        if os.path.relpath(path, REPO) in _REMOVED_EXEMPT:
            continue
        try:
            with open(path, errors="replace") as f:
                lines = f.read().split("\n")
        except OSError:
            continue
        for i, ln in enumerate(lines, 1):
            for name in sorted(set(_REMOVED_RE.findall(ln))):
                findings.append((
                    path, i, "E15",
                    f"'{name}' was removed (tools/lint.py REMOVED_NAMES): a "
                    "tile or a schedule is chosen beside its kernel, from shapes",
                ))
    return findings


def iter_py_files(paths):
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            yield p
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs if d not in
                           ("__pycache__", ".jax_cache", "build", ".git")]
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


class ImportVisitor(ast.NodeVisitor):
    """Collect module-scope imported names and every name USED anywhere."""

    def __init__(self):
        self.imports = {}  # name -> (lineno, shown)
        self.used = set()
        self._depth = 0

    def visit_Import(self, node):
        if self._depth == 0:
            for a in node.names:
                name = a.asname or a.name.split(".")[0]
                self.imports[name] = (node.lineno, a.asname or a.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        if self._depth == 0 and node.module != "__future__":
            for a in node.names:
                if a.name == "*":
                    continue
                name = a.asname or a.name
                self.imports[name] = (node.lineno, name)
        self.generic_visit(node)

    def _scoped(self, node):
        self._depth += 1
        self.generic_visit(node)
        self._depth -= 1

    visit_FunctionDef = visit_AsyncFunctionDef = visit_ClassDef = _scoped

    def visit_Name(self, node):
        self.used.add(node.id)
        self.generic_visit(node)

    def visit_Attribute(self, node):
        # mark the root of dotted access (jax.numpy -> jax)
        n = node
        while isinstance(n, ast.Attribute):
            n = n.value
        if isinstance(n, ast.Name):
            self.used.add(n.id)
        self.generic_visit(node)


def check_file(path):
    findings = []
    with open(path, "rb") as f:
        raw = f.read()
    try:
        text = raw.decode("utf-8")
    except UnicodeDecodeError as e:
        return [(path, 1, "E1", f"not utf-8: {e}")]

    lines = text.split("\n")
    noqa = {i + 1 for i, ln in enumerate(lines) if "# noqa" in ln}

    def add(lineno, code, msg):
        if lineno not in noqa:
            findings.append((path, lineno, code, msg))

    try:
        tree = ast.parse(text, filename=path)
    except SyntaxError as e:
        return [(path, e.lineno or 1, "E1", f"syntax error: {e.msg}")]

    # E9: package modules document themselves (tests/tools/benches exempt)
    rel = os.path.relpath(path, REPO)
    if rel.startswith("paddlefleetx_tpu") and ast.get_docstring(tree) is None:
        add(1, "E9", "missing module docstring")

    # E14: ops/ and models/ choose from shapes, never from the environment
    if rel.startswith(_NO_ENV_DIRS):
        for node in ast.walk(tree):
            reads = (
                isinstance(node, ast.Attribute) and node.attr in ("environ", "getenv")
                and isinstance(node.value, ast.Name) and node.value.id == "os"
            ) or (
                isinstance(node, ast.ImportFrom) and node.module == "os"
                and any(a.name in ("environ", "getenv") for a in node.names)
            )
            if reads:
                add(node.lineno, "E14",
                    "environment read under ops/ or models/: choose the tile or the "
                    "path from static shapes beside the kernel (or take an argument)")

    # E2 unused imports (skip __init__.py: re-exports are the point)
    if os.path.basename(path) != "__init__.py":
        v = ImportVisitor()
        v.visit(tree)
        # names referenced inside string ANNOTATIONS and __all__ only —
        # harvesting every string constant would let a docstring mentioning
        # "os" mask a genuinely unused `import os`
        import re as _re

        def _id_words(s):
            return _re.findall(r"[A-Za-z_][A-Za-z0-9_]*", s[:2000])

        string_refs = set()
        ann_roots = []
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = node.args
                for a in (
                    args.args + args.posonlyargs + args.kwonlyargs
                    + ([args.vararg] if args.vararg else [])
                    + ([args.kwarg] if args.kwarg else [])
                ):
                    if a.annotation is not None:
                        ann_roots.append(a.annotation)
                if node.returns is not None:
                    ann_roots.append(node.returns)
            elif isinstance(node, ast.AnnAssign):
                ann_roots.append(node.annotation)
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "__all__":
                        ann_roots.append(node.value)
        for root in ann_roots:
            for node in ast.walk(root):
                if isinstance(node, ast.Constant) and isinstance(node.value, str):
                    string_refs.update(_id_words(node.value))
        for name, (lineno, shown) in v.imports.items():
            if name not in v.used and name not in string_refs:
                add(lineno, "E2", f"unused import '{shown}'")

    # E10: metric names — call-site check (any name handed to a registry
    # accessor) + literal check (any metric-shaped string constant)
    declared = declared_metrics()
    flagged_metrics = set()

    def _check_metric_name(lineno, name):
        if (lineno, name) in flagged_metrics:
            return
        if not _METRIC_RE.match(name):
            flagged_metrics.add((lineno, name))
            add(lineno, "E10",
                f"metric name '{name}' does not match ^pfx_[a-z0-9_]+$")
        elif declared is not None and _EXPOSITION_SUFFIX.sub("", name) not in declared and name not in declared:
            flagged_metrics.add((lineno, name))
            add(lineno, "E10",
                f"metric '{name}' not declared in telemetry.METRICS "
                "(the one namespace table — declare it there)")

    # a Pallas kernel's ``name=`` is its label in the device trace
    # (pfx_flash_fwd, ...): shaped like a metric name, not one
    kernel_names = {
        id(kw.value)
        for node in ast.walk(tree)
        if isinstance(node, ast.Call)
        and getattr(node.func, "attr", getattr(node.func, "id", "")) == "pallas_call"
        for kw in node.keywords if kw.arg == "name"
    }

    for node in ast.walk(tree):
        # E3 bare except
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            add(node.lineno, "E3", "bare 'except:' (catch a class)")
        # E10 telemetry registry call sites
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _TELEMETRY_FNS
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            _check_metric_name(node.args[0].lineno, node.args[0].value)
        # E10 metric-shaped string literals anywhere
        if (
            isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and _METRIC_RE.match(node.value)
            and id(node) not in kernel_names
        ):
            _check_metric_name(node.lineno, node.value)
        # E7 eval/exec
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id in ("eval", "exec")
        ):
            add(node.lineno, "E7", f"'{node.func.id}()' call (use a typed registry)")
        # E8 mutable default args (literals and bare set()/dict()/list() calls)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            for d in list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None
            ]:
                mutable = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                    isinstance(d, ast.Call)
                    and isinstance(d.func, ast.Name)
                    and d.func.id in ("set", "dict", "list")
                )
                if mutable:
                    add(d.lineno, "E8", "mutable default argument")

    # text-level checks
    for i, ln in enumerate(lines, 1):
        stripped_nl = ln.rstrip("\r")
        indent = stripped_nl[: len(stripped_nl) - len(stripped_nl.lstrip())]
        if "\t" in indent:
            add(i, "E4", "tab in indentation")
        if stripped_nl != stripped_nl.rstrip() and stripped_nl.strip():
            add(i, "E5", "trailing whitespace")
    if text and not text.endswith("\n"):
        add(len(lines), "E6", "missing newline at end of file")

    return findings


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    paths = argv or (
        [os.path.join(REPO, d) for d in DEFAULT_DIRS]
        + [os.path.join(REPO, f) for f in DEFAULT_FILES]
    )
    all_findings = []
    n_files = 0
    for path in iter_py_files(paths):
        n_files += 1
        all_findings.extend(check_file(path))
    # E11/E12/E13/E15 are repo-level invariants (code <-> documents),
    # checked once per run rather than per file
    all_findings.extend(check_metrics_docs())
    all_findings.extend(check_env_knob_docs())
    all_findings.extend(check_doc_paths())
    all_findings.extend(check_removed_names())
    for path, lineno, code, msg in sorted(all_findings):
        rel = os.path.relpath(path, REPO)
        print(f"{rel}:{lineno}: {code} {msg}")
    if all_findings:
        print(f"\n{len(all_findings)} finding(s) in {n_files} files")
        return 1
    print(f"lint clean: {n_files} files")
    return 0


if __name__ == "__main__":
    sys.exit(main())
