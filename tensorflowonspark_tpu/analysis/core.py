"""graftcheck core: rule registry, file walker, suppressions, baseline, CLI.

Stdlib-only (``ast`` + ``argparse`` + ``json``) so the semantic lint tier
runs in environments with no package index — the same constraint that made
``scripts/lint.py`` a from-scratch style linter instead of pycodestyle.
This module owns everything rule-agnostic:

- the ``Rule`` registry (``@register``) that style and semantic analyzers
  plug into,
- one shared walker that reads + parses every file exactly once and hands
  each rule a ``FileContext``,
- a ``Project`` view for cross-file facts (mesh axes declared in
  ``parallel/mesh.py``, the repo-wide set of Pallas kernel entry points),
- suppression comments (``# graftcheck: disable=RULE[,RULE...]`` on the
  offending line, ``disable-next-line`` on the line above, or
  ``disable-file`` anywhere in the file; style rules also honor the legacy
  ``# noqa``),
- a baseline file of grandfathered finding fingerprints (new findings fail,
  fixed findings are reported as stale so the baseline only shrinks),
- text/JSON reporters and the argparse ``main`` used by both
  ``scripts/graftcheck.py`` and ``python -m tensorflowonspark_tpu.analysis``.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import os
import re
import sys
import time

# Paths scanned when the CLI is invoked with no arguments (mirrors the old
# scripts/lint.py default surface).  Semantic rules additionally restrict
# themselves to the package — test/example files build ad-hoc meshes and
# deliberately-broken fixtures that would drown the signal.
DEFAULT_PATHS = [
    "tensorflowonspark_tpu", "tests", "examples", "scripts",
    "chip_smoke.py", "__graft_entry__.py",
]
DEFAULT_BASELINE = os.path.join("scripts", "graftcheck_baseline.json")

PACKAGE_DIR = "tensorflowonspark_tpu"

_SUPPRESS_RE = re.compile(
    r"#\s*graftcheck:\s*(disable(?:-next-line|-file)?)\s*=\s*"
    r"([A-Za-z0-9_\-]+(?:\s*,\s*[A-Za-z0-9_\-]+)*)")


@dataclasses.dataclass
class Finding:
    path: str
    line: int
    rule: str
    message: str

    def fingerprint(self, lines):
        """Stable identity for the baseline: path + rule + the stripped
        source line, so findings survive unrelated line-number drift."""
        text = ""
        if 1 <= self.line <= len(lines):
            text = lines[self.line - 1].strip()
        return f"{_posix(self.path)}::{self.rule}::{text}"

    def as_dict(self):
        return {"path": _posix(self.path), "line": self.line,
                "rule": self.rule, "message": self.message}


def _posix(path):
    return path.replace(os.sep, "/")


class Rule:
    """One named check.  Subclasses set ``name``/``description`` and yield
    ``Finding``s from ``check(ctx)``.  ``scope`` is ``"all"`` (every scanned
    file) or ``"package"`` (only files under ``tensorflowonspark_tpu/``);
    ``kind`` is ``"style"`` or ``"semantic"`` (style rules honor ``# noqa``
    and are what ``scripts/lint.py`` runs)."""

    name = ""
    description = ""
    scope = "package"
    kind = "semantic"

    def applies(self, ctx):
        if self.scope == "all":
            return True
        parts = _posix(ctx.path).split("/")
        return PACKAGE_DIR in parts or ctx.path in (
            "chip_smoke.py", "__graft_entry__.py")

    def check(self, ctx):  # pragma: no cover - abstract
        raise NotImplementedError


REGISTRY = {}


def register(cls):
    """Class decorator adding a rule to the global registry."""
    rule = cls()
    if not rule.name:
        raise ValueError(f"rule {cls.__name__} has no name")
    REGISTRY[rule.name] = rule
    return cls


@dataclasses.dataclass
class FileContext:
    path: str
    src: str
    lines: list
    tree: object          # ast.Module, or None when the file failed to parse
    project: object = None
    # line -> set of rule names disabled on that line ("all" disables all)
    suppressions: dict = dataclasses.field(default_factory=dict)
    file_suppressions: set = dataclasses.field(default_factory=set)
    noqa_lines: set = dataclasses.field(default_factory=set)

    @classmethod
    def from_source(cls, src, path="<string>", project=None):
        lines = src.splitlines()
        try:
            tree = ast.parse(src)
            err = None
        except SyntaxError as e:
            tree, err = None, e
        ctx = cls(path=path, src=src, lines=lines, tree=tree, project=project)
        ctx.syntax_error = err
        ctx._scan_suppressions()
        return ctx

    def _scan_suppressions(self):
        for i, ln in enumerate(self.lines, start=1):
            if "# noqa" in ln:
                self.noqa_lines.add(i)
            m = _SUPPRESS_RE.search(ln)
            if not m:
                continue
            mode, rules = m.group(1), {r.strip() for r in m.group(2).split(",")}
            if mode == "disable":
                self.suppressions.setdefault(i, set()).update(rules)
            elif mode == "disable-next-line":
                self.suppressions.setdefault(i + 1, set()).update(rules)
            else:  # disable-file
                self.file_suppressions.update(rules)

    def suppressed(self, finding, rule):
        dis = self.suppressions.get(finding.line, ())
        if finding.rule in dis or "all" in dis:
            return True
        if finding.rule in self.file_suppressions or "all" in self.file_suppressions:
            return True
        if rule is not None and rule.kind == "style" and finding.line in self.noqa_lines:
            return True
        return False


class Project:
    """Cross-file facts shared by the semantic rules.

    ``mesh_axes`` — the physical mesh axis names.  Parsed lazily from the
    scanned file ending in ``parallel/mesh.py`` (module-level ``AXIS_* =
    "name"`` constants), falling back to that path on disk relative to the
    scan root; tests inject a set directly.

    ``pallas_entries`` — every top-level function name defined in a scanned
    module whose source contains a ``pallas_call``.  Deliberately coarse:
    a sharded-jit wrapper anywhere in the repo that calls one of these by
    name reaches a custom call GSPMD cannot partition.
    """

    def __init__(self, files=None, root=".", mesh_axes=None):
        self.files = files if files is not None else []
        self.root = root
        self._mesh_axes = mesh_axes
        self._pallas_entries = None

    @property
    def mesh_axes(self):
        if self._mesh_axes is None:
            self._mesh_axes = self._find_mesh_axes()
        return self._mesh_axes

    def _find_mesh_axes(self):
        for ctx in self.files:
            if _posix(ctx.path).endswith("parallel/mesh.py") and ctx.tree is not None:
                return _parse_mesh_axes(ctx.tree)
        fallback = os.path.join(self.root, PACKAGE_DIR, "parallel", "mesh.py")
        if os.path.isfile(fallback):
            try:
                with open(fallback, encoding="utf-8") as f:
                    return _parse_mesh_axes(ast.parse(f.read()))
            except (OSError, SyntaxError):
                pass
        return set()

    @property
    def pallas_entries(self):
        if self._pallas_entries is None:
            names = set()
            for ctx in self.files:
                if ctx.tree is None or "pallas_call" not in ctx.src:
                    continue
                if not _module_has_pallas_call(ctx.tree):
                    continue
                for node in ctx.tree.body:
                    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        names.add(node.name)
            self._pallas_entries = names
        return self._pallas_entries


def _parse_mesh_axes(tree):
    axes = set()
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if (isinstance(tgt, ast.Name) and tgt.id.startswith("AXIS_")
                        and isinstance(node.value, ast.Constant)
                        and isinstance(node.value.value, str)):
                    axes.add(node.value.value)
    return axes


def _module_has_pallas_call(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            if (isinstance(fn, ast.Name) and fn.id == "pallas_call") or \
               (isinstance(fn, ast.Attribute) and fn.attr == "pallas_call"):
                return True
    return False


# ---------------------------------------------------------------------------
# walker


def iter_py(paths, *, missing="error"):
    """Yield .py files under ``paths``.  An explicitly named path that does
    not exist raises ``FileNotFoundError`` (``missing="error"``) instead of
    being silently skipped — the old lint.py walked past typos and reported
    a clean run."""
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                yield p
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in {"__pycache__", ".git", ".tox",
                                              "build", "dist"})
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)
        elif missing == "error":
            raise FileNotFoundError(f"no such file or directory: {p}")


def load_project(paths, root="."):
    project = Project(root=root)
    for path in iter_py(paths):
        try:
            with open(path, encoding="utf-8") as f:
                src = f.read()
        except OSError as e:
            raise FileNotFoundError(f"cannot read {path}: {e}") from e
        project.files.append(FileContext.from_source(src, path=path,
                                                     project=project))
    return project


def run_rules(project, rules, stats=None):
    """Run ``rules`` over every file in ``project``; returns the unsuppressed
    findings sorted by (path, line, rule).  When ``stats`` is a dict it is
    filled with ``rule name -> [seconds, finding count]`` accumulated across
    files (rule families sharing a cached per-file pass charge the shared
    work to whichever member runs first)."""
    findings = []
    for ctx in project.files:
        if ctx.tree is None:
            e = ctx.syntax_error
            f = Finding(ctx.path, e.lineno or 1, "syntax-error",
                        f"syntax error: {e.msg}")
            findings.append(f)
            continue
        for rule in rules:
            if not rule.applies(ctx):
                continue
            t0 = time.perf_counter() if stats is not None else 0.0
            n = 0
            for f in rule.check(ctx):
                if not ctx.suppressed(f, rule):
                    findings.append(f)
                    n += 1
            if stats is not None:
                entry = stats.setdefault(rule.name, [0.0, 0])
                entry[0] += time.perf_counter() - t0
                entry[1] += n
    findings.sort(key=lambda f: (_posix(f.path), f.line, f.rule))
    return findings


def analyze_source(src, path="mod.py", rules=None, mesh_axes=None):
    """Test/embedding helper: run rules over one in-memory source string."""
    project = Project(mesh_axes=mesh_axes)
    ctx = FileContext.from_source(src, path=path, project=project)
    project.files.append(ctx)
    if rules is None:
        selected = [r for r in REGISTRY.values()]
    else:
        selected = [REGISTRY[name] for name in rules]
    return run_rules(project, selected)


# ---------------------------------------------------------------------------
# baseline


def load_baseline(path):
    if not path or not os.path.isfile(path):
        return {}
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    counts = {}
    for fp in data.get("findings", []):
        counts[fp] = counts.get(fp, 0) + 1
    return counts


def save_baseline(path, findings, line_map):
    fps = sorted(f.fingerprint(line_map.get(f.path, [])) for f in findings)
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": 1, "findings": fps}, f, indent=2)
        f.write("\n")


def apply_baseline(findings, baseline, line_map):
    """Split findings into (new, grandfathered) against baseline counts and
    return the stale baseline fingerprints (fixed findings the baseline
    still lists — the only allowed baseline edit is deleting those)."""
    remaining = dict(baseline)
    new, old = [], []
    for f in findings:
        fp = f.fingerprint(line_map.get(f.path, []))
        if remaining.get(fp, 0) > 0:
            remaining[fp] -= 1
            old.append(f)
        else:
            new.append(f)
    stale = sorted(fp for fp, n in remaining.items() if n > 0)
    return new, old, stale


# ---------------------------------------------------------------------------
# CLI


def _select_rules(select, skip, style_only):
    rules = list(REGISTRY.values())
    if style_only:
        rules = [r for r in rules if r.kind == "style"]
    if select:
        wanted = {s.strip() for s in select.split(",") if s.strip()}
        unknown = wanted - set(REGISTRY)
        if unknown:
            raise SystemExit(f"graftcheck: unknown rule(s): {', '.join(sorted(unknown))}")
        rules = [r for r in rules if r.name in wanted]
    if skip:
        dropped = {s.strip() for s in skip.split(",") if s.strip()}
        rules = [r for r in rules if r.name not in dropped]
    return rules


def sarif_report(findings, rules=None):
    """SARIF 2.1.0 document for `findings` (CI annotates these per line;
    GitHub/VS Code both ingest this shape natively)."""
    rules = rules if rules is not None else list(REGISTRY.values())
    seen_rules = {f.rule for f in findings}
    rule_objs = [{
        "id": r.name,
        "shortDescription": {"text": r.description or r.name},
        # each rule is documented under a `.. _rule-<name>:` anchor in
        # the analysis guide; tests/test_analysis.py asserts the link
        # resolves for every registered rule
        "helpUri": f"docs/source/analysis.rst#rule-{r.name}",
        "properties": {"kind": r.kind, "scope": r.scope},
    } for r in sorted(rules, key=lambda r: r.name)
        if r.name in seen_rules or not findings]
    results = [{
        "ruleId": f.rule,
        "level": "warning",
        "message": {"text": f.message},
        "locations": [{
            "physicalLocation": {
                "artifactLocation": {"uri": _posix(f.path),
                                     "uriBaseId": "SRCROOT"},
                "region": {"startLine": f.line},
            },
        }],
    } for f in findings]
    return {
        "$schema": "https://json.schemastore.org/sarif-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "graftcheck",
                "informationUri":
                    "docs/source/analysis.rst",
                "rules": rule_objs,
            }},
            "originalUriBaseIds": {"SRCROOT": {"uri": "file:///./"}},
            "results": results,
        }],
    }


def changed_files(root=".", base=None):
    """Posix-relative paths with uncommitted changes (worktree + index)
    plus untracked files, or None when git is unavailable / not a repo.
    With ``base``, also includes files changed between the merge-base of
    ``base`` and HEAD (what a PR diff shows)."""
    import subprocess
    out = set()
    cmds = [["git", "diff", "--name-only", "HEAD"],
            ["git", "ls-files", "--others", "--exclude-standard"]]
    if base:
        cmds.append(["git", "diff", "--name-only", f"{base}...HEAD"])
    for cmd in cmds:
        try:
            res = subprocess.run(cmd, cwd=root, capture_output=True,
                                 text=True, check=True)
        except (OSError, subprocess.CalledProcessError):
            return None
        out.update(ln.strip() for ln in res.stdout.splitlines()
                   if ln.strip())
    return out


def print_stats(stats, file=None):
    """Per-rule wall-time/finding-count table (sorted slowest first) —
    makes the <10 s repo-scan budget attributable per analyzer."""
    file = file or sys.stdout
    total_s = sum(s for s, _ in stats.values())
    total_n = sum(n for _, n in stats.values())
    print("graftcheck rule stats", file=file)
    print(f"{'rule':30s} {'time':>9s} {'findings':>9s}", file=file)
    for name, (secs, n) in sorted(stats.items(),
                                  key=lambda kv: -kv[1][0]):
        print(f"{name:30s} {secs * 1000.0:7.1f}ms {n:9d}", file=file)
    print(f"{'total':30s} {total_s * 1000.0:7.1f}ms {total_n:9d}",
          file=file)


def main(argv=None):
    # Importing the rule modules populates REGISTRY; done here so embedding
    # code can import core without pulling every analyzer.
    from tensorflowonspark_tpu.analysis import (  # noqa
        hostsync, lifecycle, locks, pallas_tiles, recompile, shardlint,
        style, threads, tracer, wireproto)

    ap = argparse.ArgumentParser(
        prog="graftcheck",
        description="JAX/TPU-aware stdlib static analysis (tracer hazards, "
                    "sharding lint, Pallas tile checks, lock discipline, "
                    "thread-role race analysis, jit-recompile lint, "
                    "hot-path host-sync checks, style).")
    ap.add_argument("paths", nargs="*", help="files or directories "
                    f"(default: {' '.join(DEFAULT_PATHS)})")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as JSON (same as --format json)")
    ap.add_argument("--format", default=None, dest="fmt",
                    choices=("text", "json", "sarif", "protocol"),
                    help="report format on stdout (default text); "
                    "'protocol' dumps the extracted wire contract "
                    "(endpoints, client emissions, message planes, "
                    "propagated fields) as JSON instead of findings")
    ap.add_argument("--sarif-output", default=None, metavar="FILE",
                    help="additionally write a SARIF 2.1.0 report to FILE "
                    "(whatever --format is; CI annotation side channel)")
    ap.add_argument("--output", default=None, metavar="FILE",
                    help="with --format protocol: write the contract dump "
                    "to FILE instead of stdout (tox commands cannot "
                    "shell-redirect)")
    ap.add_argument("--changed-only", action="store_true",
                    help="report findings only for files git sees as "
                    "changed/untracked (full project still loads, so "
                    "cross-file rules keep their context)")
    ap.add_argument("--changed-base", default=None, metavar="REF",
                    help="with --changed-only: also treat files changed "
                    "since merge-base(REF, HEAD) as changed (PR diffs; "
                    "e.g. --changed-base origin/main)")
    ap.add_argument("--stats", action="store_true",
                    help="print a per-rule wall-time and finding-count "
                    "table after the report (rule families sharing one "
                    "cached pass charge it to the member that runs first)")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default: {DEFAULT_BASELINE} if present)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore any baseline file")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline with the current findings "
                    "(shrink-only: refuses to ADD fingerprints unless "
                    "--grow-baseline is also given)")
    ap.add_argument("--grow-baseline", action="store_true",
                    help="with --update-baseline: allow the baseline to "
                    "gain fingerprints (bootstrap/grandfathering only)")
    ap.add_argument("--select", default=None, metavar="RULES",
                    help="comma-separated rule names to run")
    ap.add_argument("--skip", default=None, metavar="RULES",
                    help="comma-separated rule names to skip")
    ap.add_argument("--style-only", action="store_true",
                    help="run only the style tier (what scripts/lint.py runs)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--strict", action="store_true",
                    help="accepted for scripts/lint.py compatibility (no-op)")
    args = ap.parse_args(argv)
    fmt = args.fmt or ("json" if args.as_json else "text")

    if args.list_rules:
        for name in sorted(REGISTRY):
            r = REGISTRY[name]
            print(f"{name:28s} [{r.kind}/{r.scope}] {r.description}")
        return 0

    rules = _select_rules(args.select, args.skip, args.style_only)

    paths = args.paths or [p for p in DEFAULT_PATHS if os.path.exists(p)]
    try:
        project = load_project(paths)
    except FileNotFoundError as e:
        print(f"graftcheck: error: {e}", file=sys.stderr)
        return 2

    if fmt == "protocol":
        from tensorflowonspark_tpu.analysis import wireproto as _wp
        doc = json.dumps(_wp.protocol_dump(project), indent=2)
        if args.output:
            os.makedirs(os.path.dirname(args.output) or ".", exist_ok=True)
            with open(args.output, "w", encoding="utf-8") as f:
                f.write(doc + "\n")
            print(f"graftcheck: wire-protocol dump -> {args.output}")
        else:
            print(doc)
        return 0

    stats = {} if args.stats else None
    findings = run_rules(project, rules, stats=stats)
    line_map = {ctx.path: ctx.lines for ctx in project.files}

    if args.changed_only:
        changed = changed_files(base=args.changed_base)
        if changed is None:
            print("graftcheck: error: --changed-only needs a git checkout",
                  file=sys.stderr)
            return 2
        findings = [f for f in findings if _posix(f.path) in changed]

    baseline_path = args.baseline
    if baseline_path is None and not args.no_baseline:
        baseline_path = DEFAULT_BASELINE if os.path.isfile(DEFAULT_BASELINE) else None
    if args.no_baseline:
        baseline_path = None

    if args.update_baseline:
        target = args.baseline or DEFAULT_BASELINE
        # shrink-only contract: grandfathering NEW findings into the
        # baseline is a reviewed, explicit act (--grow-baseline), never a
        # side effect of refreshing it
        current = load_baseline(target)
        added = []
        pool = dict(current)
        for f in findings:
            fp = f.fingerprint(line_map.get(f.path, []))
            if pool.get(fp, 0) > 0:
                pool[fp] -= 1
            else:
                added.append(fp)
        if added and not args.grow_baseline:
            print(f"graftcheck: error: refusing to ADD {len(added)} "
                  f"fingerprint(s) to {target} (shrink-only baseline; "
                  "fix the findings or pass --grow-baseline):",
                  file=sys.stderr)
            for fp in sorted(added):
                print(f"  {fp}", file=sys.stderr)
            return 2
        save_baseline(target, findings, line_map)
        print(f"graftcheck: wrote {len(findings)} finding(s) to {target}")
        return 0

    baseline = load_baseline(baseline_path)
    new, old, stale = apply_baseline(findings, baseline, line_map)

    if args.sarif_output:
        sarif_dir = os.path.dirname(args.sarif_output)
        if sarif_dir:
            os.makedirs(sarif_dir, exist_ok=True)
        with open(args.sarif_output, "w", encoding="utf-8") as fh:
            json.dump(sarif_report(new, rules), fh, indent=2)
            fh.write("\n")

    if fmt == "sarif":
        print(json.dumps(sarif_report(new, rules), indent=2))
    elif fmt == "json":
        print(json.dumps({
            "findings": [f.as_dict() for f in new],
            "baselined": [f.as_dict() for f in old],
            "stale_baseline": stale,
        }, indent=2))
    else:
        for f in new:
            print(f"{_posix(f.path)}:{f.line}: [{f.rule}] {f.message}")
        if stale:
            print(f"graftcheck: {len(stale)} stale baseline entr"
                  f"{'y' if len(stale) == 1 else 'ies'} (finding fixed — "
                  "delete from the baseline):")
            for fp in stale:
                print(f"  {fp}")
        if new:
            n_files = len({f.path for f in new})
            print(f"graftcheck: {len(new)} finding(s) in {n_files} file(s)"
                  + (f" ({len(old)} baselined)" if old else ""))
        else:
            print("graftcheck clean"
                  + (f" ({len(old)} baselined finding(s))" if old else ""))
    if stats is not None:
        print_stats(stats)
    return 1 if new else 0
