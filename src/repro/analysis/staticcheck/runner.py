"""Orchestration: discover files, run both layers, filter, report.

``scripts/staticcheck.py`` is a thin argparse shell around :func:`run`;
``benchmarks/run.py --only staticcheck`` and ``tests/test_staticcheck.py``
call it in-process.
"""
from __future__ import annotations

import json
import subprocess
from pathlib import Path
from typing import Dict, List, Optional, Sequence

from . import astlint, jaxpr_checks
from .findings import (Baseline, Finding, filter_findings, format_findings,
                       load_baseline)

__all__ = ["discover_files", "changed_files", "run", "REPO_MARKERS",
           "TEXT_SUFFIXES"]

#: Non-python files the mechanical rules (REPRO006/REPRO007) also cover.
TEXT_SUFFIXES = (".py", ".yml", ".yaml", ".toml", ".json")

_SKIP_DIRS = {".git", "__pycache__", ".claude", ".pytest_cache",
              "node_modules", ".venv", ".jax_cache", "chiprun_out",
              "_archive"}

REPO_MARKERS = ("pyproject.toml", ".git")


def repo_root(start: Optional[Path] = None) -> Path:
    p = (start or Path(__file__)).resolve()
    for parent in [p] + list(p.parents):
        if any((parent / m).exists() for m in REPO_MARKERS):
            return parent
    return Path.cwd()


def discover_files(root: Path) -> List[str]:
    out: List[str] = []
    for path in sorted(root.rglob("*")):
        if not path.is_file() or path.suffix not in TEXT_SUFFIXES:
            continue
        rel = path.relative_to(root).as_posix()
        if any(part in _SKIP_DIRS for part in path.parts):
            continue
        out.append(rel)
    return out


def changed_files(root: Path) -> List[str]:
    """Files touched vs HEAD (staged + unstaged + untracked)."""
    try:
        proc = subprocess.run(
            ["git", "status", "--porcelain"], cwd=root,
            capture_output=True, text=True, check=True)
    except (OSError, subprocess.CalledProcessError):
        return discover_files(root)
    out = []
    for line in proc.stdout.splitlines():
        rel = line[3:].split(" -> ")[-1].strip().strip('"')
        if rel.endswith(TEXT_SUFFIXES) and (root / rel).is_file():
            out.append(rel)
    return sorted(set(out))


def run(
    *,
    root: Optional[Path] = None,
    files: Optional[Sequence[str]] = None,
    jaxpr: bool = True,
    matrix: str = "default",
    hlo: bool = False,
    baseline: Optional[Baseline] = None,
) -> Dict[str, object]:
    """Run staticcheck; returns a report dict (see keys below).

    ``files=None`` scans the whole tree. ``jaxpr=False`` skips layer 1
    (the ``--changed-only`` fast path). ``matrix`` is ``"default"`` or
    ``"full"``; ``hlo=True`` additionally compiles one representative
    plan and walks its optimized HLO.
    """
    root = root or repo_root()
    baseline = baseline if baseline is not None else load_baseline()
    files = discover_files(root) if files is None else list(files)

    findings: List[Finding] = []
    sources: Dict[str, str] = {}
    for rel in files:
        try:
            text = (root / rel).read_text()
        except (OSError, UnicodeDecodeError):
            continue
        sources[rel] = text
        if rel.endswith(".py"):
            findings.extend(astlint.lint_source(rel, text))
        else:
            findings.extend(astlint.lint_text(rel, text))

    n_plans = 0
    hlo_costs: Dict[str, float] = {}
    if jaxpr:
        plans = (jaxpr_checks.full_matrix() if matrix == "full"
                 else jaxpr_checks.default_matrix())
        n_plans = len(plans)
        findings.extend(jaxpr_checks.check_plans(plans))
        findings.extend(jaxpr_checks.check_tuned_table())
        if hlo and plans:
            hlo_findings, hlo_costs = jaxpr_checks.check_hlo(plans[0])
            findings.extend(hlo_findings)

    kept, muted = filter_findings(findings, sources=sources,
                                  baseline=baseline)
    return {
        "findings": kept,
        "suppressed": muted,
        "files_checked": len(sources),
        "plans_checked": n_plans,
        "matrix": matrix if jaxpr else "skipped",
        "hlo_costs": hlo_costs,
        "text": format_findings(kept, muted),
        "ok": not kept,
    }


def report_json(report: Dict[str, object]) -> str:
    def enc(f: Finding):
        return {"path": f.path, "line": f.line, "code": f.code,
                "message": f.message}
    return json.dumps({
        "ok": report["ok"],
        "files_checked": report["files_checked"],
        "plans_checked": report["plans_checked"],
        "matrix": report["matrix"],
        "hlo_costs": report["hlo_costs"],
        "findings": [enc(f) for f in report["findings"]],
        "suppressed": [enc(f) for f in report["suppressed"]],
    }, indent=2)
