"""CLI: ``python -m cluster_tools_tpu.analysis``.

Default run = AST lints over the package source + ``tests/``, plus the
workflow-graph validator over ``cluster_tools_tpu/workflows/``.  Exit code
is 0 unless ``--fail-on-findings`` is given and findings exist (then 1);
internal errors exit 2.
"""

from __future__ import annotations

import argparse
import os
import sys


def _force_cpu_backend() -> None:
    """The workflow-graph validator imports jax transitively but needs no
    device: force the CPU backend so the lint never claims a chip that
    another process may hold."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # no jax (pure-AST run still works); graph validation will say so


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "conformance":
        # `analysis conformance <dir>`: validate a real state/run dir
        # against the artifact registry (exit 0 clean / 1 empty / 2
        # malformed) — the chaos smokes' post-run protocol gate
        sub = argparse.ArgumentParser(
            prog="python -m cluster_tools_tpu.analysis conformance",
            description="validate a state/run dir against the "
            "analysis/protocols.py artifact registry",
        )
        sub.add_argument("dir", help="state/queue/run directory to validate")
        sub_args = sub.parse_args(argv[1:])
        from .conformance import run_conformance

        return run_conformance(sub_args.dir)

    parser = argparse.ArgumentParser(
        prog="python -m cluster_tools_tpu.analysis",
        description="ctt-lint: AST invariant checks + workflow-graph "
        "validation for the TPU pipeline",
    )
    parser.add_argument(
        "--fail-on-findings", action="store_true",
        help="exit 1 if any finding is reported (CI mode)",
    )
    parser.add_argument(
        "--paths", nargs="*", default=None,
        help="files/directories for the AST lints (default: the package "
        "source dirs + tests/)",
    )
    parser.add_argument(
        "--workflows", default=None,
        help="directory of workflow modules to graph-validate (default: "
        "cluster_tools_tpu/workflows; pass an empty string to skip)",
    )
    parser.add_argument(
        "--no-graph", action="store_true",
        help="skip the workflow-graph validator (pure-AST run, no imports)",
    )
    parser.add_argument(
        "--list-rules", action="store_true", help="print every rule id and exit"
    )
    args = parser.parse_args(argv)

    from .core import REGISTRY

    # make sure every rule family is registered before --list-rules
    from . import ast_rules  # noqa: F401
    from . import graph as graph_rules  # noqa: F401
    from . import proto_rules  # noqa: F401

    if args.list_rules:
        for info in REGISTRY.items():
            print(f"{info.rule_id}  {info.description}")
        return 0

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    repo_root = os.path.dirname(pkg_root)

    if args.paths is None:
        paths = [
            os.path.join(pkg_root, d)
            for d in ("faults", "obs", "ops", "parallel", "runtime", "serve",
                      "tasks", "workflows", "utils")
        ]
        tests_dir = os.path.join(repo_root, "tests")
        if os.path.isdir(tests_dir):
            paths.append(tests_dir)
    else:
        paths = args.paths

    pyproject = os.path.join(repo_root, "pyproject.toml")

    from .ast_rules import lint_paths

    findings = lint_paths(paths, pyproject if os.path.exists(pyproject) else None)

    if args.paths is None:
        # full-tree runs also get the reverse CTT205 check: every
        # faults.KNOWN_SITES entry must keep >= 1 call site in the
        # package source (tests excluded — chaos specs there are data)
        from .proto_rules import check_fault_site_coverage

        pkg_paths = [p for p in paths if not p.endswith("tests")]
        findings.extend(check_fault_site_coverage(pkg_paths))

    if not args.no_graph:
        workflows_dir = args.workflows
        if workflows_dir is None:
            workflows_dir = os.path.join(pkg_root, "workflows")
        if workflows_dir and os.path.isdir(workflows_dir):
            _force_cpu_backend()
            from .graph import validate_workflows_dir

            findings.extend(validate_workflows_dir(workflows_dir))

    findings.sort(key=lambda f: (f.path, f.line, f.rule_id))
    for f in findings:
        print(f.format())
    n = len(findings)
    print(f"ctt-lint: {n} finding{'s' if n != 1 else ''}", file=sys.stderr)
    if findings and args.fail_on_findings:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
