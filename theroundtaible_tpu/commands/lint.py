"""`roundtable lint` — the serving-invariant analyzer (ISSUE 15).

Runs the AST rule engine (analysis/rules, allowlist-filtered) over the
source tree and, with --jaxpr, the device-free jaxpr audit of every
registered serving program on two toy CPU engines (a dense one with
ragged + spec-tree + LoRA, and a hybrid one — together they register
every program family: prefill, decode, ragged, spec-verify, propose,
LoRA-setter, and the hybrid three). Exit code 1 on any unallowlisted
finding — the CI / pre-chip contract: a statically detectable violation must never cost
chip time.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Optional


def _source_root() -> str:
    """The tree to lint: the checkout containing this package (the
    package dir's parent), which is also where README/pyproject live."""
    import theroundtaible_tpu

    return os.path.dirname(
        os.path.dirname(os.path.abspath(theroundtaible_tpu.__file__)))


def _audit_findings() -> tuple[list, list[str]]:
    """Build the two toy CPU engines and run the jaxpr audit; returns
    (findings, audited program names). Forces the CPU platform BEFORE
    first jax import — the audit is device-free by construction and
    must never touch (or wait on) a TPU."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("ROUNDTABLE_DISABLE_TPU_DETECT", "1")
    from ..analysis.jaxpr_audit import audit_programs, collect_programs
    from ..engine.engine import InferenceEngine
    from ..engine.models.registry import get_model_config

    cfg = get_model_config("tiny-gemma", max_seq_len=512)
    engines = [
        InferenceEngine(cfg, num_slots=4,
                        mesh_shape={"data": 1, "model": 1},
                        spec_decode={"drafter": "ngram",
                                     "tree": {"branch": 2, "depth": 2}},
                        lora={"rank": 4, "max_adapters": 4}),
        # Recurrent state beside the pools (models/hybrid.py): the
        # hybrid step programs and their donated state trees.
        InferenceEngine(get_model_config("tiny-nemotron-h"), num_slots=4,
                        page_size=16,
                        mesh_shape={"data": 1, "model": 1}),
    ]
    findings, names = [], []
    for eng in engines:
        specs = collect_programs(eng)
        names.extend(s.name for s in specs)
        findings.extend(audit_programs(specs))
    return findings, sorted(set(names))


def lint_command(rules: Optional[list[str]] = None, jaxpr: bool = False,
                 as_json: bool = False,
                 root: Optional[str] = None) -> int:
    from ..analysis import run_lint, unallowlisted
    from ..analysis.astlint import LintConfigError

    root = root or _source_root()
    programs: list[str] = []
    audit: list = []
    extra_active = None
    if jaxpr:
        # Audit first: its findings must enter run_lint BEFORE the
        # allowlist applies, so a `<jaxpr:...>` finding suppresses
        # through the same [[allow]] mechanism as the AST half.
        from ..analysis.jaxpr_audit import JAXPR_RULE_IDS
        audit, programs = _audit_findings()
        extra_active = set(JAXPR_RULE_IDS)
    try:
        findings = run_lint(root, rule_ids=rules,
                            extra_findings=audit,
                            extra_active=extra_active)
    except (LintConfigError, ValueError) as e:
        print(f"lint configuration error: {e}", file=sys.stderr)
        return 2
    bad = unallowlisted(findings)

    if as_json:
        print(json.dumps({
            "root": root,
            "findings": [f.to_dict() for f in findings],
            "unallowlisted": len(bad),
            "allowlisted": sum(1 for f in findings if f.allowed),
            "jaxpr_programs": programs,
            "clean": not bad,
        }, indent=2))
        return 1 if bad else 0

    for f in findings:
        if not f.allowed:
            print(f.render())
    n_allowed = sum(1 for f in findings if f.allowed)
    if bad:
        print(f"\nroundtable lint: {len(bad)} finding(s) "
              f"({n_allowed} allowlisted)", file=sys.stderr)
        return 1
    suffix = (f" — jaxpr audit covered {len(programs)} program "
              "families" if jaxpr else "")
    print(f"roundtable lint: clean ({n_allowed} allowlisted "
          f"finding(s)){suffix}")
    return 0
