"""The comparison that decides `correct`: what the served path answered
against the configuration's plain reference, on the engine's own
weights. Logits are compared, not tokens: with random weights the top of
the vocabulary is a near-tie that rounding flips.

The served token must lie within TOLERANCE_SIGMAS standard deviations
(of that position's reference logits over the vocabulary) of the
reference maximum. The gap between the first and second of N Gaussian
logits is about sigma / sqrt(2 ln N) — 0.22 sigma at 32k, 0.2 at 152k —
and a wrong token sits some four sigma down, so 0.25 admits bf16 and
int8-activation near-ties and nothing else (chip_smoke.py's rule, and
its measured worst case was 0.013 sigma). Computing the served path in a
lower precision than the configuration states moves the gap by whole
tenths of a sigma and fails.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Any

TOLERANCE_SIGMAS = 0.25
PAD_TO = 512


def load_reference(config_file: str, config: dict[str, Any]):
    """The reference module named by the configuration, beside it (or
    where its relative name leads: a second configuration of one
    architecture shares the first one's reference)."""
    path = os.path.join(os.path.dirname(config_file),
                        config["reference"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_" + os.path.basename(config["reference"]),
        os.path.normpath(path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def score(reference, params, config: dict[str, Any],
          served: list[dict]) -> dict[str, Any]:
    """`served`: records with `prompt` (ids) and `ids` (what came back,
    greedy). Every served token is scored at its own position against
    the reference's forward over prompt + the tokens served before it.
    → {"correct", "tolerance_sigmas", "requests": [...]}"""
    import numpy as np

    longest = max(len(r["prompt"]) + len(r["ids"]) for r in served)
    width = -(-longest // PAD_TO) * PAD_TO
    out = []
    for rec in served:
        prompt, ids = list(rec["prompt"]), [int(i) for i in rec["ids"]]
        entry: dict[str, Any] = {"what": rec["what"],
                                 "prompt_tokens": len(prompt),
                                 "served_tokens": len(ids)}
        out.append(entry)
        if not ids:
            entry["worst_gap_sigmas"] = float("inf")
            continue
        seq = np.zeros((width,), np.int32)
        whole = prompt + ids[:-1]
        seq[:len(whole)] = whole
        rows = [len(prompt) - 1 + i for i in range(len(ids))]
        logits = np.asarray(reference.logits_at(params, config, seq, rows))
        if not np.isfinite(logits).all():
            entry["worst_gap_sigmas"] = float("inf")
            continue
        gaps = [(float(row.max() - row[tok]) / float(row.std()))
                for row, tok in zip(logits, ids)]
        entry["worst_gap_sigmas"] = max(gaps)
        entry["argmax_hits"] = sum(
            1 for row, tok in zip(logits, ids) if int(row.argmax()) == tok)
    worst = max(e["worst_gap_sigmas"] for e in out)
    return {"correct": bool(worst <= TOLERANCE_SIGMAS),
            "tolerance_sigmas": TOLERANCE_SIGMAS,
            "worst_gap_sigmas": worst, "requests": out}
