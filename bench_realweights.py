"""Real-TRAINED-weights discuss measurement (VERDICT r4 missing #2 / #3).

The reference serves real pretrained checkpoints through Ollama
(reference src/adapters/local-llm.ts:95-144); our prior strongest proof
was a CONSTRUCTED checkpoint whose greedy chain is a property of
hand-set weights (tests/test_emergent_consensus.py). This script
replaces that with weights that are REAL in the only sense available in
a no-download environment: a transformers Llama (registry `tiny-llama`
shape) gradient-TRAINED from scratch on a roundtable-reply corpus, then
served with TEMPERATURE SAMPLING through the unmodified
TpuLlmAdapter + orchestrator, with core/consensus.py parsing whatever
the model actually samples.

Measured quantities (the artifact `REALWEIGHTS_r05.json`):
- offline: parse-rate of raw transformers `generate` samples (sanity
  that the checkpoint itself learned the reply contract)
- served: per-turn parse-rate, score histogram, and session outcomes
  over >= 20 sampled knight turns through real `run_discussion` calls

Run on CPU (`JAX_PLATFORMS=cpu python bench_realweights.py`); pass
--steps N to change training length.
The checkpoint is cached under .cache/realweights_ckpt (delete to
retrain).

Time discipline (ISSUE 2, VERDICT item 3 — this bench twice consumed a
whole hardware window dying rc=124 at its `timeout` with NOTHING
written): the run now sits on the engine's Budget primitive
(engine/deadlines.py).
- `--budget-s` (default 840, inside the window scripts' 900 s timeout)
  is the hard root; the serve phase gets a child budget and STOPS
  ADMITTING new sessions once it expires, flushing whatever completed.
- Training is an OFF-WINDOW concern: run `--train-only` outside the
  hardware window to build/cache the checkpoint; the on-window phase is
  pure load-and-serve. If no cached checkpoint exists, training only
  runs when the remaining budget safely covers it — otherwise the
  artifact records `no_cached_checkpoint` and exits 0 instead of
  burning the window.
- The artifact is flushed to disk AFTER EVERY SESSION (and marked
  `"partial": true` until the measurement completes), so a kill at any
  point leaves the newest completed numbers on disk instead of nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARTIFACT = ROOT / "REALWEIGHTS_r05.json"
CKPT_DIR = ROOT / ".cache" / "realweights_ckpt"
LORA_DIR = ROOT / ".cache" / "realweights_lora"

VOCAB = 512  # registry tiny-llama shape — the adapter serves it as-is
BOS, EOS, PAD = 1, 2, 0

TOPICS = [
    "should the session store move to an append-only event log",
    "do we adopt paged KV for every knight slot",
    "is the verify sandbox whitelist too strict",
    "should chronicle entries carry structured outcomes",
    "do we batch knight rounds into one device program",
    "should decree topics be deduplicated by fuzzy match",
]

FILLER_POOL = [
    "The chronicle records the prior decision about the session store.",
    "Earlier rounds debated the page pool allocator at length.",
    "The manifest lists the consensus engine as already built.",
    "A verify command inspected the engine sources yesterday.",
    "The King demanded convergence on the cache design.",
    "Knights disagreed about the sandbox timeout last session.",
    "The decree log still carries a deferred topic about quantization.",
    "Git history shows the sharding specs landed in round three.",
]

AGREES = ["the store design", "the paging plan", "the test strategy",
          "the rollout order", "the sandbox rules", "the cache budget"]
ISSUES = ["needs a migration test", "verify the eviction path",
          "benchmark the copy cost", "document the failure mode"]
FILES = ["theroundtaible_tpu/utils/session.py",
         "theroundtaible_tpu/engine/paging.py",
         "theroundtaible_tpu/core/consensus.py", "README.md"]
OPENERS = [
    "I have weighed the proposal carefully.",
    "The plan is sound but the details matter.",
    "This approach fits the constraints we named.",
    "I remain skeptical of one part of this.",
    "The tradeoff is acceptable at this scale.",
    "My objection from last round still stands.",
]

# Score marginal: mostly agreeable so multi-knight rounds sometimes reach
# unanimity within max_rounds, with real disagreement mass.
SCORE_DIST = [(9, 0.45), (10, 0.15), (8, 0.15), (7, 0.10), (5, 0.08),
              (3, 0.05), (2, 0.02)]


def sample_score(rng: random.Random) -> int:
    r, acc = rng.random(), 0.0
    for s, p in SCORE_DIST:
        acc += p
        if r <= acc:
            return s
    return 9


def make_reply(rng: random.Random) -> str:
    score = sample_score(rng)
    parts = {"consensus_score": score}
    if score >= 7:
        parts["agrees_with"] = rng.sample(AGREES, 2)
        parts["pending_issues"] = ([] if score >= 9 or rng.random() < 0.5
                                   else [rng.choice(ISSUES)])
    else:
        parts["agrees_with"] = []
        parts["pending_issues"] = rng.sample(ISSUES, 2)
    if score >= 9:
        parts["files_to_modify"] = rng.sample(FILES, 2)
    body = rng.choice(OPENERS)
    return (f"{body}\n```json\n{json.dumps(parts)}\n```\n")


def make_prompt_and_reply(rng: random.Random) -> tuple[str, str]:
    """A REAL discuss prompt (the production prompt builder: full system
    template, optional transcript of earlier sampled rounds, knight
    tail) paired with a consensus reply — the exact text distribution
    the engine serves, so training windows match serving windows."""
    from theroundtaible_tpu.core.prompt import build_system_prompt
    from theroundtaible_tpu.core.types import KnightConfig, RoundEntry

    names = ["Knight-A", "Knight-B", "Knight-C"]
    knights = [KnightConfig(name=n, adapter="tpu-llm",
                            capabilities=["debate"]) for n in names]
    from theroundtaible_tpu.core.consensus import \
        parse_consensus_from_response

    me = knights[rng.randrange(3)]
    # COMPLETE previous rounds only: measure_served runs with
    # parallel_rounds=True, where every knight's prompt contains whole
    # rounds and never a partial current one — training must match.
    rounds = []
    n_rounds = rng.randrange(0, 3)
    for rnum in range(1, n_rounds + 1):
        for k in knights:
            resp = make_reply(rng)
            # attach the PARSED block so format_previous_rounds renders
            # the "Consensus score: X/10" lines real round-2+ prompts
            # carry — the serving distribution, not a lookalike
            rounds.append(RoundEntry(
                knight=k.name, round=rnum, response=resp,
                consensus=parse_consensus_from_response(resp, k.name,
                                                        rnum),
                timestamp="t"))
    chronicle = " ".join(rng.choice(FILLER_POOL)
                         for _ in range(rng.randrange(0, 3)))
    prompt = build_system_prompt(
        me, knights, rng.choice(TOPICS), chronicle, rounds)
    return prompt, make_reply(rng)


def train_checkpoint(steps: int, seed: int = 0) -> dict:
    """Train tokenizer + tiny-llama-shaped transformers model from
    scratch on the reply corpus; save HF layout to CKPT_DIR."""
    import torch
    from tokenizers import (Tokenizer, decoders, models, pre_tokenizers,
                            trainers)
    from transformers import (LlamaConfig, LlamaForCausalLM,
                              PreTrainedTokenizerFast)

    rng = random.Random(seed)
    pairs = [make_prompt_and_reply(rng) for _ in range(2000)]
    corpus = [p + r for p, r in pairs]

    CKPT_DIR.mkdir(parents=True, exist_ok=True)
    tok = Tokenizer(models.BPE(unk_token="<unk>"))
    # ByteLevel keeps newlines/backticks exact (the fenced JSON contract);
    # the matching DECODER maps the byte alphabet back on decode.
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    tok.train_from_iterator(corpus, trainers.BpeTrainer(
        vocab_size=VOCAB,
        special_tokens=["<pad>", "<bos>", "<eos>", "<unk>"]))
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tok, bos_token="<bos>", eos_token="<eos>",
        pad_token="<pad>", unk_token="<unk>")
    fast.save_pretrained(CKPT_DIR)

    torch.manual_seed(seed)
    hf = LlamaForCausalLM(LlamaConfig(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=512, rms_norm_eps=1e-6,
        rope_theta=10_000.0, tie_word_embeddings=False,
        attention_bias=False, mlp_bias=False,
        bos_token_id=BOS, eos_token_id=EOS, pad_token_id=PAD))
    hf.train()

    # Window construction mirrors the engine's serving shape EXACTLY:
    # the engine head-truncates prompts to [bos] + last (budget-1)
    # tokens where budget = max_seq_len - padded_decode_reserve - 1
    # (serving_loop.prompt_budget: 512 - 128 - 1 = 383), and the reply
    # then decodes from position ~383. Training at a shorter window
    # would put replies at positions serving never reaches — an
    # observed score-distribution shift came exactly from that.
    prompt_budget = 383
    seqs = []
    for prompt, reply in pairs:
        p_ids = fast(prompt, add_special_tokens=False)["input_ids"]
        r_ids = fast(reply, add_special_tokens=False)["input_ids"] + [EOS]
        seqs.append([BOS] + p_ids[-(prompt_budget - 1):] + r_ids)
    opt = torch.optim.AdamW(hf.parameters(), lr=3e-3, weight_decay=0.01)
    sched = torch.optim.lr_scheduler.CosineAnnealingLR(opt, T_max=steps)
    batch_size = 16
    t0 = time.time()
    losses = []
    for step in range(steps):
        batch = [seqs[rng.randrange(len(seqs))] for _ in range(batch_size)]
        width = max(len(s) for s in batch)
        x = torch.full((batch_size, width), PAD, dtype=torch.long)
        for i, s in enumerate(batch):
            x[i, :len(s)] = torch.tensor(s)
        # labels: shifted inside the model; mask pad
        labels = x.clone()
        labels[x == PAD] = -100
        out = hf(input_ids=x, labels=labels)
        out.loss.backward()
        torch.nn.utils.clip_grad_norm_(hf.parameters(), 1.0)
        opt.step()
        sched.step()
        opt.zero_grad()
        losses.append(float(out.loss.detach()))
        if step % 50 == 0 or step == steps - 1:
            print(f"  step {step}: loss {losses[-1]:.3f}", flush=True)
    hf.eval()
    hf.save_pretrained(CKPT_DIR, safe_serialization=True)

    # Offline sanity: raw transformers sampling from a fresh tail prompt.
    from theroundtaible_tpu.core.consensus import \
        parse_consensus_from_response
    import torch as _t
    prompt_rng = random.Random(seed + 99)
    parsed = 0
    n_offline = 12
    samples = []
    with _t.no_grad():
        for i in range(n_offline):
            # fresh prompts (unseen topic/transcript combinations); the
            # model samples the reply itself
            head, _ = make_prompt_and_reply(prompt_rng)
            p_ids = fast(head, add_special_tokens=False)["input_ids"]
            ids = [BOS] + p_ids[-(prompt_budget - 1):]
            out = hf.generate(
                _t.tensor([ids]), do_sample=True, temperature=0.7,
                top_p=0.95, max_new_tokens=120, pad_token_id=PAD,
                eos_token_id=EOS)
            reply = fast.decode(out[0][len(ids):],
                                skip_special_tokens=True)
            block = parse_consensus_from_response(reply, "offline", 1)
            parsed += block is not None
            if i < 2:
                samples.append(reply[-300:])
    return {
        "steps": steps, "final_loss": round(losses[-1], 4),
        "train_seconds": round(time.time() - t0, 1),
        "offline_samples": n_offline, "offline_parsed": parsed,
        "offline_parse_rate": round(parsed / n_offline, 3),
        "sample_replies": samples,
    }


def measure_served(min_turns: int = 20, budget=None,
                   flush=None) -> dict:
    """>= min_turns sampled knight turns through the REAL orchestrator:
    full prompts, budget negotiation, batched rounds, consensus parsing —
    nothing scripted.

    `budget` (engine/deadlines.Budget): the serve phase's hard budget —
    checked between sessions (no new session is admitted once it
    expires; sessions themselves get round budgets derived from the
    remaining time), so the phase degrades to PARTIAL results instead
    of dying rc=124. `flush(record_so_far)` is called after every
    session so the newest completed numbers are always on disk."""
    import tempfile

    from theroundtaible_tpu.adapters.tpu_llm import TpuLlmAdapter
    from theroundtaible_tpu.core.orchestrator import run_discussion
    from theroundtaible_tpu.core.types import (KnightConfig,
                                               RoundtableConfig,
                                               RulesConfig)
    from theroundtaible_tpu.engine import deadlines

    if budget is None:
        budget = deadlines.Budget.root(None, rung="discussion")

    adapter = TpuLlmAdapter(
        "tpu-llm",
        {"model": "tiny-llama", "checkpoint": str(CKPT_DIR),
         "max_seq_len": 512, "num_slots": 4, "dtype": "float32",
         "sampling": {"temperature": 0.7, "top_p": 0.95,
                      "max_new_tokens": 120}})
    def session_config():
        # Each session's rounds run under a budget derived from the
        # phase's remaining time — the orchestrator's own time ladder
        # (rules.discussion_budget_seconds → round budgets → turn
        # budgets in the adapter) does the in-session enforcement.
        remaining = budget.remaining()
        return RoundtableConfig(
            version="1.0", project="realweights", language="en",
            knights=[KnightConfig(name=f"Knight-{c}", adapter="tpu-llm",
                                  capabilities=["debate"], priority=i + 1)
                     for i, c in enumerate("ABC")],
            rules=RulesConfig(
                max_rounds=3, consensus_threshold=9,
                timeout_per_turn_seconds=600,
                parallel_rounds=True,
                discussion_budget_seconds=(
                    remaining if remaining != float("inf") else None)),
            chronicle="chronicle.md", adapter_config={"tpu-llm": {}})

    turns = 0
    parsed = 0
    scores: dict[str, int] = {}
    outcomes = {"consensus": 0, "unanimous_rejection": 0, "escalated": 0}
    sessions = []
    sample_turns = []
    budget_exhausted = False

    def snapshot(partial: bool) -> dict:
        return {
            "turns": turns, "parsed": parsed,
            "parse_rate": round(parsed / max(turns, 1), 3),
            "score_histogram": dict(sorted(scores.items(),
                                           key=lambda kv: int(kv[0]))),
            "session_outcomes": outcomes, "sessions": sessions,
            "sample_turns": sample_turns,
            "partial": partial,
            "budget_exhausted": budget_exhausted,
        }

    with tempfile.TemporaryDirectory() as root:
        (Path(root) / ".roundtable" / "sessions").mkdir(parents=True)
        # Cycle topics (with a pass suffix after the first lap) until the
        # promised turn count is genuinely reached — a lap of quick
        # round-1 consensus sessions must not end the measurement short.
        while (turns < min_turns or len(sessions) < 3) \
                and len(sessions) < 40:
            if budget.expired:
                # Hard per-phase deadline: stop ADMITTING sessions and
                # return what completed (flushed below) instead of
                # letting the window kill us with nothing written.
                budget_exhausted = True
                print(f"serve budget exhausted after {len(sessions)} "
                      f"session(s) / {turns} turn(s) — flushing partial "
                      "results", flush=True)
                break
            topic = TOPICS[len(sessions) % len(TOPICS)]
            if lap := len(sessions) // len(TOPICS):
                topic = f"{topic} (pass {lap + 1})"
            res = run_discussion(topic, session_config(),
                                 {"tpu-llm": adapter},
                                 root, read_source_code=False)
            for entry in res.all_rounds:
                turns += 1
                if entry.consensus is not None:
                    parsed += 1
                    s = str(entry.consensus.consensus_score)
                    scores[s] = scores.get(s, 0) + 1
                if len(sample_turns) < 2:
                    sample_turns.append(entry.response[-400:])
            if res.unanimous_rejection:
                outcomes["unanimous_rejection"] += 1
            elif res.consensus:
                outcomes["consensus"] += 1
            else:
                outcomes["escalated"] += 1
            sessions.append({"topic": topic, "rounds": res.rounds,
                             "consensus": res.consensus,
                             "unanimous_rejection":
                                 res.unanimous_rejection})
            if flush is not None:
                flush(snapshot(partial=True))
    return snapshot(partial=False)




# --- sampled-traffic speculative-decoding A/B (ISSUE 13 satellite) ---

TREE_ARTIFACT = ROOT / "TREE_r13.json"

SPEC_TREE = {"branch": 2, "depth": 3}


def measure_spec_ab(budget=None, flush=None, sessions=3,
                    turns_per_session=2, max_new=48) -> dict:
    """The honest-acceptance A/B (ISSUE 13): SAMPLED (temperature 0.7 /
    top_p 0.95) traffic from the trained realweights checkpoint through
    the REAL SessionScheduler spec phase, one arm per drafter config —
    the PR-9 n-gram chain, the draft-model chain, draft-model + tree
    verify, and the LoRA draft head (zero-init distillation
    placeholder: its proposals ARE base greedy, the well-distilled
    limit, served through the PR-10 store at rank*(in+out) bytes).

    The headline is accepted tokens PER VERIFY DISPATCH on sampled
    traffic (scripted acceptance 1.0 is explicitly NOT evidence — see
    BENCH_NOTES.md): prompts are fresh build_system_prompt transcripts
    the n-gram drafter has never seen repeat, so its lookup collapses
    exactly the way real serving makes it collapse, while the model
    drafter's acceptance is the sampler's peakedness. Greedy parity
    (spec-on == spec-off byte-identical) and the kill-switch's
    zero-dispatch restoration ride the same record."""
    import numpy as np  # noqa: F401 — engine deps resolved before arms

    from theroundtaible_tpu.engine import deadlines
    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.sampling import SamplingParams
    from theroundtaible_tpu.engine.scheduler import SessionScheduler

    if budget is None:
        budget = deadlines.Budget.root(None, rung="discussion")

    base_cfg = {
        "model": "tiny-llama", "checkpoint": str(CKPT_DIR),
        "max_seq_len": 512, "num_slots": 4, "dtype": "float32",
        "kv_layout": "paged",
        # Headroom past the slots' own demand so tree verify's loaned
        # private pages come from a real free list (a loan-starved pool
        # silently degrades every row to chain).
        "num_pages": 40,
        "sampling": {"temperature": 0.7, "top_p": 0.95,
                     "max_new_tokens": max_new},
    }
    lora_cfg = {"max_adapters": 2, "rank": 8, "scale": 1.0,
                "adapters": {"drafthead": {"seed": 7, "init_std": 0.0}}}
    arms = [
        ("ngram_chain", True, None),
        ("model_chain", {"drafter": "model"}, None),
        ("model_tree", {"drafter": "model", "tree": dict(SPEC_TREE)},
         None),
        ("lora_tree", {"drafter": "lora", "adapter": "drafthead",
                       "tree": dict(SPEC_TREE)}, lora_cfg),
    ]

    # SAME sampled-traffic prompt set for every arm: fresh production
    # prompts (build_system_prompt + sampled transcript rounds) the
    # drafters have never seen — seeded so the A/B compares drafters,
    # not prompt luck.
    rng = random.Random(1313)
    prompt_sets = []
    for _ in range(sessions):
        prompt_sets.append([
            (f"knight-{k}", make_prompt_and_reply(rng)[0])
            for k in range(turns_per_session)])

    def run_arm(name, spec_cfg, lora, greedy=False):
        cfg = dict(base_cfg, spec_decode=spec_cfg)
        if lora:
            cfg["lora"] = dict(lora)
        if greedy:
            cfg = dict(cfg, sampling=dict(cfg["sampling"],
                                          temperature=0.0, top_p=1.0))
        engine = InferenceEngine.from_config(cfg)
        sched = SessionScheduler(engine)
        sp = SamplingParams(
            temperature=cfg["sampling"]["temperature"],
            top_p=cfg["sampling"]["top_p"], max_new_tokens=max_new)
        by_round = []
        tokens = 0
        texts_all = []
        t0 = time.time()
        try:
            for si, turns in enumerate(prompt_sets):
                if budget.expired:
                    break
                before = engine.spec_describe()
                texts, stats = sched.submit(
                    f"{name}-s{si}", turns, max_new_tokens=max_new,
                    sampling_per_turn=[sp] * len(turns))
                texts_all.append(texts)
                tokens += stats.decode_tokens
                after = engine.spec_describe()
                dd = (after["verify_dispatches"]
                      - before["verify_dispatches"])
                da = after["accepted_tokens"] - before["accepted_tokens"]
                dr = after["drafted_tokens"] - before["drafted_tokens"]
                by_round.append({
                    "session": si, "verify_dispatches": dd,
                    "accepted": da, "drafted": dr,
                    "acceptance_rate": round(da / dr, 3) if dr else None,
                    "accepted_per_dispatch": (round(da / dd, 3)
                                              if dd else None)})
        finally:
            sched.close()
        wall = time.time() - t0
        info = engine.spec_describe()
        disp = info["verify_dispatches"]
        return {
            "drafter": info["drafter"],
            "tree": info["tree"],
            "drafter_reason": info["drafter_reason"],
            "verify_dispatches": disp,
            "draft_dispatches": info["draft_dispatches"],
            "drafted_tokens": info["drafted_tokens"],
            "accepted_tokens": info["accepted_tokens"],
            "acceptance_rate": info["acceptance_rate"],
            "accepted_per_dispatch": (
                round(info["accepted_tokens"] / disp, 3) if disp
                else 0.0),
            "tree_rows": info["tree_rows"],
            "tree_nodes": info["tree_nodes"],
            "throttled_rows": info["throttled_rows"],
            "decode_tokens": tokens,
            "accepted_tok_s": round(
                info["accepted_tokens"] / max(wall, 1e-9), 2),
            "tok_s": round(tokens / max(wall, 1e-9), 2),
            "wall_s": round(wall, 2),
            "acceptance_by_round": by_round,
        }, texts_all

    record = {
        "config": "sampled-traffic spec A/B on trained realweights "
                  "(ISSUE 13)",
        "traffic": {"sessions": sessions,
                    "turns_per_session": turns_per_session,
                    "max_new": max_new,
                    "sampling": base_cfg["sampling"],
                    "note": "fresh production prompts per session; "
                            "identical prompt set across arms"},
        "tree": dict(SPEC_TREE),
        "arms": {},
        "partial": True,
    }

    def _flush():
        if flush is not None:
            flush(record)

    for name, spec_cfg, lora in arms:
        if budget.expired:
            record["budget_exhausted"] = True
            break
        print(f"  arm {name}...", flush=True)
        record["arms"][name], _texts = run_arm(name, spec_cfg, lora)
        _flush()

    # Greedy parity: spec-off vs model+tree spec-on must be
    # byte-identical (the output-invariance contract) on this REAL
    # checkpoint.
    parity = None
    if not budget.expired:
        print("  greedy parity check...", flush=True)
        off_arm, off_texts = run_arm("parity_off", False, None,
                                     greedy=True)
        on_arm, on_texts = run_arm(
            "parity_on", {"drafter": "model",
                          "tree": dict(SPEC_TREE)}, None, greedy=True)
        parity = {
            "identical": off_texts == on_texts,
            "spec_off_dispatches": off_arm["verify_dispatches"],
            "spec_on_accepted": on_arm["accepted_tokens"],
        }
        record["greedy_parity"] = parity
        _flush()

    # Kill-switch restoration: spec_decode off serves ZERO verify
    # dispatches (the record's honesty witness for the baseline arm).
    if parity is not None:
        record["kill_switch"] = {
            "verify_dispatches": parity["spec_off_dispatches"],
            "zero": parity["spec_off_dispatches"] == 0,
        }

    a = record["arms"]
    if "ngram_chain" in a and ("model_tree" in a or "lora_tree" in a):
        best_tree = max(
            (a[k]["accepted_per_dispatch"]
             for k in ("model_tree", "lora_tree") if k in a))
        record["meets"] = bool(
            best_tree > a["ngram_chain"]["accepted_per_dispatch"]
            and (parity is None or parity["identical"])
            and record.get("kill_switch", {}).get("zero", True))
        record["headline"] = {
            "ngram_chain_accepted_per_dispatch":
                a["ngram_chain"]["accepted_per_dispatch"],
            "best_tree_accepted_per_dispatch": best_tree,
        }
    record["partial"] = False
    _flush()
    return record


# --- tiny per-persona LoRA training (ISSUE 10 satellite) ---

# Persona flavors for --train-lora: each gets a reply corpus skewed to
# its temperament (openers + score mass), so the fitted A/B pair steers
# the SERVED distribution measurably — real trained personas, not
# random deltas, for the multi-LoRA bench (bench_discuss
# ROUNDTABLE_BENCH_LORA=1 reads the npzs via ROUNDTABLE_BENCH_LORA_DIR).
PERSONA_STYLES = {
    "optimist": {"openers": [
        "The plan is sound but the details matter.",
        "This approach fits the constraints we named.",
        "The tradeoff is acceptable at this scale."],
        "scores": [9, 10, 9, 8]},
    "skeptic": {"openers": [
        "I remain skeptical of one part of this.",
        "My objection from last round still stands.",
        "I have weighed the proposal carefully."],
        "scores": [3, 5, 2, 5]},
    "pragmatist": {"openers": [
        "The tradeoff is acceptable at this scale.",
        "I have weighed the proposal carefully.",
        "The plan is sound but the details matter."],
        "scores": [7, 8, 7, 9]},
}


def _persona_corpus(name: str, n: int, rng: random.Random) -> list[str]:
    style = PERSONA_STYLES[name]
    out = []
    for _ in range(n):
        score = rng.choice(style["scores"])
        parts = {"consensus_score": score,
                 "agrees_with": (rng.sample(AGREES, 2) if score >= 7
                                 else []),
                 "pending_issues": ([] if score >= 9
                                    else rng.sample(ISSUES, 1))}
        out.append(f"{rng.choice(TOPICS)}\n"
                   f"{rng.choice(style['openers'])}\n"
                   f"```json\n{json.dumps(parts)}\n```\n")
    return out


def train_lora_personas(steps: int = 60, rank: int = 8,
                        seq_len: int = 96, batch: int = 8) -> dict:
    """Fit one tiny LoRA pair per persona against the CACHED realweights
    checkpoint, by SGD through the ENGINE's own forward under a
    lora_scope — the exact serving math (models/common._einsum tagged
    seams), so what training steers is literally what serving applies.
    Saves engine/lora.save_pair_tree npzs under LORA_DIR (trained at
    apply scale 1.0 — serve them with `lora: {"scale": 1.0}`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from theroundtaible_tpu.engine.checkpoint import load_hf_checkpoint
    from theroundtaible_tpu.engine.lora import (lora_dims, lora_scope,
                                                save_pair_tree)
    from theroundtaible_tpu.engine.models.common import forward
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.tokenizer import load_tokenizer

    t0 = time.time()
    cfg = get_model_config("tiny-llama", max_seq_len=512)
    params = load_hf_checkpoint(str(CKPT_DIR), cfg, jnp.float32)
    tok = load_tokenizer(str(CKPT_DIR))
    dims = lora_dims(cfg)
    LORA_DIR.mkdir(parents=True, exist_ok=True)

    def batches(texts: list[str], rng: np.random.Generator):
        ids = [([BOS] + tok.encode(t, add_bos=False))[:seq_len]
               for t in texts]
        while True:
            pick = rng.integers(0, len(ids), size=batch)
            arr = np.full((batch, seq_len), PAD, np.int32)
            lens = np.zeros(batch, np.int32)
            for j, i in enumerate(pick):
                arr[j, :len(ids[i])] = ids[i]
                lens[j] = len(ids[i])
            yield jnp.asarray(arr), jnp.asarray(lens)

    def stack_of(ab):
        # slot 0 = zero base, slot 1 = the trainable pair — the exact
        # stacked layout the serving store uses.
        return {key: {"a": jnp.stack([jnp.zeros_like(a), a]),
                      "b": jnp.stack([jnp.zeros_like(b), b])}
                for key, (a, b) in ab.items()}

    ids1 = jnp.ones((batch,), jnp.int32)
    positions = jnp.broadcast_to(
        jnp.arange(seq_len, dtype=jnp.int32), (batch, seq_len))

    def loss_fn(ab, tokens, lens):
        with lora_scope((stack_of(ab), ids1)):
            logits, _ = forward(params, cfg, tokens, positions, None,
                                None, lens)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        tgt = tokens[:, 1:]
        nll = -jnp.take_along_axis(logp, tgt[..., None],
                                   axis=-1)[..., 0]
        mask = (jnp.arange(seq_len - 1)[None, :]
                < (lens - 1)[:, None]).astype(jnp.float32)
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    @jax.jit
    def step(ab, vel, tokens, lens, lr):
        loss, grads = jax.value_and_grad(loss_fn)(ab, tokens, lens)
        vel = jax.tree_util.tree_map(
            lambda v, g: 0.9 * v + g, vel, grads)
        ab = jax.tree_util.tree_map(
            lambda p_, v: p_ - lr * v, ab, vel)
        return ab, vel, loss

    report = {}
    for pi, name in enumerate(sorted(PERSONA_STYLES)):
        rng = np.random.default_rng(100 + pi)
        key = jax.random.PRNGKey(100 + pi)
        ab = {}
        for ki, (leaf, (c, o, _tp)) in enumerate(sorted(dims.items())):
            ka, _ = jax.random.split(jax.random.fold_in(key, ki))
            # classic LoRA init UNDER TRAINING: A random, B zero — the
            # delta starts exactly 0 and the gradient shapes it.
            ab[leaf] = (jax.random.normal(ka, (rank, c), jnp.float32)
                        * (c ** -0.5),
                        jnp.zeros((rank, o), jnp.float32))
        vel = jax.tree_util.tree_map(jnp.zeros_like, ab)
        gen = batches(_persona_corpus(name, 64, random.Random(7 + pi)),
                      rng)
        first = last = None
        for i in range(steps):
            tokens, lens = next(gen)
            ab, vel, loss = step(ab, vel, tokens, lens,
                                 jnp.float32(0.05))
            if first is None:
                first = float(loss)
            last = float(loss)
        save_pair_tree(str(LORA_DIR / f"{name}.npz"),
                       {k: (np.asarray(a), np.asarray(b))
                        for k, (a, b) in ab.items()})
        report[name] = {"loss_first": round(first, 4),
                        "loss_last": round(last, 4)}
    return {"personas": report, "rank": rank, "steps": steps,
            "dir": str(LORA_DIR),
            "train_seconds": round(time.time() - t0, 1)}


def main() -> int:
    # Clean SIGTERM exit (sys.exit → atexit → PJRT teardown): this bench
    # runs under `timeout` in the window scripts, and a hard-killed JAX
    # process can wedge the single-claim relay for the rest of a window.
    from bench_common import install_sigterm_exit
    install_sigterm_exit()

    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--fresh", action="store_true",
                    help="retrain even if a cached checkpoint exists")
    ap.add_argument("--min-turns", type=int, default=20)
    ap.add_argument("--budget-s", type=float, default=840.0,
                    help="hard wall-clock budget for the whole run "
                         "(inside the window scripts' 900 s timeout); "
                         "0 = unbounded")
    ap.add_argument("--train-only", action="store_true",
                    help="train/cache the checkpoint and exit — the "
                         "OFF-WINDOW half of the run (the on-window "
                         "half is then pure load-and-serve)")
    ap.add_argument("--train-lora", action="store_true",
                    help="fit tiny per-persona LoRA pairs on the "
                         "cached checkpoint and exit (ISSUE 10): "
                         "saves npzs under .cache/realweights_lora "
                         "for the ROUNDTABLE_BENCH_LORA bench "
                         "(serve with lora scale 1.0)")
    ap.add_argument("--lora-steps", type=int, default=60)
    ap.add_argument("--spec", action="store_true",
                    help="sampled-traffic speculative-decoding A/B "
                         "(ISSUE 13): ngram chain vs draft-model chain "
                         "vs model/LoRA tree verify on the cached "
                         "checkpoint, through the real scheduler — "
                         "writes TREE_r13.json (acceptance by round, "
                         "accepted tok/s, greedy parity, kill-switch)")
    args = ap.parse_args()

    if args.spec:
        if not (CKPT_DIR / "model.safetensors").exists():
            print(json.dumps({
                "metric": "spec_tree_ab", "value": 0.0,
                "unit": "status", "status": "no_cached_checkpoint",
                "detail": {"fix": "run bench_realweights.py "
                                  "--train-only first"}}), flush=True)
            return 0
        from theroundtaible_tpu.engine import deadlines
        budget = deadlines.Budget.root(
            args.budget_s if args.budget_s > 0 else None,
            rung="discussion")
        rec = {"timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime())}

        def flush_tree(r):
            rec.update(r)
            TREE_ARTIFACT.write_text(json.dumps(rec, indent=2))

        out = measure_spec_ab(budget=budget, flush=flush_tree)
        print(json.dumps({
            "metric": "spec_tree_accepted_per_dispatch",
            "value": out.get("headline", {}).get(
                "best_tree_accepted_per_dispatch", 0.0),
            "unit": "tokens/verify-dispatch",
            "baseline_ngram": out.get("headline", {}).get(
                "ngram_chain_accepted_per_dispatch"),
            "meets": out.get("meets"),
            "partial": bool(out.get("budget_exhausted")),
            "artifact": TREE_ARTIFACT.name,
        }), flush=True)
        return 0

    if args.train_lora:
        if not (CKPT_DIR / "config.json").exists():
            print(json.dumps({
                "metric": "realweights_train_lora", "value": 0.0,
                "unit": "status", "status": "no_cached_checkpoint",
                "detail": {"fix": "run bench_realweights.py "
                                  "--train-only first"}}), flush=True)
            return 0
        rep = train_lora_personas(steps=args.lora_steps)
        print(json.dumps({
            "metric": "realweights_train_lora",
            "value": min(p["loss_last"]
                         for p in rep["personas"].values()),
            "unit": "final_nll",
            "detail": rep}), flush=True)
        return 0

    from theroundtaible_tpu.engine import deadlines
    budget = deadlines.Budget.root(
        args.budget_s if args.budget_s > 0 else None, rung="discussion")

    record = {"config": "real trained weights through discuss",
              "model": "tiny-llama (trained from scratch, see docstring)",
              "sampling": {"temperature": 0.7, "top_p": 0.95},
              "budget_s": args.budget_s,
              "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime())}

    def flush_artifact(served=None) -> None:
        """Write the artifact NOW — called after every session so a
        kill at any point leaves the newest completed numbers on disk
        (the old flow wrote once at the very end and twice wrote
        nothing, rc=124)."""
        if served is not None:
            record["served"] = served
        ARTIFACT.write_text(json.dumps(record, indent=2))

    have_ckpt = (CKPT_DIR / "model.safetensors").exists()
    # Training belongs OFF-WINDOW (--train-only); the serve phase trains
    # in-line only when the budget demonstrably covers it. ~0.5 s/step
    # CPU plus tokenizer/save overhead, doubled for safety.
    train_cost_s = args.steps * 1.0 + 120.0
    if args.fresh or args.train_only or not have_ckpt:
        if args.train_only or budget.remaining() > train_cost_s:
            print("training checkpoint...", flush=True)
            record["training"] = train_checkpoint(args.steps)
            if args.train_only:
                flush_artifact()
                print(json.dumps({
                    "metric": "realweights_train_only",
                    "value": record["training"]["offline_parse_rate"],
                    "unit": "fraction", "artifact": ARTIFACT.name}))
                return 0
        elif have_ckpt:
            # --fresh asked for a retrain the budget can't cover, but a
            # cached checkpoint EXISTS: serving stale numbers beats
            # serving none — fall through to the cached path below.
            print(f"budget {budget.remaining():.0f}s cannot cover "
                  f"~{train_cost_s:.0f}s of retraining — serving from "
                  "the cached checkpoint instead (--fresh deferred)",
                  flush=True)
            record["training"] = "cached (retrain skipped: budget)"
        else:
            # No cached checkpoint and no budget to train one: record
            # the actionable cause and exit CLEAN — never rc=124 with
            # an empty artifact.
            record["served"] = {
                "status": "no_cached_checkpoint",
                "detail": f"budget {budget.remaining():.0f}s cannot "
                          f"cover ~{train_cost_s:.0f}s of training — "
                          "run `bench_realweights.py --train-only` "
                          "off-window first",
            }
            flush_artifact()
            print(json.dumps({
                "metric": "realweights_parse_rate", "value": 0.0,
                "unit": "fraction", "status": "no_cached_checkpoint",
                "artifact": ARTIFACT.name}))
            return 0
    else:
        print("using cached checkpoint", CKPT_DIR, flush=True)
        record["training"] = "cached"
        if ARTIFACT.exists():
            # keep the cached checkpoint's training stats in the artifact
            try:
                prior = json.loads(ARTIFACT.read_text()).get("training")
                if isinstance(prior, dict):
                    record["training"] = prior
            except (json.JSONDecodeError, OSError):
                pass

    print("serving through orchestrator...", flush=True)
    # The serve phase keeps a flush reserve: the final write + teardown
    # must land inside the root budget even if a session runs long.
    serve_budget = budget.child(
        "round", timeout_s=(max(budget.remaining() - 15.0, 1.0)
                            if budget.remaining() != float("inf")
                            else None))
    served = measure_served(args.min_turns, budget=serve_budget,
                            flush=flush_artifact)
    flush_artifact(served)
    print(json.dumps({
        "metric": "realweights_parse_rate",
        "value": served["parse_rate"],
        "unit": "fraction",
        "turns": served["turns"],
        "partial": served["partial"] or served["budget_exhausted"],
        "artifact": ARTIFACT.name,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
