"""A scheduled discussion at a fixed seed on the tests' tiny engines,
and the tokens it yields (tests/test_dispatch_pack.py, ISSUE 53).

`python tests/discussion_play.py --record <file>`, run from the root of
a checkout, writes that tree's tokens for every (model, mode): the
fixture a later tree is held to. Nothing here reaches below the
scheduler's `submit`, so a tree from before a change to the step
programs can run it.
"""

import importlib
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# A plain decoder (its joins take the prologue; `gemma-ragged`: the
# ragged program, as a join into a running batch does) and the hybrid
# families, each through its serving test's own tiny engine.
MODELS = {"gemma": None, "gemma-ragged": None,
          "nemotron-h": "test_hybrid_serving",
          "brumby": "test_brumby_serving",
          "jamba": "test_jamba_serving",
          "lfm2": "test_lfm2_serving"}
MODES = ("greedy", "sampled")
KNIGHTS = ("gawain", "percival", "kay")
SEED = 3
NEW = 70        # a first token and 69 more: two segments of 64, the
                # second issued from the first one's device outputs
ROUNDS = 2


def tokens_of(seed, n):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 250, size=(n,))]


def build(model, temperature=0.0):
    """-> (engine, knights, cue(knight, round) -> tokens). `temperature`:
    the engine's OWN sampling (what its warm-up warms; a round's rows
    bring theirs)."""
    if MODELS[model] is None:
        from theroundtaible_tpu.engine.engine import InferenceEngine
        from theroundtaible_tpu.engine.models.registry import (
            get_model_config)
        from theroundtaible_tpu.engine.sampling import SamplingParams
        eng = InferenceEngine(
            get_model_config("tiny-gemma", max_seq_len=512), num_slots=8,
            kv_layout="paged", seed=SEED,
            sampling=SamplingParams(temperature=temperature,
                                    max_new_tokens=8))
        if model == "gemma-ragged":
            eng.joins_ragged_alone = True
            eng.ragged_defer_min = 0
        return eng, KNIGHTS, lambda k, r: [
            10 + KNIGHTS.index(k), 20 + r, 30]
    m = importlib.import_module(MODELS[model])
    eng = m.make_engine(seed=SEED, sampling={"temperature": temperature})
    eng.ragged_defer_min = 0
    return eng, m.KNIGHTS, m.cue


def sampling(mode, n):
    from theroundtaible_tpu.engine.sampling import SamplingParams
    if mode == "greedy":
        return None
    rows = [SamplingParams(temperature=0.9, max_new_tokens=NEW),
            SamplingParams(temperature=1.1, top_k=5, max_new_tokens=NEW),
            SamplingParams(temperature=0.8, top_p=0.7,
                           max_new_tokens=NEW)]
    return [rows[i % len(rows)] for i in range(n)]


def play(eng, knights, cue, mode, rounds=ROUNDS):
    """A discussion of `rounds` rounds through a scheduler, one session:
    every knight answers the transcript so far, the answers join it.
    -> the answers' tokens, a list a round of a list a knight."""
    from theroundtaible_tpu.engine.scheduler import SessionScheduler
    sched = SessionScheduler(eng)
    transcript, out = [1] + tokens_of(81, 60), []
    try:
        for r in range(1, rounds + 1):
            turns = [(k, transcript + cue(k, r)) for k in knights]
            sched.submit("s", turns, max_new_tokens=NEW,
                         sampling_per_turn=sampling(mode, len(turns)))
            answers = []
            for k, p in turns:
                name = next(n for n in eng.kv._slots
                            if n.endswith(k) and n.startswith("s"))
                answers.append(
                    [int(t) for t in eng.kv._slots[name].tokens[len(p):]])
                transcript = transcript + cue(k, r) + answers[-1]
            out.append(answers)
    finally:
        sched.close()
    return out


if __name__ == "__main__":
    if len(sys.argv) != 3 or sys.argv[1] != "--record":
        raise SystemExit(f"usage: {sys.argv[0]} --record <file>")
    # As tests/conftest.py sets the process up (a sampled draw can turn
    # on the last bit of a logit, and that on how XLA:CPU splits its
    # work): eight virtual devices, the CPU alone.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("ROUNDTABLE_DISABLE_TPU_DETECT", "1")
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.getcwd())
    out = {}
    for model_ in MODELS:
        for mode_ in MODES:
            out[f"{model_}/{mode_}"] = play(*build(model_), mode_)
            print(model_, mode_, [len(a) for r in out[f"{model_}/{mode_}"]
                                  for a in r], flush=True)
    with open(sys.argv[2], "w", encoding="utf-8") as f:
        json.dump(out, f, separators=(",", ":"))
        f.write("\n")
