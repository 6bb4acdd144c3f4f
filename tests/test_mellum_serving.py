"""A `mellum` decoder (window and full layers at ONE head count over one
GQA page pool, two rotary tables by layer type, a softmax top-k router
over held experts and no shared one) built from its `architecture`
block, through engine and scheduler on the CPU: prologue and decode
through `PagedKVCache`, own-slot reuse, three knights over three rounds
with ragged joins and the leader pass, what the segments' rows hold
behind their windows (`pages_held`, `pages_behind_window`), and the
decline table.

Every served token is compared with the plain reference
(benchmarks/configs/mellum_reference.py) on the engine's own weights: a
float32 engine serves the reference's own maximum at every position
(gap 0 but for rounding-level ties, held to 1e-3 of a logit whose spread
is about 1). Logit-for-logit comparisons:
tests/benchmarks/test_benchmark_reference_mellum.py."""
import os
import sys
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from configs import mellum_reference as ref  # noqa: E402

from theroundtaible_tpu.engine.engine import InferenceEngine  # noqa: E402
from theroundtaible_tpu.engine.scheduler import SessionScheduler  # noqa: E402
from theroundtaible_tpu.utils import telemetry  # noqa: E402

GAP = 1e-3
PAGE = 8
WINDOW = 16
KNIGHTS = ["lancelot", "galahad", "percival"]
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]

# one period of tiny-mellum as a published config.json would state it
PUBLISHED = {
    "model_type": "mellum", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 512, "max_window_layers": 0,
    "attention_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "tie_word_embeddings": False, "sliding_window": WINDOW,
    "use_sliding_window": True,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 8,
            "original_max_position_embeddings": 32, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2079441541679836},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "layer_types": PERIOD, "mlp_layer_types": ["sparse"] * 4,
}


def tokens_of(seed, n):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 250, size=(n,))]


def make_engine(**kw):
    config = {"model": "tiny-mellum-d4", "architecture": PUBLISHED,
              "dtype": "float32", "kv_layout": "paged", "page_size": PAGE,
              "num_slots": 8, "max_seq_len": 512, "seed": 3,
              "sampling": {"temperature": 0.0},
              "mesh": {"data": 1, "model": 1}}
    config.update(kw)
    eng = InferenceEngine.from_config(config)
    eng.ragged_defer_min = 1     # tiny prompts still join as ragged chunks
    return eng


@pytest.fixture(scope="module")
def engine():
    return make_engine()


def worst_gap(eng, prompt, served):
    seq = prompt + served
    rows = list(range(len(prompt) - 1, len(seq) - 1))
    logits = np.asarray(ref.logits_at(eng.params, PUBLISHED,
                                      np.asarray(seq), rows))
    return max(float(row.max() - row[tok])
               for row, tok in zip(logits, served))


def serve(eng, name, prompt, n=8):
    _texts, stats = eng.generate_batch_with_stats(
        [(name, prompt)], max_new_tokens=n)
    committed = eng.kv._slots[name].tokens
    assert committed[:len(prompt)] == prompt
    return committed[len(prompt):], stats


# --- engine ------------------------------------------------------------------


def test_prologue_then_decode_through_the_pages(engine):
    prompt = [1] + tokens_of(1, 69)
    served, stats = serve(engine, "a", prompt)
    assert len(served) == 7 and stats.prefill_tokens == 70
    assert worst_gap(engine, prompt, served) < GAP
    info = engine.describe()
    assert info["paged_decode"] == "pool-direct"
    assert info["ragged"]["path"] == "pallas_ragged"
    assert info["ragged"]["fallback_reason"] is None
    assert info["declines"] == {
        "spec_decode": "attn-layers:no-verify-program",
        "grouped_product": "not on a TPU (no Mosaic): lax.ragged_dot",
        "page_copy": "not on a TPU (no Mosaic): XLA's gather and scatter"}
    attn = info["attention"]
    assert set(attn) == set(telemetry.SURFACE_BINDINGS["engine_attention"])
    assert (attn["kv_heads"], attn["head_dim"], attn["gate"]) \
        == (2, 16, None)
    assert [(a["layer"], a["heads"], a["window"], a["rotary_dim"],
             a["rope_theta"]) for a in attn["layers"]] == [
        (0, 8, WINDOW, 16, 500000.0), (2, 8, WINDOW, 16, 500000.0),
        (4, 8, WINDOW, 16, 500000.0), (6, 8, None, 16, 500000.0)]
    assert attn["layers"][3]["rope_yarn"] == [8.0, 32.0, 32.0, 1.0]
    assert attn["layers"][3]["rope_attention_factor"] \
        == 1.2079441541679836
    assert attn["layers"][0]["rope_yarn"] is None
    # both classes were asked of both kernel gates, and neither declined
    assert [(c["heads"], c["window"], c["layers"], c["decode_decline"],
             c["ragged_decline"]) for c in attn["classes"]] == [
        (8, WINDOW, 3, None, None), (8, None, 1, None, None)]
    moe = info["moe"]
    assert set(moe) == set(telemetry.SURFACE_BINDINGS["engine_moe"])
    assert (moe["held"], moe["published"], moe["top_k"],
            moe["router_rule"], moe["shared_expert"]) == (
        8, 8, 2, "softmax_topk", False)
    assert moe["experts_hit"] > 0
    # One pool shape for four layers of two classes: [P, ps, 2, 16] twice.
    assert [tuple(p.shape for p in layer) for layer in engine.kv.pools] \
        == [((engine.kv.num_pages, PAGE, 2, 16),) * 2] * 4
    assert engine.hybrid.state == {"ssm": [], "conv": []}
    assert all("shared" not in engine.params["layers"][i]
               for i in (1, 3, 5, 7))


def test_own_slot_reuse_prefills_only_the_new_tokens(engine):
    first = [1] + tokens_of(2, 50)
    served, _ = serve(engine, "cont", first)
    longer = first + served + tokens_of(3, 30)
    again, stats = serve(engine, "cont", longer)
    assert stats.prefill_tokens == 30
    assert worst_gap(engine, longer, again) < GAP


# --- what declines -----------------------------------------------------------


@pytest.fixture(scope="module")
def asked_for_everything():
    return make_engine(
        num_slots=2, spec_decode=True, kv_quant="int8", quant="int8",
        seq_parallel=2, lora={"max_adapters": 2, "rank": 4})


@pytest.mark.parametrize("feature,where,reason", [
    ("spec_decode", "spec_reason", "attn-layers:no-verify-program"),
    ("lora", "lora_reason", "attn-layers:no-lora-targets"),
    ("kv_quant", "kv_quant_reason",
     "attn-layers:step-programs-carry-no-scale-pools"),
    ("quant", None, "attn-layers:quant-leaves"),
    ("seq_parallel", None, "attn-layers"),
])
def test_what_cannot_be_served_declines_with_a_reason(
        asked_for_everything, feature, where, reason):
    eng = asked_for_everything
    assert eng.describe()["declines"][feature] == reason
    if where:
        assert getattr(eng, where) == reason
    assert eng.quant == "none" and eng.kv_quant_spec is None
    assert not eng.spec_decode and eng.lora is None
    # What addresses pages by id stays on: with whole pages kept it is
    # exact.
    assert eng.prefix_cache is not None and eng.kv_offload is not None


@pytest.mark.parametrize("config,message", [
    ({"kv_layout": "contiguous"}, "paged"),
    ({"mesh": {"data": 1, "model": 2}}, "mesh"),
])
def test_what_the_model_cannot_serve_without_fails_at_build(config,
                                                            message):
    with pytest.raises(ValueError, match=message):
        make_engine(num_slots=2, **config)


# --- scheduler ---------------------------------------------------------------


def cue(knight, round_no):
    return [3 + ord(c) for c in f"\n[r{round_no}] {knight}: "]


def discussion(sched, eng, sid, opening, rounds, new=12):
    transcript, served = list(opening), []
    for r in range(1, rounds + 1):
        turns = [(k, transcript + cue(k, r)) for k in KNIGHTS]
        sched.submit(sid, turns, max_new_tokens=new)
        for k, p in turns:
            name = next(n for n in eng.kv._slots
                        if n.endswith(k) and sid in n)
            answer = eng.kv._slots[name].tokens[len(p):]
            served.append((p, answer))
            transcript = transcript + cue(k, r) + answer
    for p, a in served:
        assert worst_gap(eng, p, a) < GAP
    return served


def test_three_knights_three_rounds_and_what_lies_behind_the_windows(
        engine):
    eng = engine
    sched = SessionScheduler(eng)
    before = dict(eng.describe()["attention"])
    telemetry.arm()
    t_a = time.monotonic()
    results, errors = {}, []

    def run(sid, seed, n_open):
        try:
            results[sid] = discussion(
                sched, eng, sid, [1] + tokens_of(seed, n_open), rounds=3)
        except BaseException as e:  # noqa: BLE001 — asserted below
            errors.append(e)

    try:
        threads = [threading.Thread(target=run, args=(f"s{i}", 30 + i,
                                                      40 + 30 * i))
                   for i in range(2)]
        for t in threads:
            t.start()
            time.sleep(0.3)
        for t in threads:
            t.join()
        spans = telemetry.spans_between(t_a, time.monotonic())
    finally:
        telemetry.disarm()
        sched.close()
    assert not errors, errors
    d = sched.describe()
    assert d["failed"] == 0 and d["completed"] == 6
    assert d["ragged_joins"] >= 1
    # later rounds prefill the knights' deltas alone: the leader pass,
    # own-slot reuse and the prefix index are on over window layers
    prompts = sum(len(p) for served in results.values()
                  for p, _a in served)
    assert d["segment_prefill_tokens"] < prompts / 2
    assert eng.hybrid.describe()["share_declined"] == 0
    segs = [s["attrs"] for s in spans if s["rung"] == "segment"]
    names = {"page_visits_full", "page_visits_window", "pages_held",
             "pages_behind_window"}
    assert segs and all(names <= set(a) for a in segs)
    # Contexts of 60 to 400 positions over 8-wide pages against a
    # 16-token window on 3 layers of 4: a row at context L holds
    # ceil(L / 8) pages on every layer, of which (L - 16) // 8 lie
    # wholly behind the window on each window layer — so a segment's
    # share is under 3/4 and, past 60 positions, over 3/4 x 5/8.
    for a in segs:
        assert 0 < a["pages_behind_window"] < 0.75 * a["pages_held"]
        assert a["pages_held"] % 4 == 0 and a["pages_behind_window"] % 3 == 0
        assert a["pages_behind_window"] > 0.75 * 0.6 * a["pages_held"]
    # a plain segment's rows: each holds at least 60 / 8 pages a layer
    plain = [a for a in segs if a["kind"] == "plain" and a["steps"] > 1]
    assert plain and all(a["pages_held"] >= 4 * 8 * a["rows"]
                         for a in plain)
    attn = eng.describe()["attention"]
    for name in names:
        assert attn[name] - before[name] >= sum(a[name] for a in segs) > 0
    assert telemetry.REGISTRY.counter_total(
        "roundtable_window_pages_held_total") >= attn["pages_held"] > 0
    assert telemetry.REGISTRY.counter_total(
        "roundtable_window_pages_behind_total") \
        >= attn["pages_behind_window"] > 0
