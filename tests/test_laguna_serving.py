"""A model whose attention layers differ (window and full layers, 6 or 8
heads over one GQA page pool, two rotary tables, a per-head gate) with
all its experts held, through engine and scheduler on the CPU: prologue
and decode through `PagedKVCache`, own-slot reuse, the prefix index with
the 16-token window spanning aliased pages, three knights over two
rounds with ragged joins and the leader pass, the segment spans' reads
by layer class, and the decline table. (The offload tier moves whole
pages by id whatever the layers: tests/test_prefix_cache.py.)

Every served token is compared with the plain reference
(benchmarks/configs/laguna_reference.py) on the engine's own weights: a
float32 engine serves the reference's own maximum at every position
(gap 0 but for rounding-level ties, held to 1e-3 of a logit whose spread
is about 1). Logit-for-logit comparisons: tests/test_laguna_model.py."""
import os
import sys
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from configs import laguna_reference as ref  # noqa: E402

from test_laguna_model import PUBLISHED, tokens_of  # noqa: E402

from theroundtaible_tpu.engine.engine import InferenceEngine  # noqa: E402
from theroundtaible_tpu.engine.scheduler import SessionScheduler  # noqa: E402
from theroundtaible_tpu.utils import telemetry  # noqa: E402

GAP = 1e-3
PAGE = 8
WINDOW = 16
KNIGHTS = ["lancelot", "galahad", "percival"]


def make_engine(**kw):
    config = {"model": "tiny-laguna", "dtype": "float32",
              "kv_layout": "paged", "page_size": PAGE, "num_slots": 8,
              "max_seq_len": 512, "seed": 3,
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
        == (2, 16, "per-head")
    assert [(a["layer"], a["heads"], a["window"], a["rotary_dim"])
            for a in attn["layers"]] == [
        (0, 6, None, 8), (2, 8, WINDOW, 16), (4, 8, WINDOW, 16),
        (6, 8, WINDOW, 16), (8, 6, None, 8)]
    assert attn["layers"][0]["rope_yarn"] == [8.0, 32.0, 64.0, 1.0]
    assert attn["layers"][1]["rope_yarn"] is None
    assert [(c["heads"], c["window"], c["layers"], c["decode_decline"],
             c["ragged_decline"]) for c in attn["classes"]] == [
        (6, None, 2, None, None), (8, WINDOW, 3, None, None)]
    # Every expert of a layer is held: the deployment's own token share.
    assert info["moe"]["held"] == 8 and info["moe"]["experts_hit"] > 0
    # One pool shape for five layers that differ: [P, ps, 2, 16] twice.
    assert [tuple(p.shape for p in layer) for layer in engine.kv.pools] \
        == [((engine.kv.num_pages, PAGE, 2, 16),) * 2] * 5
    assert engine.hybrid.state == {"ssm": [], "conv": []}


def test_own_slot_reuse_prefills_only_the_new_tokens(engine):
    first = [1] + tokens_of(2, 50)
    served, _ = serve(engine, "cont", first)
    longer = first + served + tokens_of(3, 30)
    again, stats = serve(engine, "cont", longer)
    assert stats.prefill_tokens == 30
    assert worst_gap(engine, longer, again) < GAP


def test_the_prefix_index_hands_whole_pages_to_another_slot(engine):
    """Window layers keep whole pages under the one page table, so the
    index stays exact page-id work: the taker's window (16) spans the
    donor's last two pages and its own first."""
    base = [1] + tokens_of(4, 70)
    serve(engine, "donor", base)
    other = base[:64] + tokens_of(5, 25)
    served, stats = serve(engine, "taker", other)
    assert stats.prefill_tokens == 25           # eight whole pages by alias
    assert engine.kv._slots["taker"].pages[:8] \
        == engine.kv._slots["donor"].pages[:8]
    assert worst_gap(engine, other, served) < GAP


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
    ({"attn": "dense"}, "pool-direct"),
])
def test_what_the_model_cannot_serve_without_fails_at_build(config,
                                                            message):
    with pytest.raises(ValueError, match=message):
        make_engine(num_slots=2, **config)


def test_a_group_the_ragged_kernel_declines_is_named(monkeypatch):
    """One class's decline decides the path and is written down: the
    cell may not run on it (`degraded_paths` reads the same fields)."""
    from theroundtaible_tpu.engine.pallas import attention as pattn
    real = pattn.ragged_decline_reason

    def declines_group_three(page_size, d, kh=1, group=1, **kw):
        if group == 3:
            return f"vmem:ps={page_size},d={d},kh={kh},g={group}"
        return real(page_size, d, kh, group, **kw)

    monkeypatch.setattr(pattn, "ragged_decline_reason",
                        declines_group_three)
    eng = make_engine(num_slots=2)
    assert eng.ragged_path == "xla_ragged"
    info = eng.describe()
    assert info["declines"]["ragged_kernel"] == "vmem:ps=8,d=16,kh=2,g=3"
    assert info["ragged"]["fallback_reason"] \
        == info["declines"]["ragged_kernel"]
    assert [c["ragged_decline"] for c in info["attention"]["classes"]] \
        == ["vmem:ps=8,d=16,kh=2,g=3", None]


# --- scheduler ---------------------------------------------------------------


def cue(knight, round_no):
    return [3 + ord(c) for c in f"\n[r{round_no}] {knight}: "]


def discussion(sched, eng, sid, opening, rounds=3, new=12):
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


def test_three_knights_two_rounds_and_the_reads_by_layer_class(engine):
    eng = engine
    sched = SessionScheduler(eng)
    telemetry.arm()
    t_a = time.monotonic()
    results, errors = {}, []

    def run(sid, seed, n_open):
        try:
            results[sid] = discussion(
                sched, eng, sid, [1] + tokens_of(seed, n_open), rounds=2)
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
    assert d["failed"] == 0 and d["completed"] == 4
    assert d["ragged_joins"] >= 1
    # round 2 prefills the knights' deltas alone: the leader pass,
    # own-slot reuse and the prefix index are on over window layers
    prompts = sum(len(p) for served in results.values()
                  for p, _a in served)
    assert d["segment_prefill_tokens"] < prompts / 2
    assert eng.hybrid.describe()["share_declined"] == 0
    segs = [s["attrs"] for s in spans if s["rung"] == "segment"]
    names = {"page_visits_full", "page_visits_window"}
    assert segs and all(names <= set(a) for a in segs)
    assert all({"experts_hit", "local_assignments", "expert_layer_steps"}
               <= set(a) for a in segs[1:])
    # A plain segment of `steps` steps over rows whose contexts (60 to
    # 250 positions) pass the 16-token window: each of the 3 window
    # layers reads 2 or 3 pages a row a step, each of the 2 full layers
    # every page the row holds.
    plain = [a for a in segs if a["kind"] == "plain" and a["steps"] > 1]
    assert plain
    for a in plain:
        row_steps = a["steps"] * a["rows"]
        assert 3 * 2 * row_steps <= a["page_visits_window"] \
            <= 3 * 3 * row_steps
        assert a["page_visits_full"] >= 2 * (60 // PAGE) * row_steps
        assert a["page_visits_full"] * 3 > a["page_visits_window"] * 2
    ragged = [a for a in segs if a["kind"] == "ragged"]
    assert ragged and all(a["page_visits_window"] > 0
                          and a["page_visits"] > 0 for a in ragged)
    attn = eng.describe()["attention"]
    for name in names:
        assert attn[name] >= sum(a[name] for a in segs) > 0
    assert telemetry.REGISTRY.counter_total(
        "roundtable_window_page_visits_window_total") > 0
    assert telemetry.REGISTRY.counter_total(
        "roundtable_window_page_visits_full_total") > 0


def test_a_model_without_attn_layers_carries_none_of_it():
    """Mistral's path is as it was: no class counts on its spans, no
    `attention` in its describe()."""
    eng = InferenceEngine.from_config({
        "model": "tiny-mistral", "dtype": "float32", "kv_layout": "paged",
        "page_size": PAGE, "num_slots": 2, "max_seq_len": 256, "seed": 3,
        "sampling": {"temperature": 0.0},
        "mesh": {"data": 1, "model": 1}})
    eng.generate_batch_with_stats([("a", [1] + tokens_of(1, 20))],
                                  max_new_tokens=3)
    assert "attention" not in eng.describe()
    assert eng._window_reads == {"page_visits_full": 0,
                                 "page_visits_window": 0,
                                 "pages_held": 0, "pages_behind_window": 0}
