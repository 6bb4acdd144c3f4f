"""A model whose Mamba-1 layers run as SCANNED RUNS beside attention
layers of one kv head, through the serving path on the CPU (tiny-jamba:
one period — seven Mamba-1 blocks, attention at layer 7, six more;
models/mamba1.py): prefill and decode through the stacked tree and the
run-stacked slot states, own-slot continuation, capture at a page
boundary and restore into another slot, ragged joins through the
scheduler with their `scan_tokens`, the decline table, the seeded
recipe's memory, the one-kv-head pool through the three paged kernels at
the published group, and two controls that must FAIL — the state rounded
to bfloat16 every step, and a state restored from the wrong snapshot.

Every path ends in a comparison with the plain reference
(benchmarks/configs/jamba_reference.py: the recurrence a token at a
time, no chunks, no scan over layers) on the engine's own weights. GAP:
a float32 engine serves the reference's own maximum at every position
but for rounding-level ties (1e-3 of a logit whose spread is about 0.16,
as the other models' serving tests hold it). LOGIT_TOL, for logits
compared as logits: the scanned, chunked form sums the same float32
products in another order, which moves a logit by 2.4e-6 here (the
reading); with the state rounded to bfloat16 after every decode step it
moves by 2.3e-2 (the control's reading). 2e-5 lies between, an order
above the first and three under the second."""
import os
import sys
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from configs import jamba_reference as ref  # noqa: E402

from theroundtaible_tpu.engine.engine import InferenceEngine  # noqa: E402
from theroundtaible_tpu.engine.hybrid_state import page_keys  # noqa: E402
from theroundtaible_tpu.engine.models import hybrid, mamba1  # noqa: E402
from theroundtaible_tpu.engine.models.registry import (  # noqa: E402
    get_model_config, resolve_model_config)
from theroundtaible_tpu.engine.pallas import attention as pattn  # noqa: E402
from theroundtaible_tpu.engine.scheduler import SessionScheduler  # noqa: E402
from theroundtaible_tpu.utils import telemetry  # noqa: E402

GAP = 1e-3
LOGIT_TOL = 2e-5
PAGE = 16
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 8,
    "mamba_dt_rank": 4, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 4, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 14, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-6, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 512}


def make_engine(**kw):
    config = {"model": "tiny-jamba", "dtype": "float32",
              "kv_layout": "paged", "page_size": PAGE, "num_slots": 8,
              "max_seq_len": 512, "seed": 3,
              "sampling": {"temperature": 0.0},
              "mesh": {"data": 1, "model": 1}}
    config.update(kw)
    return InferenceEngine.from_config(config)


@pytest.fixture(scope="module")
def engine():
    return make_engine()


def tokens_of(seed, n):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 250, size=(n,))]


def reference_logits(eng, seq, rows):
    return np.asarray(ref.logits_at(eng.params, PUBLISHED, np.asarray(seq),
                                    rows))


def worst_gap(eng, prompt, served):
    seq = prompt + served
    logits = reference_logits(
        eng, seq, list(range(len(prompt) - 1, len(seq) - 1)))
    return max(float(row.max() - row[tok])
               for row, tok in zip(logits, served))


def serve(eng, name, prompt, n=8):
    _texts, stats = eng.generate_batch_with_stats(
        [(name, prompt)], max_new_tokens=n)
    committed = eng.kv._slots[name].tokens
    assert committed[:len(prompt)] == prompt
    return committed[len(prompt):], stats


def test_the_published_keys_build_the_preset_and_an_unknown_key_fails():
    cfg = resolve_model_config({"model": "tiny-jamba",
                                "architecture": dict(PUBLISHED),
                                "max_seq_len": 512})
    assert cfg == get_model_config("tiny-jamba")
    assert cfg.layer_kinds[14:16] == (hybrid.ATTENTION, hybrid.MLP)
    assert cfg.layer_kinds.count(hybrid.MAMBA1) == 13
    assert cfg.recurrent and cfg.tie_embeddings and not cfg.rope
    with pytest.raises(ValueError, match="unknown keys .*mamba_dt_scale"):
        resolve_model_config({"model": "x", "architecture": dict(
            PUBLISHED, mamba_dt_scale=2)})
    with pytest.raises(ValueError, match="num_experts=2"):
        resolve_model_config({"model": "x", "architecture": dict(
            PUBLISHED, num_experts=2)})
    with pytest.raises(ValueError, match="rope=True"):
        resolve_model_config({"model": "x", "architecture": dict(
            PUBLISHED, rope=True)})
    with pytest.raises(ValueError, match="lacks the key 'mamba_dt_rank'"):
        resolve_model_config({"model": "x", "architecture": {
            k: v for k, v in PUBLISHED.items() if k != "mamba_dt_rank"}})
    full = get_model_config("jamba2-3b")
    assert (full.num_layers, full.embed_dim, full.mlp_dim, full.mamba1_dim,
            full.kv_repeat) == (56, 2560, 8192, 5120, 20)
    assert full.attention_layers == (14, 42)


def test_prefill_then_decode_through_the_scanned_runs(engine):
    prompt = [1] + tokens_of(1, 69)
    served, stats = serve(engine, "a", prompt)
    assert len(served) == 7 and stats.prefill_tokens == 70
    assert len(set(served)) > 3           # not the last token read, again
    assert worst_gap(engine, prompt, served) < GAP
    info = engine.describe()
    assert info["paged_decode"] == "pool-direct"
    assert info["hybrid_state"]["misses"] >= 1
    # One attention layer of ONE kv head: two pools [P, ps, 1, D]; the
    # state a leaf a scanned run, rows ahead of layers.
    assert len(engine.kv.pools) == 1
    assert engine.kv.pools[0][0].shape[1:] == (PAGE, 1, 16)
    state = engine.hybrid.state
    assert set(state) == {"ssm", "conv", "ssm1", "conv1"}
    assert state["ssm"] == [] and [a.shape for a in state["ssm1"]] == [
        (9, 7, 8, 1, 128), (9, 6, 8, 1, 128)]
    assert [a.shape[1:3] for a in state["conv1"]] == [(7, 3), (6, 3)]
    # ... and the parameters: one stacked entry a run, never a layer.
    assert [sorted(e) == ["mamba1", "mlp"] for e in engine.params["layers"]
            ] == [True, False, False, True]


def test_describe_names_the_layout_the_kernel_and_the_declines(engine):
    info = engine.describe()
    scanned = info["mamba1"].pop("scan_tokens")
    assert scanned > 0 and scanned % 13 == 0
    assert info["mamba1"] == {
        "layers": 13, "d_inner": 128, "d_state": 8,
        "bytes_per_state": (8 + 3) * 128 * 4,
        "state_layout": "[rows, run_layers, 8, 1, 128] float32 a run",
        "kernel": "jnp", "scan_runs": [7, 6]}
    assert info["hybrid_state"]["bytes_per_state"] == 13 * 11 * 128 * 4
    assert info["declines"]["mamba1_scan"].startswith("not on a TPU")
    assert info["declines"]["spec_decode"] == "recurrent-state"
    assert "evacuation" in info["declines"]
    # (the leader pass hands its state on: tests/test_state_handover.py)
    assert "leader_state_handover" not in info["declines"]
    assert set(info["mamba1"]) | {"scan_tokens"} == set(
        telemetry.SURFACE_BINDINGS["engine_mamba1"])
    assert engine.joins_ragged_alone


def test_what_is_asked_for_and_declined_says_why():
    eng = make_engine(quant="int8", kv_quant="int8", kv_offload=True,
                      lora={"adapters": {}}, seq_parallel=2)
    declines = eng.describe()["declines"]
    assert {k: declines[k] for k in ("quant", "kv_quant", "kv_offload",
                                     "lora", "seq_parallel")} == {
        "quant": "recurrent-state:quant-leaves",
        "kv_quant": "recurrent-state", "kv_offload": "recurrent-state",
        "lora": "recurrent-state", "seq_parallel": "recurrent-state"}
    assert eng.quant == "none" and eng.kv_offload is None


def test_own_slot_continuation(engine):
    first = [1] + tokens_of(2, 50)
    served, _ = serve(engine, "cont", first)
    before = engine.hybrid.describe()
    longer = first + served + tokens_of(3, 30)
    again, stats = serve(engine, "cont", longer)
    after = engine.hybrid.describe()
    assert stats.prefill_tokens == 30
    assert after["continued_tokens"] - before["continued_tokens"] \
        == len(first + served)
    assert after["restore_bytes"] == before["restore_bytes"]
    assert worst_gap(engine, longer, again) < GAP


def test_a_snapshot_restores_into_another_slot_and_the_wrong_one_fails(
        engine):
    """Two donors of 64 tokens leave snapshots at the page boundary. A
    taker of the first donor's prefix starts from that state and
    prefills 64 tokens of its own: the logits of its LAST position, 64
    tokens after the restore, are the reference's. Then the store's
    index is made to hand it the second donor's state: the same logits
    leave LOGIT_TOL — the seeded decays carry a wrong restore that far."""
    seen = []
    program = engine._prefill_step_hybrid

    def spy(*args, **kw):
        out = program(*args, **kw)
        seen.append(np.asarray(out[0])[0])
        return out

    engine._prefill_step_hybrid = spy
    try:
        one, two = [1] + tokens_of(14, 69), [1] + tokens_of(15, 69)
        serve(engine, "one", one, n=2)
        serve(engine, "two", two, n=2)
        assert engine.hybrid.holds(one, 64) and engine.hybrid.holds(two, 64)
        taker = one[:64] + tokens_of(16, 64)
        want = reference_logits(engine, taker, [127])[0]
        store = engine.hybrid
        before = store.describe()
        served, stats = serve(engine, "right", taker, n=4)
        after = store.describe()
        assert stats.prefill_tokens == 64      # the prefix: reused, by id
        assert after["reused_tokens"] - before["reused_tokens"] == 64
        per = after["bytes_per_state"]
        assert after["restore_bytes"] - before["restore_bytes"] == per
        assert np.abs(seen[-1] - want).max() < LOGIT_TOL
        assert worst_gap(engine, taker, served) < GAP
        key_one = page_keys(one, PAGE, 64)[-1]
        key_two = page_keys(two, PAGE, 64)[-1]
        snap = store._snap
        snap[key_one], snap[key_two] = snap[key_two], snap[key_one]
        serve(engine, "wrong", taker, n=2)
        snap[key_one], snap[key_two] = snap[key_two], snap[key_one]
        assert store.describe()["reused_tokens"] \
            - before["reused_tokens"] == 128
        assert np.abs(seen[-1] - want).max() > 5 * LOGIT_TOL
    finally:
        engine._prefill_step_hybrid = program


def _median_decay(eng, tokens):
    """The median of exp(dt A) at state index 0 (A = -1) over `tokens`
    and every channel of three Mamba layers, from the weights alone
    (the reference's residual stream, the layer equations by hand)."""
    decays = []
    layers = list(ref.published_layers(eng.params, ref.sizes_of(PUBLISHED)))
    for at in (0, 6, 13):
        x = ref.hidden_after(eng.params, PUBLISHED, np.asarray(tokens),
                             n_blocks=at)
        _attn, layer, _mlp = layers[at]
        f32 = ref.as_float32
        h = ref._normed(x, f32(layer["norm"]), 1e-6)
        u = (h @ f32(layer["in_proj"]))[:, :128]
        pad = jnp.concatenate([jnp.zeros((3, 128)), u], 0)
        c = jax.nn.silu(f32(layer["conv_b"]) + sum(
            f32(layer["conv_w"])[j] * pad[j:j + len(tokens)]
            for j in range(4)))
        dl = ref._normed((c @ f32(layer["x_proj"]))[:, :4],
                         f32(layer["dt_norm"]), 1e-6)
        dt = jax.nn.softplus(dl @ f32(layer["dt_proj"])
                             + f32(layer["dt_bias"]))
        assert np.allclose(np.exp(np.asarray(layer["A_log"])[0]), 1.0)
        decays.append(np.asarray(jnp.exp(-dt)))
    return float(np.median(np.concatenate(decays)))


def test_the_seeded_recipe_remembers_for_tens_to_hundreds_of_tokens(engine):
    """hybrid.init_layer / mamba1.init_mixer / common.init_params: the
    reference implementation's initialisation, the embedding at the
    initialiser's range (tied head), every out-projection at
    MAMBA1_SHARE."""
    decay = _median_decay(engine, [1] + tokens_of(12, 199))
    assert 0.98 <= decay <= 0.999, decay
    emb = np.asarray(engine.params["embedding"])
    assert emb.std() == pytest.approx(hybrid.TIED_EMBED_STD, rel=0.05)
    assert "lm_head" not in engine.params
    for kind, layer in hybrid.layers_unrolled(engine.cfg, engine.params):
        out = np.asarray(layer.get("out_proj", layer.get(
            "o_proj", layer.get("down_proj"))), np.float32)
        fan_in = out.size // out.shape[-1]
        assert out.std() * fan_in ** 0.5 == pytest.approx(
            hybrid.MAMBA1_SHARE, rel=0.1), kind


def step_logits(eng, tokens, n_prompt):
    """Logits [len(tokens) - n_prompt + 1, V] of the serving path's own
    forward (paged_forward.forward_paged_hybrid, as the step programs
    call it, its runs scanned): the prompt as one prefill from a zero
    state, then every further token as one decode step on that state."""
    from theroundtaible_tpu.engine.paged_forward import forward_paged_hybrid
    cfg = eng.cfg
    state = hybrid.zero_state(cfg, 2)          # the row and the scratch
    pools = [tuple(jnp.zeros((8, PAGE, 1, 16), jnp.float32)
                   for _ in range(2))]
    rows = jnp.asarray([0])
    table = jnp.arange(1, 9, dtype=jnp.int32)[None] % 8
    prompt = jnp.asarray(tokens[:n_prompt])[None]
    logits, pools, state, _c, _n = forward_paged_hybrid(
        eng.params, cfg, prompt, jnp.arange(n_prompt)[None], pools, table,
        jnp.asarray([n_prompt]), state, lengths=jnp.asarray([n_prompt]),
        last_pos=jnp.asarray([n_prompt - 1]), page_size=PAGE, rows=rows)
    out = [np.asarray(logits[0, 0])]
    step = jax.jit(lambda tok, at, pools, state: forward_paged_hybrid(
        eng.params, cfg, tok, at, pools, table, at[0] + 1, state,
        active=jnp.asarray([True]), page_size=PAGE, rows=rows)[:3])
    for at in range(n_prompt, len(tokens)):
        logits, pools, state = step(jnp.asarray([[tokens[at]]]),
                                    jnp.asarray([[at]]), pools, state)
        out.append(np.asarray(logits[0, 0]))
    return np.stack(out)


def test_prefill_then_decode_logits_and_the_bfloat16_state_control(
        engine, monkeypatch):
    """LOGITS of the served forward against the reference's, the last
    position of a 48-token prefill and 33 decode steps, within LOGIT_TOL. The
    control: the same steps with the Mamba state rounded to bfloat16
    after each one — the next precision down — leaves that tolerance."""
    tokens = [1] + tokens_of(13, 80)
    want = reference_logits(engine, tokens, list(range(47, len(tokens))))
    got = step_logits(engine, tokens, 48)
    assert np.abs(got - want).max() < LOGIT_TOL
    exact = mamba1.mamba1_step

    def rounded(*args):
        out, ssm, conv = exact(*args)
        return out, ssm.astype(jnp.bfloat16).astype(jnp.float32), conv

    monkeypatch.setattr(mamba1, "mamba1_step", rounded)
    off = np.abs(step_logits(engine, tokens, 48) - want).max()
    assert off > 5 * LOGIT_TOL, off


# --- the one-kv-head pool through the three paged kernels --------------------


def _dense(q, keys, vals, pos):
    """float32 attention of q [n, H, D] at positions `pos` over one
    sequence's keys / values [L, D] (ONE kv head, every query head's)."""
    s = np.einsum("nhd,ld->nhl", q, keys)
    s = np.where((np.arange(len(keys))[None] <= pos[:, None])[:, None],
                 s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("nhl,ld->nhd", w / w.sum(-1, keepdims=True), vals)


@pytest.mark.parametrize("kernel", ["decode", "prologue", "ragged"])
def test_one_kv_head_at_group_20_through_the_paged_kernels(kernel):
    """The published attention geometry (20 query heads of 128 over ONE
    kv head: `_token_major(1, 2)` is false, the head-major form) in
    interpret mode against a dense softmax, bfloat16 pages read as the
    rounded values they hold."""
    from theroundtaible_tpu.engine.serving_loop import (RaggedSeq,
                                                        build_ragged_batch)
    h, d, ps = 20, 128, 16
    assert not pattn._token_major(1, 2)
    rng = np.random.default_rng(47)
    pool = [jnp.asarray(rng.standard_normal((12, ps, 1, d)), jnp.bfloat16)
            for _ in range(2)]
    kf, vf = (np.asarray(p.astype(jnp.float32)) for p in pool)
    tables = np.zeros((3, 6), np.int32)
    tables[0, :3], tables[1, :4], tables[2, :2] = [1, 2, 3], [4, 5, 6, 7], \
        [8, 9]

    def seq(i, n):
        return (kf[tables[i]].reshape(-1, d)[:n],
                vf[tables[i]].reshape(-1, d)[:n])

    def queries(*shape):
        q = jnp.asarray(rng.standard_normal(shape + (h, d)) * d ** -0.5,
                        jnp.bfloat16)
        return q, np.asarray(q.astype(jnp.float32))

    if kernel == "decode":
        valid = np.array([40, 57, 17], np.int32)
        q, qf = queries(3, 1)
        out = pattn.paged_decode_attention(
            q, pool[0], pool[1], jnp.asarray(tables), jnp.asarray(valid))
        for i in range(3):
            want = _dense(qf[i], *seq(i, valid[i]),
                          np.array([valid[i] - 1]))
            np.testing.assert_allclose(
                np.asarray(out[i], np.float32), want, atol=2e-2, rtol=2e-2)
    elif kernel == "prologue":
        first, t = np.array([16, 32], np.int32), 16
        q, qf = queries(2, t)
        out = pattn.paged_prefill_attention(
            q, pool[0], pool[1], jnp.asarray(tables[:2]),
            jnp.asarray(first), jnp.asarray(first + t))
        assert out is not None
        for i in range(2):
            want = _dense(qf[i], *seq(i, first[i] + t),
                          first[i] + np.arange(t))
            np.testing.assert_allclose(
                np.asarray(out[i], np.float32), want, atol=2e-2, rtol=2e-2)
    else:
        runs = [(21, 19), (1, 56), (9, 8)]
        batch = build_ragged_batch(
            [RaggedSeq([5] * n, pos, tables[i])
             for i, (n, pos) in enumerate(runs)],
            t_budget=64, s_max=4, pages_per_seq=6, scratch_page=0,
            pad_id=0, page_size=ps)
        q, qf = queries(64)
        out = np.asarray(pattn.ragged_paged_attention(
            q, pool[0], pool[1], *(jnp.asarray(batch[k]) for k in (
                "tables", "seq_of_block", "block_qstart", "query_offsets",
                "kv_valid"))), np.float32)
        row = 0
        for i, (n, pos) in enumerate(runs):
            want = _dense(qf[row:row + n], *seq(i, pos + n),
                          pos + np.arange(n))
            np.testing.assert_allclose(out[row:row + n], want, atol=2e-2,
                                       rtol=2e-2)
            row += -(-n // 8) * 8


# --- through the scheduler -------------------------------------------------

KNIGHTS = ["lancelot", "galahad", "percival"]


def cue(knight, round_no):
    return [3 + ord(c) for c in f"\n[r{round_no}] {knight}: "]


def discussion(sched, eng, sid, opening, rounds=2, new=10):
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


def test_three_knights_two_rounds_with_joins_mid_decode(engine):
    eng = engine
    sched = SessionScheduler(eng)
    telemetry.arm()
    t_a = time.monotonic()
    scanned = eng.describe()["mamba1"]["scan_tokens"]
    errors = []

    def run(sid, seed, n_open):
        try:
            discussion(sched, eng, sid, [1] + tokens_of(seed, n_open))
        except BaseException as e:  # noqa: BLE001 — asserted below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(f"s{i}", 30 + i,
                                                  40 + 30 * i))
               for i in range(2)]
    for t in threads:
        t.start()
        time.sleep(0.3)
    for t in threads:
        t.join()
    spans = telemetry.spans_between(t_a, time.monotonic())
    telemetry.disarm()
    sched.close()
    assert not errors, errors
    d = sched.describe()
    assert d["failed"] == 0 and d["completed"] == 4
    # Every join takes the ragged program: the state lives on the slot
    # arrays, so a prologue's program would be the same scan again.
    assert d["ragged_joins"] == d["admitted"] == 4
    info = eng.hybrid.describe()
    assert info["continued_tokens"] > 0 and info["reused_tokens"] > 0
    admits = [s["attrs"] for s in spans if s["rung"] == "admit"]
    assert admits and all(
        {"state_from", "state_copy_bytes", "kv_matched_tokens",
         "state_reused_tokens"} <= set(a) for a in admits)
    segs = [s["attrs"] for s in spans if s["rung"] == "segment"][1:]
    assert segs and all({"scan_tokens", "state_capture_bytes",
                         "snapshot_bytes"} <= set(a) for a in segs)
    # What the segments' join programs scanned: tokens x 13 layers, pads
    # left out; one writer (HybridStateStore.note_scan), one series.
    total = eng.describe()["mamba1"]["scan_tokens"] - scanned
    assert total > 0 and total % 13 == 0
    assert 0 < sum(a["scan_tokens"] for a in segs) <= total
    counters = telemetry.REGISTRY.snapshot()["counters"]
    assert any(k.startswith("roundtable_mamba1_scan_tokens_total")
               and v >= total for k, v in counters.items())
    assert eng.describe()["prefix_cache"]["hits"] > 0
