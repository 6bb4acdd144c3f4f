"""A model with latent pages and held experts through the serving path,
on the CPU: prefill and decode through `PagedKVCache`'s second page
shape and the latent kernels (absorbed form), own-slot reuse, the leader
pass and the prefix index over latent pages, the offload tier over
one-pool layers, and the decline table (through the scheduler:
tests/test_mla_scheduled.py).

Every path ends in a comparison with the plain reference
(benchmarks/configs/mla_moe_reference.py, the EXPANDED form over whole
sequences) on the engine's own weights: a float32 engine serves the
reference's own maximum at every position (gap 0 but for rounding-level
ties, held to 1e-3 of a logit whose spread is about 1).

Logits, prefill then decode (`test_prefill_then_decode_logits...`):
float32 pages and weights within 1e-4 of the reference at every
position — the same bound, for the same reason, as the whole forward in
test_mla_model.py (order of sums; absorbed against expanded) — and a
bfloat16 program 0.02 to 0.06 off (seeds 0-4), held to 0.15: it FAILS
1e-4, so a bfloat16-for-float32 swap is told apart."""
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from configs import mla_moe_reference as ref  # noqa: E402

from theroundtaible_tpu.engine.engine import InferenceEngine  # noqa: E402
from theroundtaible_tpu.engine.models.common import init_params  # noqa: E402
from theroundtaible_tpu.engine.models.registry import (  # noqa: E402
    get_model_config)
from theroundtaible_tpu.engine.paged_forward import (  # noqa: E402
    forward_paged_hybrid)
from theroundtaible_tpu.utils import telemetry  # noqa: E402

GAP = 1e-3
PAGE = 16
PUBLISHED = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "rms_norm_eps": 1e-6, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "kv_lora_rank": 32, "rope_theta": 10000.0,
    "rope_scaling": {"type": "yarn", "factor": 32.0,
                     "original_max_position_embeddings": 64.0,
                     "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 1.0,
                     "mscale_all_dim": 1.0},
    "num_experts_per_tok": 2, "routed_scaling_factor": 2.5,
    "n_routed_experts": 8}


def make_engine(**kw):
    config = {"model": "tiny-axk1", "dtype": "float32",
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


def tokens_of(seed, n):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 250, size=(n,))]


def worst_gap(eng, prompt, served):
    """How far below the reference's maximum the served tokens lie, at
    their own positions, over prompt + what was served before them."""
    seq = prompt + served
    rows = list(range(len(prompt) - 1, len(seq) - 1))
    logits = np.asarray(ref.logits_at(eng.params, PUBLISHED,
                                      np.asarray(seq), rows))
    return max(float(row.max() - row[tok])
               for row, tok in zip(logits, served))


def serve(eng, name, prompt, n=8):
    """-> (tokens the slot committed after the prompt, stats)."""
    _texts, stats = eng.generate_batch_with_stats(
        [(name, prompt)], max_new_tokens=n)
    committed = eng.kv._slots[name].tokens
    assert committed[:len(prompt)] == prompt
    return committed[len(prompt):], stats


# --- logits through latent pages ---------------------------------------------


@pytest.mark.parametrize("dtype,low,high", [
    (jnp.float32, 0.0, 1e-4), (jnp.bfloat16, 1e-4, 0.15)])
def test_prefill_then_decode_logits_against_the_whole_forward(dtype, low,
                                                              high):
    """48 tokens as one prefill chunk into latent pages, then 6 single
    steps that read them back through the decode walk: each position's
    logits against the reference's forward over the whole sequence."""
    cfg = get_model_config("tiny-axk1")
    params = init_params(cfg, jax.random.PRNGKey(3), dtype)
    tokens = np.asarray([1] + tokens_of(40, 53))
    pools = [(jnp.zeros((9, PAGE, cfg.page_width), dtype),)
             for _ in cfg.attention_layers]
    table = jnp.arange(1, 9)[None]
    state = {"ssm": [], "conv": []}
    want = np.asarray(ref.logits_at(params, PUBLISHED, tokens,
                                    list(range(47, 54))))
    with jax.default_matmul_precision("highest"):
        logits, pools, _, _, counts = forward_paged_hybrid(
            params, cfg, jnp.asarray(tokens[None, :48]),
            jnp.arange(48)[None], pools, table, jnp.asarray([48]), state,
            lengths=jnp.asarray([48]), last_pos=jnp.asarray([47]))
        got = [np.asarray(logits[0, 0], np.float32)]
        assert int(counts[2]) == 2 and int(counts[1]) == 48 * 2 * 2
        for pos in range(48, 54):
            logits, pools, _, _, _ = forward_paged_hybrid(
                params, cfg, jnp.asarray(tokens[None, pos:pos + 1]),
                jnp.asarray([[pos]]), pools, table,
                jnp.asarray([pos + 1]), state,
                active=jnp.asarray([True]))
            got.append(np.asarray(logits[0, 0], np.float32))
    # One pool a layer, 32 + 8 live columns of a lane row, no values.
    assert all(len(p) == 1 and p[0].shape == (9, PAGE, 128) for p in pools)
    assert not np.asarray(pools[0][0][..., 40:], np.float32).any()
    worst = max(np.abs(g - w).max() for g, w in zip(got, want))
    assert low <= worst < high


# --- engine ----------------------------------------------------------------


def test_prefill_then_decode_through_latent_pages(engine):
    prompt = [1] + tokens_of(1, 69)
    served, stats = serve(engine, "a", prompt)
    assert len(served) == 7 and stats.prefill_tokens == 70
    assert worst_gap(engine, prompt, served) < GAP
    info = engine.describe()
    assert info["paged_decode"] == "pool-direct"
    assert info["ragged"]["path"] == "pallas_ragged"
    mla = info["mla"]
    assert mla["pool_shape"] == [engine.kv.num_pages, PAGE, 128]
    assert mla["pools_per_layer"] == 1 and mla["layers"] == 3
    assert (mla["entry_width"], mla["page_width"]) == (40, 128)
    assert (mla["bytes_per_position_published"],
            mla["bytes_per_position_stored"]) == (160, 512)
    assert mla["form"] == mla["prologue_form"] == "absorbed"
    assert (mla["paged_decode"], mla["paged_prefill"], mla["ragged"]) \
        == ("mla_paged_decode", "mla_paged_prefill", "mla_ragged")
    assert mla["decode_decline"] is None and mla["ragged_decline"] is None
    assert set(mla) == set(telemetry.SURFACE_BINDINGS["engine_mla"])
    assert info["moe"]["held"] == 8 and info["moe"]["experts_hit"] > 0
    # Pools: the attention layers alone, one latent pool each; the
    # state tree is empty and holds no bytes.
    assert [len(p) for p in engine.kv.pools] == [1, 1, 1]
    assert engine.hybrid.state == {"ssm": [], "conv": []}
    assert engine.hybrid.hbm_bytes() == 0
    assert engine.kv.hbm_bytes() == 3 * engine.kv.num_pages * PAGE * 128 * 4


def test_own_slot_reuse_prefills_only_the_new_tokens(engine):
    first = [1] + tokens_of(2, 50)
    served, _ = serve(engine, "cont", first)
    longer = first + served + tokens_of(3, 30)
    again, stats = serve(engine, "cont", longer)
    assert stats.prefill_tokens == 30
    assert worst_gap(engine, longer, again) < GAP


def test_the_prefix_index_hands_latent_pages_to_another_slot(engine):
    base = [1] + tokens_of(4, 70)
    serve(engine, "donor", base)
    other = base[:64] + tokens_of(5, 25)
    served, stats = serve(engine, "taker", other)
    assert stats.prefill_tokens == 25           # four whole pages by alias
    assert engine.kv._slots["taker"].pages[:4] \
        == engine.kv._slots["donor"].pages[:4]
    assert worst_gap(engine, other, served) < GAP


def test_the_leader_pass_shares_a_prefix_inside_one_batch(engine):
    """Three rows with one 100-token opening, in one batch: the leader
    prefills it once and the others alias its pages — nothing declines
    for a model without recurrent state."""
    opening = [1] + tokens_of(6, 99)
    turns = [(f"k{i}", opening + tokens_of(60 + i, 10)) for i in range(3)]
    _texts, stats = engine.generate_batch_with_stats(turns,
                                                     max_new_tokens=4)
    assert stats.prefill_tokens < 100 + 3 * 10 + 3 * PAGE
    assert engine.hybrid.describe()["share_declined"] == 0
    for name, prompt in turns:
        served = engine.kv._slots[name].tokens[len(prompt):]
        assert worst_gap(engine, prompt, served) < GAP


def test_a_spilled_session_comes_back_from_host_memory():
    """The offload tier over layers of ONE pool: spill, restore, and the
    restored latent pages serve the reference's tokens."""
    from theroundtaible_tpu.engine.kvcache import scoped_slot
    eng = make_engine(num_slots=4, prefix_cache=False)  # isolate the tier
    assert eng.kv_offload is not None
    name = scoped_slot("sess", "lancelot")
    prompt = [1] + tokens_of(7, 40)
    served, _ = serve(eng, name, prompt)
    tier = eng.kv_offload
    assert tier.spill_session("sess") == 1
    assert tier.has("sess") and name not in eng.kv._slots
    longer = prompt + served + tokens_of(8, 12)
    again, stats = serve(eng, name, longer)
    assert tier.restores >= 1 and stats.prefill_tokens == 12
    assert worst_gap(eng, longer, again) < GAP


# --- what declines ---------------------------------------------------------


@pytest.fixture(scope="module")
def asked_for_everything():
    """One engine asked for every feature that reads inside a latent
    page or a weight leaf, at once."""
    return make_engine(
        num_slots=2, spec_decode=True, kv_quant="int8", quant="int8",
        seq_parallel=2, lora={"max_adapters": 2, "rank": 4})


@pytest.mark.parametrize("feature,where,reason", [
    ("spec_decode", "spec_reason", "latent-pages:no-verify-program"),
    ("lora", "lora_reason", "latent-pages:no-lora-targets"),
    ("kv_quant", "kv_quant_reason", "latent-pages:cells-are-per-head"),
    ("quant", None, "latent-pages:quant-leaves"),
    ("seq_parallel", None, "latent-pages"),
])
def test_what_cannot_read_a_latent_page_declines_with_a_reason(
        asked_for_everything, feature, where, reason):
    eng = asked_for_everything
    assert eng.describe()["declines"][feature] == reason
    if where:
        assert getattr(eng, where) == reason
    assert eng.quant == "none" and eng.kv_quant_spec is None
    assert not eng.spec_decode and eng.lora is None
    assert eng._ring_prefill_fn is None
    # What addresses pages by id stays on.
    assert eng.prefix_cache is not None and eng.kv_offload is not None


def test_an_engine_that_declined_still_serves_right(asked_for_everything):
    eng = asked_for_everything
    prompt = [1] + tokens_of(9, 20)
    served, _ = serve(eng, "x", prompt, n=3)
    assert worst_gap(eng, prompt, served) < GAP


@pytest.mark.parametrize("config,message", [
    ({"kv_layout": "contiguous"}, "paged"),
    ({"mesh": {"data": 1, "model": 2}}, "mesh"),
    ({"attn": "dense"}, "pool-direct"),
])
def test_what_the_model_cannot_serve_without_fails_at_build(config,
                                                            message):
    with pytest.raises(ValueError, match=message):
        make_engine(num_slots=2, **config)


def test_fleet_counts_a_latent_page_at_its_stored_width():
    from theroundtaible_tpu.engine.fleet import estimate_engine_hbm_bytes
    base = {"model": "tiny-axk1", "kv_layout": "paged", "num_slots": 4,
            "page_size": 16}
    more = (estimate_engine_hbm_bytes(dict(base, num_pages=64))
            - estimate_engine_hbm_bytes(dict(base, num_pages=32)))
    # 32 pages x 16 positions x 3 attention layers x 128 cells x 2 B
    assert more == 32 * 16 * 3 * 128 * 2
