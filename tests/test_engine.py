"""Engine tests on CPU (8 virtual devices via conftest XLA flags).

Covers: forward-pass shape/causality invariants, KV-slot prefix reuse,
chunked prefill == one-shot prefill, decode determinism, batched == serial
generation, TP sharding on the virtual mesh, checkpoint round-trip.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from theroundtaible_tpu.engine.engine import InferenceEngine, _bucket
from theroundtaible_tpu.engine.models.common import (
    forward,
    init_params,
    param_count,
)
from theroundtaible_tpu.engine.models.registry import get_model_config, list_models
from theroundtaible_tpu.engine.sampling import SamplingParams, sample_token
from theroundtaible_tpu.engine.sharding import build_mesh, param_specs, shard_params
from theroundtaible_tpu.engine.tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def tiny_engine():
    return InferenceEngine(
        get_model_config("tiny-gemma"), num_slots=4,
        sampling=SamplingParams(temperature=0.0, max_new_tokens=16))


class TestModelCore:
    @pytest.mark.parametrize("name", ["tiny-gemma", "tiny-llama",
                                      "tiny-mistral"])
    def test_forward_shapes(self, name):
        cfg = get_model_config(name)
        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.arange(8)[None, :] % cfg.vocab_size
        positions = jnp.arange(8)[None, :]
        logits, caches = forward(params, cfg, tokens, positions, None, None,
                                 jnp.array([8]))
        assert logits.shape == (1, 8, cfg.vocab_size)
        assert len(caches) == cfg.num_layers
        assert caches[0][0].shape == (1, 8, cfg.num_kv_heads, cfg.head_dim)

    def test_last_pos_matches_post_slice(self):
        """forward(last_pos=p) must equal slicing full logits at p —
        the prefill paths pass last_pos so the lm head only ever sees
        one row per batch element (a batched full-sequence [B,T,V] f32
        logits temp OOM'd the discuss bench on hardware — measured once
        before PR 1; not re-measured);
        this pins the gather-before-head refactor to the old semantics,
        including ragged per-row positions."""
        cfg = get_model_config("tiny-llama")
        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8],
                              [4, 3, 2, 1, 0, 0, 0, 0]])
        positions = jnp.broadcast_to(jnp.arange(8), (2, 8))
        valid = jnp.asarray([8, 4])
        last = valid - 1
        full, _ = forward(params, cfg, tokens, positions, None, None,
                          valid)
        got, _ = forward(params, cfg, tokens, positions, None, None,
                         valid, last_pos=last)
        assert got.shape == (2, 1, cfg.vocab_size)
        want = np.stack([np.asarray(full[i, int(last[i])], np.float32)
                         for i in range(2)])
        np.testing.assert_allclose(np.asarray(got[:, 0], np.float32),
                                   want, rtol=1e-5, atol=1e-5)

    def test_causality(self):
        """Changing a future token must not affect earlier logits."""
        cfg = get_model_config("tiny-llama")
        params = init_params(cfg, jax.random.PRNGKey(0))
        positions = jnp.arange(8)[None, :]
        t1 = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]])
        t2 = t1.at[0, 6].set(9)  # change token 6
        l1, _ = forward(params, cfg, t1, positions, None, None, jnp.array([8]))
        l2, _ = forward(params, cfg, t2, positions, None, None, jnp.array([8]))
        np.testing.assert_allclose(np.asarray(l1[0, :6], np.float32),
                                   np.asarray(l2[0, :6], np.float32),
                                   rtol=1e-4, atol=1e-4)
        assert not np.allclose(np.asarray(l1[0, 6], np.float32),
                               np.asarray(l2[0, 6], np.float32))

    def test_param_count_scales(self):
        cfg = get_model_config("tiny-gemma")
        n = param_count(init_params(cfg, jax.random.PRNGKey(0)))
        # embedding 512*64 + 2 layers — sanity bounds, not exact bookkeeping
        assert 100_000 < n < 300_000

    def test_registry_contains_baseline_families(self):
        models = list_models()
        for required in ("gemma-2b-it", "gemma-7b-it", "llama-3-8b-instruct",
                         "llama-3.2-1b-instruct", "llama-3.2-3b-instruct",
                         "mistral-7b-instruct", "mixtral-8x7b-instruct",
                         "qwen2.5-1.5b-instruct"):
            assert required in models

    def test_per_row_max_new_tokens(self):
        """knight_sampling max_new_tokens is a PER-ROW budget: the terse
        row stops at its own cap (same text as a solo run with that
        cap), the hungry row keeps decoding past it."""
        from theroundtaible_tpu.engine.engine import InferenceEngine
        cfg = get_model_config("tiny-llama", max_seq_len=256)

        def build():
            return InferenceEngine(
                cfg, num_slots=4, dtype=jnp.float32,
                sampling=SamplingParams(temperature=0.0,
                                        max_new_tokens=12))

        eng = build()
        terse = SamplingParams(temperature=0.0, max_new_tokens=3)
        hungry = SamplingParams(temperature=0.0, max_new_tokens=12)
        outs = eng.generate_batch(
            [("a", "the quick brown fox"), ("b", "the lazy dog waits")],
            max_new_tokens=12, sampling_per_turn=[terse, hungry])
        solo = build()
        a_solo = solo.generate("the quick brown fox", slot_name="s",
                               max_new_tokens=3)
        b_solo = solo.generate("the lazy dog waits", slot_name="s2",
                               max_new_tokens=12)
        assert outs[0] == a_solo
        assert outs[1] == b_solo
        assert len(outs[1]) > len(outs[0])

    def test_cache_too_small_for_decode_reserve_raises(self):
        """max_seq_len ≤ the padded decode reserve used to silently
        truncate every prompt to [bos]; it must be a clear config
        error instead."""
        from theroundtaible_tpu.engine.engine import InferenceEngine
        eng = InferenceEngine(
            get_model_config("tiny-llama", max_seq_len=64), num_slots=2,
            page_size=32,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=6))
        with pytest.raises(ValueError, match="decode\\s+reserve"):
            eng.generate("any prompt at all", slot_name="x",
                         max_new_tokens=6)

    def test_qwen_family_serves_end_to_end(self):
        """Qwen2 (attention bias) through the full serving engine: cached
        decode must equal a cache-free greedy recompute — the bias path
        has to behave identically under prefill and per-token decode."""
        from theroundtaible_tpu.engine.engine import InferenceEngine
        eng = InferenceEngine(
            get_model_config("tiny-qwen", max_seq_len=256), num_slots=2,
            dtype=jnp.float32,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8))
        out = eng.generate("the quick brown fox", slot_name="q",
                           max_new_tokens=8)
        assert isinstance(out, str) and len(out) > 0
        follow = "the quick brown fox" + out
        out2 = eng.generate(follow, slot_name="q", max_new_tokens=8)
        assert eng.last_stats.reused_tokens > 0
        fresh = InferenceEngine(
            get_model_config("tiny-qwen", max_seq_len=256), num_slots=2,
            dtype=jnp.float32,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8))
        assert out2 == fresh.generate(follow, slot_name="f",
                                      max_new_tokens=8)

    def test_registry_unknown_raises(self):
        with pytest.raises(ValueError, match="Unknown model"):
            get_model_config("gpt-17")


class TestSampling:
    def test_greedy_is_argmax(self):
        logits = jnp.array([[0.1, 3.0, 0.2], [5.0, 0.0, 0.1]])
        out = sample_token(logits, jax.random.PRNGKey(0),
                           SamplingParams(temperature=0.0))
        assert out.tolist() == [1, 0]

    def test_top_k_restricts(self):
        logits = jnp.array([[0.0, 1.0, 2.0, 3.0]] * 64)
        out = sample_token(logits, jax.random.PRNGKey(1),
                           SamplingParams(temperature=1.0, top_k=2))
        assert set(np.asarray(out).tolist()) <= {2, 3}

    def test_top_p_restricts(self):
        logits = jnp.array([[10.0, 0.0, 0.0, 0.0]] * 32)
        out = sample_token(logits, jax.random.PRNGKey(2),
                           SamplingParams(temperature=1.0, top_p=0.5))
        assert set(np.asarray(out).tolist()) == {0}

    def test_batch_per_row_params(self):
        """sample_token_batch: each row follows ITS OWN params — greedy,
        top-k, and top-p rows coexist in one call."""
        from theroundtaible_tpu.engine.sampling import (sample_token_batch,
                                                        sampling_arrays)
        logits = jnp.array([[0.1, 3.0, 0.2, 0.0],   # greedy row → 1
                            [0.0, 1.0, 2.0, 3.0],   # top_k=2 → {2,3}
                            [10.0, 0.0, 0.0, 0.0]])  # top_p=0.5 → {0}
        params = [SamplingParams(temperature=0.0),
                  SamplingParams(temperature=1.0, top_k=2),
                  SamplingParams(temperature=1.0, top_p=0.5)]
        results = [[], [], []]
        for seed in range(32):
            out = sample_token_batch(logits, jax.random.PRNGKey(seed),
                                     *sampling_arrays(params))
            for i, t in enumerate(np.asarray(out).tolist()):
                results[i].append(t)
        assert set(results[0]) == {1}
        assert set(results[1]) <= {2, 3} and len(set(results[1])) == 2
        assert set(results[2]) == {0}

    def test_batch_matches_static_per_row(self):
        """A batch where all rows share one config must equal the static
        sample_token path row for row (same key)."""
        from theroundtaible_tpu.engine.sampling import (sample_token_batch,
                                                        sampling_arrays)
        rng = np.random.default_rng(7)
        logits = jnp.asarray(rng.normal(size=(4, 16)) * 3, jnp.float32)
        for p in (SamplingParams(temperature=0.0),
                  SamplingParams(temperature=0.8, top_k=5),
                  SamplingParams(temperature=1.2, top_p=0.7)):
            key = jax.random.PRNGKey(11)
            a = sample_token(logits, key, p)
            b = sample_token_batch(logits, key, *sampling_arrays([p] * 4))
            assert a.tolist() == b.tolist()

    def test_batch_fast_path_with_pool_smaller_than_vocab(self):
        """The candidate-pool fast path itself (vocab strictly larger
        than _K_CAND, thresholds provable inside the pool) must match
        sample_token draw-for-draw: top_k well under the pool size, and
        a PEAKED top-p row whose cutoff mass sits in the first few
        candidates."""
        from theroundtaible_tpu.engine.sampling import (_K_CAND,
                                                        sample_token_batch,
                                                        sampling_arrays)
        rng = np.random.default_rng(19)
        v = 4 * _K_CAND
        peaked = jnp.asarray(rng.normal(size=(3, v)) * 3.0, jnp.float32)
        for p in (SamplingParams(temperature=0.9, top_k=50),
                  SamplingParams(temperature=0.8, top_p=0.7),
                  SamplingParams(temperature=1.1, top_k=64, top_p=0.9)):
            for seed in (23, 29, 31):
                key = jax.random.PRNGKey(seed)
                a = sample_token(peaked, key, p)
                b = sample_token_batch(peaked, key,
                                       *sampling_arrays([p] * 3))
                assert a.tolist() == b.tolist(), (p, seed)

    def test_batch_fallback_beyond_candidate_pool(self):
        """Rows the lax.top_k candidate pool cannot prove (top_k bigger
        than the pool; near-flat logits whose top-p cutoff needs more
        than the pool's mass) must take the exact full-sort fallback and
        still match sample_token draw-for-draw under the same key."""
        from theroundtaible_tpu.engine.sampling import (_K_CAND,
                                                        sample_token_batch,
                                                        sampling_arrays)
        rng = np.random.default_rng(13)
        v = 4 * _K_CAND
        # near-flat: top-p 0.99 needs far more than _K_CAND candidates
        flat = jnp.asarray(rng.normal(size=(3, v)) * 0.01, jnp.float32)
        for p in (SamplingParams(temperature=1.0, top_k=2 * _K_CAND),
                  SamplingParams(temperature=1.0, top_p=0.99)):
            key = jax.random.PRNGKey(17)
            a = sample_token(flat, key, p)
            b = sample_token_batch(flat, key, *sampling_arrays([p] * 3))
            assert a.tolist() == b.tolist()


# One sampled batch for the conditional pool's tests (ISSUE 41): sixteen
# rows of mixed temperatures, greedy ones among them.
POOL_ROWS = 16
POOL_TEMPS = (0.0, 0.3, 0.7, 1.0, 1.5, 0.7, 0.0, 0.9,
              0.7, 1.2, 0.7, 0.5, 0.7, 2.0, 0.7, 0.7)
FILTERED_ROW = 5
# kind -> (the one filtered row's params, how wide its logits spread)
FILTERED_KINDS = {
    "top_k": (dict(temperature=0.9, top_k=7), 3.0),
    "top_p": (dict(temperature=0.8, top_p=0.9), 3.0),
    "both": (dict(temperature=1.1, top_k=40, top_p=0.8), 3.0),
    # beyond the pool's 128: the two-sort tail, for that row alone
    "exact_top_k": (dict(temperature=1.0, top_k=200), 3.0),
    "exact_top_p": (dict(temperature=1.0, top_p=0.99), 0.01),
}


@pytest.fixture(scope="module")
def sampler_program():
    """sample_token_batch as the step programs run it: compiled."""
    from theroundtaible_tpu.engine.sampling import sample_token_batch
    return jax.jit(sample_token_batch)


def _plain_draw(logits, key, temps):
    """What a batch with no filter must draw, bit for bit."""
    temps = jnp.asarray(temps, jnp.float32)
    drawn = jax.random.categorical(
        key, logits / jnp.maximum(temps[:, None], 1e-6), axis=-1)
    return jnp.where(temps <= 0.0, jnp.argmax(logits, axis=-1), drawn)


def _reachable_primitives(jaxpr, enter_cond: bool) -> set:
    """Names of the primitives of `jaxpr` and of every jaxpr nested in
    its equations — a `cond`'s branches only where `enter_cond`."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        if eqn.primitive.name == "cond" and not enter_cond:
            continue
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names |= _reachable_primitives(inner, enter_cond)
    return names


class TestConditionalPool:
    """The sampler draws its candidate pool only when a sampled row of
    the batch asks for top_k or top_p (ISSUE 41)."""

    @pytest.mark.parametrize("seed", range(5))
    @pytest.mark.parametrize("vocab", [257, 4096])
    def test_an_unfiltered_batch_is_the_divide_and_the_draw(
            self, sampler_program, vocab, seed):
        from theroundtaible_tpu.engine.sampling import sampling_arrays
        rng = np.random.default_rng(1000 * vocab + seed)
        logits = jnp.asarray(rng.normal(size=(POOL_ROWS, vocab)) * 3,
                             jnp.float32)
        arrays = sampling_arrays(
            [SamplingParams(temperature=t) for t in POOL_TEMPS])
        key = jax.random.PRNGKey(seed)
        got = sampler_program(logits, key, *arrays)
        assert got.tolist() == _plain_draw(logits, key,
                                           POOL_TEMPS).tolist()

    @pytest.mark.parametrize("vocab", [257, 4096])
    @pytest.mark.parametrize("kind", sorted(FILTERED_KINDS))
    def test_one_filtered_row_among_plain_ones(self, sampler_program,
                                               kind, vocab):
        """The filtered row draws what sample_token draws for it, and
        its batchmates what they drew without it."""
        from theroundtaible_tpu.engine.sampling import sampling_arrays
        filtered, spread = FILTERED_KINDS[kind]
        filtered = SamplingParams(**filtered)
        rng = np.random.default_rng(vocab + len(kind))
        rows = rng.normal(size=(POOL_ROWS, vocab)) * 3
        rows[FILTERED_ROW] *= spread / 3
        logits = jnp.asarray(rows, jnp.float32)
        params = [SamplingParams(temperature=t) for t in POOL_TEMPS]
        params[FILTERED_ROW] = filtered
        arrays = sampling_arrays(params)
        for seed in range(6):
            key = jax.random.PRNGKey(seed)
            got = sampler_program(logits, key, *arrays).tolist()
            want = _plain_draw(logits, key, POOL_TEMPS).tolist()
            want[FILTERED_ROW] = sample_token(
                logits, key, filtered).tolist()[FILTERED_ROW]
            assert got == want, seed

    def test_the_pool_stands_inside_the_conditional(self):
        """No vocabulary-wide top_k, sort or cumsum is reachable from
        the program's top level but through the cond: an edit that
        pulls the pool back out fails here, on the CPU."""
        from theroundtaible_tpu.engine.sampling import (sample_token_batch,
                                                        sampling_arrays)
        arrays = sampling_arrays(
            [SamplingParams(temperature=t) for t in POOL_TEMPS])
        jaxpr = jax.make_jaxpr(sample_token_batch)(
            jnp.zeros((POOL_ROWS, 4096), jnp.float32),
            jax.random.PRNGKey(0), *arrays).jaxpr
        pool = {"top_k", "sort", "cumsum"}
        outside = _reachable_primitives(jaxpr, enter_cond=False)
        assert "cond" in outside and not outside & pool
        assert pool <= _reachable_primitives(jaxpr, enter_cond=True)

    @pytest.mark.parametrize("mode,rows", [
        ("greedy", [dict(temperature=0.0), dict(temperature=0.0, top_k=5)]),
        ("plain", [dict(temperature=0.7), dict(temperature=0.0, top_p=0.5)]),
        ("sort-free", [dict(temperature=0.7), dict(temperature=0.9,
                                                   top_p=0.9)]),
        ("sort", [dict(temperature=0.7, top_k=5),
                  dict(temperature=1.0, top_k=129)]),
    ])
    def test_sampler_mode_names_the_branch(self, mode, rows):
        from theroundtaible_tpu.engine.sampling import sampler_mode
        assert sampler_mode([SamplingParams(**r) for r in rows]) == mode


class TestEngineGenerate:
    def test_generate_deterministic_greedy(self, tiny_engine):
        tiny_engine.kv.reset_slot("g1")
        tiny_engine.kv.reset_slot("g2")
        out1 = tiny_engine.generate("hello world", slot_name="g1",
                                    max_new_tokens=12)
        out2 = tiny_engine.generate("hello world", slot_name="g2",
                                    max_new_tokens=12)
        assert out1 == out2
        assert isinstance(out1, str)

    def test_prefix_reuse_matches_fresh(self, tiny_engine):
        """Turn 2 extending turn 1's prompt must equal a fresh computation."""
        base = "round one says X."
        extended = base + " round two adds Y and asks again."
        out_reused = None
        tiny_engine.generate(base, slot_name="reuse", max_new_tokens=8)
        stats0 = tiny_engine.last_stats
        out_reused = tiny_engine.generate(extended, slot_name="reuse",
                                          max_new_tokens=8)
        stats1 = tiny_engine.last_stats
        out_fresh = tiny_engine.generate(extended, slot_name="fresh",
                                         max_new_tokens=8)
        assert out_reused == out_fresh
        assert stats1.reused_tokens > 0

    def test_batched_matches_serial(self, tiny_engine):
        prompts = [("bA", "alpha beta"), ("bB", "gamma delta epsilon")]
        batched = tiny_engine.generate_batch(prompts, max_new_tokens=8)
        for name, _ in prompts:
            tiny_engine.kv.reset_slot(name)
        serial = [tiny_engine.generate(p, slot_name=n + "s",
                                       max_new_tokens=8)
                  for n, p in prompts]
        assert batched == serial

    def test_long_prompt_head_truncated(self):
        engine = InferenceEngine(
            get_model_config("tiny-gemma", max_seq_len=128), num_slots=2,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8))
        out = engine.generate("z" * 1000, slot_name="long",
                              max_new_tokens=8)
        assert isinstance(out, str)
        committed = engine.kv.acquire("long").tokens
        assert len(committed) <= 128

    def test_stats_populated(self, tiny_engine):
        tiny_engine.generate("stats probe", slot_name="stats",
                             max_new_tokens=8)
        s = tiny_engine.last_stats
        assert s.prefill_tokens > 0
        assert s.decode_tokens > 0
        assert s.prefill_tps > 0 and s.decode_tps > 0

    def test_per_turn_sampling_in_one_batch(self, tiny_engine):
        """A greedy row and a hot row in the same batch: the greedy row's
        output must equal an all-greedy run (per-row sampling params,
        VERDICT r1 weak #8)."""
        greedy = SamplingParams(temperature=0.0, max_new_tokens=8)
        hot = SamplingParams(temperature=1.5, max_new_tokens=8)
        prompts = [("pgA", "the deterministic knight speaks"),
                   ("pgB", "the spicy knight speaks")]
        for n, _ in prompts:
            tiny_engine.kv.release(n)
        mixed = tiny_engine.generate_batch(
            prompts, max_new_tokens=8, sampling_per_turn=[greedy, hot])
        for n, _ in prompts:
            tiny_engine.kv.release(n)
        all_greedy = tiny_engine.generate_batch(
            prompts, max_new_tokens=8, sampling_per_turn=[greedy, greedy])
        assert mixed[0] == all_greedy[0]

    def test_bucket_ladder(self):
        assert _bucket(1) == 64
        assert _bucket(65) == 128
        assert _bucket(2048) == 2048
        assert _bucket(9999) == 2048


# greedy / sampled x B = 1 / 3: the cases of every first-token test.
FIRST_TOKEN_CASES = [pytest.param(temp, b, id=f"{mode}-b{b}")
                     for mode, temp in (("greedy", 0.0), ("sampled", 0.7))
                     for b in (1, 3)]


def _first_token_compiles(b):
    """Lifetime compiles under the first-token program's label (the
    registry's counter: compile_watch.history() is a capped ring)."""
    from theroundtaible_tpu.utils import telemetry
    return telemetry.REGISTRY.counter_total(
        "roundtable_compiles_total", label=f"prefill[b={b},first_token]")


def _prologue_engine(temp, seed=0):
    return InferenceEngine(
        get_model_config("tiny-gemma", max_seq_len=256), num_slots=4,
        kv_layout="paged", seed=seed,
        sampling=SamplingParams(temperature=temp, max_new_tokens=8))


def _first_token(engine, logits, keys, arrays, greedy):
    """The first-token program as the prologue calls it: the engine's
    pair of keys (`split(chain key)`: row 1 is the program's own) and
    the rows' sampling parameters as one packed buffer (ISSUE 53).
    -> (tokens, the next pair)."""
    from theroundtaible_tpu.engine import dispatch_pack
    layout = dispatch_pack.sampler_layout(len(arrays[0]))
    return engine._first_token(
        logits, keys,
        layout.pack(dict(zip(("temps", "top_ks", "top_ps"), arrays))),
        layout=layout, greedy=greedy)


class TestFirstTokenProgram:
    """The prologue samples its first token inside jit (ISSUE 26): one
    compiled program per ([B, V], greedy) and one blocking read, where
    the eager sampler was fifty dispatches and a recompile of its
    lax.cond on every call."""

    @pytest.fixture(scope="class")
    def engines(self):
        return {temp: _prologue_engine(temp) for temp in (0.0, 0.7)}

    def _prologue(self, engine, b, rep):
        import time

        from theroundtaible_tpu.engine import deadlines
        turns = [(f"ft{rep}_{i}", f"knight {i} speaks in round {rep}")
                 for i in range(b)]
        budget = deadlines.Budget.root(120.0, rung="turn")
        try:
            return engine._prepare_batch(
                turns, 64, time.monotonic() + 120.0,
                budget.child("prefill"))
        finally:
            for name, _ in turns:
                engine.kv.release(name)

    @pytest.mark.parametrize("temp,b", FIRST_TOKEN_CASES)
    def test_second_prologue_of_a_shape_compiles_nothing(self, engines,
                                                         temp, b):
        from theroundtaible_tpu.engine import compile_watch
        engine = engines[temp]
        before = _first_token_compiles(b)
        # Twice: the prefill step meets its donated pool's layout.
        for rep in range(2):
            self._prologue(engine, b, rep)
        assert _first_token_compiles(b) - before == 1
        seen = compile_watch.compiles_seen()
        prep = self._prologue(engine, b, 2)
        assert compile_watch.compiles_seen() == seen
        assert prep["first_np"].shape == (b,)
        assert prep["first_np"].dtype == np.int32

    def test_warmup_compiles_the_program_once_a_batch_size(
            self, monkeypatch):
        """warmup() walks the prologue for every batch size it warms;
        after it a prologue of a warmed shape compiles nothing, which
        the STRICT sentinel would turn into an error."""
        from theroundtaible_tpu.engine import compile_watch
        engine = _prologue_engine(0.7)
        before = {b: _first_token_compiles(b) for b in (1, 2)}
        engine.warmup(max_prompt_tokens=64, batch_sizes=(1, 2))
        for b in (1, 2):
            assert _first_token_compiles(b) - before[b] == 1
        monkeypatch.setenv(compile_watch.STRICT_ENV, "1")
        try:
            seen = compile_watch.compiles_seen()
            engine.generate_batch([("wa", "a warmed shape"),
                                   ("wb", "another knight")],
                                  max_new_tokens=2)
            assert compile_watch.compiles_seen() == seen
        finally:
            compile_watch.reopen_warmup(engine.cfg.name)

    @pytest.mark.parametrize("temp,b", FIRST_TOKEN_CASES)
    def test_program_matches_the_eager_sampler(self, engines, temp, b):
        from theroundtaible_tpu.engine.sampling import (sample_token_batch,
                                                        sampling_arrays)
        rng = np.random.default_rng(100 * b + int(10 * temp))
        logits = jnp.asarray(rng.normal(size=(b, 640)) * 3, jnp.bfloat16)
        arrays = sampling_arrays([SamplingParams(temperature=temp)] * b)
        for seed in (3, 5):
            key = jax.random.PRNGKey(seed)
            # The engine holds `split(key)`: the chain's next key and
            # the key the host's `_next_key` would have handed out. A
            # sampled batch draws from that one and hands back the next
            # pair; a greedy one draws none and moves nothing.
            keys = jax.random.split(key)
            got, nxt = _first_token(engines[temp], logits, keys, arrays,
                                    greedy=temp <= 0.0)
            f32 = logits.astype(jnp.float32)
            want = (jnp.argmax(f32, axis=-1) if temp <= 0.0
                    else sample_token_batch(f32, keys[1], *arrays))
            assert got.dtype == jnp.int32
            assert got.tolist() == want.tolist()
            assert nxt.tolist() == (
                keys if temp <= 0.0 else jax.random.split(keys[0])).tolist()

    def test_program_matches_the_eager_sampler_on_a_mixed_batch(
            self, engines):
        """One greedy row, one top_k row and one top_p < 1 row."""
        from theroundtaible_tpu.engine.sampling import (sample_token_batch,
                                                        sampling_arrays)
        rng = np.random.default_rng(41)
        logits = jnp.asarray(rng.normal(size=(3, 640)) * 3, jnp.float32)
        arrays = sampling_arrays([
            SamplingParams(temperature=0.0),
            SamplingParams(temperature=0.9, top_k=5),
            SamplingParams(temperature=1.1, top_p=0.7)])
        for seed in range(8):
            key = jax.random.PRNGKey(seed)
            got, _keys = _first_token(engines[0.7], logits,
                                      jax.random.split(key), arrays,
                                      greedy=False)
            want = sample_token_batch(logits, jax.random.split(key)[1],
                                      *arrays)
            assert got.tolist() == want.tolist(), seed
        assert got.tolist()[0] == int(jnp.argmax(logits[0]))

    @pytest.mark.parametrize("temp,b", FIRST_TOKEN_CASES)
    def test_scheduler_admission_and_generate_batch_agree(self, temp, b):
        """An admission into an empty batch and generate_batch run the
        one prologue: same prompts, same seed, same first tokens — each
        from one call of the program. Sampled rows run hot, so that a
        key out of step would show."""
        from theroundtaible_tpu.engine.scheduler import SessionScheduler

        def recording(engine):
            program, calls = engine._first_token, []

            def spy(*args, **kw):
                calls.append(program(*args, **kw))
                return calls[-1]
            engine._first_token = spy
            return engine, calls

        temp = temp and 8.0
        turns = [(f"k{i}", f"knight {i} opens the round with a claim")
                 for i in range(b)]
        engine, direct_calls = recording(_prologue_engine(temp, seed=7))
        direct, _ = engine.generate_batch_with_stats(
            turns, max_new_tokens=1, session="s")
        engine, sched_calls = recording(_prologue_engine(temp, seed=7))
        sched = SessionScheduler(engine)
        try:
            scheduled, _ = sched.submit("s", turns, max_new_tokens=1)
        finally:
            sched.close()
        assert len(direct_calls) == len(sched_calls) == 1
        assert sched_calls[0][0].tolist() == direct_calls[0][0].tolist()
        assert sched_calls[0][1].tolist() == direct_calls[0][1].tolist()
        assert scheduled == direct


class TestSharedPrefix:
    """Cross-knight shared-prefix reuse (SURVEY §7.3 hard part 2,
    VERDICT r1 #3): K/V spans shared between slots instead of
    re-prefilling the common context+transcript preamble."""

    SHARED = ("The roundtable context: the codebase uses a session store "
              "under .roundtable with chronicle, manifest and decree logs. "
              "Transcript so far: knight A proposed caching; knight B "
              "objected on memory grounds; scores were 7 and 5. ")

    def _fresh_engine(self, **kw):
        return InferenceEngine(
            get_model_config("tiny-gemma"), num_slots=4,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8),
            **kw)

    def _control(self, prompts):
        """Full-prefill outputs: every slot released between calls and no
        prefix index to keep its pages, so neither own-slot LCP, donor
        shares nor an index hit can kick in."""
        eng = self._fresh_engine(prefix_cache=False)
        outs = []
        for name, p in prompts:
            for n in list(eng.kv.slot_names()):
                eng.kv.release(n)
            outs.append(eng.generate(p, slot_name=name, max_new_tokens=8))
            assert eng.last_stats.reused_tokens == 0
        return outs

    def test_donor_reuse_across_slot_names(self):
        """Knight B's FRESH slot takes knight A's committed K/V for the
        shared preamble — reuse across different slot names."""
        eng = self._fresh_engine()
        prompts = [("knight-a", self.SHARED + "You are A. Respond."),
                   ("knight-b", self.SHARED + "You are B, the skeptic.")]
        out_a = eng.generate(prompts[0][1], slot_name="knight-a",
                             max_new_tokens=8)
        assert eng.last_stats.reused_tokens == 0  # nothing to share yet
        out_b = eng.generate(prompts[1][1], slot_name="knight-b",
                             max_new_tokens=8)
        assert eng.last_stats.reused_tokens >= len(self.SHARED) - 8
        control = self._control(prompts)
        assert [out_a, out_b] == control

    def test_batch_leader_shares_prefix(self):
        """3-knight fresh batch: the shared span prefills once, the other
        rows alias it — prefill_tokens ≈ shared + Σ small deltas."""
        eng = self._fresh_engine()
        tails = ["You are A. Speak.", "You are B. Speak.",
                 "You are C. Speak."]
        prompts = [(f"knight-{i}", self.SHARED + t)
                   for i, t in enumerate(tails)]
        outs, stats = eng.generate_batch_with_stats(prompts,
                                                    max_new_tokens=8)
        total = sum(len(eng.tokenizer.encode(p)) for _, p in prompts)
        shared_len = len(eng.tokenizer.encode(self.SHARED + "You are "))
        # prefill ≈ shared once + three tails; reused ≈ 2 × shared
        assert stats.prefill_tokens <= total - shared_len
        assert stats.reused_tokens >= 2 * (shared_len - 16)
        assert self._control(prompts) == outs

    def test_second_round_delta_still_reuses_own_slot(self):
        """Sharing must not break own-slot LCP across rounds."""
        eng = self._fresh_engine()
        p1 = [("a", self.SHARED + "A speaks."),
              ("b", self.SHARED + "B speaks.")]
        eng.generate_batch(p1, max_new_tokens=8)
        grown = self.SHARED + "Round 1 happened; new arguments appeared. "
        p2 = [("a", grown + "A speaks."), ("b", grown + "B speaks.")]
        outs, stats = eng.generate_batch_with_stats(p2, max_new_tokens=8)
        # both rows kept their own shared-preamble coverage
        assert stats.reused_tokens >= 2 * (len(self.SHARED) - 8)
        assert self._control(p2) == outs

    def test_short_prefix_not_shared(self):
        """Below MIN_SHARED_PREFIX nothing is shared."""
        eng = self._fresh_engine()
        outs, stats = eng.generate_batch_with_stats(
            [("x", "tiny common A"), ("y", "tiny common B")],
            max_new_tokens=8)
        assert stats.reused_tokens == 0


class TestSharding:
    def test_mesh_default_all_model(self):
        mesh = build_mesh()
        assert mesh.shape["model"] == len(jax.devices())
        assert mesh.shape["data"] == 1

    def test_mesh_explicit(self):
        mesh = build_mesh({"data": 2, "model": 4})
        assert mesh.shape["data"] == 2 and mesh.shape["model"] == 4

    def test_mesh_bad_shape(self):
        with pytest.raises(ValueError, match="needs"):
            build_mesh({"data": 3, "model": 3})

    def test_mesh_subset_allowed(self):
        mesh = build_mesh({"data": 1, "model": 4})
        assert mesh.devices.size == 4

    def test_dcn_axis_single_granule_same_as_plain(self):
        """One process / one slice: the dcn_axis config is accepted and
        produces the identical mesh — the single-process dryrun story."""
        plain = build_mesh({"data": 2, "model": 4})
        hybrid = build_mesh({"data": 2, "model": 4}, dcn_axis="data")
        assert (hybrid.devices == plain.devices).all()
        assert hybrid.shape == plain.shape

    def test_dcn_axis_invalid_name(self):
        with pytest.raises(ValueError, match="dcn_axis"):
            build_mesh({"data": 2, "model": 4}, dcn_axis="pipe")

    def test_dcn_axis_multi_process_layout(self):
        """Two process granules, dcn_axis='data': every data row must sit
        wholly inside one granule's devices, so the per-layer TP
        all-reduces ('model' axis) never cross DCN — the placement the
        module docstring prescribes. Fake device objects stand in for a
        2-host group (the real 2-process path is covered by
        tests/test_distributed.py)."""
        from types import SimpleNamespace
        from theroundtaible_tpu.engine.sharding import _hybrid_device_array
        devs = [SimpleNamespace(platform="cpu", device_kind="cpu",
                                process_index=p, id=p * 4 + i)
                for p in range(2) for i in range(4)]
        arr = _hybrid_device_array(devs, 2, 4, "data")
        assert arr.shape == (2, 4)
        for row in arr:  # each data replica = one granule
            assert len({d.process_index for d in row}) == 1
        assert ({d.process_index for d in arr[:, 0]} == {0, 1})
        # dcn_axis='model' would put TP across DCN — legal, layout holds
        arr2 = _hybrid_device_array(devs, 1, 8, "model")
        assert arr2.shape == (1, 8)
        # granule-contiguous: first 4 one process, last 4 the other
        assert len({d.process_index for d in arr2[0][:4]}) == 1
        assert len({d.process_index for d in arr2[0][4:]}) == 1

    def test_dcn_axis_indivisible_raises(self):
        from types import SimpleNamespace
        from theroundtaible_tpu.engine.sharding import _hybrid_device_array
        devs = [SimpleNamespace(platform="cpu", device_kind="cpu",
                                process_index=p, id=p * 3 + i)
                for p in range(3) for i in range(2)]
        with pytest.raises(ValueError, match="granules"):
            _hybrid_device_array(devs, 2, 3, "data")

    def test_dcn_axis_reachable_from_adapter_config(self):
        """dcn_axis flows from the tpu-llm config dict to build_mesh
        (single-granule here, so the engine serves normally)."""
        from theroundtaible_tpu.engine.engine import InferenceEngine
        eng = InferenceEngine.from_config({
            "model": "tiny-gemma", "max_seq_len": 128,
            "mesh": {"data": 2, "model": 4}, "dcn_axis": "data",
            "num_slots": 2,
            "sampling": {"temperature": 0.0, "max_new_tokens": 4}})
        assert eng.mesh.shape == {"data": 2, "model": 4}
        out = eng.generate("hello dcn", slot_name="d", max_new_tokens=4)
        assert isinstance(out, str)

    def test_param_specs_match_tree(self):
        cfg = get_model_config("tiny-gemma")
        params = init_params(cfg, jax.random.PRNGKey(0))
        specs = param_specs(cfg)
        jax.tree_util.tree_map(lambda a, s: None, params, specs)  # no raise

    def test_sharded_params_on_mesh(self):
        cfg = get_model_config("tiny-llama")  # 4 heads, 2 kv heads
        mesh = build_mesh({"data": 1, "model": 4})
        params = init_params(cfg, jax.random.PRNGKey(0))
        sharded = shard_params(params, cfg, mesh)
        q = sharded["layers"][0]["q_proj"]
        assert q.sharding.is_fully_replicated is False
        # kv heads (2) don't divide model axis (4) → replicated fallback
        k = sharded["layers"][0]["k_proj"]
        assert k.sharding.is_fully_replicated

    def test_engine_on_virtual_tp_mesh(self):
        """End-to-end generate with TP over the 8 virtual CPU devices."""
        engine = InferenceEngine(
            get_model_config("tiny-llama"), num_slots=2,
            mesh_shape={"data": 1, "model": 4},
            sampling=SamplingParams(temperature=0.0, max_new_tokens=6))
        out = engine.generate("sharded hello", slot_name="tp",
                              max_new_tokens=6)
        assert isinstance(out, str)
        single = InferenceEngine(
            get_model_config("tiny-llama"), num_slots=2,
            mesh_shape={"data": 1, "model": 1},
            sampling=SamplingParams(temperature=0.0, max_new_tokens=6))
        out_single = single.generate("sharded hello", slot_name="tp",
                                     max_new_tokens=6)
        assert out == out_single  # TP must not change results (greedy)


    def test_born_sharded_init_matches_eager_init(self):
        """Random init under jit with out_shardings (ISSUE 22): every
        leaf lands with its NamedSharding, re-placing the tree is a
        no-op, and greedy tokens equal the old eager init + shard_params
        build for the same seed."""
        from jax.sharding import NamedSharding

        from theroundtaible_tpu.engine.sharding import param_shardings

        def build():
            return InferenceEngine(
                get_model_config("tiny-llama"), num_slots=2, seed=3,
                mesh_shape={"model": 2},
                sampling=SamplingParams(temperature=0.0,
                                        max_new_tokens=8))

        engine = build()
        want = param_shardings(engine.cfg, engine.mesh, engine.params)
        for leaf, sharding in zip(jax.tree_util.tree_leaves(engine.params),
                                  jax.tree_util.tree_leaves(want)):
            assert isinstance(leaf.sharding, NamedSharding)
            assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim)
        q = engine.params["layers"][0]["q_proj"]
        assert not q.sharding.is_fully_replicated
        assert {s.data.shape for s in q.addressable_shards} == {
            (q.shape[0], q.shape[1] // 2, q.shape[2])}
        again = shard_params(engine.params, engine.cfg, engine.mesh)
        for a, b in zip(jax.tree_util.tree_leaves(again),
                        jax.tree_util.tree_leaves(engine.params)):
            assert a is b
        out = engine.generate("born sharded", slot_name="s",
                              max_new_tokens=8)

        eager = build()
        eager.params = shard_params(
            init_params(eager.cfg, jax.random.PRNGKey(3), eager.dtype),
            eager.cfg, eager.mesh)
        assert eager.generate("born sharded", slot_name="s",
                              max_new_tokens=8) == out


class TestTokenizer:
    def test_byte_roundtrip(self):
        tok = ByteTokenizer()
        ids = tok.encode("héllo ⚔️")
        assert ids[0] == tok.bos_id
        assert tok.decode(ids) == "héllo ⚔️"

    def test_engine_from_config(self):
        from theroundtaible_tpu.engine import get_engine, reset_engines
        reset_engines()
        e1 = get_engine({"model": "tiny-gemma", "max_seq_len": 256})
        e2 = get_engine({"model": "tiny-gemma", "max_seq_len": 256})
        assert e1 is e2  # cached
        e3 = get_engine({"model": "tiny-llama"})
        assert e3 is not e1
        reset_engines()


class TestReviewRegressions:
    """Regressions for the engine review findings."""

    def test_prefill_never_overruns_cache(self):
        """A suffix whose bucket padding would cross max_seq_len must not
        corrupt the position-aligned cache (offsets would be clamped)."""
        engine = InferenceEngine(
            get_model_config("tiny-gemma", max_seq_len=160), num_slots=2,
            page_size=32,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8))
        # turn 1 fills most of the cache; turn 2 adds a short suffix whose
        # 64-bucket pad would overrun 160 without the shrink logic.
        # Prompt budget = max_seq_len - roundup(max_new, DECODE_SEGMENT) - 1
        # = 160 - 64 - 1 = 95 tokens; +3 fed decode tokens = 98 cached.
        engine.generate("a" * 120, slot_name="edge", max_new_tokens=4)
        cached = len(engine.kv.acquire("edge").tokens)
        assert cached == 98
        out_reused = engine.generate("a" * 120 + "bcd", slot_name="edge",
                                     max_new_tokens=4)
        out_fresh = engine.generate("a" * 120 + "bcd", slot_name="fresh",
                                    max_new_tokens=4)
        assert out_reused == out_fresh  # corrupted cache would diverge

    def test_batch_larger_than_slots_raises(self):
        engine = InferenceEngine(
            get_model_config("tiny-gemma"), num_slots=2,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=4))
        with pytest.raises(RuntimeError, match="num_slots"):
            engine.generate_batch(
                [("k1", "a"), ("k2", "b"), ("k3", "c")], max_new_tokens=4)

    def test_batch_does_not_evict_own_members(self):
        engine = InferenceEngine(
            get_model_config("tiny-gemma"), num_slots=2,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=4))
        engine.generate("warm", slot_name="old", max_new_tokens=4)
        # 2-slot cache with "old" resident: batch of 2 must evict "old",
        # not a batch member
        engine.generate_batch([("n1", "x"), ("n2", "y")], max_new_tokens=4)
        names = set(engine.kv.slot_names())
        assert names == {"n1", "n2"}
        s1, s2 = engine.kv.acquire("n1"), engine.kv.acquire("n2")
        assert s1.pages and s2.pages
        assert not set(s1.pages) & set(s2.pages)

    def test_oversized_max_new_clamped_not_garbage(self):
        engine = InferenceEngine(
            get_model_config("tiny-gemma", max_seq_len=128), num_slots=2,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=9999))
        engine.generate("real prompt text", slot_name="c")
        # the prompt must NOT have collapsed to [bos]
        committed = engine.kv.acquire("c").tokens
        assert len(committed) > 10

    def test_timeout_raises(self):
        engine = InferenceEngine(
            get_model_config("tiny-gemma"), num_slots=2,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=200))
        with pytest.raises(TimeoutError):
            engine.generate("slow", slot_name="t", timeout_s=0.0)

    def test_tokenizer_loud_failure_on_corrupt_files(self, tmp_path):
        from theroundtaible_tpu.engine.tokenizer import load_tokenizer
        (tmp_path / "tokenizer.json").write_text("{corrupt")
        with pytest.raises(RuntimeError, match="failed to load"):
            load_tokenizer(str(tmp_path))

    def test_tokenizer_byte_fallback_without_files(self, tmp_path):
        from theroundtaible_tpu.engine.tokenizer import (
            ByteTokenizer,
            load_tokenizer,
        )
        assert isinstance(load_tokenizer(str(tmp_path)), ByteTokenizer)
