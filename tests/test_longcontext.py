"""Sequence-parallel long-context tests on the virtual 8-device CPU mesh.

Parity discipline (SURVEY.md §4): every sharded core is checked against the
dense single-device math it replaces — ring attention and Ulysses vs a
plain masked softmax, the full ring prefill program vs models.common.forward
logits and caches, and the engine-level ring path vs the chunked path.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.longcontext import (
    SEQ_AXIS,
    _shard_map,
    blockwise_sdpa,
    build_seq_mesh,
    make_ring_prefill,
    pad_to_ring,
    ring_attention,
    ulysses_attention,
)
from theroundtaible_tpu.engine.models.common import forward, init_params
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.sampling import SamplingParams

N_DEV = 8


def _dense_reference(q, k, v, q_pos, kv_valid, cfg):
    """Plain masked-softmax attention in f64-ish f32 — the ground truth."""
    repeat = q.shape[2] // k.shape[2]
    k_att = jnp.repeat(k, repeat, axis=2) if repeat > 1 else k
    v_att = jnp.repeat(v, repeat, axis=2) if repeat > 1 else v
    logits = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                        k_att.astype(jnp.float32))
    if cfg.attn_logit_softcap:
        logits = cfg.attn_logit_softcap * jnp.tanh(
            logits / cfg.attn_logit_softcap)
    kv_pos = q_pos
    mask = kv_pos[:, None, :] <= q_pos[:, :, None]
    mask &= kv_pos[:, None, :] < kv_valid[:, None, None]
    if cfg.sliding_window is not None:
        mask &= kv_pos[:, None, :] > q_pos[:, :, None] - cfg.sliding_window
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    # pad query rows (all keys masked) are defined as 0 in the sharded cores
    row_has_key = mask.any(-1)[:, None, :, None]      # [B,1,T,1]
    probs = probs * row_has_key
    out = jnp.einsum("bhts,bshd->bthd", probs, v_att.astype(jnp.float32))
    return out


def _make_qkv(cfg, b=2, t=64, seed=0):
    key = jax.random.PRNGKey(seed)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, cfg.num_heads, cfg.head_dim),
                          jnp.float32)
    k = jax.random.normal(kk, (b, t, cfg.num_kv_heads, cfg.head_dim),
                          jnp.float32)
    v = jax.random.normal(kv_, (b, t, cfg.num_kv_heads, cfg.head_dim),
                          jnp.float32)
    q_pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    valid = jnp.asarray([t, t - 11], jnp.int32)  # one ragged row
    return q, k, v, q_pos, valid


class TestRingAttention:
    @pytest.mark.parametrize("name", ["tiny-gemma", "tiny-llama",
                                      "tiny-mistral"])
    def test_parity_vs_dense(self, name):
        cfg = get_model_config(name)
        q, k, v, q_pos, valid = _make_qkv(cfg)
        mesh = build_seq_mesh(N_DEV)

        def f(q, k, v, q_pos, valid):
            return ring_attention(q, k, v, q_pos, q_pos, valid, cfg,
                                  SEQ_AXIS, N_DEV)

        spec = P(None, SEQ_AXIS)
        got = _shard_map(f, mesh,
                         in_specs=(spec, spec, spec, spec, P(None)),
                         out_specs=spec)(q, k, v, q_pos, valid)
        want = _dense_reference(q, k, v, q_pos, valid, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_softcap_parity(self):
        cfg = get_model_config("tiny-gemma", attn_logit_softcap=50.0)
        q, k, v, q_pos, valid = _make_qkv(cfg, seed=3)
        mesh = build_seq_mesh(N_DEV)
        spec = P(None, SEQ_AXIS)
        got = _shard_map(
            lambda *a: ring_attention(*a[:3], a[3], a[3], a[4], cfg,
                                      SEQ_AXIS, N_DEV),
            mesh, in_specs=(spec, spec, spec, spec, P(None)),
            out_specs=spec)(q, k, v, q_pos, valid)
        want = _dense_reference(q, k, v, q_pos, valid, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestUlysses:
    @pytest.mark.parametrize("name", ["tiny-gemma", "tiny-llama",
                                      "tiny-mistral"])
    def test_parity_vs_dense(self, name):
        cfg = get_model_config(name)
        if cfg.num_heads % 4 != 0:
            pytest.skip("heads must divide seq size")
        n = 4  # tiny models have 4 heads
        mesh = build_seq_mesh(n)
        q, k, v, q_pos, valid = _make_qkv(cfg, seed=1)
        spec = P(None, SEQ_AXIS)

        def f(q, k, v, q_pos, valid):
            return ulysses_attention(q, k, v, q_pos, valid, cfg,
                                     SEQ_AXIS, n, block=16)

        got = _shard_map(f, mesh,
                         in_specs=(spec, spec, spec, spec, P(None)),
                         out_specs=spec)(q, k, v, q_pos, valid)
        want = _dense_reference(q, k, v, q_pos, valid, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestBlockwise:
    def test_blockwise_equals_dense(self):
        cfg = get_model_config("tiny-llama")
        q, k, v, q_pos, valid = _make_qkv(cfg, seed=2)
        got = blockwise_sdpa(q, k, v, q_pos, q_pos, valid, cfg, block=10)
        want = _dense_reference(q, k, v, q_pos, valid, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestRingPrefill:
    @pytest.mark.parametrize("scheme", ["ring", "ulysses"])
    def test_logits_and_caches_match_dense_forward(self, scheme):
        cfg = get_model_config("tiny-gemma")
        params = init_params(cfg, jax.random.PRNGKey(0))
        mesh = build_seq_mesh(4)
        prefill = make_ring_prefill(cfg, mesh, scheme=scheme)

        b, tpad = 2, 64
        lengths = jnp.asarray([64, 40], jnp.int32)
        tokens = (jnp.arange(b * tpad).reshape(b, tpad) * 7 + 3) \
            % cfg.vocab_size
        positions = jnp.broadcast_to(jnp.arange(tpad), (b, tpad))
        logits, caches = prefill(params, tokens, positions, lengths)

        dense_logits, dense_caches = forward(
            params, cfg, tokens, positions, None, None, lengths)
        want_last = jnp.take_along_axis(
            dense_logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
        np.testing.assert_allclose(np.asarray(logits, np.float32),
                                   np.asarray(want_last, np.float32),
                                   rtol=5e-2, atol=5e-2)
        # K/V parity inside valid lengths (bf16 → loose)
        for (k_got, v_got), (k_want, v_want) in zip(caches, dense_caches):
            for i in range(b):
                n = int(lengths[i])
                np.testing.assert_allclose(
                    np.asarray(k_got[i, :n], np.float32),
                    np.asarray(k_want[i, :n], np.float32),
                    rtol=5e-2, atol=5e-2)
                np.testing.assert_allclose(
                    np.asarray(v_got[i, :n], np.float32),
                    np.asarray(v_want[i, :n], np.float32),
                    rtol=5e-2, atol=5e-2)


class TestPadToRing:
    def test_buckets(self):
        assert pad_to_ring(100, 8, 512) == 128
        assert pad_to_ring(8, 8, 512) == 8
        assert pad_to_ring(513, 8, 1024) == 1024
        assert pad_to_ring(600, 8, 512) == 0       # doesn't fit cache
        assert pad_to_ring(500, 8, 510) == 504     # capped at 8-multiple

    def test_too_long_rejected(self):
        assert pad_to_ring(511, 8, 510) == 0


class TestEngineRingPath:
    def test_paged_ring_prefill_matches_chunked(self):
        """paged + seq_parallel (VERDICT r2 weak #5, last hole): the ring
        program's whole-sequence K/V scatters through the page tables;
        decode + the follow-up delta turn must match the cache-free
        decode (tests/reference_decode.py) token for token."""
        from reference_decode import assert_greedy
        cfg = get_model_config("tiny-gemma")
        sampling = SamplingParams(temperature=0.0, max_new_tokens=8)
        paged_ring = InferenceEngine(
            cfg, num_slots=2, sampling=sampling, seq_parallel=4,
            long_threshold=32, page_size=32, dtype=jnp.float32)
        prompt = "the quick brown fox jumps over the lazy dog " * 8
        assert_greedy(paged_ring, [("k", prompt)], 8)
        a = paged_ring.generate(prompt, slot_name="k2")
        follow = prompt + a + " and then what happened next was "
        assert_greedy(paged_ring, [("k", follow)], 8)
        assert paged_ring.last_stats.reused_tokens > 0

    def test_ring_prefill_then_decode_matches_chunked_engine(self):
        cfg = get_model_config("tiny-gemma")
        sampling = SamplingParams(temperature=0.0, max_new_tokens=8)
        ring_engine = InferenceEngine(cfg, num_slots=2, sampling=sampling,
                                      seq_parallel=4, long_threshold=32,
                                      page_size=32)
        chunked = InferenceEngine(cfg, num_slots=2, sampling=sampling,
                                  page_size=32)
        prompt = "the quick brown fox jumps over the lazy dog " * 12
        a = ring_engine.generate(prompt, slot_name="k")
        b = chunked.generate(prompt, slot_name="k")
        assert a == b
        # prefix reuse on the follow-up turn goes through the chunked path
        follow = prompt + a + " and then what happened next was "
        a2 = ring_engine.generate(follow, slot_name="k")
        b2 = chunked.generate(follow, slot_name="k")
        assert a2 == b2
