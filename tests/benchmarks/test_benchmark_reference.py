"""The benchmark's plain reference against the program's own forward, at
tiny sizes on the CPU: the equations written out independently give the
program's logits, with a bias on q/k/v, with a tied and an untied head,
and with int8 leaves dequantised."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths  # noqa: F401
from configs import gqa_decoder_reference as ref
from harness import correct


def _sizes(cfg):
    return {"rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "sliding_window": cfg.sliding_window,
            "tie_word_embeddings": cfg.tie_embeddings}


def _program_logits(params, cfg, tokens, row):
    from theroundtaible_tpu.engine.models.common import forward
    t = len(tokens)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(
            params, cfg, jnp.asarray(tokens)[None], jnp.arange(t)[None],
            None, None, jnp.asarray([t]), last_pos=jnp.asarray([row]))
    return np.asarray(logits[0, 0], np.float32)


@pytest.mark.parametrize("model,quant", [
    ("tiny-llama", "none"), ("tiny-qwen", "none"),
    ("tiny-mistral", "none"), ("tiny-llama", "int8"),
    ("tiny-qwen", "int8")])
def test_reference_gives_the_programs_logits(model, quant):
    from theroundtaible_tpu.engine.models.common import init_params
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.quant import quantize_params

    cfg = get_model_config(model)
    params = init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    if quant == "int8":
        params = quantize_params(params, cfg, act_dtype=jnp.float32)
    tokens = np.random.RandomState(1).randint(3, 500, size=(96,))
    got = np.asarray(ref.logits_at(params, _sizes(cfg), tokens, [95, 40]))
    # Float32 both ways, sums in another order: agreement to 1e-4 of a
    # logit whose spread is about 1.
    assert np.abs(got[0] - _program_logits(params, cfg, tokens, 95)
                  ).max() < 1e-4
    assert np.abs(got[1] - _program_logits(params, cfg, tokens[:41], 40)
                  ).max() < 1e-4


def test_what_follows_a_row_never_reaches_it():
    from theroundtaible_tpu.engine.models.common import init_params
    from theroundtaible_tpu.engine.models.registry import get_model_config
    cfg = get_model_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    a = np.arange(3, 67)
    b = np.concatenate([a[:32], np.full((32,), 9)])
    la = ref.logits_at(params, _sizes(cfg), a, [31])
    lb = ref.logits_at(params, _sizes(cfg), b, [31])
    assert np.allclose(np.asarray(la), np.asarray(lb), atol=1e-5)


class _Oracle:
    """A stand-in reference whose logits are known: position p prefers
    token (p % 7) + 10 by a wide margin, token 5 is a near-tie."""

    @staticmethod
    def logits_at(_params, _config, seq, rows):
        out = np.random.RandomState(0).normal(size=(len(rows), 64))
        for i, p in enumerate(rows):
            out[i, (p % 7) + 10] = 9.0
            out[i, 5] = 9.0 - 0.1 * out[i].std()
        return out


@pytest.mark.parametrize("ids,ok", [
    ([13, 14, 15], True),       # prompt of 4: rows 3, 4, 5 prefer 13..15
    ([5, 14, 15], True),        # a near-tie within 0.25 sigma passes
    ([13, 20, 15], False),      # a token four sigma down does not
    ([], False)])               # nothing served is not an answer
def test_score_admits_near_ties_and_nothing_else(ids, ok):
    out = correct.score(_Oracle, None, {}, [
        {"what": "x", "prompt": [1, 2, 3, 4], "ids": ids}])
    assert out["correct"] is ok
    assert out["tolerance_sigmas"] == 0.25
