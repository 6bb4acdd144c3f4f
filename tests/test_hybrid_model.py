"""The layers of a hybrid decoder (models/hybrid.py) against the plain
reference (benchmarks/configs/nemotron_h_reference.py), at tiny sizes on
the CPU with seeded random weights; the resolver that builds a
ModelConfig from a published config.json; the share of an
expert-parallel pair.

Tolerances. A float32 engine and the float32 reference differ only in
the order of their sums (a chunked scan against the recurrence, rows
sorted by expert against a per-expert loop): every logit within 1e-4 where the
logits' spread is about 1 — the bound test_benchmark_reference.py holds
the GQA reference to (measured here: about 1e-6). A bfloat16 engine was
measured 0.022 to 0.045 from the reference on these sizes (seeds 0-4, CPU),
so it is held to 0.12 and must FAIL 1e-4: computing in a lower precision
than stated is told apart."""
import dataclasses
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from configs import nemotron_h_reference as ref  # noqa: E402

from theroundtaible_tpu.engine.fleet import estimate_param_count  # noqa: E402
from theroundtaible_tpu.engine.models import hybrid  # noqa: E402
from theroundtaible_tpu.engine.models.common import (  # noqa: E402
    forward, init_params, param_count)
from theroundtaible_tpu.engine.models.registry import (  # noqa: E402
    get_model_config, resolve_model_config)

F32_BOUND = 1e-4
BF16_BOUND = 0.12


def published(cfg, **extra):
    """The keys of a config.json that describe `cfg`."""
    letters = {v: k for k, v in hybrid.PATTERN_LETTERS.items()}
    return dict({
        "model_type": "nemotron_h",
        "hybrid_override_pattern": "".join(letters[k]
                                           for k in cfg.layer_kinds),
        "num_hidden_layers": cfg.num_layers, "hidden_size": cfg.embed_dim,
        "vocab_size": cfg.vocab_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "mamba_num_heads": cfg.mamba_heads,
        "mamba_head_dim": cfg.mamba_head_dim,
        "ssm_state_size": cfg.ssm_state, "n_groups": cfg.ssm_groups,
        "conv_kernel": cfg.conv_kernel, "chunk_size": cfg.mamba_chunk,
        "n_routed_experts": cfg.experts_held,
        "num_experts_per_tok": cfg.moe_top_k,
        "moe_intermediate_size": cfg.expert_dim,
        "moe_shared_expert_intermediate_size": cfg.shared_expert_dim,
        "routed_scaling_factor": cfg.routed_scaling,
        "norm_eps": cfg.norm_eps}, **extra)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_model_config("tiny-nemotron-h")
    return cfg, init_params(cfg, jax.random.PRNGKey(3), jnp.float32)


def program_logits(params, cfg, tokens):
    t = len(tokens)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, jnp.asarray(tokens)[None],
                            jnp.arange(t)[None], None, None,
                            jnp.asarray([t]))
    return np.asarray(logits[0], np.float32)


@pytest.mark.parametrize("length", [96, 128, 130, 256, 300])
def test_whole_forward_matches_the_reference(tiny, length):
    """Lengths that are and are not multiples of the 128-token chunk:
    the chunked scan against the reference's recurrence."""
    cfg, params = tiny
    tokens = np.random.RandomState(length).randint(3, 500, size=(length,))
    got = program_logits(params, cfg, tokens)
    want = np.asarray(ref.logits_at(params, published(cfg), tokens,
                                    [length - 1, 40]))
    assert abs(got.std() - 1.0) < 0.3
    assert np.abs(want[0] - got[length - 1]).max() < F32_BOUND
    assert np.abs(want[1] - got[40]).max() < F32_BOUND


@pytest.mark.parametrize("kind", list(hybrid.PATTERN_LETTERS.values()))
def test_each_layer_kind_matches_the_reference(tiny, kind):
    cfg, params = tiny
    li = cfg.layer_kinds.index(kind)
    layer = params["layers"][li]
    x = jax.random.normal(jax.random.PRNGKey(li), (1, 200, cfg.embed_dim))
    sizes = ref.sizes_of(published(cfg))
    with jax.default_matmul_precision("highest"):
        h = hybrid.layer_norm_in(x, layer, cfg)
        if kind == hybrid.MAMBA2:
            zero = hybrid.zero_state(cfg, 1)
            out, _, _ = hybrid.mamba2_prefill(
                h, layer, cfg, zero["ssm"][0], zero["conv"][0],
                jnp.asarray([200]))
            want = ref.mamba2_layer(
                layer, x[0], heads=sizes["heads"],
                head_dim=sizes["head_dim"], state=sizes["state"],
                groups=sizes["groups"], kernel=sizes["kernel"],
                eps=sizes["eps"])
        elif kind == hybrid.EXPERTS:
            out, counts = hybrid.experts_mlp(h, layer, cfg)
            want = ref.experts_layer(layer, x[0], sizes)
            assert int(counts[1]) == 200 * cfg.moe_top_k
            assert 0 < int(counts[0]) <= cfg.experts_held
        else:
            from theroundtaible_tpu.engine.models.common import (
                attention, make_attention_mask)
            pos = jnp.arange(200)[None]
            valid = jnp.asarray([200])
            out, _ = attention(h, layer, cfg, pos, None, None,
                               make_attention_mask(pos, 200, valid, None),
                               valid)
            want = ref.attention_layer(layer, x[0], eps=sizes["eps"])
    assert np.abs(np.asarray(x[0] + out[0]) - np.asarray(want)).max() \
        < F32_BOUND


def test_the_recurrence_step_continues_the_chunked_scan(tiny):
    """Prefill 150 tokens, then 10 single steps from the state it left:
    the same outputs as one scan over 160."""
    cfg, params = tiny
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 160, cfg.embed_dim))
    zero = hybrid.zero_state(cfg, 2)
    with jax.default_matmul_precision("highest"):
        whole, s_end, c_end = hybrid.mamba2_prefill(
            x, layer, cfg, zero["ssm"][0], zero["conv"][0],
            jnp.asarray([160, 160]))
        out, ssm, conv, s_cap, c_cap = hybrid.mamba2_prefill(
            x[:, :150], layer, cfg, zero["ssm"][0], zero["conv"][0],
            jnp.asarray([150, 150]), jnp.asarray([128, 40]))
        # The state captured mid-run is the state a run of that length
        # leaves.
        _, s128, c128 = hybrid.mamba2_prefill(
            x[:1, :128], layer, cfg, zero["ssm"][0][:1],
            zero["conv"][0][:1], jnp.asarray([128]))
        assert np.abs(np.asarray(s_cap[0] - s128[0])).max() < 1e-5
        assert np.abs(np.asarray(c_cap[0] - c128[0])).max() < 1e-6
        steps = []
        for t in range(150, 160):
            # Row 1 is not active: its state must not move.
            y, ssm, conv = hybrid.mamba2_step(
                x[:, t:t + 1], layer, cfg, ssm, conv,
                jnp.asarray([True, False]))
            steps.append(y)
    got = jnp.concatenate([out] + steps, axis=1)
    assert np.abs(np.asarray(got[0] - whole[0])).max() < F32_BOUND
    assert np.abs(np.asarray(ssm[0] - s_end[0])).max() < 1e-5
    assert np.abs(np.asarray(conv[0] - c_end[0])).max() < 1e-6
    _, s150, _ = hybrid.mamba2_prefill(
        x[1:, :150], layer, cfg, zero["ssm"][0][:1], zero["conv"][0][:1],
        jnp.asarray([150]))
    assert np.abs(np.asarray(ssm[1] - s150[0])).max() == 0.0


@pytest.mark.parametrize("seed", [0, 1])
def test_a_bfloat16_engine_is_told_from_a_float32_one(seed):
    """bfloat16 weights and activations (the dtype the published config
    states): over the float32 bound, under the measured one. The same
    weights read into a float32 engine are a float32 engine again."""
    cfg = get_model_config("tiny-nemotron-h")
    tokens = np.random.RandomState(seed).randint(3, 500, size=(200,))
    t = len(tokens)
    stored = init_params(cfg, jax.random.PRNGKey(seed), jnp.bfloat16)
    gaps = {}
    for dtype in (jnp.bfloat16, jnp.float32):
        params = jax.tree_util.tree_map(lambda a: a.astype(dtype), stored)
        with jax.default_matmul_precision("highest"):
            logits, _ = forward(
                params, cfg, jnp.asarray(tokens)[None],
                jnp.arange(t)[None], None, None, jnp.asarray([t]),
                last_pos=jnp.asarray([t - 1]))
        want = np.asarray(ref.logits_at(stored, published(cfg), tokens,
                                        [t - 1]))[0]
        gaps[dtype] = np.abs(
            np.asarray(logits[0, 0], np.float32) - want).max()
    assert F32_BOUND < gaps[jnp.bfloat16] < BF16_BOUND
    assert gaps[jnp.float32] < F32_BOUND


def test_the_two_shares_add_up_to_the_uncut_layer(tiny):
    """Experts 0-3 and 4-7 of one `experts` layer, each computed as one
    chip of an expert-parallel pair by the PROGRAM, the shared expert
    counted once: together the uncut reference's layer."""
    cfg, params = tiny
    li = cfg.layer_kinds.index(hybrid.EXPERTS)
    layer = params["layers"][li]
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 64, cfg.embed_dim))
    whole = ref.experts_layer(layer, x[0], ref.sizes_of(published(cfg)))
    total = None
    with jax.default_matmul_precision("highest"):
        h = hybrid.layer_norm_in(x, layer, cfg)
        shared = {"up": layer["shared"]["up"],
                  "down": layer["shared"]["down"]}
        for rank in (0, 1):
            half = dataclasses.replace(cfg, experts_held=4,
                                       expert_offset=4 * rank)
            part = dict(layer, experts={
                k: v[4 * rank:4 * rank + 4]
                for k, v in layer["experts"].items()})
            if rank:    # the shared expert is counted once
                part["shared"] = {k: jnp.zeros_like(v)
                                  for k, v in shared.items()}
            out, counts = hybrid.experts_mlp(h, part, half)
            total = out if total is None else total + out
            # The reference given the same share agrees with the
            # program's half (what the other chip adds is left out in
            # both).
            sizes = ref.sizes_of(published(half, ep_size=2, ep_rank=rank))
            assert sizes["published"] == 8 and sizes["offset"] == 4 * rank
            if not rank:
                want = ref.experts_layer(part, x[0], sizes)
                assert np.abs(np.asarray(x[0] + out[0])
                              - np.asarray(want)).max() < F32_BOUND
            assert 0 < int(counts[1]) < 64 * cfg.moe_top_k
    assert np.abs(np.asarray(x[0] + total[0]) - np.asarray(whole)).max() \
        < F32_BOUND


# --- the resolver ----------------------------------------------------------


def test_an_architecture_block_builds_the_model(tiny):
    cfg, _ = tiny
    got = resolve_model_config({
        "model": "my-hybrid", "max_seq_len": 512,
        "architecture": published(cfg, ep_size=1, rope_theta=10000,
                                  mamba_hidden_act="silu", expand=2)})
    assert got == dataclasses.replace(cfg, name="my-hybrid")
    half = resolve_model_config({
        "model": "half", "architecture": published(
            dataclasses.replace(cfg, experts_held=4), ep_size=2,
            ep_rank=1)})
    assert (half.routed_experts, half.experts_held,
            half.expert_offset) == (8, 4, 4)
    assert resolve_model_config({"model": "tiny-gemma"}) \
        == get_model_config("tiny-gemma")
    dense = resolve_model_config({"model": "m", "architecture": {
        "model_type": "mistral", "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 512, "rms_norm_eps": 1e-5}})
    assert dense.layer_kinds is None and dense.head_dim == 16


@pytest.mark.parametrize("edit,message", [
    ({"model_type": "gigachat"}, "model_type"),
    ({"frobnicate": 3}, "unknown keys"),
    ({"hybrid_override_pattern": "ME-ME"}, "layer kind"),
    ({"mlp_hidden_act": "silu"}, "relu2"),
    ({"n_group": 4}, "n_group"),
    ({"num_hidden_layers": 7}, "num_hidden_layers"),
    ({"ep_rank": 3}, "ep_rank"),
])
def test_what_the_engine_cannot_run_fails_at_once(tiny, edit, message):
    cfg, _ = tiny
    with pytest.raises(ValueError, match=message):
        resolve_model_config({"model": "x",
                              "architecture": published(cfg, **edit)})
    arch = published(cfg)
    del arch["ssm_state_size"]
    with pytest.raises(ValueError, match="ssm_state_size"):
        resolve_model_config({"model": "x", "architecture": arch})


def test_parameters_are_counted_by_layer_kind(tiny):
    cfg, params = tiny
    assert estimate_param_count(cfg) == param_count(params)
    big = get_model_config("nemotron-3-nano-30b-a3b")
    assert (len(big.mamba_layers), len(big.expert_layers),
            len(big.attention_layers)) == (23, 23, 6)
    # The family's stated size: 31.6 B parameters.
    assert abs(estimate_param_count(big) / 1e9 - 31.6) < 0.1
    # A dense model is counted as before.
    gemma = get_model_config("tiny-gemma")
    assert estimate_param_count(gemma) == param_count(
        init_params(gemma, jax.random.PRNGKey(0)))
