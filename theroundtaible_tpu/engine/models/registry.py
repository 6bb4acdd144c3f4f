"""Model registry — named hyperparameter sets, and the resolver that
builds one from a published `config.json`.

Presets: the reference-targeted open-weight families (BASELINE.md:
Gemma-2B/7B, Llama-3-8B/3.2, Mistral-7B), Mixtral (compute-dense MoE),
Qwen2.5 (attention bias), Nemotron-3-Nano (hybrid: Mamba-2, routed and
shared experts, attention — models/hybrid.py), A.X-K1 (latent attention
— models/mla.py — beside a dense MLP or gated routed and shared
experts), Brumby (power retention and no attention layer —
models/retention.py), Jamba (Mamba-1 layers in scanned runs beside
attention layers of one kv head — models/mamba1.py), LFM2 (gated short
convolutions — models/shortconv.py), Phi-4-mini-flash (a decoder whose
upper half keeps no cache of its own: differential attention —
models/diffattn.py — over one layer's pages, gated memory units over one
Mamba-1 layer's scan output) and tiny test presets.
Architecture behavior lives in ModelConfig fields (common.py).

`resolve_model_config(adapter_config)` is the one way an engine gets its
ModelConfig: from the `architecture` block of the adapter's config (the
published config.json's own keys, plus the chip's share of an
expert-parallel group) under the name in `model`, else by name from the
presets. A model the presets lack is served by giving its published
keys; a key or `model_type` this engine cannot run fails at once.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from .common import AttnLayer, ModelConfig
from .hybrid import (ATTENTION, CROSS, EXPERTS, GMU, MAMBA1, MLP,
                     RETENTION, SHORTCONV, kinds_of_pattern)

_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


# --- Gemma (GeGLU, scaled embeddings, RMSNorm 1+w, tied head) ---

GEMMA_2B = register(ModelConfig(
    name="gemma-2b-it", vocab_size=256_000, num_layers=18, embed_dim=2048,
    num_heads=8, num_kv_heads=1, head_dim=256, mlp_dim=16_384,
    max_seq_len=8192, gelu_mlp=True, scale_embeddings=True,
    rmsnorm_unit_offset=True, tie_embeddings=True))

GEMMA_7B = register(ModelConfig(
    name="gemma-7b-it", vocab_size=256_000, num_layers=28, embed_dim=3072,
    num_heads=16, num_kv_heads=16, head_dim=256, mlp_dim=24_576,
    max_seq_len=8192, gelu_mlp=True, scale_embeddings=True,
    rmsnorm_unit_offset=True, tie_embeddings=True))

# --- Llama 3 (SiLU, GQA, untied head, big rope theta) ---

LLAMA3_8B = register(ModelConfig(
    name="llama-3-8b-instruct", vocab_size=128_256, num_layers=32,
    embed_dim=4096, num_heads=32, num_kv_heads=8, head_dim=128,
    mlp_dim=14_336, max_seq_len=8192, rope_theta=500_000.0,
    norm_eps=1e-5, tie_embeddings=False))

LLAMA32_1B = register(ModelConfig(
    name="llama-3.2-1b-instruct", vocab_size=128_256, num_layers=16,
    embed_dim=2048, num_heads=32, num_kv_heads=8, head_dim=64,
    mlp_dim=8192, max_seq_len=8192, rope_theta=500_000.0,
    norm_eps=1e-5, tie_embeddings=True))

LLAMA32_3B = register(ModelConfig(
    name="llama-3.2-3b-instruct", vocab_size=128_256, num_layers=28,
    embed_dim=3072, num_heads=24, num_kv_heads=8, head_dim=128,
    mlp_dim=8192, max_seq_len=8192, rope_theta=500_000.0,
    norm_eps=1e-5, tie_embeddings=True))

# --- Mistral (SiLU, GQA, sliding window) ---

MISTRAL_7B = register(ModelConfig(
    name="mistral-7b-instruct", vocab_size=32_000, num_layers=32,
    embed_dim=4096, num_heads=32, num_kv_heads=8, head_dim=128,
    mlp_dim=14_336, max_seq_len=8192, rope_theta=1_000_000.0,
    norm_eps=1e-5, sliding_window=4096, tie_embeddings=False))

# --- Qwen2.5 (SiLU, GQA, attention bias, tied head at small sizes) ---

QWEN25_1_5B = register(ModelConfig(
    name="qwen2.5-1.5b-instruct", vocab_size=151_936, num_layers=28,
    embed_dim=1536, num_heads=12, num_kv_heads=2, head_dim=128,
    mlp_dim=8960, max_seq_len=8192, rope_theta=1_000_000.0,
    norm_eps=1e-6, attn_bias=True, tie_embeddings=True))

# --- Mixtral (SiLU, GQA, sparse MoE, sliding window in v0.1 only) ---

MIXTRAL_8X7B = register(ModelConfig(
    name="mixtral-8x7b-instruct", vocab_size=32_000, num_layers=32,
    embed_dim=4096, num_heads=32, num_kv_heads=8, head_dim=128,
    mlp_dim=14_336, max_seq_len=8192, rope_theta=1_000_000.0,
    norm_eps=1e-5, tie_embeddings=False,
    num_experts=8, num_experts_per_tok=2))

# --- tiny presets: CPU tests, sharding dry-runs, CI ---

TINY_GEMMA = register(ModelConfig(
    name="tiny-gemma", vocab_size=512, num_layers=2, embed_dim=64,
    num_heads=4, num_kv_heads=2, head_dim=16, mlp_dim=128,
    max_seq_len=512, gelu_mlp=True, scale_embeddings=True,
    rmsnorm_unit_offset=True, tie_embeddings=True))

TINY_LLAMA = register(ModelConfig(
    name="tiny-llama", vocab_size=512, num_layers=2, embed_dim=64,
    num_heads=4, num_kv_heads=2, head_dim=16, mlp_dim=128,
    max_seq_len=512, tie_embeddings=False))

TINY_MISTRAL = register(ModelConfig(
    name="tiny-mistral", vocab_size=512, num_layers=2, embed_dim=64,
    num_heads=4, num_kv_heads=2, head_dim=16, mlp_dim=128,
    max_seq_len=512, sliding_window=64, tie_embeddings=False))

TINY_QWEN = register(ModelConfig(
    name="tiny-qwen", vocab_size=512, num_layers=2, embed_dim=64,
    num_heads=4, num_kv_heads=2, head_dim=16, mlp_dim=128,
    max_seq_len=512, attn_bias=True, tie_embeddings=True))

TINY_MIXTRAL = register(ModelConfig(
    name="tiny-mixtral", vocab_size=512, num_layers=2, embed_dim=64,
    num_heads=4, num_kv_heads=2, head_dim=16, mlp_dim=128,
    max_seq_len=512, tie_embeddings=False,
    num_experts=4, num_experts_per_tok=2))


# --- Nemotron-3-Nano / nemotron_h (one mixer a layer: Mamba-2 | experts |
# attention without position embedding; sigmoid router, squared ReLU) ---

NEMOTRON3_NANO = register(ModelConfig(
    name="nemotron-3-nano-30b-a3b", vocab_size=131_072, num_layers=52,
    embed_dim=2688, num_heads=32, num_kv_heads=2, head_dim=128,
    mlp_dim=1856, max_seq_len=8192, norm_eps=1e-5, tie_embeddings=False,
    rope=False,
    layer_kinds=kinds_of_pattern(
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"),
    mamba_heads=64, mamba_head_dim=64, ssm_state=128, ssm_groups=8,
    conv_kernel=4, mamba_chunk=128,
    routed_experts=128, experts_held=128, expert_offset=0, moe_top_k=6,
    expert_dim=1856, shared_expert_dim=3712, routed_scaling=2.5))

TINY_NEMOTRON_H = register(ModelConfig(
    name="tiny-nemotron-h", vocab_size=512, num_layers=5, embed_dim=64,
    num_heads=4, num_kv_heads=2, head_dim=16, mlp_dim=32,
    max_seq_len=512, norm_eps=1e-5, tie_embeddings=False, rope=False,
    layer_kinds=kinds_of_pattern("ME*ME"),
    mamba_heads=4, mamba_head_dim=16, ssm_state=16, ssm_groups=2,
    conv_kernel=4, mamba_chunk=128,
    routed_experts=8, experts_held=8, expert_offset=0, moe_top_k=2,
    expert_dim=32, shared_expert_dim=64, routed_scaling=2.5))


# --- A.X-K1 / axk1 (DeepSeek-V3's block: latent attention, then a dense
# MLP in the first layers and gated routed + shared experts after; every
# published layer is TWO layers here, a mixer behind a norm each) ---


def axk1_kinds(n_blocks: int, dense_blocks: int) -> tuple[str, ...]:
    return tuple(k for b in range(n_blocks)
                 for k in (ATTENTION, MLP if b < dense_blocks else EXPERTS))


AXK1_YARN = (32.0, 4096.0, 32.0, 1.0, 1.0, 1.0)

AXK1 = register(ModelConfig(
    name="a.x-k1", vocab_size=163_840, num_layers=122, embed_dim=7168,
    num_heads=64, num_kv_heads=64, head_dim=192, mlp_dim=18_432,
    max_seq_len=8192, norm_eps=1e-6, tie_embeddings=False,
    layer_kinds=axk1_kinds(61, 1),
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128, rope_yarn=AXK1_YARN,
    routed_experts=192, experts_held=192, expert_offset=0, moe_top_k=8,
    expert_dim=2048, shared_expert_dim=2048, routed_scaling=2.5,
    router_rule="sigmoid_topk", expert_act="silu", expert_gated=True))

TINY_AXK1 = register(ModelConfig(
    name="tiny-axk1", vocab_size=512, num_layers=6, embed_dim=64,
    num_heads=4, num_kv_heads=4, head_dim=24, mlp_dim=128,
    max_seq_len=512, norm_eps=1e-6, tie_embeddings=False,
    layer_kinds=axk1_kinds(3, 1),
    q_lora_rank=32, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
    v_head_dim=16, rope_yarn=(32.0, 64.0, 32.0, 1.0, 1.0, 1.0),
    routed_experts=8, experts_held=8, expert_offset=0, moe_top_k=2,
    expert_dim=32, shared_expert_dim=32, routed_scaling=2.5,
    router_rule="sigmoid_topk", expert_act="silu", expert_gated=True))


# --- Laguna / laguna (window and full attention layers with a geometry of
# their own each over one GQA page pool, a per-head output gate; a dense
# MLP or routed + shared gated experts; a published layer is TWO layers
# here, as axk1's) ---

def laguna_layers(layer_types, mlp_layer_types, heads_per_layer, *,
                  window: int, full: AttnLayer, sliding: AttnLayer):
    """(layer_kinds, attn_layers) of a laguna stack: `full` and `sliding`
    carry each type's rotary table; the heads are the layer's own and
    the window the sliding layers' alone."""
    kinds, geometry = [], []
    for lt, mt, heads in zip(layer_types, mlp_layer_types,
                             heads_per_layer):
        if lt not in ("full_attention", "sliding_attention") \
                or mt not in ("dense", "sparse"):
            raise ValueError(f"layer types ({lt!r}, {mt!r}): "
                             "known are full_attention / "
                             "sliding_attention and dense / sparse")
        kinds += [ATTENTION, MLP if mt == "dense" else EXPERTS]
        geometry.append(dataclasses.replace(
            full if lt == "full_attention" else sliding,
            num_heads=int(heads),
            sliding_window=None if lt == "full_attention" else window))
    return tuple(kinds), tuple(geometry)


def _laguna_preset(name, *, blocks, heads, **kw):
    """dense + `S S S F ...`: block 0 full attention and a dense MLP,
    then sliding, sliding, sliding, full with experts."""
    types = ["full_attention" if b % 4 == 0 else "sliding_attention"
             for b in range(blocks)]
    kinds, geometry = laguna_layers(
        types, ["dense"] + ["sparse"] * (blocks - 1),
        [heads[0] if t == "full_attention" else heads[1] for t in types],
        window=kw.pop("window"), full=kw.pop("full"),
        sliding=kw.pop("sliding"))
    return ModelConfig(
        name=name, num_layers=2 * blocks, num_heads=heads[0],
        norm_eps=1e-6, tie_embeddings=False, layer_kinds=kinds,
        attn_layers=geometry, attn_gate=True, moe_top_k=kw.pop(
            "top_k"), routed_scaling=2.5, router_rule="sigmoid_topk",
        expert_act="silu", expert_gated=True, **kw)


LAGUNA_XS2 = register(_laguna_preset(
    "laguna-xs.2", blocks=40, heads=(48, 64), vocab_size=100_352,
    embed_dim=2048, num_kv_heads=8, head_dim=128, mlp_dim=8192,
    max_seq_len=8192, window=512,
    full=AttnLayer(0, rope_theta=500_000.0, rotary_dim=64,
                   rope_yarn=(64.0, 4096.0, 64.0, 1.0),
                   rope_attention_factor=1.4158883083359672),
    sliding=AttnLayer(0, rope_theta=10_000.0, rotary_dim=128),
    routed_experts=256, experts_held=256, top_k=8, expert_dim=512,
    shared_expert_dim=512))

# Groups 3 and 4 over 2 kv heads, a window of two 8-wide pages, YaRN
# over half a head on the full layers.
TINY_LAGUNA = register(_laguna_preset(
    "tiny-laguna", blocks=5, heads=(6, 8), vocab_size=512, embed_dim=64,
    num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=512, window=16,
    full=AttnLayer(0, rope_theta=500_000.0, rotary_dim=8,
                   rope_yarn=(8.0, 32.0, 64.0, 1.0),
                   rope_attention_factor=1.2079441541679836),
    sliding=AttnLayer(0, rope_theta=10_000.0, rotary_dim=16),
    routed_experts=8, experts_held=8, top_k=2, expert_dim=32,
    shared_expert_dim=32))


# --- Mellum 2 / mellum (window and full attention layers at ONE head
# count over one GQA page pool, two rotary tables by layer type; every
# layer's MLP softmax-routed gated experts with NO shared expert; a
# published layer is TWO layers here, as laguna's) ---

def _mellum_config(name, layer_types, *, heads, window, full, sliding,
                   **kw):
    """Every block `layer_types` names, each with experts and `heads`
    query heads; no gate, no shared expert, the router a softmax."""
    n = len(layer_types)
    kinds, geometry = laguna_layers(
        layer_types, ["sparse"] * n, [heads] * n, window=window, full=full,
        sliding=sliding)
    return ModelConfig(
        name=name, num_layers=2 * n, num_heads=heads, tie_embeddings=False,
        layer_kinds=kinds, attn_layers=geometry,
        router_rule="softmax_topk", expert_act="silu", expert_gated=True,
        **kw)


def _mellum_preset(name, *, blocks, **kw):
    """`S S S F` repeated."""
    return _mellum_config(
        name, ["full_attention" if b % 4 == 3 else "sliding_attention"
               for b in range(blocks)], norm_eps=1e-6, **kw)


MELLUM2_12B = register(_mellum_preset(
    "mellum2-12b-a2.5b", blocks=28, heads=32, vocab_size=98_304,
    embed_dim=2304, num_kv_heads=4, head_dim=128, mlp_dim=7168,
    max_seq_len=8192, window=1024,
    full=AttnLayer(0, rope_theta=500_000.0, rotary_dim=128,
                   rope_yarn=(16.0, 8192.0, 32.0, 1.0),
                   rope_attention_factor=1.2772588722239782),
    sliding=AttnLayer(0, rope_theta=500_000.0, rotary_dim=128),
    routed_experts=64, experts_held=64, moe_top_k=8, expert_dim=896))

# Group 4 over 2 kv heads, a window of two 8-wide pages, two periods.
TINY_MELLUM = register(_mellum_preset(
    "tiny-mellum", blocks=8, heads=8, vocab_size=512, embed_dim=64,
    num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=512, window=16,
    full=AttnLayer(0, rope_theta=500_000.0, rotary_dim=16,
                   rope_yarn=(8.0, 32.0, 32.0, 1.0),
                   rope_attention_factor=1.2079441541679836),
    sliding=AttnLayer(0, rope_theta=500_000.0, rotary_dim=16),
    routed_experts=8, experts_held=8, moe_top_k=2, expert_dim=32))


# --- Brumby / brumby (Qwen3's block with the attention product replaced
# by power retention — models/retention.py: NO attention layer, so no
# page holds a byte; q and k normed a head ahead of full rotary; a
# published layer is TWO layers here, retention then a SwiGLU MLP) ---

def _brumby_config(name, *, blocks, **kw):
    return ModelConfig(
        name=name, num_layers=2 * blocks, tie_embeddings=False,
        layer_kinds=(RETENTION, MLP) * blocks, qk_norm=True, **kw)


BRUMBY_14B = register(_brumby_config(
    "brumby-14b", blocks=40, vocab_size=151_936, embed_dim=5120,
    num_heads=40, num_kv_heads=8, head_dim=128, mlp_dim=17_408,
    max_seq_len=8192, rope_theta=1_000_000.0, norm_eps=1e-6))

# Group 3 over 2 kv heads of 16: a state of 9 x 16 x 17 floats a head.
TINY_BRUMBY = register(_brumby_config(
    "tiny-brumby", blocks=3, vocab_size=512, embed_dim=64, num_heads=6,
    num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=512,
    rope_theta=1_000_000.0, norm_eps=1e-6))


# --- Jamba / jamba (a published layer is TWO layers here: a Mamba-1
# mixer — models/mamba1.py — or, where i % period == offset, attention
# over the model's kv heads WITHOUT position embedding, then a SwiGLU
# MLP; tied head. Consecutive (mamba1, mlp) blocks are one scanned run:
# ModelConfig.layer_runs) ---

def jamba_kinds(blocks: int, period: int, offset: int) -> tuple[str, ...]:
    return tuple(k for i in range(blocks)
                 for k in (ATTENTION if i % period == offset else MAMBA1,
                           MLP))


def _jamba_config(name, *, blocks, period, offset, **kw):
    return ModelConfig(
        name=name, num_layers=2 * blocks, tie_embeddings=True, rope=False,
        layer_kinds=jamba_kinds(blocks, period, offset), **kw)


JAMBA2_3B = register(_jamba_config(
    "jamba2-3b", blocks=28, period=14, offset=7, vocab_size=65_536,
    embed_dim=2560, num_heads=20, num_kv_heads=1, head_dim=128,
    mlp_dim=8192, max_seq_len=8192, norm_eps=1e-6, mamba1_dim=5120,
    ssm_state=16, conv_kernel=4, dt_rank=160))

# One period: attention at 7 of 14, so runs of 7 and 6 blocks; group 4
# over one kv head.
TINY_JAMBA = register(_jamba_config(
    "tiny-jamba", blocks=14, period=14, offset=7, vocab_size=512,
    embed_dim=64, num_heads=4, num_kv_heads=1, head_dim=16, mlp_dim=128,
    max_seq_len=512, norm_eps=1e-6, mamba1_dim=128, ssm_state=8,
    conv_kernel=4, dt_rank=4))


# --- LFM2 / lfm2_moe (a published layer is TWO layers here: the operator
# — a gated short convolution, models/shortconv.py, or grouped-query
# attention with q and k normed a head ahead of plain rotary — then a
# dense SwiGLU in the leading layers and sigmoid-routed gated experts
# with a selection bias and NO shared expert after them; tied head) ---

LFM2_OPERATORS = {"conv": SHORTCONV, "full_attention": ATTENTION}


def lfm2_kinds(layer_types, dense_layers: int) -> tuple[str, ...]:
    unknown = sorted(set(layer_types) - set(LFM2_OPERATORS))
    if unknown:
        raise ValueError(f"layer_types {unknown}: known are "
                         f"{', '.join(LFM2_OPERATORS)}")
    return tuple(k for i, lt in enumerate(layer_types)
                 for k in (LFM2_OPERATORS[lt],
                           MLP if i < dense_layers else EXPERTS))


def _lfm2_config(name, layer_types, *, dense_layers, experts, **kw):
    return ModelConfig(
        name=name, num_layers=2 * len(layer_types), tie_embeddings=True,
        layer_kinds=lfm2_kinds(layer_types, dense_layers), qk_norm=True,
        routed_experts=experts, experts_held=experts,
        router_rule="sigmoid_bias_topk", expert_act="silu",
        expert_gated=True, **kw)


def _lfm2_types(blocks: int) -> list[str]:
    """conv conv, then `attention conv conv conv` repeated."""
    return ["full_attention" if b % 4 == 2 else "conv"
            for b in range(blocks)]


LFM2_24B_A2B = register(_lfm2_config(
    "lfm2-24b-a2b", _lfm2_types(40), dense_layers=2, experts=64,
    vocab_size=65_536, embed_dim=2048, num_heads=32, num_kv_heads=8,
    head_dim=64, mlp_dim=11_776, max_seq_len=8192,
    rope_theta=1_000_000.0, norm_eps=1e-5, conv_kernel=3, moe_top_k=4,
    expert_dim=1536))

# Both dense layers and one period: group 2 over 4 kv heads of 64, so
# that the pool packs two heads a lane row as the published widths do.
TINY_LFM2 = register(_lfm2_config(
    "tiny-lfm2", _lfm2_types(6), dense_layers=2, experts=8,
    vocab_size=512, embed_dim=64, num_heads=8, num_kv_heads=4,
    head_dim=64, mlp_dim=128, max_seq_len=512, rope_theta=1_000_000.0,
    norm_eps=1e-5, conv_kernel=3, moe_top_k=2, expert_dim=32))


# --- Phi-4-mini-flash / phi4flash (SambaY, arXiv:2507.06607: with L =
# `num_hidden_layers`, L % 4 == 0, and `mb_per_layer` 2, published layer i
# is a Mamba-1 mixer WITHOUT Jamba's inner norms where i is even and
# i <= L/2; differential attention — models/diffattn.py — over a window
# where i is odd and i < L/2, causal and unbounded at i = L/2 + 1; above
# that a gated memory unit over layer L/2's scan output where i is even,
# and a differential CROSS layer over layer L/2 + 1's pages where i is
# odd: the layers from L/2 + 2 up keep nothing, and a join runs them on
# each row's last token (`last_token_from`). Each then a SwiGLU MLP;
# LayerNorm with bias; no position embedding; tied head) ---


def phi4flash_kinds(blocks: int) -> tuple[str, ...]:
    """The mixer of every published layer by the model's own depth rule,
    each followed by its MLP."""
    if blocks % 4 or blocks < 4:
        raise ValueError(f"num_hidden_layers {blocks}: the depth rule "
                         "needs a multiple of 4")
    half = blocks // 2
    return tuple(k for i in range(blocks) for k in (
        (MAMBA1 if i <= half else GMU) if i % 2 == 0
        else (ATTENTION if i <= half + 1 else CROSS), MLP))


def _phi4flash_config(name, *, blocks, heads, window, **kw):
    kinds = phi4flash_kinds(blocks)
    n_window = blocks // 4
    return ModelConfig(
        name=name, num_layers=2 * blocks, num_heads=heads,
        tie_embeddings=True, rope=False, layer_kinds=kinds,
        attn_layers=(AttnLayer(heads, window),) * n_window
        + (AttnLayer(heads, None),),
        attn_bias=True, layer_norm=True, diff_attn=True,
        mamba1_norms=False, last_token_from=2 * (blocks // 2 + 2), **kw)


PHI4_MINI_FLASH = register(_phi4flash_config(
    "phi-4-mini-flash-reasoning", blocks=32, heads=40, window=512,
    vocab_size=200_064, embed_dim=2560, num_kv_heads=20, head_dim=64,
    mlp_dim=10_240, max_seq_len=8192, norm_eps=1e-5, mamba1_dim=5120,
    ssm_state=16, conv_kernel=4, dt_rank=160))

# Depth 8 (3 Mamba, 2 window, 1 full, 1 memory unit, 1 cross layer): 8
# heads over 4 of 64, so that a kv pair fills a 128-lane row as the
# published widths do.
TINY_PHI4FLASH = register(_phi4flash_config(
    "tiny-phi4flash", blocks=8, heads=8, window=16, vocab_size=512,
    embed_dim=64, num_kv_heads=4, head_dim=64, mlp_dim=128,
    max_seq_len=512, norm_eps=1e-5, mamba1_dim=128, ssm_state=8,
    conv_kernel=4, dt_rank=4))


# --- from a published config.json -------------------------------------------

# Keys of a nemotron_h config.json that say nothing this engine acts on
# (initialisation ranges, HF runtime switches).
_NEMOTRON_INERT = {
    "num_logits_to_keep", "rescale_prenorm_residual", "residual_in_fp32",
    "time_step_floor", "time_step_max", "time_step_min",
    "use_mamba_kernels", "expand", "intermediate_size",
    "max_position_embeddings", "rope_theta", "partial_rotary_factor",
    "layer_norm_epsilon", "model_type"}
# ... and the values the layer equations of models/hybrid.py assume.
_NEMOTRON_FIXED = {
    "attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
    "use_bias": False, "use_conv_bias": True, "mamba_hidden_act": "silu",
    "mlp_hidden_act": "relu2", "n_group": 1, "topk_group": 1,
    "n_shared_experts": 1, "norm_topk_prob": True, "sliding_window": None,
    "tie_word_embeddings": False}
_DENSE_TYPES = ("llama", "mistral", "qwen2")


def _acted_on(name: str, arch: dict[str, Any], kind: str,
              fixed: dict[str, Any], inert: set) -> dict[str, Any]:
    """A copy of `arch` without the keys that say nothing this engine
    acts on; a key whose value the layer equations do not assume fails."""
    arch = dict(arch)
    for key, want in fixed.items():
        got = arch.pop(key, want)
        if got != want:
            raise ValueError(
                f"architecture of {name!r}: {key}={got!r}, and this "
                f"engine's {kind} layers are written for {want!r}")
    for key in inert:
        arch.pop(key, None)
    return arch


def _all_read(name: str, arch: dict[str, Any], kind: str,
              ep_rank: int = 0, ep_size: int = 1) -> None:
    """What is left of `arch` when every key has been read is unknown."""
    if arch:
        raise ValueError(f"architecture of {name!r}: unknown keys "
                         f"{sorted(arch)} for model_type {kind!r}")
    if not 0 <= ep_rank < ep_size:
        raise ValueError(f"architecture of {name!r}: ep_rank {ep_rank} "
                         f"outside 0..{ep_size - 1}")


def _nemotron_h(name: str, arch: dict[str, Any],
                max_seq_len: int) -> ModelConfig:
    arch = _acted_on(name, arch, "nemotron_h", _NEMOTRON_FIXED,
                     _NEMOTRON_INERT)
    try:
        kinds = kinds_of_pattern(arch.pop("hybrid_override_pattern"))
        n_layers = int(arch.pop("num_hidden_layers"))
        held = int(arch.pop("n_routed_experts"))
        ep_size = int(arch.pop("ep_size", 1))
        ep_rank = int(arch.pop("ep_rank", 0))
        cfg = ModelConfig(
            name=name, vocab_size=int(arch.pop("vocab_size")),
            num_layers=n_layers, embed_dim=int(arch.pop("hidden_size")),
            num_heads=int(arch.pop("num_attention_heads")),
            num_kv_heads=int(arch.pop("num_key_value_heads")),
            head_dim=int(arch.pop("head_dim")),
            mlp_dim=int(arch["moe_intermediate_size"]),
            max_seq_len=max_seq_len, norm_eps=float(arch.pop("norm_eps")),
            tie_embeddings=False,
            # The nemotron_h modelling code applies no position
            # embedding; `rope: true` is the other reading, one key.
            rope=bool(arch.pop("rope", False)), layer_kinds=kinds,
            mamba_heads=int(arch.pop("mamba_num_heads")),
            mamba_head_dim=int(arch.pop("mamba_head_dim")),
            ssm_state=int(arch.pop("ssm_state_size")),
            ssm_groups=int(arch.pop("n_groups")),
            conv_kernel=int(arch.pop("conv_kernel")),
            mamba_chunk=int(arch.pop("chunk_size")),
            routed_experts=held * ep_size, experts_held=held,
            expert_offset=held * ep_rank,
            moe_top_k=int(arch.pop("num_experts_per_tok")),
            expert_dim=int(arch.pop("moe_intermediate_size")),
            shared_expert_dim=int(
                arch.pop("moe_shared_expert_intermediate_size")),
            routed_scaling=float(arch.pop("routed_scaling_factor")))
    except KeyError as e:
        raise ValueError(f"architecture of {name!r} lacks the key "
                         f"{e.args[0]!r}") from None
    _all_read(name, arch, "nemotron_h", ep_rank, ep_size)
    if len(kinds) != n_layers:
        raise ValueError(
            f"architecture of {name!r}: hybrid_override_pattern has "
            f"{len(kinds)} layers, num_hidden_layers says {n_layers}")
    return cfg


# Keys of an axk1 config.json that say nothing this engine acts on, and
# the values its layer equations assume. `topk_method: "none"` is read
# literally: plain top-k over every score, so n_group and topk_group are
# recorded and unused.
_AXK1_INERT = {"model_type", "max_position_embeddings", "n_group",
               "topk_group", "seq_aux", "num_key_value_heads"}
_AXK1_FIXED = {
    "attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "none",
    "tie_word_embeddings": False}


def _axk1(name: str, arch: dict[str, Any],
          max_seq_len: int) -> ModelConfig:
    arch = _acted_on(name, arch, "axk1", _AXK1_FIXED, _AXK1_INERT)
    try:
        yarn = dict(arch.pop("rope_scaling"))
        if yarn.pop("type") != "yarn":
            raise ValueError(f"architecture of {name!r}: rope_scaling "
                             "must be of type 'yarn'")
        rope_yarn = tuple(float(yarn.pop(k)) for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim"))
        if yarn:
            raise ValueError(f"architecture of {name!r}: unknown "
                             f"rope_scaling keys {sorted(yarn)}")
        n_blocks = int(arch.pop("num_hidden_layers"))
        dense_blocks = int(arch.pop("first_k_dense_replace"))
        held = int(arch.pop("n_routed_experts"))
        ep_size = int(arch.pop("ep_size", 1))
        ep_rank = int(arch.pop("ep_rank", 0))
        nope = int(arch.pop("qk_nope_head_dim"))
        rot = int(arch.pop("qk_rope_head_dim"))
        heads = int(arch.pop("num_attention_heads"))
        width = int(arch.pop("moe_intermediate_size"))
        cfg = ModelConfig(
            name=name, vocab_size=int(arch.pop("vocab_size")),
            num_layers=2 * n_blocks,
            embed_dim=int(arch.pop("hidden_size")), num_heads=heads,
            num_kv_heads=heads, head_dim=nope + rot,
            mlp_dim=int(arch.pop("intermediate_size")),
            max_seq_len=max_seq_len,
            rope_theta=float(arch.pop("rope_theta")),
            norm_eps=float(arch.pop("rms_norm_eps")),
            tie_embeddings=False,
            layer_kinds=axk1_kinds(n_blocks, dense_blocks),
            q_lora_rank=int(arch.pop("q_lora_rank")),
            kv_lora_rank=int(arch.pop("kv_lora_rank")),
            qk_nope_dim=nope, qk_rope_dim=rot,
            v_head_dim=int(arch.pop("v_head_dim")), rope_yarn=rope_yarn,
            routed_experts=held * ep_size, experts_held=held,
            expert_offset=held * ep_rank,
            moe_top_k=int(arch.pop("num_experts_per_tok")),
            expert_dim=width, shared_expert_dim=width,
            routed_scaling=float(arch.pop("routed_scaling_factor")),
            router_rule="sigmoid_topk", expert_act="silu",
            expert_gated=True)
    except KeyError as e:
        raise ValueError(f"architecture of {name!r} lacks the key "
                         f"{e.args[0]!r}") from None
    _all_read(name, arch, "axk1", ep_rank, ep_size)
    if not 0 <= dense_blocks <= n_blocks:
        raise ValueError(
            f"architecture of {name!r}: first_k_dense_replace "
            f"{dense_blocks} outside 0..{n_blocks}")
    return cfg


# Keys of a laguna config.json that say nothing this engine acts on
# (the rotary fraction and the original context are read a layer type,
# from rope_parameters), and the values its layer equations assume.
# `scoring_func` is this engine's key, not a published one: the config
# names no scoring rule, and sigmoid is what a scale of 2.5 beside a
# shared expert goes with.
_LAGUNA_INERT = {"model_type", "max_position_embeddings",
                 "partial_rotary_factor"}
_LAGUNA_FIXED = {
    "attention_bias": False, "tie_word_embeddings": False,
    "moe_apply_router_weight_on_input": False, "norm_topk_prob": True,
    "moe_router_logit_softcapping": 0, "scoring_func": "sigmoid"}


def _laguna_rotary(name: str, kind: str, params: dict[str, Any],
                   head_dim: int) -> AttnLayer:
    """One layer type's entry of `rope_parameters` -> its rotary table
    (the heads and the window are filled in a layer)."""
    params = dict(params)
    rope_type = params.pop("rope_type", "default")
    table = {"rope_theta": float(params.pop("rope_theta")),
             "rotary_dim": int(head_dim * float(
                 params.pop("partial_rotary_factor", 1)))}
    if rope_type == "yarn":
        table["rope_yarn"] = tuple(float(params.pop(k)) for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow"))
        factor = params.pop("attention_factor", None)
        table["rope_attention_factor"] = \
            None if factor is None else float(factor)
    elif rope_type != "default":
        raise ValueError(f"architecture of {name!r}: rope_type "
                         f"{rope_type!r} of {kind} layers is not one "
                         "this engine runs (default, yarn)")
    if params:
        raise ValueError(f"architecture of {name!r}: unknown "
                         f"rope_parameters.{kind} keys {sorted(params)}")
    return AttnLayer(0, **table)


def _rotary_tables(name: str, rotary: dict[str, Any],
                   head_dim: int) -> tuple[AttnLayer, AttnLayer]:
    """`rope_parameters` -> the (full, sliding) layers' rotary tables."""
    rotary = dict(rotary)
    rotary.pop("original_max_position_embeddings", None)
    tables = [_laguna_rotary(name, kind, rotary.pop(kind), head_dim)
              for kind in ("full_attention", "sliding_attention")]
    if rotary:
        raise ValueError(f"architecture of {name!r}: unknown "
                         f"rope_parameters keys {sorted(rotary)}")
    return tables[0], tables[1]


def _laguna(name: str, arch: dict[str, Any],
            max_seq_len: int) -> ModelConfig:
    arch = _acted_on(name, arch, "laguna", _LAGUNA_FIXED, _LAGUNA_INERT)
    try:
        n_blocks = int(arch.pop("num_hidden_layers"))
        head_dim = int(arch.pop("head_dim"))
        full, sliding = _rotary_tables(
            name, arch.pop("rope_parameters"), head_dim)
        lists = [list(arch.pop(k)) for k in (
            "layer_types", "mlp_layer_types",
            "num_attention_heads_per_layer")]
        if any(len(x) != n_blocks for x in lists):
            raise ValueError(
                f"architecture of {name!r}: layer_types, mlp_layer_types "
                "and num_attention_heads_per_layer have "
                f"{[len(x) for x in lists]} entries, num_hidden_layers "
                f"says {n_blocks}")
        kinds, geometry = laguna_layers(
            *lists, window=int(arch.pop("sliding_window")), full=full,
            sliding=sliding)
        gating = arch.pop("gating")
        if gating is not True and gating != "per-head":
            raise ValueError(
                f"architecture of {name!r}: gating={gating!r}, and this "
                "engine's laguna layers are written for true / "
                "'per-head' (one logit a head)")
        held = int(arch.pop("num_experts"))
        cfg = ModelConfig(
            name=name, vocab_size=int(arch.pop("vocab_size")),
            num_layers=2 * n_blocks,
            embed_dim=int(arch.pop("hidden_size")),
            num_heads=int(arch.pop("num_attention_heads")),
            num_kv_heads=int(arch.pop("num_key_value_heads")),
            head_dim=head_dim, mlp_dim=int(arch.pop("intermediate_size")),
            max_seq_len=max_seq_len,
            norm_eps=float(arch.pop("rms_norm_eps")),
            tie_embeddings=False, layer_kinds=kinds,
            attn_layers=geometry, attn_gate=True,
            routed_experts=held, experts_held=held,
            moe_top_k=int(arch.pop("num_experts_per_tok")),
            expert_dim=int(arch.pop("moe_intermediate_size")),
            shared_expert_dim=int(
                arch.pop("shared_expert_intermediate_size")),
            routed_scaling=float(arch.pop("moe_routed_scaling_factor")),
            router_rule="sigmoid_topk", expert_act="silu",
            expert_gated=True)
    except KeyError as e:
        raise ValueError(f"architecture of {name!r} lacks the key "
                         f"{e.args[0]!r}") from None
    _all_read(name, arch, "laguna")
    return cfg


# Keys of a mellum config.json that say nothing this engine acts on
# (`intermediate_size` is the width of a dense MLP and no layer has one;
# `max_window_layers: 0` beside `layer_types` names no layer), and the
# values its layer equations assume. The config names no scoring
# function: `norm_topk_prob: true` beside no scaling factor is softmax,
# top-k, renormalise (`softmax_topk`), and a key that names another
# rule is unknown here and fails as every unknown key does.
_MELLUM_INERT = {"model_type", "max_position_embeddings",
                 "max_window_layers"}
_MELLUM_FIXED = {
    "attention_bias": False, "tie_word_embeddings": False,
    "hidden_act": "silu", "norm_topk_prob": True,
    "use_sliding_window": True}


def _mellum(name: str, arch: dict[str, Any],
            max_seq_len: int) -> ModelConfig:
    arch = _acted_on(name, arch, "mellum", _MELLUM_FIXED, _MELLUM_INERT)
    try:
        n_blocks = int(arch.pop("num_hidden_layers"))
        head_dim = int(arch.pop("head_dim"))
        heads = int(arch.pop("num_attention_heads"))
        full, sliding = _rotary_tables(
            name, arch.pop("rope_parameters"), head_dim)
        lists = [list(arch.pop(k)) for k in ("layer_types",
                                             "mlp_layer_types")]
        if any(len(x) != n_blocks for x in lists):
            raise ValueError(
                f"architecture of {name!r}: layer_types and "
                f"mlp_layer_types have {[len(x) for x in lists]} entries, "
                f"num_hidden_layers says {n_blocks}")
        if set(lists[1]) != {"sparse"}:
            raise ValueError(
                f"architecture of {name!r}: a dense layer among "
                "mlp_layer_types, and this engine's mellum layers are "
                "written for every layer sparse")
        held = int(arch.pop("num_experts"))
        cfg = _mellum_config(
            name, lists[0], heads=heads,
            window=int(arch.pop("sliding_window")), full=full,
            sliding=sliding, vocab_size=int(arch.pop("vocab_size")),
            embed_dim=int(arch.pop("hidden_size")),
            num_kv_heads=int(arch.pop("num_key_value_heads")),
            head_dim=head_dim, mlp_dim=int(arch.pop("intermediate_size")),
            max_seq_len=max_seq_len,
            norm_eps=float(arch.pop("rms_norm_eps")),
            routed_experts=held, experts_held=held,
            moe_top_k=int(arch.pop("num_experts_per_tok")),
            expert_dim=int(arch.pop("moe_intermediate_size")))
    except KeyError as e:
        raise ValueError(f"architecture of {name!r} lacks the key "
                         f"{e.args[0]!r}") from None
    _all_read(name, arch, "mellum")
    return cfg


# Keys of a brumby config.json that say nothing this engine acts on
# (Qwen3's window keys, kept by the config: `use_sliding_window: false`
# names no layer), and the values its layer equations assume.
_BRUMBY_INERT = {"model_type", "max_position_embeddings",
                 "max_window_layers"}
_BRUMBY_FIXED = {
    "attention_bias": False, "tie_word_embeddings": False,
    "hidden_act": "silu", "use_sliding_window": False,
    "sliding_window": None, "rope_scaling": None}


def _brumby(name: str, arch: dict[str, Any],
            max_seq_len: int) -> ModelConfig:
    arch = _acted_on(name, arch, "brumby", _BRUMBY_FIXED, _BRUMBY_INERT)
    try:
        cfg = _brumby_config(
            name, blocks=int(arch.pop("num_hidden_layers")),
            vocab_size=int(arch.pop("vocab_size")),
            embed_dim=int(arch.pop("hidden_size")),
            num_heads=int(arch.pop("num_attention_heads")),
            num_kv_heads=int(arch.pop("num_key_value_heads")),
            head_dim=int(arch.pop("head_dim")),
            mlp_dim=int(arch.pop("intermediate_size")),
            max_seq_len=max_seq_len,
            rope_theta=float(arch.pop("rope_theta")),
            norm_eps=float(arch.pop("rms_norm_eps")))
    except KeyError as e:
        raise ValueError(f"architecture of {name!r} lacks the key "
                         f"{e.args[0]!r}") from None
    _all_read(name, arch, "brumby")
    if cfg.head_dim % 2 or cfg.num_heads % cfg.num_kv_heads:
        raise ValueError(
            f"architecture of {name!r}: power retention needs an even "
            "head_dim and whole groups of query heads a kv head")
    return cfg


# Keys of a jamba config.json that say nothing this engine acts on (with
# num_experts 1 the expert_layer_* keys select nothing and the router's
# are unused), and the values its layer equations assume. `rope`,
# `rope_theta` and `head_dim` are not the model's keys: a configuration
# file may state them beside the published ones (`rope: true` is the
# other reading of "no rotary key", one key).
_JAMBA_INERT = {
    "model_type", "max_position_embeddings", "num_logits_to_keep",
    "use_mamba_kernels", "expert_layer_offset", "expert_layer_period",
    "num_experts_per_tok", "rope_theta"}
_JAMBA_FIXED = {
    "hidden_act": "silu", "mamba_conv_bias": True, "mamba_proj_bias": False,
    "sliding_window": None, "tie_word_embeddings": True, "num_experts": 1,
    "rope": False}


def _jamba(name: str, arch: dict[str, Any],
           max_seq_len: int) -> ModelConfig:
    arch = _acted_on(name, arch, "jamba", _JAMBA_FIXED, _JAMBA_INERT)
    try:
        e, heads = int(arch.pop("hidden_size")), \
            int(arch.pop("num_attention_heads"))
        cfg = _jamba_config(
            name, blocks=int(arch.pop("num_hidden_layers")),
            period=int(arch.pop("attn_layer_period")),
            offset=int(arch.pop("attn_layer_offset")),
            vocab_size=int(arch.pop("vocab_size")), embed_dim=e,
            num_heads=heads,
            num_kv_heads=int(arch.pop("num_key_value_heads")),
            head_dim=int(arch.pop("head_dim", e // heads)),
            mlp_dim=int(arch.pop("intermediate_size")),
            max_seq_len=max_seq_len,
            norm_eps=float(arch.pop("rms_norm_eps")),
            mamba1_dim=int(arch.pop("mamba_expand")) * e,
            ssm_state=int(arch.pop("mamba_d_state")),
            conv_kernel=int(arch.pop("mamba_d_conv")),
            dt_rank=int(arch.pop("mamba_dt_rank")))
    except KeyError as e:
        raise ValueError(f"architecture of {name!r} lacks the key "
                         f"{e.args[0]!r}") from None
    _all_read(name, arch, "jamba")
    if cfg.num_heads % cfg.num_kv_heads:
        raise ValueError(
            f"architecture of {name!r}: {cfg.num_heads} query heads are "
            f"not whole groups over {cfg.num_kv_heads} kv heads")
    return cfg


# Keys of an lfm2_moe config.json that say nothing this engine acts on,
# and the values its layer equations assume. `head_dim` and
# `tie_word_embeddings` are not the model's keys: a configuration file
# may state them beside the published ones.
_LFM2_INERT = {"model_type", "max_position_embeddings"}
_LFM2_FIXED = {
    "conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True,
    "tie_word_embeddings": True}


def _lfm2_moe(name: str, arch: dict[str, Any],
              max_seq_len: int) -> ModelConfig:
    arch = _acted_on(name, arch, "lfm2_moe", _LFM2_FIXED, _LFM2_INERT)
    try:
        e, heads = int(arch.pop("hidden_size")), \
            int(arch.pop("num_attention_heads"))
        types = list(arch.pop("layer_types"))
        n_blocks = int(arch.pop("num_hidden_layers"))
        if len(types) != n_blocks:
            raise ValueError(
                f"architecture of {name!r}: layer_types has {len(types)} "
                f"entries, num_hidden_layers says {n_blocks}")
        rotary = dict(arch.pop("rope_parameters"))
        if rotary.pop("rope_type", "default") != "default":
            raise ValueError(
                f"architecture of {name!r}: this engine's lfm2_moe layers "
                "are written for plain rotary (rope_type default)")
        theta = float(rotary.pop("rope_theta"))
        if rotary:
            raise ValueError(f"architecture of {name!r}: unknown keys "
                             f"{sorted(rotary)} in rope_parameters")
        cfg = _lfm2_config(
            name, types, dense_layers=int(arch.pop("num_dense_layers")),
            experts=int(arch.pop("num_experts")),
            vocab_size=int(arch.pop("vocab_size")), embed_dim=e,
            num_heads=heads,
            num_kv_heads=int(arch.pop("num_key_value_heads")),
            head_dim=int(arch.pop("head_dim", e // heads)),
            mlp_dim=int(arch.pop("intermediate_size")),
            max_seq_len=max_seq_len, rope_theta=theta,
            norm_eps=float(arch.pop("norm_eps")),
            conv_kernel=int(arch.pop("conv_L_cache")),
            moe_top_k=int(arch.pop("num_experts_per_tok")),
            expert_dim=int(arch.pop("moe_intermediate_size")),
            routed_scaling=float(arch.pop("routed_scaling_factor")))
    except KeyError as e:
        raise ValueError(f"architecture of {name!r} lacks the key "
                         f"{e.args[0]!r}") from None
    _all_read(name, arch, "lfm2_moe")
    if cfg.num_heads % cfg.num_kv_heads or cfg.head_dim % 2:
        raise ValueError(
            f"architecture of {name!r}: {cfg.num_heads} query heads over "
            f"{cfg.num_kv_heads} kv heads of {cfg.head_dim} are not whole "
            "groups of even heads")
    return cfg


# Keys of a phi4flash config.json that say nothing this engine acts on,
# and the values its layer equations assume. The Mamba sizes, the two
# bias switches and `head_dim` are not in the published file: they are
# the family's defaults, which a configuration file may state beside the
# published keys (each flips by its one key).
_PHI4FLASH_INERT = {"model_type", "max_position_embeddings", "embd_pdrop",
                    "resid_pdrop"}
_PHI4FLASH_FIXED = {
    "hidden_act": "silu", "mb_per_layer": 2, "tie_word_embeddings": True,
    "mlp_bias": False, "lm_head_bias": False, "attention_bias": True,
    "mamba_conv_bias": True, "mamba_proj_bias": False}


def _phi4flash(name: str, arch: dict[str, Any],
               max_seq_len: int) -> ModelConfig:
    arch = _acted_on(name, arch, "phi4flash", _PHI4FLASH_FIXED,
                     _PHI4FLASH_INERT)
    try:
        e, heads = int(arch.pop("hidden_size")), \
            int(arch.pop("num_attention_heads"))
        expand = int(arch.pop("mamba_expand", 2))
        cfg = _phi4flash_config(
            name, blocks=int(arch.pop("num_hidden_layers")), heads=heads,
            window=int(arch.pop("sliding_window")),
            vocab_size=int(arch.pop("vocab_size")), embed_dim=e,
            num_kv_heads=int(arch.pop("num_key_value_heads")),
            head_dim=int(arch.pop("head_dim", e // heads)),
            mlp_dim=int(arch.pop("intermediate_size")),
            max_seq_len=max_seq_len,
            norm_eps=float(arch.pop("layer_norm_eps")),
            mamba1_dim=expand * e,
            ssm_state=int(arch.pop("mamba_d_state", 16)),
            conv_kernel=int(arch.pop("mamba_d_conv", 4)),
            dt_rank=int(arch.pop("mamba_dt_rank", -(-e // 16))))
    except KeyError as e:
        raise ValueError(f"architecture of {name!r} lacks the key "
                         f"{e.args[0]!r}") from None
    _all_read(name, arch, "phi4flash")
    if cfg.num_kv_heads % 2 or cfg.num_heads % cfg.num_kv_heads:
        raise ValueError(
            f"architecture of {name!r}: {cfg.num_heads} query heads over "
            f"{cfg.num_kv_heads} kv heads are not whole groups of query "
            "head pairs over kv head pairs")
    if cfg.lane_pack != 2:
        raise ValueError(
            f"architecture of {name!r}: a kv pair of {cfg.head_dim}-wide "
            "heads does not fill a 128-lane row (models/diffattn.py)")
    return cfg


def _dense_gqa(name: str, arch: dict[str, Any],
               max_seq_len: int) -> ModelConfig:
    heads = int(arch["num_attention_heads"])
    return ModelConfig(
        name=name, vocab_size=int(arch["vocab_size"]),
        num_layers=int(arch["num_hidden_layers"]),
        embed_dim=int(arch["hidden_size"]), num_heads=heads,
        num_kv_heads=int(arch.get("num_key_value_heads", heads)),
        head_dim=int(arch.get("head_dim")
                     or int(arch["hidden_size"]) // heads),
        mlp_dim=int(arch["intermediate_size"]), max_seq_len=max_seq_len,
        rope_theta=float(arch.get("rope_theta", 10_000.0)),
        norm_eps=float(arch.get("rms_norm_eps", 1e-6)),
        sliding_window=arch.get("sliding_window"),
        attn_bias=bool(arch.get("attention_bias",
                                arch["model_type"] == "qwen2")),
        tie_embeddings=bool(arch.get("tie_word_embeddings", False)))


def resolve_model_config(config: dict[str, Any]) -> ModelConfig:
    """The ModelConfig an adapter config asks for: built from its
    `architecture` block (a published config.json's keys) under the
    name in `model` when there is one, else the preset of that name."""
    name = config.get("model", "tiny-gemma")
    arch = config.get("architecture")
    if arch is None:
        return get_model_config(name)
    if not isinstance(arch, dict):
        raise ValueError("architecture: expected the keys of a published "
                         f"config.json, got {type(arch).__name__}")
    kind = arch.get("model_type")
    max_seq_len = int(config.get("max_seq_len") or min(
        int(arch.get("max_position_embeddings", 8192)), 8192))
    if kind == "nemotron_h":
        return _nemotron_h(name, arch, max_seq_len)
    if kind == "axk1":
        return _axk1(name, arch, max_seq_len)
    if kind == "laguna":
        return _laguna(name, arch, max_seq_len)
    if kind == "mellum":
        return _mellum(name, arch, max_seq_len)
    if kind == "brumby":
        return _brumby(name, arch, max_seq_len)
    if kind == "jamba":
        return _jamba(name, arch, max_seq_len)
    if kind == "lfm2_moe":
        return _lfm2_moe(name, arch, max_seq_len)
    if kind == "phi4flash":
        return _phi4flash(name, arch, max_seq_len)
    if kind in _DENSE_TYPES:
        try:
            return _dense_gqa(name, arch, max_seq_len)
        except KeyError as e:
            raise ValueError(f"architecture of {name!r} lacks the key "
                             f"{e.args[0]!r}") from None
    raise ValueError(
        f"architecture of {name!r}: model_type {kind!r} is not one this "
        f"engine runs (nemotron_h, axk1, laguna, mellum, brumby, jamba, "
        f"lfm2_moe, phi4flash, {', '.join(_DENSE_TYPES)})")


def get_model_config(name: str, **overrides) -> ModelConfig:
    """Look up a family by name; unknown names raise with the known list."""
    if name not in _REGISTRY:
        known = ", ".join(sorted(_REGISTRY))
        raise ValueError(f"Unknown model '{name}'. Known: {known}")
    cfg = _REGISTRY[name]
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def list_models() -> list[str]:
    return sorted(_REGISTRY)
