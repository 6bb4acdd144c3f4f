"""The routed experts as one grouped product a projection
(models/hybrid.py: routed_experts; pallas/grouped.py): the rows sorted by
expert
against an expert-at-a-time float32 reference, and `counts` against what
the masked loop over every held expert returned on the same inputs (the
loop went with PR 36; its values were recorded here before it did)."""

import dataclasses
import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from theroundtaible_tpu.engine.models import hybrid  # noqa: E402
from theroundtaible_tpu.engine.pallas import grouped  # noqa: E402
from theroundtaible_tpu.engine.models.common import init_params  # noqa: E402
from theroundtaible_tpu.engine.models.registry import (  # noqa: E402
    get_model_config)

TINY = ("tiny-nemotron-h", "tiny-axk1", "tiny-laguna")
BOUND = 1e-5


def _pull(router, experts, sign):
    """Column 0 of every input is 4.0 (see `_inputs`), so a router row 0
    of +-50 puts an expert's score at 1 or 0 for every token."""
    return router.at[0, jnp.asarray(experts)].set(sign * 50.0)


def _inputs(cfg, shape, seed):
    h = jax.random.normal(jax.random.PRNGKey(seed),
                          (*shape, cfg.embed_dim), jnp.float32)
    return h.at[..., 0].set(4.0)


def case_plain(cfg, layer):
    return cfg, layer, _inputs(cfg, (40,), 1), None


def case_an_expert_nobody_chose(cfg, layer):
    layer = dict(layer, router=_pull(layer["router"], [3], -1.0))
    return cfg, layer, _inputs(cfg, (40,), 2), None


def case_every_token_on_one_expert(cfg, layer):
    """Experts 2 and 5 always chosen (top-2); the chip holds 0-3: every
    row lands on expert 2 and the other assignment is another chip's."""
    share = dataclasses.replace(cfg, experts_held=4, expert_offset=0)
    layer = dict(layer, router=_pull(layer["router"], [2, 5], 1.0),
                 experts={k: v[:4] for k, v in layer["experts"].items()})
    return share, layer, _inputs(cfg, (40,), 3), None


def case_assignments_on_both_sides_of_the_held_range(cfg, layer):
    share = dataclasses.replace(cfg, experts_held=3, expert_offset=2)
    layer = dict(layer,
                 experts={k: v[2:5] for k, v in layer["experts"].items()})
    return share, layer, _inputs(cfg, (40,), 4), None


def case_rows_that_fill_no_whole_tile(cfg, layer):
    return cfg, layer, _inputs(cfg, (131,), 5), None


def case_a_token_mask_with_pads(cfg, layer):
    mask = jnp.arange(24)[None, :] < jnp.asarray([24, 9])[:, None]
    return cfg, layer, _inputs(cfg, (2, 24), 6), mask


CASES = {f.__name__[5:]: f for f in (
    case_plain, case_an_expert_nobody_chose,
    case_every_token_on_one_expert,
    case_assignments_on_both_sides_of_the_held_range,
    case_rows_that_fill_no_whole_tile, case_a_token_mask_with_pads)}

# (experts hit, assignments of counted tokens to held experts) as the
# masked loop (`lax.scan` over the held experts, a `lax.cond` each)
# returned them at commit 0f58e88, before it was removed.
LOOP_COUNTS = {
    ("tiny-nemotron-h", "plain"): (8, 80),
    ("tiny-nemotron-h", "an_expert_nobody_chose"): (7, 80),
    ("tiny-nemotron-h", "every_token_on_one_expert"): (1, 37),
    ("tiny-nemotron-h", "assignments_on_both_sides_of_the_held_range"):
        (3, 25),
    ("tiny-nemotron-h", "rows_that_fill_no_whole_tile"): (8, 262),
    ("tiny-nemotron-h", "a_token_mask_with_pads"): (7, 66),
    ("tiny-axk1", "plain"): (8, 80),
    ("tiny-axk1", "an_expert_nobody_chose"): (7, 80),
    ("tiny-axk1", "every_token_on_one_expert"): (1, 40),
    ("tiny-axk1", "assignments_on_both_sides_of_the_held_range"): (3, 29),
    ("tiny-axk1", "rows_that_fill_no_whole_tile"): (8, 262),
    ("tiny-axk1", "a_token_mask_with_pads"): (8, 66),
    ("tiny-laguna", "plain"): (8, 80),
    ("tiny-laguna", "an_expert_nobody_chose"): (7, 80),
    ("tiny-laguna", "every_token_on_one_expert"): (1, 40),
    ("tiny-laguna", "assignments_on_both_sides_of_the_held_range"): (3, 20),
    ("tiny-laguna", "rows_that_fill_no_whole_tile"): (8, 262),
    ("tiny-laguna", "a_token_mask_with_pads"): (8, 66),
}


def reference(cfg, layer, h):
    """An expert at a time, float32, in numpy: every assignment to a
    held expert computed, none to another chip's; plus the shared
    expert."""
    x = np.asarray(h, np.float64).reshape(-1, h.shape[-1])
    with jax.default_matmul_precision("highest"):
        ids, w = hybrid.route(jnp.asarray(x, jnp.float32), layer, cfg)
    ids, w = np.asarray(ids), np.asarray(w, np.float64)

    def act(v):
        if cfg.expert_act == "relu2":
            return np.square(np.maximum(v, 0.0))
        return v / (1.0 + np.exp(-v))

    def expert(p, rows):
        a = act(rows @ np.asarray(p["gate" if cfg.expert_gated else "up"],
                                  np.float64))
        if cfg.expert_gated:
            a = a * (rows @ np.asarray(p["up"], np.float64))
        return a @ np.asarray(p["down"], np.float64)

    out = expert(layer["shared"], x)
    for e in range(cfg.experts_held):
        one = {k: v[e] for k, v in layer["experts"].items()}
        for j in range(cfg.moe_top_k):
            rows = ids[:, j] == e + cfg.expert_offset
            out[rows] += expert(one, x[rows]) * w[rows, j:j + 1]
    return out.reshape(h.shape)


@pytest.fixture(scope="module", params=TINY)
def tiny(request):
    cfg = get_model_config(request.param)
    params = init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    return cfg, params["layers"][cfg.expert_layers[-1]]


@pytest.mark.parametrize("case", list(CASES))
def test_the_grouped_product_against_an_expert_at_a_time(tiny, case):
    cfg, layer, h, mask = CASES[case](*tiny)
    with jax.default_matmul_precision("highest"):
        out, counts = hybrid.experts_mlp(h, layer, cfg, mask)
    assert out.shape == h.shape
    assert np.abs(np.asarray(out, np.float64)
                  - reference(cfg, layer, h)).max() < BOUND
    assert tuple(int(c) for c in counts[:2]) \
        == LOOP_COUNTS[tiny[0].name, case]


# --- set-up: what a program lowers ------------------------------------------


@pytest.mark.parametrize("gated", [False, True])
def test_a_step_program_lowers_the_kernel_once_a_distinct_shape(
        monkeypatch, gated):
    """Set-up's guard, without a chip: the decode step of a model with
    THREE expert layers, lowered for the TPU with the kernel on. Every
    start lowers every program again (only the compile is cached), and a
    Pallas kernel is lowered to Mosaic once for every function that
    holds it: the module must hold ONE function for the routed experts
    and one kernel a distinct product shape (rows x in x out: gate and
    up agree, down differs) — not one a layer, not one a projection."""
    from theroundtaible_tpu.engine.paged_forward import forward_paged_hybrid

    monkeypatch.setattr(grouped, "_interpret", lambda: False)
    cfg = dataclasses.replace(
        get_model_config("tiny-nemotron-h"), num_layers=5,
        layer_kinds=hybrid.kinds_of_pattern("ME*EE"), embed_dim=128,
        expert_dim=256, shared_expert_dim=128, expert_gated=gated,
        expert_act="silu" if gated else "relu2")
    assert len(cfg.expert_layers) == 3
    assert grouped.decline_reason(128, 256, jnp.bfloat16) is None
    rows, pages, ps = 8, 4, 16
    params = jax.eval_shape(lambda k: init_params(cfg, k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: hybrid.zero_state(cfg, rows))
    pool = jax.ShapeDtypeStruct((32, ps, cfg.num_kv_heads, cfg.head_dim),
                                jnp.bfloat16)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)

    def step(params, pools, state, tokens, positions, table, valid,
             active):
        return forward_paged_hybrid(params, cfg, tokens, positions, pools,
                                    table, valid, state, active=active)

    text = jax.jit(step).trace(
        params, [(pool, pool)], state, i32((rows, 1)), i32((rows, 1)),
        i32((rows, pages)), i32((rows,)),
        jax.ShapeDtypeStruct((rows,), jnp.bool_)
    ).lower(lowering_platforms=("tpu",)).as_text()
    defined = [line.split("@")[1].split("(")[0]
               for line in text.splitlines() if "func.func" in line]
    assert len([f for f in defined if f.startswith("routed_experts")]) == 1
    # (gate and up: one function of `grouped_matmul`'s own jit, called
    # twice — ISSUE 55; before it the gated layer lowered three)
    assert text.count("tpu_custom_call") == 2
    # ... and the visits are computed once for all of a layer's products.
    assert text.count("kernel_name = \"grouped_matmul\"") == 2


@pytest.mark.parametrize("m,k,n,groups", [
    (32, 128, 256, 5),     # one row tile
    (256, 128, 192, 3),    # columns that fill no whole lane rows: the
                           # matrix is taken as the chip stores it
    (384, 256, 128, 12),   # several row tiles, most groups inside one
    (256, 4096, 128, 2)])  # a contraction in two blocks
def test_the_kernel_interpreted_against_ragged_dot(m, k, n, groups):
    """The Pallas form at the tiling rule's own tiles, interpreted (the
    only place it runs off the chip): an empty group first, a group that
    spans tiles, rows past the last group left undefined."""
    rng = np.random.RandomState(m)
    cuts = np.sort(rng.randint(0, m - 8, size=groups - 1))
    sizes = np.diff(np.concatenate([[0], cuts, [m - 8]])).astype(np.int32)
    sizes[1] += sizes[0]
    sizes[0] = 0
    rows = jnp.asarray(rng.randn(m, k), jnp.float32)
    weights = jnp.asarray(rng.randn(groups, k, n), jnp.float32) * k ** -.5
    assert grouped.padded_rows(m) == m
    with jax.default_matmul_precision("highest"):
        got = grouped.grouped_matmul(
            rows, weights, grouped.group_visits(jnp.asarray(sizes), m),
            interpret=True)
        want = jax.lax.ragged_dot(rows, weights, jnp.asarray(sizes))
    live = int(sizes.sum())
    assert np.abs(np.asarray(got[:live] - want[:live])).max() < BOUND


def test_the_visits_of_a_hand_made_case():
    """Five groups over three tiles of 128 rows: (group, tile) pairs in
    row order, an empty group never visited, a group that spans tiles
    visited once a tile; the grid ends at `visits`."""
    sizes = jnp.asarray([100, 0, 60, 200, 10], jnp.int32)   # 370 of 384
    offsets, gid, tile, visits = grouped.group_visits(sizes, 384)
    assert offsets.tolist() == [0, 100, 100, 160, 360, 370]
    n = int(visits[0])
    assert list(zip(gid.tolist()[:n], tile.tolist()[:n])) == [
        (0, 0), (2, 0), (2, 1), (3, 1), (3, 2), (4, 2)]
    assert gid.shape == tile.shape == (3 + 5 - 1,)
    assert max(tile.tolist()) <= 2 and max(gid.tolist()) <= 4
