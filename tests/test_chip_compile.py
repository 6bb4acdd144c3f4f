"""The chip's compiler, asked in the sandbox (ISSUE 22).

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED, not attached (`topologies.get_topology_desc("tpu",
"v5e:2x2")`): `jit(f).lower(shapes).compile()` raises whatever the v5e's
compiler would raise — a lane slice it cannot prove aligned, a shape
cast it has no layout for, a kernel over the fast-memory cap — so the
main path's kernels are checked at real widths before any chip time is
spent. That is a compile, never a run: nothing here says a result is
right or fast (tests/test_pallas_tpu_lowering.py stops one step
earlier, at Mosaic lowering; numeric parity lives in the interpret-mode
suites).

This is the ONLY file that describes a topology. Only one process may
load the TPU library, and it keeps it until exit — so the description
happens inside module-scoped fixtures that are neither autouse nor in
conftest: every xdist worker collects the same tests, and only the
worker that RUNS this file loads the library. Nothing here touches the
topology at import, `skipif`, `parametrize` or conftest time, and the
compiles run in the test's own process.

The persistent compilation cache is turned off around the compiles
(conftest turns it on): an entry written for a described chip cannot be
read back without one, and the next run would warn on every case.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from theroundtaible_tpu.engine.pallas import attention as pattn
from theroundtaible_tpu.engine.pallas import grouped
from theroundtaible_tpu.engine.pallas import int4mm

D, PAGE = 128, 128           # head_dim and page size of every case
POOL_PAGES = 256             # chip_smoke.py's pool
PAGES_PER_SEQ = 64           # max_seq_len 8192 / page 128
ROWS = 8                     # ragged sequences / whole-step batch rows
DECODE_ROWS = 16             # the benchmark's decode batch (16 slots)
RAGGED_T = 256               # flat token buffer
CHUNK = 256                  # prefill chunk

# (H, K) per case family: Llama-3.2-3B on one chip; one model-axis
# shard of Llama-3-8B (H=32, K=8) over four chips; and the benchmark's
# own widths — Mistral-7B and the attention layers of Nemotron-3-Nano —
# so the compiler is asked about the shapes the cells run (ISSUE 28).
HEADS = {"llama-3.2-3b": (24, 8), "llama-3-8b/4": (8, 2),
         "mistral-7b": (32, 8), "nemotron-3-nano": (32, 2)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The engine's (data=1, model=4) mesh over the described devices."""
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(f, *shapes) -> str:
    return jax.jit(f).lower(*shapes).compile().as_text()


def _assert_kernel(hlo: str) -> None:
    assert "tpu_custom_call" in hlo, "no Mosaic kernel in the program"


def _pool_shapes(kh: int, kv: str, sharding):
    """(pool, scale-or-None, kv_bits) shapes for a bf16 / int8 / int4
    page pool with `kh` kv heads (kv_quant.py's storage contract)."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    if kv == "bf16":
        return s((POOL_PAGES, PAGE, kh, D), jnp.bfloat16), None, 8
    from theroundtaible_tpu.engine.kv_quant import KVQuantSpec
    spec = KVQuantSpec(bits=8 if kv == "int8" else 4)
    return (s((POOL_PAGES, PAGE, kh, spec.packed_dim(D)), jnp.int8),
            s((POOL_PAGES, PAGE, kh, spec.num_groups(D)), jnp.float32),
            spec.bits)


def _attention_case(kernel: str, h: int, kh: int, kv: str, one_chip):
    """(fn, shapes) for one single-device kernel at these widths; the
    scale pools, where the pages are quantized, ride last."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32 = jnp.int32
    pool, scale, bits = _pool_shapes(kh, kv, one_chip)
    scales = () if scale is None else (scale, scale)

    def on_pool(call, *shapes):
        def fn(*args):
            n = len(shapes)
            kw = dict(zip(("k_scale", "v_scale"), args[n:]))
            return call(*args[:n], interpret=False, kv_bits=bits, **kw)
        return fn, shapes + scales

    if kernel == "paged_decode":
        return on_pool(
            pattn.paged_decode_attention,
            s((DECODE_ROWS, 1, h, D), jnp.bfloat16), pool, pool,
            s((DECODE_ROWS, PAGES_PER_SEQ), i32), s((DECODE_ROWS,), i32))
    if kernel == "ragged":
        blocks = RAGGED_T // pattn.RAGGED_BLOCK_Q
        return on_pool(
            pattn.ragged_paged_attention,
            s((RAGGED_T, h, D), jnp.bfloat16), pool, pool,
            s((ROWS, PAGES_PER_SEQ), i32), s((blocks,), i32),
            s((blocks,), i32), s((ROWS,), i32), s((ROWS,), i32))
    if kernel == "paged_prefill":
        return on_pool(
            pattn.paged_prefill_attention,
            s((1, CHUNK, h, D), jnp.bfloat16), pool, pool,
            s((1, PAGES_PER_SEQ), i32), s((1,), i32), s((1,), i32))
    assert kernel == "flash_prefill" and kv == "bf16"
    cache = s((1, 2048, kh, D), jnp.bfloat16)
    return (functools.partial(pattn.flash_prefill_attention,
                              interpret=False),
            (s((1, CHUNK, h, D), jnp.bfloat16), cache, cache,
             s((1,), i32), s((1,), i32)))


ATTENTION_CASES = [
    ("paged_decode", "bf16"), ("paged_decode", "int8"),
    ("ragged", "bf16"), ("ragged", "int8"),
    ("paged_prefill", "bf16"), ("flash_prefill", "bf16"),
]


@pytest.mark.parametrize("widths", list(HEADS))
@pytest.mark.parametrize("kernel,kv", ATTENTION_CASES)
def test_attention_kernel_compiles_for_v5e(one_chip, kernel, kv, widths):
    h, kh = HEADS[widths]
    fn, shapes = _attention_case(kernel, h, kh, kv, one_chip)
    hlo = _compile(fn, *shapes)
    _assert_kernel(hlo)
    # The kernel's `name=` is the instruction's name, which is what the
    # profiler's operations line shows (ISSUE 25).
    name = {"ragged": "ragged_paged"}.get(kernel, kernel) + "_attention"
    assert f"%{name}" in hlo


def test_paged_decode_gate_declines_what_the_compiler_refuses(
        one_chip, monkeypatch):
    """`paged_decode_supported` against the compiler itself. A bf16
    page of Mistral-7B's pool is stored in VMEM at the array's own
    bytes (the walk lands it flattened, dense), so sixteen pages a trip
    are 16 MiB of copy buffers by either count — and with the product's
    own temporaries the kernel asks the compiler for more than its
    16 MiB scope. The gate's estimate, not the arrays' bytes, is what
    keeps a plan under it: it declines sixteen and plans two."""
    h, kh = HEADS["mistral-7b"]
    page = pattn._walk_page_bytes(PAGE, kh, D, 2, 0)
    assert page == 2 * PAGE * kh * D * 2            # stored == logical
    assert pattn._walk_vmem_est(16, PAGE, D, kh, h // kh, D, 2, 0) \
        > pattn._VMEM_BUDGET
    assert pattn._walk_pages(PAGE, D, kh, h // kh) == 2
    with monkeypatch.context() as m:
        # plan sixteen pages a trip past the estimate
        m.setattr(pattn, "_walk_vmem_est", lambda n, *a, **k: 0)
        m.setattr(pattn, "_WALK_TRIP_BYTES", 16 * page)
        fn, shapes = _attention_case("paged_decode", h, kh, "bf16",
                                     one_chip)
        with pytest.raises(Exception, match="exceeded scoped vmem"):
            _compile(fn, *shapes)
    fn, shapes = _attention_case("paged_decode", h, kh, "bf16", one_chip)
    _assert_kernel(_compile(fn, *shapes))


@pytest.mark.parametrize("h,kh,kv", [
    (32, 8, "bf16"), (32, 2, "bf16"), (32, 8, "int8"),     # token-major
    (8, 1, "bf16"), (24, 3, "bf16"), (8, 2, "int8")])      # head-major
def test_paged_decode_takes_the_pools_as_xla_stores_them(one_chip, h, kh,
                                                         kv):
    """The decode walk's operands are views of the pools as XLA lays
    them out — token-major where a token's heads fill whole tiles,
    head-major where not, the scale pools token-minor — so no operand
    of the call is a copy: a re-laid-out pool (the whole pool, on every
    call) is what a row-major operand of the other shapes costs, and a
    flattened view of one reads its padding."""
    import re
    fn, shapes = _attention_case("paged_decode", h, kh, kv, one_chip)
    hlo = _compile(fn, *shapes)
    _assert_kernel(hlo)
    pool = rf"\[{POOL_PAGES},[0-9,]+\]"
    copies = [line.strip()[:160] for line in hlo.splitlines()
              if re.search(rf"= \w+{pool}\S* copy(-start)?\(", line)]
    assert not copies, copies
    call = next(line for line in hlo.splitlines()
                if "tpu_custom_call" in line)
    rows = PAGE * kh
    want = (f"[{POOL_PAGES},{PAGE},{kh},{D}]"
            if pattn._token_major(kh, 2 if kv == "bf16" else 1)
            else f"[{POOL_PAGES},{rows},{D}]")
    assert want in call
    if kv == "int8":
        assert f"f32[{POOL_PAGES},{kh},{PAGE}]" in call


@pytest.mark.parametrize("kernel", ["paged_decode", "ragged"])
def test_spmd_kernel_compiles_for_four_chips(four_chips, kernel):
    """paged_decode_spmd / ragged_paged_spmd at Llama-3-8B widths on a
    4-device mesh of described chips, arguments placed as the engine
    places them: kv heads of the pool and q heads on "model", metadata
    replicated."""
    h, kh = 32, 8
    mesh = four_chips

    def s(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    pool = s((POOL_PAGES, PAGE, kh, D), jnp.bfloat16,
             P(None, None, "model", None))
    i32 = jnp.int32
    if kernel == "paged_decode":
        fn = functools.partial(pattn.paged_decode_spmd, mesh,
                               interpret=False)
        shapes = (s((ROWS, 1, h, D), jnp.bfloat16,
                    P(None, None, "model", None)), pool, pool,
                  s((ROWS, PAGES_PER_SEQ), i32), s((ROWS,), i32))
    else:
        blocks = RAGGED_T // pattn.RAGGED_BLOCK_Q
        fn = functools.partial(pattn.ragged_paged_spmd, mesh,
                               interpret=False)
        shapes = (s((RAGGED_T, h, D), jnp.bfloat16,
                    P(None, "model", None)), pool, pool,
                  s((ROWS, PAGES_PER_SEQ), i32), s((blocks,), i32),
                  s((blocks,), i32), s((ROWS,), i32), s((ROWS,), i32))
    _assert_kernel(_compile(fn, *shapes))


def test_whole_decode_step_of_the_3b_engine_compiles(one_chip,
                                                     monkeypatch):
    """One whole pool-direct decode step (paged_forward.forward_paged,
    the program the engine's decode dispatch wraps) of Llama-3.2-3B at
    published widths and depth, from jax.eval_shape shapes: 28 unrolled
    layers, each with its scatter and its paged-decode kernel, plus the
    128k-vocab head — and it fits one 16 GB chip beside its pool. The
    kernels ask jax.default_backend() whether to interpret, and that
    still says "cpu" here, so the test steers it; the program gets no
    new option."""
    from theroundtaible_tpu.engine.models.common import init_params
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.paged_forward import forward_paged

    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_model_config("llama-3.2-3b-instruct"),
                              attn_impl="flash")

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = placed(jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    pool = s((POOL_PAGES, PAGE, cfg.num_kv_heads, D), jnp.bfloat16)
    pools = [(pool, pool)] * cfg.num_layers
    i32 = jnp.int32

    def step(params, tokens, positions, pools, table, valid, last):
        return forward_paged(params, cfg, tokens, positions, pools,
                             table, valid, last_pos=last)

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        params, s((ROWS, 1), i32), s((ROWS, 1), i32), pools,
        s((ROWS, PAGES_PER_SEQ), i32), s((ROWS,), i32),
        s((ROWS,), i32)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= cfg.num_layers
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert resident < 16e9, f"decode step needs {resident / 1e9:.1f} GB"


@pytest.mark.parametrize("program", ["decode", "ragged"])
def test_hybrid_step_of_the_nemotron_cut_compiles(one_chip, monkeypatch,
                                                   program):
    """One decode step and one ragged join of the benchmark's
    Nemotron-3-Nano cut (the pattern's first three kinds, `ME*`, at
    published widths, 64 of 128 experts held), as the hybrid step
    programs wrap them: what the chip's compiler refuses of the scans,
    the grouped expert product or the kernels' operands fails here, not
    there."""
    from theroundtaible_tpu.engine.models import hybrid
    from theroundtaible_tpu.engine.models.common import init_params
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.paged_forward import (
        forward_paged_hybrid, forward_ragged_hybrid)
    from theroundtaible_tpu.engine.serving_loop import (RAGGED_BLOCK_Q,
                                                        RaggedSeq,
                                                        build_ragged_batch)

    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    monkeypatch.setattr(grouped, "_interpret", lambda: False)
    cfg = dataclasses.replace(
        get_model_config("nemotron-3-nano-30b-a3b"), num_layers=3,
        layer_kinds=hybrid.kinds_of_pattern("ME*"), vocab_size=65_536,
        experts_held=64, attn_impl="flash")

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32 = jnp.int32
    params = placed(jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    pool = s((POOL_PAGES, PAGE, cfg.num_kv_heads, D), jnp.bfloat16)
    pools = [(pool, pool)]
    if program == "decode":
        state = placed(jax.eval_shape(lambda: hybrid.zero_state(cfg, ROWS)))

        def step(params, pools, state, tokens, positions, table, valid,
                 active):
            return forward_paged_hybrid(
                params, cfg, tokens, positions, pools, table, valid,
                state, active=active)

        hlo = _compile(step, params, pools, state, s((ROWS, 1), i32),
                       s((ROWS, 1), i32), s((ROWS, PAGES_PER_SEQ), i32),
                       s((ROWS,), i32), s((ROWS,), jnp.bool_))
    else:
        state = placed(jax.eval_shape(
            lambda: hybrid.zero_state(cfg, ROWS + 1)))
        table = np.zeros((PAGES_PER_SEQ,), np.int32)
        b = build_ragged_batch(
            [RaggedSeq([5] * 150, 100, table), RaggedSeq([7], 300, table)],
            t_budget=RAGGED_T, s_max=ROWS + 1,
            pages_per_seq=PAGES_PER_SEQ, scratch_page=0, pad_id=0,
            page_size=PAGE)
        assert RAGGED_T % RAGGED_BLOCK_Q == 0
        names = ("tokens", "positions", "tables", "seq_of_block",
                 "block_qstart", "query_offsets", "kv_valid",
                 "token_pages", "token_offs", "token_seq", "last_rows")

        def step(params, pools, state, seq_slot, cap_n, *arrays):
            kw = dict(zip(names, arrays))
            return forward_ragged_hybrid(
                params, cfg, kw["tokens"], kw["positions"], pools,
                kw["tables"], kw["seq_of_block"], kw["block_qstart"],
                kw["query_offsets"], kw["kv_valid"], kw["token_pages"],
                kw["token_offs"], kw["token_seq"], kw["last_rows"], state,
                seq_slot, cap_n)

        hlo = _compile(step, params, pools, state, s((ROWS + 1,), i32),
                       s((ROWS + 1,), i32),
                       *[s(np.asarray(b[n]).shape, i32) for n in names])
    _assert_kernel(hlo)


# --- the routed experts' grouped product (ISSUE 36) -------------------------

# (hidden, expert width, held, top-k, gated, activation) of the three
# expert cells: Nemotron-3-Nano's share of a pair, A.X-K1's of sixteen,
# Laguna-XS.2 whole, Mellum2-12B whole.
EXPERT_WIDTHS = {
    "nemotron-3-nano-ep2": (2688, 1856, 64, 6, False, "relu2"),
    "a.x-k1-ep16": (7168, 2048, 12, 8, True, "silu"),
    "laguna-xs.2": (2048, 512, 256, 8, True, "silu"),
    "mellum2-12b": (2304, 896, 64, 8, True, "silu")}


@pytest.mark.parametrize("tokens", [16, 256, 1024])
@pytest.mark.parametrize("widths", list(EXPERT_WIDTHS))
def test_the_grouped_product_compiles_at_the_cells_shapes(
        one_chip, monkeypatch, widths, tokens):
    """Sort, the two or three kernels and the sum back at a decode
    step's rows (16 slots: 96 or 128 assignments), the 256-token bucket
    and join and the 1024-token ones (6144 or 8192): two kernels where
    the experts are not gated, three where they are — and NO copy of the
    held experts' matrices before them: nemotron_h's up matrices lie
    contraction-minor on the chip ([64, 2688, 1856] fills no whole lane
    rows), and a kernel that asks for them the other way has all 638 MB
    copied before every call (2.0 ms a layer: PERF.md, PR 36)."""
    from theroundtaible_tpu.engine.models import hybrid

    monkeypatch.setattr(grouped, "_interpret", lambda: False)
    e, f, held, k, gated, act = EXPERT_WIDTHS[widths]
    assert grouped.decline_reason(e, f, jnp.bfloat16) is None
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    experts = {"up": s((held, e, f), jnp.bfloat16),
               "down": s((held, f, e), jnp.bfloat16)}
    if gated:
        experts["gate"] = experts["up"]
    hlo = hybrid.routed_experts.lower(
        s((tokens, e), jnp.bfloat16), experts, s((tokens, k), jnp.int32),
        s((tokens, k), jnp.float32), held=held, act=act,
        gated=gated).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') \
        == 2 + gated
    assert not [line for line in hlo.splitlines()
                if " copy(" in line and f"bf16[{held}," in line]


# --- latent pages (ISSUE 31) ------------------------------------------------

LATENT_W, LATENT_V, LATENT_HEADS = 640, 512, 64     # A.X-K1: 576 -> 640


def _latent_case(kernel: str, one_chip):
    """(fn, shapes) for one latent kernel at the published shape: page
    128, the 576-value entry in five lane rows, one kv head, group 64,
    values the first 512 columns of the keys — ONE pool operand."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32 = jnp.int32
    pool = s((640, PAGE, LATENT_W), jnp.bfloat16)   # the cell's pool

    def on_pool(call, q, *meta):
        def fn(q, pool, *meta):
            return call(q, pool, None, *meta, v_dim=LATENT_V,
                        interpret=False)
        return fn, (q, pool) + meta

    if kernel == "paged_decode":
        return on_pool(
            pattn.paged_decode_attention,
            s((DECODE_ROWS, 1, LATENT_HEADS, LATENT_W), jnp.bfloat16),
            s((DECODE_ROWS, PAGES_PER_SEQ), i32), s((DECODE_ROWS,), i32))
    if kernel == "ragged":
        blocks = 1024 // pattn.RAGGED_BLOCK_Q       # the leaders' shape
        return on_pool(
            pattn.ragged_paged_attention,
            s((1024, LATENT_HEADS, LATENT_W), jnp.bfloat16),
            s((DECODE_ROWS + 1, PAGES_PER_SEQ), i32), s((blocks,), i32),
            s((blocks,), i32), s((DECODE_ROWS + 1,), i32),
            s((DECODE_ROWS + 1,), i32))
    assert kernel == "paged_prefill"
    return on_pool(
        pattn.paged_prefill_attention,
        s((1, 1024, LATENT_HEADS, LATENT_W), jnp.bfloat16),
        s((1, PAGES_PER_SEQ), i32), s((1,), i32), s((1,), i32))


@pytest.mark.parametrize("kernel", ["paged_decode", "ragged",
                                    "paged_prefill"])
def test_latent_kernel_compiles_for_v5e(one_chip, monkeypatch, kernel):
    """The gates say yes for the padded shape, no for the bare 576
    (`head_dim:576`), and the compiler agrees with the yes; the latent
    pool is the kernel's only pool operand."""
    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    assert pattn.paged_decode_decline_reason(
        PAGE, LATENT_W, 1, LATENT_HEADS, latent=True, dk=LATENT_W) is None
    assert pattn.paged_decode_decline_reason(
        PAGE, 576, 1, LATENT_HEADS, latent=True, dk=576) == "head_dim:576"
    assert pattn.ragged_decline_reason(PAGE, LATENT_W, 1,
                                       LATENT_HEADS) is None
    assert pattn.ragged_decline_reason(PAGE, 576, 1, LATENT_HEADS) \
        == "head_dim:576"
    assert pattn.paged_pool_direct_supported(1024, PAGE, LATENT_W, 1,
                                             LATENT_HEADS)
    fn, shapes = _latent_case(kernel, one_chip)
    hlo = _compile(fn, *shapes)
    _assert_kernel(hlo)
    call = next(line for line in hlo.splitlines()
                if "tpu_custom_call" in line)
    assert call.count("bf16[640,128,640]") == 1


# --- the ragged walk at the three cells' widths (ISSUE 32) -------------------

CELL_POOL = 640              # the cells' pool, in pages
WALK_WIDTHS = {"mistral-7b": (32, 8), "nemotron-3-nano": (32, 2),
               "a.x-k1": (LATENT_HEADS, 1)}
WALK_CASES = [(w, t, kv) for w in WALK_WIDTHS for t in (1024, 256)
              for kv in ("bf16", "int8")
              if not (w == "a.x-k1" and kv == "int8")]  # declines: S1b
# ... and 1 536, a plain decoder's top shape at 16 slots (ISSUE 57:
# serving_loop.ragged_token_budget) — Mistral's cell takes it; the
# latent kernel's case is what ROADMAP S13 (d') starts from
WALK_CASES += [("mistral-7b", 1536, "bf16"), ("a.x-k1", 1536, "bf16")]


def _walk_case(widths: str, t: int, kv: str, one_chip):
    """(fn, shapes) of the ragged kernel at one cell's widths and one
    of its two flat-buffer shapes, seventeen page tables (16 slots and
    the inert sequence)."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32 = jnp.int32
    h, kh = WALK_WIDTHS[widths]
    blocks, seqs = t // pattn.RAGGED_BLOCK_Q, DECODE_ROWS + 1
    meta = (s((seqs, PAGES_PER_SEQ), i32), s((blocks,), i32),
            s((blocks,), i32), s((seqs,), i32), s((seqs,), i32))
    if widths == "a.x-k1":
        def fn(q, pool, *meta):
            return pattn.ragged_paged_attention(
                q, pool, None, *meta, v_dim=LATENT_V, interpret=False)
        return fn, (s((t, h, LATENT_W), jnp.bfloat16),
                    s((CELL_POOL, PAGE, LATENT_W), jnp.bfloat16)) + meta
    pool, scale, bits = (
        a if a is None or isinstance(a, int) else
        s((CELL_POOL,) + a.shape[1:], a.dtype)
        for a in _pool_shapes(kh, kv, one_chip))
    scales = () if scale is None else (scale, scale)

    def fn(q, k, v, *rest):
        kw = dict(zip(("k_scale", "v_scale"), rest[5:]))
        return pattn.ragged_paged_attention(
            q, k, v, *rest[:5], interpret=False, kv_bits=bits, **kw)
    return fn, (s((t, h, D), jnp.bfloat16), pool, pool) + meta + scales


@pytest.mark.parametrize("widths,t,kv", WALK_CASES)
def test_ragged_kernel_compiles_at_the_cells_widths(one_chip, widths, t,
                                                    kv):
    """Two flat-buffer shapes of all three cells, bf16 and int8 pages,
    and 1 536 at the two attention-only widths.
    A bf16 pool is an operand of the call as the cell holds it — the
    benchmark's readers find the attention kernels by that operand —
    and is not copied on the way in."""
    import re
    fn, shapes = _walk_case(widths, t, kv, one_chip)
    hlo = _compile(fn, *shapes)
    _assert_kernel(hlo)
    call = next(line for line in hlo.splitlines()
                if "tpu_custom_call" in line)
    if kv == "int8":
        return
    h, kh = WALK_WIDTHS[widths]
    pool = (f"bf16[{CELL_POOL},{PAGE},{LATENT_W}]" if widths == "a.x-k1"
            else f"bf16[{CELL_POOL},{PAGE},{kh},{D}]")
    assert call.count(pool) == (1 if widths == "a.x-k1" else 2)
    assert f"%{'mla_ragged' if widths == 'a.x-k1' else 'ragged_paged_attention'}" \
        in hlo
    copies = [line.strip()[:160] for line in hlo.splitlines()
              if re.search(rf"= \w+\[{CELL_POOL},[0-9,]+\]\S* copy(-start)?\(",
                           line)]
    assert not copies, copies


def test_ragged_gate_plans_what_the_compiler_takes(one_chip, monkeypatch):
    """`_ragged_block_q` against the compiler itself. The estimate is
    the walk's own: the block's float32 state, its q and out blocks
    twice, three times a trip's four pages; the call asks the compiler
    for `_RAGGED_VMEM_LIMIT` and the estimate is held to two thirds of
    it. At 32 heads x 128 it plans the largest block, 128 rows; a
    latent block of 128 rows (64 heads x 640: q alone is 10 MiB, twice,
    the state 24) is over, the estimate plans 64 — and a plan of 128
    past the estimate is what the compiler refuses."""
    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    gqa = dict(dk=D, dv=D)
    lat = dict(dk=LATENT_W, dv=LATENT_V, latent=True)
    assert pattn._ragged_block_q(1024, PAGE, D, 8, 4, **gqa) == 128
    assert pattn._ragged_block_q(1024, PAGE, D, 2, 16, **gqa) == 128
    assert pattn._ragged_block_q(1024, PAGE, LATENT_W, 1, LATENT_HEADS,
                                 **lat) == 64
    assert pattn._ragged_trip_pages(PAGE) == 4
    est = pattn._ragged_vmem_est(128, PAGE, D, 8, 4, **gqa)
    rows = 32 * 128
    assert est == (rows * (2 * 128 + D) * 4 + 2 * rows * 2 * D * 2
                   + 3 * 4 * 2 * PAGE * 8 * D * 2)
    assert est <= pattn._RAGGED_VMEM_BUDGET < pattn._RAGGED_VMEM_LIMIT
    assert pattn._ragged_vmem_est(
        128, PAGE, LATENT_W, 1, LATENT_HEADS, **lat) \
        > pattn._RAGGED_VMEM_BUDGET
    assert pattn.ragged_decline_reason(
        PAGE, LATENT_W, 1, LATENT_HEADS, **lat) is None
    # what does not fit the packing's own 8 rows declines, by the same
    # estimate: 4096 q heads of 128
    assert pattn.ragged_decline_reason(PAGE, D, 8, 512).startswith(
        "vmem:")
    with monkeypatch.context() as m:
        m.setattr(pattn, "_ragged_vmem_est", lambda *a, **k: 0)
        pattn._ragged_walk.clear_cache()    # (a jit: it keeps its plans)
        fn, shapes = _walk_case("a.x-k1", 1024, "bf16", one_chip)
        with pytest.raises(Exception, match="exceeded scoped vmem"):
            _compile(fn, *shapes)
    pattn._ragged_walk.clear_cache()
    fn, shapes = _walk_case("a.x-k1", 1024, "bf16", one_chip)
    _assert_kernel(_compile(fn, *shapes))


@pytest.mark.parametrize("program", ["decode", "ragged", "prefill"])
def test_hybrid_step_of_the_axk1_cut_compiles(one_chip, monkeypatch,
                                              program):
    """One decode step, one ragged join and one prologue chunk of the
    benchmark's A.X-K1 cut (the dense block and one expert block at
    published widths, 12 of 192 experts held, an eighth of the
    vocabulary), as the hybrid step programs wrap them with an EMPTY
    state tree: what the chip's compiler refuses of the absorbed
    projections, the gated experts' grouped product or the latent kernels'
    operands
    fails here, not there."""
    from theroundtaible_tpu.engine.models import hybrid
    from theroundtaible_tpu.engine.models.common import init_params
    from theroundtaible_tpu.engine.models.registry import (axk1_kinds,
                                                           get_model_config)
    from theroundtaible_tpu.engine.paged_forward import (
        forward_paged_hybrid, forward_ragged_hybrid)
    from theroundtaible_tpu.engine.serving_loop import (RaggedSeq,
                                                        build_ragged_batch)

    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    monkeypatch.setattr(grouped, "_interpret", lambda: False)
    cfg = dataclasses.replace(
        get_model_config("a.x-k1"), num_layers=4,
        layer_kinds=axk1_kinds(2, 1), vocab_size=20_480, experts_held=12,
        attn_impl="flash")
    assert cfg.page_width == LATENT_W and not cfg.recurrent

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32 = jnp.int32
    params = placed(jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    pools = [(s((640, PAGE, LATENT_W), jnp.bfloat16),)] * 2
    state = hybrid.zero_state(cfg, ROWS)
    assert state == {"ssm": [], "conv": []}
    if program == "decode":
        def step(params, pools, tokens, positions, table, valid, active):
            return forward_paged_hybrid(
                params, cfg, tokens, positions, pools, table, valid,
                state, active=active)

        hlo = _compile(step, params, pools, s((DECODE_ROWS, 1), i32),
                       s((DECODE_ROWS, 1), i32),
                       s((DECODE_ROWS, PAGES_PER_SEQ), i32),
                       s((DECODE_ROWS,), i32),
                       s((DECODE_ROWS,), jnp.bool_))
    elif program == "prefill":
        def step(params, pools, tokens, positions, table, valid, lengths):
            return forward_paged_hybrid(
                params, cfg, tokens, positions, pools, table, valid,
                state, lengths=lengths, last_pos=lengths - 1)

        hlo = _compile(step, params, pools, s((1, 1024), i32),
                       s((1, 1024), i32), s((1, PAGES_PER_SEQ), i32),
                       s((1,), i32), s((1,), i32))
    else:
        table = np.zeros((PAGES_PER_SEQ,), np.int32)
        b = build_ragged_batch(
            [RaggedSeq([5] * 150, 100, table), RaggedSeq([7], 300, table)],
            t_budget=RAGGED_T, s_max=ROWS + 1,
            pages_per_seq=PAGES_PER_SEQ, scratch_page=0, pad_id=0,
            page_size=PAGE)
        names = ("tokens", "positions", "tables", "seq_of_block",
                 "block_qstart", "query_offsets", "kv_valid",
                 "token_pages", "token_offs", "token_seq", "last_rows")

        def step(params, pools, seq_slot, cap_n, *arrays):
            kw = dict(zip(names, arrays))
            return forward_ragged_hybrid(
                params, cfg, kw["tokens"], kw["positions"], pools,
                kw["tables"], kw["seq_of_block"], kw["block_qstart"],
                kw["query_offsets"], kw["kv_valid"], kw["token_pages"],
                kw["token_offs"], kw["token_seq"], kw["last_rows"], state,
                seq_slot, cap_n)

        hlo = _compile(step, params, pools, s((ROWS + 1,), i32),
                       s((ROWS + 1,), i32),
                       *[s(np.asarray(b[n]).shape, i32) for n in names])
    assert hlo.count("tpu_custom_call") >= 2        # a kernel a block


# --- window and full layers of one model, at Laguna-XS.2's widths (ISSUE 33) --
#
# 48 or 64 query heads over 8 kv heads of 128: group 6 has never been a
# served shape (tiles of 32 rows x 6 heads, the [K, G, T, D] blocks),
# and no served model has handed the kernels a window narrower than its
# context. Two (heads, window) pairs are two lowerings of each kernel.

LAGUNA_CLASSES = {"full": (48, None), "sliding": (64, 512)}


def _laguna_case(kernel: str, h: int, window, t: int, one_chip,
                 pool_pages: int = CELL_POOL, kh: int = 8):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32 = jnp.int32
    pool = s((pool_pages, PAGE, kh, D), jnp.bfloat16)
    kw = dict(sliding_window=window, interpret=False)
    if kernel == "paged_decode":
        return (functools.partial(pattn.paged_decode_attention, **kw),
                (s((DECODE_ROWS, 1, h, D), jnp.bfloat16), pool, pool,
                 s((DECODE_ROWS, PAGES_PER_SEQ), i32),
                 s((DECODE_ROWS,), i32)))
    if kernel == "paged_prefill":
        return (functools.partial(pattn.paged_prefill_attention, **kw),
                (s((1, t, h, D), jnp.bfloat16), pool, pool,
                 s((1, PAGES_PER_SEQ), i32), s((1,), i32), s((1,), i32)))
    blocks, seqs = t // pattn.RAGGED_BLOCK_Q, DECODE_ROWS + 1
    return (functools.partial(pattn.ragged_paged_attention, **kw),
            (s((t, h, D), jnp.bfloat16), pool, pool,
             s((seqs, PAGES_PER_SEQ), i32), s((blocks,), i32),
             s((blocks,), i32), s((seqs,), i32), s((seqs,), i32)))


@pytest.mark.parametrize("layers", list(LAGUNA_CLASSES))
@pytest.mark.parametrize("kernel,t", [
    ("paged_decode", 1), ("ragged", 1024), ("ragged", 256),
    ("paged_prefill", 1024), ("paged_prefill", 256)])
def test_laguna_kernel_compiles_for_v5e(one_chip, kernel, t, layers):
    """Each paged kernel at both layer classes of the new cell, on its
    pool `[640,128,8,128]` (Mistral's shape: the benchmark's readers
    find the calls by that operand, uncopied)."""
    import re
    h, window = LAGUNA_CLASSES[layers]
    assert pattn.paged_decode_decline_reason(PAGE, D, 8, h // 8) is None
    assert pattn.ragged_decline_reason(PAGE, D, 8, h // 8) is None
    fn, shapes = _laguna_case(kernel, h, window, t, one_chip)
    hlo = _compile(fn, *shapes)
    _assert_kernel(hlo)
    call = next(line for line in hlo.splitlines()
                if "tpu_custom_call" in line)
    assert call.count(f"bf16[{CELL_POOL},{PAGE},8,{D}]") == 2
    name = {"ragged": "ragged_paged"}.get(kernel, kernel) + "_attention"
    assert f"%{name}" in hlo
    copies = [line.strip()[:160] for line in hlo.splitlines()
              if re.search(rf"= \w+\[{CELL_POOL},[0-9,]+\]\S* copy(-start)?\(",
                           line)]
    assert not copies, copies


def _window_step_hlo(cfg, pool, program: str, one_chip, check,
                     join_at: int = 900, ragged_t: int = RAGGED_T) -> str:
    """The compiled text of one decode step, one ragged join (a 150-token
    run at `join_at` beside a decode row, in a buffer of `ragged_t`) or
    one 1024-token prologue chunk of `cfg` over `pool` a layer;
    `check(params)` sees the parameter shapes first."""
    from theroundtaible_tpu.engine.models import hybrid
    from theroundtaible_tpu.engine.models.common import init_params
    from theroundtaible_tpu.engine.paged_forward import (
        forward_paged_hybrid, forward_ragged_hybrid)
    from theroundtaible_tpu.engine.serving_loop import (RaggedSeq,
                                                        build_ragged_batch)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32 = jnp.int32
    params = placed(jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    check(params)
    pools = [(pool, pool)] * len(cfg.attention_layers)
    # (a part gathered by row rides in batch order: hybrid.ROW_PARTS)
    state = hybrid.zero_state(cfg, {"decode": DECODE_ROWS, "prefill": 1,
                                    "ragged": ROWS + 1}[program])
    if program == "decode":
        def step(params, pools, tokens, positions, table, valid, active):
            return forward_paged_hybrid(
                params, cfg, tokens, positions, pools, table, valid,
                state, active=active)

        hlo = _compile(step, params, pools, s((DECODE_ROWS, 1), i32),
                       s((DECODE_ROWS, 1), i32),
                       s((DECODE_ROWS, PAGES_PER_SEQ), i32),
                       s((DECODE_ROWS,), i32),
                       s((DECODE_ROWS,), jnp.bool_))
    elif program == "prefill":
        def step(params, pools, tokens, positions, table, valid, lengths):
            return forward_paged_hybrid(
                params, cfg, tokens, positions, pools, table, valid,
                state, lengths=lengths, last_pos=lengths - 1)

        hlo = _compile(step, params, pools, s((1, 1024), i32),
                       s((1, 1024), i32), s((1, PAGES_PER_SEQ), i32),
                       s((1,), i32), s((1,), i32))
    else:
        table = np.zeros((PAGES_PER_SEQ,), np.int32)
        b = build_ragged_batch(
            [RaggedSeq([5] * 150, join_at, table), RaggedSeq([7], 1300, table)],
            t_budget=ragged_t, s_max=ROWS + 1,
            pages_per_seq=PAGES_PER_SEQ, scratch_page=0, pad_id=0,
            page_size=PAGE)
        names = ("tokens", "positions", "tables", "seq_of_block",
                 "block_qstart", "query_offsets", "kv_valid",
                 "token_pages", "token_offs", "token_seq", "last_rows")

        def step(params, pools, seq_slot, cap_n, *arrays):
            kw = dict(zip(names, arrays))
            return forward_ragged_hybrid(
                params, cfg, kw["tokens"], kw["positions"], pools,
                kw["tables"], kw["seq_of_block"], kw["block_qstart"],
                kw["query_offsets"], kw["kv_valid"], kw["token_pages"],
                kw["token_offs"], kw["token_seq"], kw["last_rows"], state,
                seq_slot, cap_n)

        hlo = _compile(step, params, pools, s((ROWS + 1,), i32),
                       s((ROWS + 1,), i32),
                       *[s(np.asarray(b[n]).shape, i32) for n in names])
    return hlo


@pytest.mark.parametrize("program", ["decode", "ragged", "prefill"])
def test_hybrid_step_of_the_laguna_cut_compiles(one_chip, monkeypatch,
                                                program):
    """One decode step, one ragged join and one prologue chunk of the
    benchmark's Laguna-XS.2 cut at published widths, with all 256
    experts of a layer held (the dense block, one sliding and one full
    block with experts: both kernels' lowerings, the gate, both rotary
    tables, the masked loop over 256): what the chip's compiler refuses
    fails here, not there."""
    from theroundtaible_tpu.engine.models.registry import get_model_config

    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    monkeypatch.setattr(grouped, "_interpret", lambda: False)
    whole = get_model_config("laguna-xs.2")
    # blocks 0 (full, dense), 1 (sliding, experts), 4 (full, experts)
    cfg = dataclasses.replace(
        whole, num_layers=6,
        layer_kinds=whole.layer_kinds[:4] + whole.layer_kinds[8:10],
        attn_layers=whole.attn_layers[:2] + whole.attn_layers[4:5],
        attn_impl="flash")
    assert cfg.attention_classes == ((48, None, 2), (64, 512, 1))
    assert cfg.experts_held == cfg.routed_experts == 256

    def check(params):
        assert params["layers"][2]["q_proj"].shape == (2048, 64, D)
        assert params["layers"][4]["g_proj"].shape == (2048, 48)

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    hlo = _window_step_hlo(cfg, s((CELL_POOL, PAGE, 8, D), jnp.bfloat16),
                           program, one_chip, check)
    assert hlo.count("tpu_custom_call") >= 3        # a kernel a block


# --- window 1024 and none at 32 heads over 4 kv heads (ISSUE 40) ------------
#
# Mellum2-12B: every layer 32 query heads over FOUR kv heads of 128
# (group 8 over a pool `[1024,128,4,128]`: no served model has had four),
# three layers of four behind a 1024 window (8 pages and the one it
# starts in). harness/kernel_cost.py finds attention by the pool among a
# call's operands, so the operand is pinned as the cell holds it.

MELLUM_POOL, MELLUM_KV, MELLUM_HEADS = 1024, 4, 32
MELLUM_CLASSES = {"full": None, "sliding": 1024}


@pytest.mark.parametrize("layers", list(MELLUM_CLASSES))
@pytest.mark.parametrize("kernel,t", [
    ("paged_decode", 1), ("ragged", 1024), ("ragged", 256),
    ("paged_prefill", 1024), ("paged_prefill", 256)])
def test_mellum_kernel_compiles_for_v5e(one_chip, kernel, t, layers):
    """The decode walk, the ragged walk and the prefill kernel at both
    layer classes of the new cell, on its pool `[1024,128,4,128]` as
    the cell holds it: twice among the call's operands, uncopied (the
    benchmark's `kernel.attn_busy_share` finds the calls by it)."""
    import re
    window = MELLUM_CLASSES[layers]
    group = MELLUM_HEADS // MELLUM_KV
    assert pattn.paged_decode_decline_reason(
        PAGE, D, MELLUM_KV, group) is None
    assert pattn.ragged_decline_reason(PAGE, D, MELLUM_KV, group) is None
    fn, shapes = _laguna_case(kernel, MELLUM_HEADS, window, t, one_chip,
                              MELLUM_POOL, MELLUM_KV)
    hlo = _compile(fn, *shapes)
    _assert_kernel(hlo)
    call = next(line for line in hlo.splitlines()
                if "tpu_custom_call" in line)
    assert call.count(f"bf16[{MELLUM_POOL},{PAGE},{MELLUM_KV},{D}]") == 2
    name = {"ragged": "ragged_paged"}.get(kernel, kernel) + "_attention"
    assert f"%{name}" in hlo
    copies = [line.strip()[:160] for line in hlo.splitlines()
              if re.search(
                  rf"= \w+\[{MELLUM_POOL},{PAGE},[0-9,]+\]\S* "
                  r"copy(-start)?\(", line)]   # (q is [1024, 32, 128])
    assert not copies, copies


@pytest.mark.parametrize("program", ["decode", "ragged", "prefill"])
def test_hybrid_step_of_the_mellum_cut_compiles(one_chip, monkeypatch,
                                                program):
    """One decode step, one ragged join and one prologue chunk of the
    benchmark's Mellum2-12B cut at published widths (one sliding and
    one full block: both kernels' lowerings, both rotary tables, the
    softmax router, 64 held experts and NO shared expert)."""
    from theroundtaible_tpu.engine.models.registry import get_model_config

    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    monkeypatch.setattr(grouped, "_interpret", lambda: False)
    whole = get_model_config("mellum2-12b-a2.5b")
    # blocks 2 (sliding) and 3 (full)
    cfg = dataclasses.replace(
        whole, num_layers=4, layer_kinds=whole.layer_kinds[4:8],
        attn_layers=whole.attn_layers[2:4], attn_impl="flash")
    assert cfg.attention_classes == ((32, 1024, 1), (32, None, 1))
    assert cfg.experts_held == cfg.routed_experts == 64
    assert cfg.router_rule == "softmax_topk" and not cfg.shared_expert_dim

    def check(params):
        assert params["layers"][0]["k_proj"].shape == (2304, MELLUM_KV, D)
        assert set(params["layers"][1]) == {"norm", "router", "experts"}

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    hlo = _window_step_hlo(
        cfg, s((MELLUM_POOL, PAGE, MELLUM_KV, D), jnp.bfloat16), program,
        one_chip, check, join_at=5900)
    # attention a block, and the grouped products of its experts
    assert hlo.count("tpu_custom_call") >= 2
    assert "grouped_matmul" in hlo


@pytest.mark.parametrize("model,pool,compiles", [
    ("laguna-xs.2", (CELL_POOL, 8), True),
    ("mellum2-12b-a2.5b", (MELLUM_POOL, MELLUM_KV), False)])
def test_hybrid_join_at_the_plain_decoders_top_shape(one_chip, monkeypatch,
                                                     model, pool, compiles):
    """Why `serving_loop.ragged_token_budget` leaves an engine of the
    hybrid step programs its 1 024 (ISSUE 57): the ragged join of one
    expert block in a 1 536-token buffer compiles at Laguna's widths
    and is REFUSED at Mellum's — the gather of 1 536 x 8 assignments'
    rows, `bf16[12288,2304]`, asks 16.41 MB of a 16 MB scoped limit
    (the chip said the same: PERF.md, Findings PR 57). The day this
    case compiles, ROADMAP S13 (d') is open."""
    from theroundtaible_tpu.engine.models.registry import get_model_config

    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    monkeypatch.setattr(grouped, "_interpret", lambda: False)
    whole = get_model_config(model)
    at = next(i for i in range(0, len(whole.layer_kinds), 2)
              if whole.layer_kinds[i + 1] == "experts")  # a mixer, experts
    cfg = dataclasses.replace(
        whole, num_layers=2, layer_kinds=whole.layer_kinds[at:at + 2],
        attn_layers=whole.attn_layers[at // 2:at // 2 + 1],
        attn_impl="flash")
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pages, kv = pool

    def join():
        return _window_step_hlo(
            cfg, s((pages, PAGE, kv, D), jnp.bfloat16), "ragged", one_chip,
            lambda params: None, ragged_t=1536)

    if compiles:
        assert "grouped_matmul" in join()
    else:
        with pytest.raises(Exception, match="vmem.*12288,2304"):
            join()


# --- power retention: the step kernel and the chunked runs (ISSUE 42) --------

RETENTION_ROWS = 17          # 16 slots and the scratch row
RETENTION_SNAPS = 15         # 14 snapshots and the scratch one


def test_retention_step_kernel_compiles_for_v5e(one_chip, monkeypatch):
    """The decode step of one retention layer as the cell runs it: 16
    batch rows scattered over 17 state rows, 8 kv heads, group 5, the
    laid-out state (65 lane rows of 128 a head), donated. The compiled
    program keeps no second copy of the state: what it aliases is the
    two state arrays, and its temporaries are a rounding of them."""
    from theroundtaible_tpu.engine.models import retention as rmodel
    from theroundtaible_tpu.engine.pallas import retention as rkernel

    monkeypatch.setattr(rkernel, "_interpret", lambda: False)
    assert rkernel.decline_reason(D, 5) is None
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    f32, nd = jnp.float32, rmodel.feature_rows(D)
    assert (nd, rmodel.state_rows(D)) == (65, 8320)
    compiled = jax.jit(rkernel.retention_step, donate_argnums=(4, 5)).lower(
        s((DECODE_ROWS, 8, 5, D), f32), s((DECODE_ROWS, 8, D), f32),
        s((DECODE_ROWS, 8, D), f32), s((DECODE_ROWS, 8), f32),
        s((RETENTION_ROWS, 8, nd, D, D), f32),
        s((RETENTION_ROWS, 8, nd, D), f32),
        s((DECODE_ROWS,), jnp.int32)).compile()
    hlo = compiled.as_text()
    _assert_kernel(hlo)
    assert "retention_step" in hlo
    mem = compiled.memory_analysis()
    state = RETENTION_ROWS * 8 * nd * D * (D + 1) * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < state // 100


# The cells' pools as the page copier takes them (ISSUE 45): pages, a
# page's trailing shape, pools a layer.
COPIER_POOLS = {
    "mistral-7b": (640, (PAGE, 8, D), 2),
    "nemotron-3-nano": (640, (PAGE, 2, D), 2),
    "mellum2-12b": (1024, (PAGE, 4, D), 2),
    "a.x-k1": (640, (PAGE, 640), 1),
}


@pytest.mark.parametrize("width", [8, 32])
@pytest.mark.parametrize("cell", list(COPIER_POOLS))
def test_page_copier_compiles_and_copies_no_pool_whole(one_chip, cell,
                                                       width):
    """engine/pallas/page_copy.py takes the pools where they lie: no
    temporary, and no `copy` of a pool on the way in or out — what
    XLA's scatter holds at Mellum's `[1024, 128, 4, 128]`, a whole pool
    in and a whole pool out a call (PERF.md, PR 45)."""
    from theroundtaible_tpu.engine.pallas import page_copy
    pages, tail, per_layer = COPIER_POOLS[cell]
    pools = [tuple(jax.ShapeDtypeStruct((pages, *tail), jnp.bfloat16,
                                        sharding=one_chip)
                   for _ in range(per_layer)) for _ in range(2)]
    ids = jax.ShapeDtypeStruct((width,), jnp.int32, sharding=one_chip)
    compiled = page_copy.copy_pages.lower(pools, ids, ids).compile()
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    text = compiled.as_text()
    assert "tpu_custom_call" in text and " copy(" not in text
    if cell == "mellum2-12b" and width == 8:
        scatter = jax.jit(lambda pools, src, dst: [
            tuple(p.at[dst].set(p[src]) for p in layer)
            for layer in pools], donate_argnums=(0,))
        held = scatter.lower(pools, ids, ids).compile()
        assert held.memory_analysis().temp_size_in_bytes >= (
            pages * PAGE * 4 * D * 2)


@pytest.mark.parametrize("program", ["decode", "ragged", "prefill"])
def test_hybrid_step_of_the_brumby_cut_compiles(one_chip, monkeypatch,
                                                program):
    """Decode steps in a loop, one ragged join and one prologue chunk of
    the benchmark's Brumby-14B cut at published widths (one block: a
    retention layer and its MLP, the whole vocabulary), with NO pool,
    the slot states and the snapshot store donated. The state is 584 MB
    a layer: the programs must update it in place, so what they keep
    beside their arguments stays under a layer's state (phi(Q) of one
    128-token chunk is 170 MB of it)."""
    from theroundtaible_tpu.engine.models import hybrid
    from theroundtaible_tpu.engine.models.common import init_params
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.pallas import retention as rkernel
    from theroundtaible_tpu.engine.paged_forward import (
        forward_paged_hybrid, forward_ragged_hybrid)
    from theroundtaible_tpu.engine.serving_loop import (RaggedSeq,
                                                        build_ragged_batch)

    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    monkeypatch.setattr(rkernel, "_interpret", lambda: False)
    cfg = dataclasses.replace(
        get_model_config("brumby-14b"), num_layers=2,
        layer_kinds=(hybrid.RETENTION, hybrid.MLP), attn_impl="flash")

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32 = jnp.int32
    params = placed(jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    assert params["layers"][0]["g_proj"].shape == (5120, 8)
    state = placed(jax.eval_shape(
        lambda: hybrid.zero_state(cfg, RETENTION_ROWS)))
    snaps = placed(jax.eval_shape(
        lambda: hybrid.zero_state(cfg, RETENTION_SNAPS)))
    layer_state = RETENTION_ROWS * 8 * 65 * D * (D + 1) * 4
    if program == "decode":
        def step(params, state, tokens, positions, table, valid, active,
                 rows):
            def body(i, carry):
                st, tok = carry
                logits, _p, st, _c, _n = forward_paged_hybrid(
                    params, cfg, tok, positions + i, [], table, valid, st,
                    active=active, page_size=PAGE, rows=rows)
                return st, jnp.argmax(logits[:, 0], -1)[:, None].astype(i32)
            return jax.lax.fori_loop(0, 4, body, (state, tokens))

        compiled = jax.jit(step, donate_argnums=(1,)).lower(
            params, state, s((DECODE_ROWS, 1), i32),
            s((DECODE_ROWS, 1), i32), s((DECODE_ROWS, PAGES_PER_SEQ), i32),
            s((DECODE_ROWS,), i32), s((DECODE_ROWS,), jnp.bool_),
            s((DECODE_ROWS,), i32)).compile()
        _assert_kernel(compiled.as_text())
    elif program == "prefill":
        rows, t = 4, 1024

        def step(params, state, snaps, tokens, offsets, lengths, table,
                 at, cap_len, snap_idx):
            positions = offsets[:, None] + jnp.arange(t)[None]
            logits, _p, st, cap, _n = forward_paged_hybrid(
                params, cfg, tokens, positions, [], table,
                offsets + lengths, state, lengths=lengths, cap_len=cap_len,
                last_pos=jnp.maximum(lengths - 1, 0), page_size=PAGE,
                rows=at, snaps=snaps, snap_idx=snap_idx)
            return logits, st, cap

        compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
            params, state, snaps, s((rows, t), i32), s((rows,), i32),
            s((rows,), i32), s((rows, PAGES_PER_SEQ), i32), s((rows,), i32),
            s((rows,), i32), s((rows,), i32)).compile()
    else:
        table = np.zeros((PAGES_PER_SEQ,), np.int32)
        b = build_ragged_batch(
            [RaggedSeq([5] * 150, 100, table), RaggedSeq([7], 300, table)],
            t_budget=1536, s_max=RETENTION_ROWS,     # the cell's buffer
            pages_per_seq=PAGES_PER_SEQ, scratch_page=0, pad_id=0,
            page_size=PAGE)
        names = ("tokens", "positions", "tables", "seq_of_block",
                 "block_qstart", "query_offsets", "kv_valid",
                 "token_pages", "token_offs", "token_seq", "last_rows")

        def step(params, state, snaps, seq_slot, cap_n, snap_idx, *arrays):
            kw = dict(zip(names, arrays))
            logits, _p, st, cap, _n = forward_ragged_hybrid(
                params, cfg, kw["tokens"], kw["positions"], [],
                kw["tables"], kw["seq_of_block"], kw["block_qstart"],
                kw["query_offsets"], kw["kv_valid"], kw["token_pages"],
                kw["token_offs"], kw["token_seq"], kw["last_rows"], state,
                seq_slot, cap_n, page_size=PAGE, snaps=snaps,
                snap_idx=snap_idx)
            return logits, st, cap

        compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
            params, state, snaps, s((RETENTION_ROWS,), i32),
            s((RETENTION_ROWS,), i32), s((RETENTION_ROWS,), i32),
            *[s(np.asarray(b[n]).shape, i32) for n in names]).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= layer_state
    assert mem.temp_size_in_bytes < 1.25 * layer_state, (
        f"{program}: {mem.temp_size_in_bytes / 1e6:.0f} MB of temporaries "
        f"beside a state of {layer_state / 1e6:.0f} MB a layer")


# --- Jamba2-3B: the scan kernel, one kv head at group 20, the whole model --

JAMBA_ROWS, JAMBA_SNAPS = 17, 198    # 16 slots / 197 snapshots + scratch
JAMBA_POOL = (640, PAGE, 1, D)       # the cell's pool: ONE kv head


@pytest.mark.parametrize("tokens,block", [(128, 8), (1024, 8), (17, 1)])
def test_mamba1_scan_kernel_compiles_for_v5e(one_chip, tokens, block):
    """The selective scan at the published widths (5120 channels as 40
    lane rows, 16 state indices) over a page's chunk, a 1 024-token
    join buffer and a decode step's 17 slots (one token a block), on
    the 13-layer run's state, donated: the program keeps no second copy
    of the state beside what it aliases."""
    from theroundtaible_tpu.engine.pallas import mamba1 as m1

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    f32, i32 = jnp.float32, jnp.int32
    g, w = m1.fold(5120)
    assert (g, w) == (40, 128)
    nb = tokens // block
    state = (JAMBA_ROWS, 13, 16, g, w)
    compiled = jax.jit(
        functools.partial(m1.mamba1_scan, block=block, n_seqs=JAMBA_ROWS,
                          interpret=False), donate_argnums=(4,)).lower(
        s((tokens, g, w), f32), s((tokens, g, w), f32),
        s((tokens, 32), f32), s((16, g, w), f32), s(state, f32),
        s((), i32), s((nb,), i32), s((nb,), i32), s((nb,), i32)).compile()
    hlo = compiled.as_text()
    _assert_kernel(hlo)
    assert ("mamba1_scan" if block > 1 else "mamba1_step") in hlo
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= int(np.prod(state)) * 4
    assert mem.temp_size_in_bytes < int(np.prod(state)) * 4 // 10


@pytest.mark.parametrize("kernel", ["paged_decode", "ragged",
                                    "paged_prefill"])
def test_one_kv_head_at_group_20_compiles_for_v5e(one_chip, kernel):
    """The three paged kernels at Jamba's attention geometry over the
    cell's pool [640, 128, 1, 128]: `_token_major(1, 2)` is false, so
    they take the head-major form, which no cell had compiled."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32, h = jnp.int32, 20
    pool = s(JAMBA_POOL, jnp.bfloat16)
    assert not pattn._token_major(1, 2)
    if kernel == "paged_decode":
        fn, shapes = pattn.paged_decode_attention, (
            s((DECODE_ROWS, 1, h, D), jnp.bfloat16), pool, pool,
            s((DECODE_ROWS, PAGES_PER_SEQ), i32), s((DECODE_ROWS,), i32))
    elif kernel == "ragged":
        blocks = 1024 // pattn.RAGGED_BLOCK_Q
        fn, shapes = pattn.ragged_paged_attention, (
            s((1024, h, D), jnp.bfloat16), pool, pool,
            s((JAMBA_ROWS, PAGES_PER_SEQ), i32), s((blocks,), i32),
            s((blocks,), i32), s((JAMBA_ROWS,), i32),
            s((JAMBA_ROWS,), i32))
    else:
        fn, shapes = pattn.paged_prefill_attention, (
            s((4, CHUNK, h, D), jnp.bfloat16), pool, pool,
            s((4, PAGES_PER_SEQ), i32), s((4,), i32), s((4,), i32))
    hlo = _compile(functools.partial(fn, interpret=False), *shapes)
    _assert_kernel(hlo)


def _whole_jamba(one_chip, monkeypatch):
    """-> (cfg, params, state, pools, s): the WHOLE model at published
    widths as shapes on the described chip, kernels not interpreted."""
    from theroundtaible_tpu.engine.models import hybrid
    from theroundtaible_tpu.engine.models.common import init_params
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.pallas import mamba1 as m1

    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    monkeypatch.setattr(m1, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_model_config("jamba2-3b"),
                              attn_impl="flash")
    assert cfg.scan_runs == (7, 13, 6)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = placed(jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    state = placed(jax.eval_shape(
        lambda: hybrid.zero_state(cfg, JAMBA_ROWS)))
    pools = [(s(JAMBA_POOL, jnp.bfloat16), s(JAMBA_POOL, jnp.bfloat16))
             for _ in range(2)]
    return cfg, params, state, pools, s


def test_whole_jamba_decode_step_compiles_with_its_runs_scanned(
        one_chip, monkeypatch):
    """Four decode steps in a loop of the WHOLE model at published
    widths — 28 published layers, 3.03 G parameters — pools, slot states
    donated. Its Mamba-1 blocks run as THREE `lax.scan`s over stacked
    parameters (runs of 7, 13 and 6): the program holds four loops (the
    steps' and one a run) whatever the depth, aliases pools and states,
    and keeps no copy of a run's state beside them."""
    import re

    from theroundtaible_tpu.engine.paged_forward import forward_paged_hybrid

    cfg, params, state, pools, s = _whole_jamba(one_chip, monkeypatch)
    i32 = jnp.int32

    def step(params, pools, state, tokens, positions, table, valid, active,
             rows):
        def body(i, carry):
            pl, st, tok = carry
            logits, pl, st, _c, _n = forward_paged_hybrid(
                params, cfg, tok, positions + i, pl, table, valid + i, st,
                active=active, page_size=PAGE, rows=rows)
            return pl, st, jnp.argmax(logits[:, 0], -1)[:, None].astype(i32)
        return jax.lax.fori_loop(0, 4, body, (pools, state, tokens))

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, pools, state, s((DECODE_ROWS, 1), i32),
        s((DECODE_ROWS, 1), i32), s((DECODE_ROWS, PAGES_PER_SEQ), i32),
        s((DECODE_ROWS,), i32), s((DECODE_ROWS,), jnp.bool_),
        s((DECODE_ROWS,), i32)).compile()
    hlo = compiled.as_text()
    _assert_kernel(hlo)
    assert "mamba1_step" in hlo
    assert len(re.findall(r" while\(", hlo)) == 4
    mem = compiled.memory_analysis()
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves((state, pools)))
    assert mem.alias_size_in_bytes >= held
    run_state = JAMBA_ROWS * 13 * 16 * 5120 * 4
    assert mem.temp_size_in_bytes < 2 * run_state, (
        f"{mem.temp_size_in_bytes / 1e6:.0f} MB of temporaries beside a "
        f"run's state of {run_state / 1e6:.0f} MB")
    assert mem.argument_size_in_bytes > 6.0e9       # the model, whole


def _moved_between_memory_spaces(hlo: str, at_least: int) -> list:
    """(name, shape, MB) of every `copy-start` in `hlo` that moves
    `at_least` bytes or more: what the compiler's memory-space
    assignment prefetches and writes back."""
    import re
    sizes = {"bf16": 2, "f16": 2, "s8": 1, "u8": 1, "pred": 1}
    moved = []
    for m in re.finditer(r"(%?copy-start[.\w]*) = \((\w+)\[([\d,]+)\]", hlo):
        n = int(np.prod([int(d) for d in m.group(3).split(",")]))
        if n * sizes.get(m.group(2), 4) >= at_least:
            moved.append((m.group(1), f"{m.group(2)}[{m.group(3)}]",
                          n * sizes.get(m.group(2), 4) // 10 ** 6))
    return moved


def test_jamba_decode_segment_moves_no_run_state_between_memory_spaces(
        one_chip, monkeypatch):
    """A decode SEGMENT of the whole model as `engine.decode_loop_hybrid`
    issues it since ISSUE 53 — the packed buffer cut apart at the head,
    the carried rows, the sampler in the loop, the engine's pair of keys
    in and the next pair out — compiles with every run's slot state left
    where it is. With `jax.random.split(key)` at the program's head (a
    computed loop key AND a computed output key) the compiler moved the
    13-layer run's whole slot state, 72 MB, into its alternate memory
    and back inside the run's loop: `copy-done.49` / `.50`, 0.9 of 5.7
    decode seconds on the chip, `tokens_per_s` 1193 -> 1022 (PERF.md,
    PR 53). The pair — the split made one program ahead, so that the
    loop starts from an argument — does not. (The program is rebuilt
    here from the engine's pieces: an engine needs devices to build.)"""
    from theroundtaible_tpu.engine import dispatch_pack
    from theroundtaible_tpu.engine.hybrid_state import MOE_COUNTS
    from theroundtaible_tpu.engine.models.hybrid import ROW_PARTS
    from theroundtaible_tpu.engine.paged_forward import forward_paged_hybrid
    from theroundtaible_tpu.engine.sampling import sample_token_batch

    cfg, params, state, pools, s = _whole_jamba(one_chip, monkeypatch)
    layout = dispatch_pack.decode_layout(DECODE_ROWS, PAGES_PER_SEQ,
                                         rows=True)
    max_new, eos = 64, jnp.int32(2)

    def segment(params, pools, state, buf, carry, keys):
        f = layout.unpack(buf)
        last, valid, done, budgets = (
            jnp.where(f["carried"], c, f[name])
            for c, (name, _k) in zip(carry, dispatch_pack.CARRY))
        tables, rows = f["tables"], f["rows"]
        keys, sub = jax.random.split(keys[0]), keys[1]     # chain_key

        def cond(st):
            return ((st[0] < max_new) & (st[0] < f["budget"])
                    & ~jnp.all(st[3]))

        def body(st):
            step, last, valid, done, out, (pl, rs, counts), key = st
            logits, pl, rs, _cap, c = forward_paged_hybrid(
                params, cfg, last[:, None], valid[:, None], pl, tables,
                valid + 1, rs, active=~done & (step < budgets),
                page_size=PAGE, rows=rows)
            key, draw = jax.random.split(key)
            nxt = sample_token_batch(
                logits[:, 0].astype(jnp.float32), draw, f["temps"],
                f["top_ks"], f["top_ps"]).astype(jnp.int32)
            nxt = jnp.where(done | (step >= budgets), eos, nxt)
            return (step + 1, nxt, jnp.where(done, valid, valid + 1),
                    done | (nxt == eos), out.at[:, step].set(nxt),
                    (pl, rs, counts + c), key)

        rows_state = {p: [a[rows] for a in v] if p in ROW_PARTS else v
                      for p, v in state.items()}
        step, last, valid, done, out, (pl, rs, counts), _ = \
            jax.lax.while_loop(cond, body, (
                jnp.int32(0), last, valid, done,
                jnp.zeros((DECODE_ROWS, max_new), jnp.int32),
                (pools, rows_state,
                 jnp.zeros((len(MOE_COUNTS),), jnp.int32)), sub))
        new_state = {p: [a.at[rows].set(n) for a, n in zip(v, rs[p])]
                     if p in ROW_PARTS else rs[p] for p, v in state.items()}
        return (out, step, last, valid, done,
                jnp.maximum(budgets - step, 0), pl, new_state, counts, keys)

    compiled = jax.jit(segment, donate_argnums=(1, 2)).lower(
        params, pools, state, s((layout.size,), jnp.int32),
        tuple(s((DECODE_ROWS,), kind) for _n, kind in dispatch_pack.CARRY),
        s((2, 2), jnp.uint32)).compile()
    hlo = compiled.as_text()
    _assert_kernel(hlo)
    assert "mamba1_step" in hlo
    moved = _moved_between_memory_spaces(hlo, 50 * 10 ** 6)
    assert not moved, moved


# --- 64-wide heads, two a lane row (ISSUE 52) -------------------------------
#
# LFM2-24B-A2B: 32 query heads over 8 kv heads of SIXTY-FOUR. The pool
# holds two heads of one token a 128-lane row, `[640, 128, 4, 128]`
# (pallas/attention.py: lane_pack), the queries arrive 64 wide, and the
# wrappers hand the kernels rows that carry a head's half and zeros.

LFM2_POOL, LFM2_HEADS, LFM2_KV, LFM2_D = (640, PAGE, 4, 128), 32, 8, 64


@pytest.mark.parametrize("kernel,t", [
    ("paged_decode", 1), ("ragged", 1024), ("ragged", 64),
    ("paged_prefill", 256)])
def test_heads_of_64_compile_over_the_packed_pool(one_chip, kernel, t):
    """The decode walk, the ragged walk and the prefill kernel at LFM2's
    attention geometry: the gates accept (K, D) = (8, 64) outside
    interpret mode, the pool is an operand as the cell holds it — twice,
    uncopied, 2 KB a position — and the result is 64 wide."""
    import re
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32, h, d = jnp.int32, LFM2_HEADS, LFM2_D
    pool = s(LFM2_POOL, jnp.bfloat16)
    assert pattn.lane_pack(LFM2_KV, d) == 2
    assert int(np.prod(LFM2_POOL[2:])) * 2 * 2 == 2048
    if kernel == "paged_decode":
        fn, shapes = pattn.paged_decode_attention, (
            s((DECODE_ROWS, 1, h, d), jnp.bfloat16), pool, pool,
            s((DECODE_ROWS, PAGES_PER_SEQ), i32), s((DECODE_ROWS,), i32))
    elif kernel == "ragged":
        blocks = t // pattn.RAGGED_BLOCK_Q
        fn, shapes = pattn.ragged_paged_attention, (
            s((t, h, d), jnp.bfloat16), pool, pool,
            s((ROWS + 1, PAGES_PER_SEQ), i32), s((blocks,), i32),
            s((blocks,), i32), s((ROWS + 1,), i32), s((ROWS + 1,), i32))
    else:
        fn, shapes = pattn.paged_prefill_attention, (
            s((4, t, h, d), jnp.bfloat16), pool, pool,
            s((4, PAGES_PER_SEQ), i32), s((4,), i32), s((4,), i32))
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *shapes).compile()
    hlo = compiled.as_text()
    _assert_kernel(hlo)
    call = next(line for line in hlo.splitlines()
                if "tpu_custom_call" in line)
    assert call.count("bf16[640,128,4,128]") == 2
    assert compiled.out_info.shape[-2:] == (h, d)
    copies = [line.strip()[:160] for line in hlo.splitlines()
              if re.search(r"= \w+\[640,128,[0-9,]+\]\S* copy(-start)?\(",
                           line)]
    assert not copies, copies


def test_the_gates_take_64_wide_pairs_and_decline_the_rest(monkeypatch):
    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    group = LFM2_HEADS // LFM2_KV
    assert pattn.paged_decode_decline_reason(PAGE, 64, 8, group) is None
    assert pattn.ragged_decline_reason(PAGE, 64, 8, group) is None
    assert pattn.supported(256, 1024, 64, 8)
    assert pattn.paged_pool_direct_supported(1024, PAGE, 64, 8, group)
    # ... asked about the cell itself, the same answer
    assert pattn.paged_decode_decline_reason(PAGE, 128, 4, 2 * group) is None
    # an odd head count has no pairs; 32 and 96 are no half of a lane row;
    # a quantized pool keeps plain cells (its scales are a head's)
    assert pattn.paged_decode_decline_reason(PAGE, 64, 3, 1) == "head_dim:64"
    assert pattn.ragged_decline_reason(PAGE, 64, 1, 4) == "head_dim:64"
    assert pattn.ragged_decline_reason(PAGE, 32, 8, 4) == "head_dim:32"
    assert pattn.paged_decode_decline_reason(PAGE, 96, 8, 4) == "head_dim:96"
    assert pattn.paged_decode_decline_reason(
        PAGE, 64, 8, group, itemsize=1, scale_groups=2) == "head_dim:64"
    assert pattn.ragged_decline_reason(
        PAGE, 64, 8, group, quantized=True) == "head_dim:64"
    assert not pattn.supported(256, 1024, 64, 3)
    # a plain [K, 64] pool handed to a kernel for the chip is refused
    pool = jnp.zeros((4, PAGE, 8, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="2 heads a lane row"):
        pattn.paged_decode_attention(
            jnp.zeros((2, 1, 32, 64), jnp.bfloat16), pool, pool,
            jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32),
            interpret=False)


@pytest.mark.parametrize("kernel", ["flash_prefill", "ragged_decode"])
def test_the_cache_kernels_take_64_wide_pairs_on_the_chip(one_chip, kernel):
    """`supported` accepts (8, 64): the position-aligned kernels view the
    cache [B, S, 4, 128] on the chip."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32, b, length = jnp.int32, 4, 1024
    cache = s((b, length, LFM2_KV, LFM2_D), jnp.bfloat16)
    if kernel == "flash_prefill":
        fn, shapes = pattn.flash_prefill_attention, (
            s((b, CHUNK, LFM2_HEADS, LFM2_D), jnp.bfloat16), cache, cache,
            s((b,), i32), s((b,), i32))
    else:
        fn, shapes = pattn.ragged_decode_attention, (
            s((b, 1, LFM2_HEADS, LFM2_D), jnp.bfloat16), cache, cache,
            s((b,), i32))
    _assert_kernel(_compile(functools.partial(fn, interpret=False), *shapes))


@pytest.mark.parametrize("program", ["decode", "ragged", "prefill"])
def test_hybrid_step_of_the_lfm2_cut_compiles(one_chip, monkeypatch,
                                              program):
    """One decode step, one ragged join and one prologue chunk of
    LFM2-24B-A2B at published widths: a conv block with the dense SwiGLU,
    an attention block and a conv block with all 64 experts held — the
    short convolution's three forms, the packed pool through both walks
    and the prefill kernel, the head norms, the biased sigmoid router."""
    from theroundtaible_tpu.engine.models import hybrid
    from theroundtaible_tpu.engine.models.registry import get_model_config

    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    monkeypatch.setattr(grouped, "_interpret", lambda: False)
    whole = get_model_config("lfm2-24b-a2b")
    # blocks 0 (conv, dense), 2 (attention, experts), 3 (conv, experts)
    cfg = dataclasses.replace(
        whole, num_layers=6,
        layer_kinds=whole.layer_kinds[:2] + whole.layer_kinds[4:8],
        attn_impl="flash")
    assert cfg.layer_kinds == (
        hybrid.SHORTCONV, hybrid.MLP, hybrid.ATTENTION, hybrid.EXPERTS,
        hybrid.SHORTCONV, hybrid.EXPERTS)
    assert (cfg.page_heads, cfg.page_width) == LFM2_POOL[2:]
    assert cfg.experts_held == cfg.routed_experts == 64

    def check(params):
        assert params["layers"][0]["in_proj"].shape == (2048, 6144)
        assert params["layers"][0]["conv_w"].shape == (3, 2048)
        assert params["layers"][2]["k_proj"].shape == (2048, LFM2_KV, LFM2_D)
        assert params["layers"][2]["q_norm"].shape == (LFM2_D,)
        assert params["layers"][3]["router_bias"].shape == (64,)

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    hlo = _window_step_hlo(cfg, s(LFM2_POOL, jnp.bfloat16), program,
                           one_chip, check)
    assert hlo.count("tpu_custom_call") >= 2
    assert "grouped_matmul" in hlo
    assert "bf16[640,128,4,128]" in hlo


# --- a decoder whose upper half keeps no cache (ISSUE 56) --------------------
#
# Phi-4-mini-flash at published widths and depth 8 (3 Mamba-1, 2 window,
# 1 full, 1 gated memory unit, 1 cross layer: every kind, both halves,
# the seam): 40 query heads over 20 kv heads of 64 reach the paged kernels
# as 40 over 10 of 128 — a kv pair a lane row `[640,128,10,128]`, group 4
# (models/diffattn.py) — the cross layer reads the full layer's pools
# through the decode walk at one token a row, and a join's upper half
# multiplies `[rows, 2560]`.

PHI4_POOL = (640, PAGE, 10, 128)


@pytest.mark.parametrize("program", ["decode", "ragged", "prefill"])
def test_hybrid_step_of_the_phi4flash_cut_compiles(one_chip, monkeypatch,
                                                   program):
    """One decode step, one ragged join and one prologue chunk at the
    cell's widths: the Mamba-1 kernels without inner norms and with the
    memory emitted, differential attention through both walks and the
    prefill kernel at group 4, the seam's gather, the cross layer over
    another layer's pools, the LayerNorms."""
    import re

    from theroundtaible_tpu.engine.models import hybrid
    from theroundtaible_tpu.engine.models.common import init_params
    from theroundtaible_tpu.engine.models.registry import (
        phi4flash_kinds, get_model_config)
    from theroundtaible_tpu.engine.paged_forward import (
        forward_paged_hybrid, forward_ragged_hybrid)
    from theroundtaible_tpu.engine.pallas import mamba1 as m1
    from theroundtaible_tpu.engine.serving_loop import (RaggedSeq,
                                                        build_ragged_batch)

    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    monkeypatch.setattr(m1, "_interpret", lambda: False)
    whole = get_model_config("phi-4-mini-flash-reasoning")
    cfg = dataclasses.replace(
        whole, num_layers=16, layer_kinds=phi4flash_kinds(8),
        attn_layers=whole.attn_layers[:2] + whole.attn_layers[-1:],
        last_token_from=12, attn_impl="flash")
    assert cfg.attention_classes == ((40, 512, 2), (40, None, 1))
    assert (cfg.page_heads, cfg.page_width) == PHI4_POOL[2:]
    assert cfg.memory_layer == 8 and cfg.cross_layers == (14,)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    def _compile(f, *shapes):
        # (pools, state and the store donated, as the engine's programs
        # take them: an update in place is what is asked about)
        donated = tuple(i for i, a in enumerate(shapes)
                        if a is pools or a is state or a is snaps)
        return jax.jit(f, donate_argnums=donated).lower(
            *shapes).compile().as_text()

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32 = jnp.int32
    params = placed(jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    assert params["layers"][0]["mamba1"]["in_proj"].shape == (1, 2560, 10240)
    assert "dt_norm" not in params["layers"][0]["mamba1"]
    assert params["layers"][1]["o_proj"].shape == (20, 128, 2560)
    assert sorted(params["layers"][-2]) == sorted(
        ["norm", "norm_b", "q_proj", "q_bias", "o_proj", "o_bias",
         "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2",
         "lambda_init", "sub_norm"])
    pools = [(s(PHI4_POOL, jnp.bfloat16), s(PHI4_POOL, jnp.bfloat16))
             for _ in cfg.attention_layers]
    rows_n = DECODE_ROWS + 1
    state = placed(jax.eval_shape(lambda: hybrid.zero_state(cfg, rows_n)))
    snaps = {p: state[p] for p in hybrid.SLOT_PARTS if p in state}
    if program == "decode":
        def step(params, pools, state, tokens, positions, table, valid,
                 active, rows):
            return forward_paged_hybrid(
                params, cfg, tokens, positions, pools, table, valid, state,
                active=active, page_size=PAGE, rows=rows)

        hlo = _compile(step, params, pools, state, s((DECODE_ROWS, 1), i32),
                       s((DECODE_ROWS, 1), i32),
                       s((DECODE_ROWS, PAGES_PER_SEQ), i32),
                       s((DECODE_ROWS,), i32),
                       s((DECODE_ROWS,), jnp.bool_), s((DECODE_ROWS,), i32))
        assert "mamba1_step" in hlo
    elif program == "prefill":
        def step(params, pools, state, snaps, tokens, positions, table,
                 valid, lengths, rows, cap_len, snap_idx):
            return forward_paged_hybrid(
                params, cfg, tokens, positions, pools, table, valid, state,
                lengths=lengths, cap_len=cap_len, last_pos=lengths - 1,
                page_size=PAGE, rows=rows, snaps=snaps, snap_idx=snap_idx)

        one = s((1,), i32)
        hlo = _compile(step, params, pools, state, snaps, s((1, 1024), i32),
                       s((1, 1024), i32), s((1, PAGES_PER_SEQ), i32), one,
                       one, one, one, one)
        # The seam: the layers above it multiply ONE row of the chunk's
        # 1024 — the unit's gate [1, 1, 5120], never [1, 1024, 5120]
        # there (the Mamba-1 layers' own in-projection is twice as wide).
        assert re.search(r"bf16\[1,1,2560\]", hlo)
        assert not re.search(r"f32\[1,1024,200064\]", hlo)
    else:
        table = np.zeros((PAGES_PER_SEQ,), np.int32)
        b = build_ragged_batch(
            [RaggedSeq([5] * 150, 900, table), RaggedSeq([7], 1300, table)],
            t_budget=RAGGED_T, s_max=ROWS + 1,
            pages_per_seq=PAGES_PER_SEQ, scratch_page=0, pad_id=0,
            page_size=PAGE)
        names = ("tokens", "positions", "tables", "seq_of_block",
                 "block_qstart", "query_offsets", "kv_valid",
                 "token_pages", "token_offs", "token_seq", "last_rows")

        def step(params, pools, state, snaps, seq_slot, cap_n, snap_idx,
                 *arrays):
            kw = dict(zip(names, arrays))
            return forward_ragged_hybrid(
                params, cfg, kw["tokens"], kw["positions"], pools,
                kw["tables"], kw["seq_of_block"], kw["block_qstart"],
                kw["query_offsets"], kw["kv_valid"], kw["token_pages"],
                kw["token_offs"], kw["token_seq"], kw["last_rows"], state,
                seq_slot, cap_n, page_size=PAGE, snaps=snaps,
                snap_idx=snap_idx)

        nine = s((ROWS + 1,), i32)
        hlo = _compile(step, params, pools, state, snaps, nine, nine, nine,
                       *[s(np.asarray(b[n]).shape, i32) for n in names])
        assert "ragged_paged_attention" in hlo
        # (the cross layer, above the seam: the decode walk over the
        # sequences' last tokens)
        assert "paged_decode_attention" in hlo
    _assert_kernel(hlo)
    assert "mamba1" in hlo
    assert "bf16[640,128,10,128]" in hlo
    # Ten rows a token do not fill whole tiles, so XLA stores the pool
    # head-major: written through that view (paged_forward._write_cells)
    # no pool is re-laid out — a whole-pool copy a pool a step was 47 %
    # of the device's busy time (PERF.md, PR 56). (The prologue's kernel
    # takes row-major page blocks and still makes XLA copy such a pool:
    # this model's joins all take the ragged program, PERF.md section 7.)
    copies = [line.strip()[:160] for line in hlo.splitlines()
              if re.search(r"= \w+\[640,[0-9,]+\]\S* copy(-start)?\(",
                           line)]
    assert program == "prefill" or not copies, copies


# --- the int4 kernels the compiler refuses --------------------------------
#
# Shapes below come from a real Int4Leaf (quant.quantize_params on a
# Llama-3.2-3B-wide layer) and a real int4 pool spec (KVQuantSpec). Each refusal is
# what the plan-time gates now quote (int4mm.MOSAIC_REFUSAL,
# attention.kv_quant_decline_reason for bits=4), so describe() carries
# the reason from construction and no runtime rung is taken. The
# xfail(strict=True) cases tell the repair PR when it has worked: a
# kernel that compiles turns its case into a failure, and the gate for
# it then goes.


def _int4_case(spec: str):
    """(a_shape, Int4Leaf of shapes) for one matmul of a Llama-3.2-3B-
    wide layer, laid out by the real quantizer (quant.quantize_params
    under eval_shape)."""
    from theroundtaible_tpu.engine.models.common import (Int4Leaf,
                                                         init_params)
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.quant import quantize_params

    cfg = dataclasses.replace(get_model_config("llama-3.2-3b-instruct"),
                              num_layers=1)
    tree = jax.eval_shape(
        lambda k: quantize_params(init_params(cfg, k, jnp.bfloat16), cfg,
                                  bits=4), jax.random.PRNGKey(0))
    leaf = {"bte,ef->btf": tree["layers"][0]["gate_proj"],
            "bte,ve->btv": tree["embedding"]}[spec]
    assert isinstance(leaf, Int4Leaf)
    return (ROWS, 1, cfg.embed_dim), leaf


INT4_MM_CASES = [
    pytest.param(
        "bte,ef->btf", "out",
        marks=pytest.mark.xfail(strict=True, reason=(
            "_mm_pack_out: " + int4mm.MOSAIC_REFUSAL["out"]))),
    pytest.param(
        "bte,ve->btv", "contract",
        marks=pytest.mark.xfail(strict=True, reason=(
            "_mm_pack_contract: " + int4mm.MOSAIC_REFUSAL["contract"]))),
]


@pytest.mark.parametrize("spec,mode", INT4_MM_CASES)
def test_int4_matmul_kernel_compiles_for_v5e(one_chip, monkeypatch, spec,
                                             mode):
    a_shape, leaf = _int4_case(spec)
    cls, _ = int4mm._classify(spec, leaf)
    assert cls is not None and cls[0] == mode
    # The dispatch as it would run on the chip were the gate lifted.
    monkeypatch.setattr(int4mm, "_interpret", lambda: False)
    monkeypatch.setattr(int4mm, "MOSAIC_REFUSAL", {})

    def fn(a, leaf):
        y, reason = int4mm.einsum_int4_or_reason(spec, a, leaf)
        assert y is not None, reason
        return y

    placed = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        leaf)
    _assert_kernel(_compile(
        fn, jax.ShapeDtypeStruct(a_shape, jnp.bfloat16, sharding=one_chip),
        placed))


@pytest.mark.parametrize("kernel", ["paged_decode", "ragged"])
@pytest.mark.xfail(strict=True, reason=(
    "int4 page dequant (_dequant_kv): " + pattn.MOSAIC_INT4_KV_REFUSAL))
def test_int4_kv_kernel_compiles_for_v5e(one_chip, kernel):
    h, kh = HEADS["llama-3.2-3b"]
    fn, shapes = _attention_case(kernel, h, kh, "int4", one_chip)
    _assert_kernel(_compile(fn, *shapes))


def test_int4_gates_decline_at_plan_time_with_the_compilers_reason(
        monkeypatch):
    """On the chip (`_interpret()` false) the three refused kernels
    decline when they are PLANNED, quoting the compiler — never by a
    runtime degradation rung. In interpret mode (this suite's CPU
    parity tests) the plans stand."""
    assert int4mm._plan_pack_out(8, 3072, 4096, 32)[0] is not None
    assert int4mm._plan_pack_contract(8, 1536, 128_256, 32)[0] is not None
    assert pattn.kv_quant_decline_reason(PAGE, D, 8, 3, bits=4) is None
    assert pattn.kv_quant_decline_reason(PAGE, D, 8, 3, bits=8) is None

    monkeypatch.setattr(int4mm, "_interpret", lambda: False)
    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    plan, reason = int4mm._plan_pack_out(8, 3072, 4096, 32)
    assert plan is None and reason.startswith("mosaic:")
    assert int4mm.MOSAIC_REFUSAL["out"] in reason
    plan, reason = int4mm._plan_pack_contract(8, 1536, 128_256, 32)
    assert plan is None and reason.startswith("mosaic:")
    assert int4mm.MOSAIC_REFUSAL["contract"] in reason
    reason = pattn.kv_quant_decline_reason(PAGE, D, 8, 3, bits=4)
    assert reason.startswith("mosaic:")
    assert pattn.MOSAIC_INT4_KV_REFUSAL in reason
    # int8 pages compile (ATTENTION_CASES above) and stay on the kernels.
    assert pattn.kv_quant_decline_reason(PAGE, D, 8, 3, bits=8) is None
