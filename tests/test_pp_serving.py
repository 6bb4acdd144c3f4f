"""Pipeline-parallel SERVING (engine/pp_serving.py): stage-local KV
prefill + decode must match the single-mesh engine token for token, and
be reachable from the tpu-llm adapter config (VERDICT r1 #7)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.pp_serving import PPEngine
from theroundtaible_tpu.engine.sampling import SamplingParams



# Cross-engine comparisons run in f32: PP's program structure (stacked
# scan, psum gathers) legitimately reorders bf16 summations, and random
# tiny-model logits sit close enough to ties that greedy argmax flips on
# bf16 rounding alone (the reference engine's own batch-vs-single outputs
# differ the same way under bf16).
def build_pp(n_stages=2, n_micro=2, **kw):
    return PPEngine(
        get_model_config("tiny-llama", max_seq_len=256),
        n_stages=n_stages, n_micro=n_micro, num_slots=4,
        dtype=jnp.float32,
        sampling=SamplingParams(temperature=0.0, max_new_tokens=8), **kw)


def build_ref():
    return InferenceEngine(
        get_model_config("tiny-llama", max_seq_len=256),
        mesh_shape={"data": 1, "model": 1}, num_slots=4,
        dtype=jnp.float32,
        sampling=SamplingParams(temperature=0.0, max_new_tokens=8))


class TestPPServingParity:
    def test_single_prompt_matches_reference(self):
        pp, ref = build_pp(), build_ref()
        p = "the knights debate the merits of pipeline parallel serving"
        assert (pp.generate(p, slot_name="a", max_new_tokens=8)
                == ref.generate(p, slot_name="a", max_new_tokens=8))

    def test_batch_microbatched_matches_reference(self):
        pp, ref = build_pp(n_micro=2), build_ref()
        prompts = [("a", "first knight question about caching"),
                   ("b", "second knight question, a bit longer than one")]
        assert (pp.generate_batch(prompts, max_new_tokens=8)
                == ref.generate_batch(prompts, max_new_tokens=8))

    def test_slot_reuse_across_turns(self):
        """Second turn extending the first must delta-prefill against the
        stage-local caches and match a fresh computation."""
        pp = build_pp()
        base = "round one says the store needs an event log."
        ext = base + " round two asks for sizing estimates."
        pp.generate(base, slot_name="k", max_new_tokens=8)
        out_reused = pp.generate(ext, slot_name="k", max_new_tokens=8)
        assert pp.last_stats.reused_tokens > 0
        out_fresh = build_pp().generate(ext, slot_name="f",
                                        max_new_tokens=8)
        assert out_reused == out_fresh

    def test_four_stages(self):
        pp = PPEngine(
            get_model_config("tiny-llama", max_seq_len=256, num_layers=4),
            n_stages=4, n_micro=2, num_slots=2, dtype=jnp.float32,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=6))
        ref = InferenceEngine(
            get_model_config("tiny-llama", max_seq_len=256, num_layers=4),
            mesh_shape={"data": 1, "model": 1}, num_slots=2,
            dtype=jnp.float32,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=6))
        p = "four stage pipeline question"
        assert (pp.generate(p, slot_name="x", max_new_tokens=6)
                == ref.generate(p, slot_name="x", max_new_tokens=6))


class TestPPPerRowSampling:
    def test_greedy_row_unaffected_by_hot_row(self):
        pp = build_pp()
        greedy = SamplingParams(temperature=0.0, max_new_tokens=8)
        hot = SamplingParams(temperature=1.5, max_new_tokens=8)
        prompts = [("ga", "the deterministic knight"),
                   ("gb", "the spicy knight")]
        mixed = pp.generate_batch(prompts, max_new_tokens=8,
                                  sampling_per_turn=[greedy, hot])
        for n, _ in prompts:
            pp.kv.release(n)
        all_greedy = pp.generate_batch(prompts, max_new_tokens=8,
                                       sampling_per_turn=[greedy, greedy])
        assert mixed[0] == all_greedy[0]

    def test_length_mismatch_raises(self):
        pp = build_pp()
        with pytest.raises(ValueError, match="entries"):
            pp.generate_batch(
                [("x", "one"), ("y", "two")], max_new_tokens=4,
                sampling_per_turn=[SamplingParams(temperature=0.0)])


class TestPPPrefixSharing:
    """Cross-knight shared-prefix reuse on the stage-local caches (the
    main engine's donor + leader passes, PP edition)."""

    # ByteTokenizer ≈ 1 token/char and build_pp's budget is ~191 tokens:
    # the shared span must clear MIN_SHARED_PREFIX (64) while the whole
    # prompt stays under budget (truncation would destroy the prefix).
    SHARED = ("the common context paragraph that every knight receives "
              "before their personal instructions begin. ")

    def test_donor_copy_matches_fresh(self):
        pp = build_pp()
        a = self.SHARED + "You are knight Alpha."
        b = self.SHARED + "You are knight Beta."
        pp.generate(a, slot_name="alpha", max_new_tokens=8)
        out_shared = pp.generate(b, slot_name="beta", max_new_tokens=8)
        assert pp.last_stats.reused_tokens > 0  # donor span copied
        out_fresh = build_pp().generate(b, slot_name="solo",
                                        max_new_tokens=8)
        assert out_shared == out_fresh

    def test_leader_pass_batch_matches_reference(self):
        pp, ref = build_pp(), build_ref()
        prompts = [(f"kn{i}", self.SHARED + f"You are knight {i}.")
                   for i in range(3)]
        out_pp, stats_pp = pp.generate_batch_with_stats(
            prompts, max_new_tokens=8)
        out_ref, stats_ref = ref.generate_batch_with_stats(
            prompts, max_new_tokens=8)
        assert out_pp == out_ref
        # both engines shared the batch-wide prefix, same token accounting
        assert stats_pp.reused_tokens == stats_ref.reused_tokens > 0
        assert stats_pp.prefill_tokens == stats_ref.prefill_tokens


class TestPPInt8:
    """int8 w8a16 under PP (VERDICT r2 #5): quantized {"q","s"} leaves
    stack per stage and must serve token-for-token like the main engine
    quantized the same way. f32 activations/scales for tie-stability
    (same discipline as the parity tests above)."""

    def test_int8_matches_main_engine_int8(self):
        pp = build_pp(quant="int8")
        ref = InferenceEngine(
            get_model_config("tiny-llama", max_seq_len=256),
            mesh_shape={"data": 1, "model": 1}, num_slots=4,
            dtype=jnp.float32, quant="int8",
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8))
        p = "the quantized knights deliberate over streamed bytes"
        assert (pp.generate(p, slot_name="q", max_new_tokens=8)
                == ref.generate(p, slot_name="q", max_new_tokens=8))

    def test_int8_batch_with_slot_reuse(self):
        pp = build_pp(quant="int8")
        base = "first round establishes the premise."
        ext = base + " second round refines it."
        pp.generate(base, slot_name="k", max_new_tokens=8)
        out_reused = pp.generate(ext, slot_name="k", max_new_tokens=8)
        assert pp.last_stats.reused_tokens > 0
        out_fresh = build_pp(quant="int8").generate(
            ext, slot_name="f", max_new_tokens=8)
        assert out_reused == out_fresh

    def test_int8_actually_quantized(self):
        pp = build_pp(quant="int8")
        leaves = jax.tree_util.tree_leaves(pp.staged)
        assert any(x.dtype == jnp.int8 for x in leaves)
        assert pp.describe()["quant"] == "int8"

    def test_from_config_accepts_int8(self):
        eng = PPEngine.from_config({
            "model": "tiny-llama", "max_seq_len": 256,
            "mesh": {"pipe": 2}, "quant": "int8", "num_slots": 2,
            "dtype": "float32",
            "sampling": {"temperature": 0.0, "max_new_tokens": 4}})
        out = eng.generate("hello there", slot_name="c", max_new_tokens=4)
        assert isinstance(out, str)


class TestPPConfigValidation:
    """from_config must refuse (not silently drop) settings the PP
    engine does not implement (advisor r2 finding)."""

    def _cfg(self, **extra):
        return {"model": "tiny-llama", "max_seq_len": 256,
                "mesh": {"pipe": 2}, **extra}

    def test_extra_mesh_axes_raise(self):
        with pytest.raises(ValueError, match="mesh axes"):
            PPEngine.from_config(
                self._cfg(mesh={"pipe": 2, "data": 2}))

    def test_seq_parallel_raises(self):
        with pytest.raises(ValueError, match="seq_parallel"):
            PPEngine.from_config(self._cfg(seq_parallel=4))

    def test_flash_attn_honored_on_pipe_only_mesh(self):
        eng = PPEngine.from_config(self._cfg(attn="flash"))
        assert eng.cfg.attn_impl == "flash"

    def test_flash_attn_honored_with_tp_in_stage(self):
        """Divisible heads (tiny-llama H4/K2 over model 2): explicit
        flash runs via the nested-shard_map spmd wrappers."""
        eng = PPEngine.from_config(
            self._cfg(mesh={"pipe": 2, "model": 2}, attn="flash"))
        assert eng.cfg.attn_impl == "flash"

    def test_flash_attn_raises_on_nonpartitionable_heads(self):
        """tiny-llama K=2 kv heads cannot split 4 ways (and K!=1, so no
        MQA replication either) — explicit flash must refuse, exactly as
        on the main engine."""
        with pytest.raises(ValueError, match="divisible"):
            PPEngine.from_config(
                self._cfg(mesh={"pipe": 2, "model": 4}, attn="flash"))

    def test_auto_attn_resolves_dense_on_cpu(self):
        # auto mirrors the main engine: kernels only on TPU backends
        eng = PPEngine.from_config(
            self._cfg(mesh={"pipe": 2, "model": 2}, attn="auto"))
        assert eng.cfg.attn_impl == "dense"


class TestPPTensorParallel:
    """mesh={"pipe": N, "model": M} — TP inside each pipeline stage
    (SURVEY §2.3's (pipeline, tensor, data) split; VERDICT r3 missing
    #3). The PP programs stay shard_map-manual over "pipe" while "model"
    is an auto axis: staged leaves carry param_specs' TP shardings
    shifted past the two stacking dims, and XLA inserts the in-stage TP
    collectives — so serving must stay token-identical to both the
    pipe-only PP engine and the main engine."""

    PROMPTS = [("a", "the knights debate tensor parallel stages today"),
               ("b", "a second, longer question about memory layouts")]

    def _pp(self, **kw):
        return PPEngine(
            get_model_config("tiny-gemma", max_seq_len=256),
            n_stages=2, n_model=2, n_micro=2, num_slots=4,
            dtype=jnp.float32, seed=3,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=12),
            **kw)

    def _ref(self, **kw):
        return InferenceEngine(
            get_model_config("tiny-gemma", max_seq_len=256),
            mesh_shape={"data": 1, "model": 1}, num_slots=4,
            dtype=jnp.float32, seed=3,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=12),
            **kw)

    def test_batch_matches_reference(self):
        pp, ref = self._pp(), self._ref()
        assert (pp.generate_batch(self.PROMPTS, max_new_tokens=12)
                == ref.generate_batch(self.PROMPTS, max_new_tokens=12))
        assert pp.last_stats.decode_tokens > 0  # non-trivial decode

    def test_staged_leaves_are_tp_sharded(self):
        """The memory property PP x TP exists for: a stage's weight leaf
        is additionally split over the model axis (not replicated)."""
        pp = self._pp()
        specs = [x.sharding.spec for x in
                 jax.tree_util.tree_leaves(pp.staged)]
        assert any("model" in [a for a in spec if isinstance(a, str)]
                   for spec in specs)
        # kv-head dim of the cache shards over model too (2 kv heads / 2)
        kc_spec = tuple(pp.kc.sharding.spec)
        assert kc_spec[0] == "pipe" and kc_spec[4] == "model"

    def test_int8_matches_reference(self):
        pp, ref = self._pp(quant="int8"), self._ref(quant="int8")
        assert (pp.generate_batch(self.PROMPTS, max_new_tokens=12)
                == ref.generate_batch(self.PROMPTS, max_new_tokens=12))

    def test_paged_matches_reference(self):
        pp, ref = self._pp(kv_layout="paged"), self._ref()
        assert (pp.generate_batch(self.PROMPTS, max_new_tokens=12)
                == ref.generate_batch(self.PROMPTS, max_new_tokens=12))

    def test_slot_reuse_across_turns(self):
        pp = self._pp()
        base = "round one says the store needs an event log."
        pp.generate(base, slot_name="k", max_new_tokens=8)
        pp.generate(base + " round two asks for sizing.", slot_name="k",
                    max_new_tokens=8)
        assert pp.last_stats.reused_tokens > 0

    def test_from_config_and_describe(self):
        eng = PPEngine.from_config(
            {"model": "tiny-gemma", "max_seq_len": 256,
             "mesh": {"pipe": 2, "model": 2}, "dtype": "float32",
             "sampling": {"temperature": 0.0, "max_new_tokens": 4}})
        d = eng.describe()
        assert d["mesh"] == {"pipe": 2, "model": 2}
        assert len(d["devices"]) == 4
        assert eng.generate("hello", slot_name="s", max_new_tokens=4) \
            is not None


class TestPPFlashAndPoolDirect:
    """Flash kernels and pool-direct paged serving inside PP stages
    (VERDICT r3 missing #4): on a pipe-only mesh the stage body is fully
    manual, so the raw single-device Pallas kernels serve prefill AND
    decode (interpret mode on CPU) — generations must match the main
    engine token for token."""

    PROMPTS = [("a", "the knights debate flash attention inside stages"),
               ("b", "a second, longer question about paging and pools")]

    def _ref(self, **kw):
        return InferenceEngine(
            get_model_config("tiny-gemma", max_seq_len=256),
            mesh_shape={"data": 1, "model": 1}, num_slots=4,
            dtype=jnp.float32, seed=3,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=12),
            **kw)

    def _pp(self, **kw):
        return PPEngine(
            get_model_config("tiny-gemma", max_seq_len=256),
            n_stages=2, n_micro=2, num_slots=4, dtype=jnp.float32,
            seed=3,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=12),
            **kw)

    def test_flash_contiguous_matches_reference(self):
        pp = self._pp(attn="flash")
        assert pp.cfg.attn_impl == "flash"
        assert (pp.generate_batch(self.PROMPTS, max_new_tokens=12)
                == self._ref().generate_batch(self.PROMPTS,
                                              max_new_tokens=12))
        assert pp.last_stats.decode_tokens > 0

    def test_paged_is_pool_direct_and_matches_reference(self):
        pp = self._pp(kv_layout="paged")
        assert pp._pool_direct
        assert "pool-direct" in pp.describe()["kv_layout"]
        assert (pp.generate_batch(self.PROMPTS, max_new_tokens=12)
                == self._ref().generate_batch(self.PROMPTS,
                                              max_new_tokens=12))

    def test_pool_direct_slot_reuse(self):
        pp = self._pp(kv_layout="paged")
        base = self.PROMPTS[0][1]
        pp.generate(base, slot_name="a", max_new_tokens=8)
        pp.generate(base + " and a follow-up turn", slot_name="a",
                    max_new_tokens=8)
        assert pp.last_stats.reused_tokens > 0

    def test_flash_paged_int8_pool_direct_matches_reference(self):
        pp = self._pp(kv_layout="paged", attn="flash", quant="int8")
        assert pp._pool_direct
        assert (pp.generate_batch(self.PROMPTS, max_new_tokens=12)
                == self._ref(quant="int8").generate_batch(
                    self.PROMPTS, max_new_tokens=12))

    def test_dense_opt_out_keeps_gather_view(self):
        pp = self._pp(kv_layout="paged", attn="dense")
        assert not pp._pool_direct
        assert "gather-view" in pp.describe()["kv_layout"]
        assert (pp.generate_batch(self.PROMPTS, max_new_tokens=12)
                == self._ref().generate_batch(self.PROMPTS,
                                              max_new_tokens=12))

    def test_tp_in_stage_paged_is_pool_direct_and_matches(self):
        """Partitionable heads: pool-direct survives TP-in-stage via the
        paged spmd wrappers (nested shard_map over "model")."""
        pp = PPEngine(
            get_model_config("tiny-gemma", max_seq_len=256),
            n_stages=2, n_model=2, n_micro=2, num_slots=4,
            dtype=jnp.float32, seed=3, kv_layout="paged",
            sampling=SamplingParams(temperature=0.0, max_new_tokens=12))
        assert pp._pool_direct
        assert (pp.generate_batch(self.PROMPTS, max_new_tokens=12)
                == self._ref().generate_batch(self.PROMPTS,
                                              max_new_tokens=12))

    def test_tp_in_stage_flash_matches_reference(self):
        """Explicit flash under pipe 2 x model 2: attention runs through
        the spmd wrappers as a nested shard_map inside the manual-pipe
        stage body — token-identical to the main engine."""
        pp = PPEngine(
            get_model_config("tiny-gemma", max_seq_len=256),
            n_stages=2, n_model=2, n_micro=2, num_slots=4,
            dtype=jnp.float32, seed=3, attn="flash",
            sampling=SamplingParams(temperature=0.0, max_new_tokens=12))
        assert pp.cfg.attn_impl == "flash"
        assert (pp.generate_batch(self.PROMPTS, max_new_tokens=12)
                == self._ref().generate_batch(self.PROMPTS,
                                              max_new_tokens=12))
        assert pp.last_stats.decode_tokens > 0

    def test_tp_in_stage_full_matrix_matches_reference(self):
        """flash + int8 + paged pool-direct + pipe 2 x model 2 — the
        complete composition in one engine."""
        pp = PPEngine(
            get_model_config("tiny-gemma", max_seq_len=256),
            n_stages=2, n_model=2, n_micro=2, num_slots=4,
            dtype=jnp.float32, seed=3, attn="flash", quant="int8",
            kv_layout="paged",
            sampling=SamplingParams(temperature=0.0, max_new_tokens=12))
        assert pp._pool_direct
        assert (pp.generate_batch(self.PROMPTS, max_new_tokens=12)
                == self._ref(quant="int8").generate_batch(
                    self.PROMPTS, max_new_tokens=12))


class TestPPPaged:
    """Paged KV under pipeline parallelism: the stage-stacked page pool
    must serve token-identically to the contiguous PP engine, with HBM
    scaling by pages used and prefix sharing via page aliasing."""

    def test_generate_and_reuse_parity(self):
        paged = build_pp(kv_layout="paged", page_size=32)
        dense = build_pp()
        base = "the paged pipeline debates its own page tables at length."
        ext = base + " a second turn crosses a page boundary here."
        for eng in (paged, dense):
            eng.generate(base, slot_name="k", max_new_tokens=8)
        out_p = paged.generate(ext, slot_name="k", max_new_tokens=8)
        out_d = dense.generate(ext, slot_name="k", max_new_tokens=8)
        assert paged.last_stats.reused_tokens > 0
        assert out_p == out_d

    def test_batch_shared_prefix_aliases_pages(self):
        paged = build_pp(kv_layout="paged", page_size=32)
        dense = build_pp()
        shared = ("the common context paragraph that every knight "
                  "receives before personal instructions begin. ")
        prompts = [(f"kn{i}", shared + f"knight {i} speaks")
                   for i in range(3)]
        out_p, stats_p = paged.generate_batch_with_stats(
            prompts, max_new_tokens=8)
        out_d, stats_d = dense.generate_batch_with_stats(
            prompts, max_new_tokens=8)
        assert out_p == out_d
        assert stats_p.reused_tokens == stats_d.reused_tokens > 0

    def test_pages_scale_with_use_and_describe(self):
        paged = build_pp(kv_layout="paged", page_size=32)
        paged.generate("short", slot_name="s", max_new_tokens=8)
        used_short = paged.kv.pages_in_use()
        paged.generate("a much longer prompt " * 6, slot_name="l",
                       max_new_tokens=8)
        assert paged.kv.pages_in_use() > used_short
        d = paged.describe()
        assert d["kv_layout"].startswith("stage-local paged")
        assert paged.kv.hbm_bytes() > 0

    def test_int8_paged_pp_serves(self):
        paged = build_pp(kv_layout="paged", page_size=32, quant="int8")
        out = paged.generate("every axis at once", slot_name="q",
                             max_new_tokens=8)
        assert isinstance(out, str)
        assert build_pp(quant="int8").generate(
            "every axis at once", slot_name="q", max_new_tokens=8) == out

    def test_reachable_from_adapter_config(self):
        eng = PPEngine.from_config({
            "model": "tiny-llama", "max_seq_len": 256,
            "mesh": {"pipe": 2}, "kv_layout": "paged", "page_size": 32,
            "num_slots": 4, "dtype": "float32",
            "sampling": {"temperature": 0.0, "max_new_tokens": 4}})
        out = eng.generate("hello pages", slot_name="c", max_new_tokens=4)
        assert isinstance(out, str)

    def test_timeout_mid_serve_leaves_engine_serviceable(self):
        """A deadline hit inside the gather→serve→scatter window must
        not strand the view or corrupt the pool (the try/finally): the
        next call serves normally and matches a fresh engine."""
        paged = build_pp(kv_layout="paged", page_size=32)
        # >1 decode segment so work is genuinely unfinished at the
        # deadline check (a completed single-segment run goes all-done
        # and rightly does not time out)
        with pytest.raises(TimeoutError):
            paged.generate("a prompt that will never finish",
                           slot_name="t", max_new_tokens=120,
                           timeout_s=0.0)
        assert paged.kc is None and paged.vc is None  # view released
        p = "recovery prompt after the timeout"
        out = paged.generate(p, slot_name="t", max_new_tokens=8)
        fresh = build_pp(kv_layout="paged", page_size=32)
        assert out == fresh.generate(p, slot_name="f", max_new_tokens=8)


class TestPPAdapterConfig:
    def test_reachable_from_adapter_config(self):
        """mesh {'pipe': N} in the tpu-llm adapter config builds a
        PPEngine and serves a round end to end."""
        from theroundtaible_tpu.adapters.base import KnightTurn
        from theroundtaible_tpu.adapters.tpu_llm import TpuLlmAdapter
        from theroundtaible_tpu.engine import reset_engines

        reset_engines()
        adapter = TpuLlmAdapter("pp-knight", {
            "model": "tiny-llama", "max_seq_len": 256,
            "mesh": {"pipe": 2}, "n_micro": 2, "num_slots": 4,
            "sampling": {"temperature": 0.0, "max_new_tokens": 8}})
        assert adapter.is_available()
        assert adapter._get_engine().describe()["mesh"] == {"pipe": 2}
        outs = adapter.execute_round(
            [KnightTurn("a", "what say you about pipelines?"),
             KnightTurn("b", "and what about stage local caches?")])
        assert len(outs) == 2 and all(isinstance(o, str) for o in outs)
        assert adapter.last_stats()["decode_tokens"] > 0
        reset_engines()

    def test_describe_scope_is_honest(self):
        d = build_pp().describe()
        assert d["kv_layout"] == "stage-local contiguous"
        assert "prefix sharing" in d["scope"]
        assert d["quant"] == "none"
