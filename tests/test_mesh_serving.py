"""InferenceEngine over a (data, model) mesh it names: the paged serving
path a multi-chip deployment runs (`mesh {"model": N}`, as the
four-chip bring-up did), held to the one-device engine token for token.

Three meshes on the conftest's eight virtual devices: tensor parallel
over two and over four (tiny-gemma has 2 kv heads, so four does NOT
partition them and the pool is served through the gather view), and
data 2 x model 2 (pool-direct with the page axis split over "data").
Greedy float32 on both sides: bf16 rounding alone flips a near-tied
argmax of random tiny weights between two partitionings.

The scheduled cases (joins, speculation, the prefix cache, quantised
pages) are in tests/test_mesh_scheduled.py.
"""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from theroundtaible_tpu.engine import deadlines, faults
from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.sampling import SamplingParams

ONE_DEVICE = {"data": 1, "model": 1}
MESHES = [pytest.param({"model": 2}, id="model2"),
          pytest.param({"model": 4}, id="model4"),
          pytest.param({"data": 2, "model": 2}, id="data2-model2")]
MAX_NEW = 12


def build(mesh, **kw):
    kw.setdefault("num_slots", 8)
    return InferenceEngine(
        get_model_config("tiny-gemma", max_seq_len=256),
        mesh_shape=mesh, dtype=jnp.float32, seed=3, kv_layout="paged",
        page_size=32,
        sampling=SamplingParams(temperature=0.0, max_new_tokens=MAX_NEW),
        **kw)


def partitions(mesh) -> bool:
    """Do tiny-gemma's 2 kv heads divide this mesh's model axis?"""
    return 2 % mesh.get("model", 1) == 0


def tag(mesh) -> str:
    """Opens every prompt of a mesh's cases: the one-device reference
    is shared by the three meshes, and a prompt it has already served
    would come back from its prefix cache with other reuse counts."""
    return "[" + " ".join(f"{k}={v}" for k, v in mesh.items()) + "] "


@pytest.fixture(autouse=True)
def clean_faults():
    faults.disarm()
    deadlines.reset_rungs()
    yield
    faults.disarm()
    deadlines.reset_rungs()


@pytest.fixture(scope="module", params=MESHES)
def mesh(request):
    return request.param


@pytest.fixture(scope="module")
def eng(mesh):
    return build(mesh)


@pytest.fixture(scope="module")
def ref():
    return build(ONE_DEVICE)


@pytest.fixture(scope="module")
def ref_int8():
    return build(ONE_DEVICE, quant="int8", num_slots=2)


# ~105 byte-tokenizer tokens: past MIN_SHARED_PREFIX (64) and three
# whole 32-token pages, inside the prompt budget.
SHARED = ("the common context paragraph that every knight receives "
          "before their personal instructions begin. ")


class TestParity:
    def test_the_mesh_is_the_one_asked_for(self, eng, mesh):
        want = {"data": mesh.get("data", 1), "model": mesh["model"]}
        assert eng.describe()["mesh"] == want
        assert eng.mesh.devices.size == want["data"] * want["model"]

    def test_single_prompt(self, eng, ref, mesh):
        p = tag(mesh) + "the knights debate serving over a mesh"
        assert (eng.generate(p, slot_name="one", max_new_tokens=MAX_NEW)
                == ref.generate(p, slot_name="one",
                                max_new_tokens=MAX_NEW))
        assert eng.last_stats.decode_tokens > 0

    def test_batch(self, eng, ref, mesh):
        prompts = [("ba", tag(mesh) + "first knight asks about caching"),
                   ("bb", tag(mesh) + "second knight asks, a bit longer, "
                                      "about paging and pools"),
                   ("bc", tag(mesh) + "third")]
        assert (eng.generate_batch(prompts, max_new_tokens=MAX_NEW)
                == ref.generate_batch(prompts, max_new_tokens=MAX_NEW))

    def test_slot_reuse_across_turns(self, eng, ref, mesh):
        """A second turn that extends the first prefills only the
        delta, and says what a fresh computation says."""
        base = tag(mesh) + "round one says the store needs an event log."
        ext = base + " round two asks for sizing estimates."
        eng.generate(base, slot_name="turns", max_new_tokens=MAX_NEW)
        reused = eng.generate(ext, slot_name="turns",
                              max_new_tokens=MAX_NEW)
        assert eng.last_stats.reused_tokens > 0
        assert reused == ref.generate(ext, slot_name="turns-fresh",
                                      max_new_tokens=MAX_NEW)

    def test_greedy_row_unaffected_by_hot_row(self, eng, mesh):
        greedy = SamplingParams(temperature=0.0, max_new_tokens=MAX_NEW)
        hot = SamplingParams(temperature=1.5, max_new_tokens=MAX_NEW)
        prompts = [("calm", tag(mesh) + "the deterministic knight"),
                   ("hot", tag(mesh) + "the spicy knight")]
        mixed = eng.generate_batch(prompts, max_new_tokens=MAX_NEW,
                                   sampling_per_turn=[greedy, hot])
        for name, _ in prompts:
            eng.kv.release(name)
        both = eng.generate_batch(prompts, max_new_tokens=MAX_NEW,
                                  sampling_per_turn=[greedy, greedy])
        assert mixed[0] == both[0]


class TestPrefixSharing:
    def test_donor_copy_over_a_shared_prefix(self, eng, ref, mesh):
        a = tag(mesh) + SHARED + "You are knight Alpha."
        b = tag(mesh) + SHARED + "You are knight Beta."
        eng.generate(a, slot_name="alpha", max_new_tokens=MAX_NEW)
        shared = eng.generate(b, slot_name="beta", max_new_tokens=MAX_NEW)
        assert eng.last_stats.reused_tokens >= 64
        assert shared == ref.generate(b, slot_name="beta-solo",
                                      max_new_tokens=MAX_NEW)

    def test_leader_pass_aliases_pages(self, eng, ref, mesh):
        """Three fresh rows over one preamble: the leader prefills it
        once, the others take its pages — the same token accounting as
        on one device, and whole pages held by more than one slot."""
        prompts = [(f"kn{i}", tag(mesh) + "leader. " + SHARED
                    + f"You are knight {i}.") for i in range(3)]
        out, stats = eng.generate_batch_with_stats(
            prompts, max_new_tokens=MAX_NEW)
        out_ref, stats_ref = ref.generate_batch_with_stats(
            prompts, max_new_tokens=MAX_NEW)
        assert out == out_ref
        assert stats.reused_tokens == stats_ref.reused_tokens > 0
        assert stats.prefill_tokens == stats_ref.prefill_tokens
        first = eng.kv.acquire("kn0").pages[:2]
        assert all(eng.kv.refcount(p) > 1 for p in first)


class TestPagedPaths:
    def test_pool_direct_where_the_heads_partition(self, eng, mesh):
        d = eng.describe()
        if partitions(mesh):
            assert eng.paged_direct
            assert d["paged_decode"] == "pool-direct"
            assert eng._paged_replicas == mesh.get("data", 1)
        else:
            # 2 kv heads over a 4-way model axis: no kernel partition,
            # the batched programs read a gather view of the pool.
            assert not eng.paged_direct
            assert d["paged_decode"] == "gather-view"
            assert d["ragged"]["fallback_reason"] == "heads:model-axis"

    def test_dense_opts_out_and_keeps_the_gather_view(self, ref, mesh):
        dense = build(mesh, attn="dense", num_slots=2)
        assert not dense.paged_direct
        assert dense.describe()["paged_decode"] == "gather-view"
        prompts = [("da", tag(mesh) + "a question served densely"),
                   ("db", tag(mesh) + "and another one beside it")]
        assert (dense.generate_batch(prompts, max_new_tokens=MAX_NEW)
                == ref.generate_batch(prompts, max_new_tokens=MAX_NEW))

    def test_int8_weights_match_the_one_device_int8_engine(
            self, ref_int8, mesh):
        q8 = build(mesh, quant="int8", num_slots=2)
        leaf = q8.params["layers"][0]["q_proj"]
        assert set(leaf) == {"q", "s"} and leaf["q"].dtype == jnp.int8
        assert q8.describe()["quant"] == "int8"
        prompts = [("qa", tag(mesh) + "eight bits a weight"),
                   ("qb", tag(mesh) + "and a scale for every row of it")]
        assert (q8.generate_batch(prompts, max_new_tokens=MAX_NEW)
                == ref_int8.generate_batch(prompts,
                                           max_new_tokens=MAX_NEW))


class TestFaults:
    def test_timeout_mid_serve_leaves_engine_serviceable(self, eng, ref,
                                                         mesh):
        # More than one decode segment, so work is unfinished at the
        # deadline check.
        with pytest.raises(TimeoutError):
            eng.generate(tag(mesh) + "a prompt that will never finish",
                         slot_name="late", max_new_tokens=120,
                         timeout_s=0.0)
        p = tag(mesh) + "recovery prompt after the timeout"
        assert (eng.generate(p, slot_name="late", max_new_tokens=MAX_NEW)
                == ref.generate(p, slot_name="late-fresh",
                                max_new_tokens=MAX_NEW))

    @pytest.mark.chaos
    def test_dispatch_fault_retried_in_place(self, eng, ref, mesh):
        p = tag(mesh) + "a question whose first dispatch fails"
        spec = faults.arm("dispatch", count=1)
        out = eng.generate(p, slot_name="retry", max_new_tokens=MAX_NEW)
        assert spec.fired == 1
        assert out == ref.generate(p, slot_name="retry",
                                   max_new_tokens=MAX_NEW)

    @pytest.mark.chaos
    def test_dead_pools_revive_and_serve(self, eng, ref, mesh):
        """A dispatch that died after donation leaves deleted pools:
        the allocator reallocates them on the mesh's sharding and the
        next call serves from scratch."""
        eng.generate(tag(mesh) + "warm", slot_name="doomed",
                     max_new_tokens=4)
        for k, v in eng.kv.pools:
            k.delete()
            v.delete()
        assert eng.revive_kv_if_dead() is True
        assert not eng.kv.pools[0][0].is_deleted()
        assert eng.kv.slot_names() == []
        p = tag(mesh) + "served again after the pools were lost"
        assert (eng.generate(p, slot_name="doomed", max_new_tokens=MAX_NEW)
                == ref.generate(p, slot_name="doomed-fresh",
                                max_new_tokens=MAX_NEW))
