"""tests/reference_decode.py against the two serving paths that remain:
the cache-free greedy decode equals pool-direct (`attn="auto"`: the paged
kernels, interpreted here) and equals the gather view (`attn="dense"`:
XLA alone, the fault ladder's last rung), token for token."""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.sampling import SamplingParams

from reference_decode import assert_greedy, greedy_decode


@pytest.mark.parametrize("attn,path", [("auto", "pool-direct"),
                                       ("dense", "gather-view")])
def test_cache_free_decode_equals_the_serving_path(attn, path):
    eng = InferenceEngine(
        get_model_config("tiny-gemma", max_seq_len=256), num_slots=2,
        page_size=32, dtype=jnp.float32, attn=attn,
        mesh_shape={"data": 1, "model": 1},
        sampling=SamplingParams(temperature=0.0, max_new_tokens=12))
    assert eng.describe()["paged_decode"] == path
    base = "the knights weigh the eastern gate against the harvest levy."
    ids = assert_greedy(eng, [("k", base)], 12)[0]
    # a second turn on the slot's own pages, across a page boundary
    assert_greedy(eng, [("k", base + " then galahad asks for a vote.")], 12)
    assert eng.last_stats.reused_tokens > 0
    # the decode is a function of the parameters and the prompt alone
    assert greedy_decode(eng, ids, 12) == greedy_decode(eng, ids, 12)


@pytest.mark.parametrize("model", ["tiny-gemma", "tiny-nemotron-h"])
@pytest.mark.parametrize("layout", ["contiguous", "", "Paged"])
def test_any_layout_but_paged_is_refused_by_name(model, layout):
    """One KV layout: the key is accepted with its one value, and any
    other is refused before anything is built, for every model kind."""
    with pytest.raises(ValueError) as err:
        InferenceEngine(get_model_config(model), kv_layout=layout)
    for part in ("kv_layout", repr(layout),
                 "the contiguous layout was removed in PR 46"):
        assert part in str(err.value)
    with pytest.raises(ValueError, match="kv_layout"):
        InferenceEngine.from_config({"model": model, "kv_layout": layout})
