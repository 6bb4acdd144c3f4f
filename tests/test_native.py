"""Native runtime library (native/rt_native.cc via ctypes): safetensors
mmap reader with multithreaded dtype conversion, and the KV-allocator LCP
primitive. The library self-builds with g++ on first use; tests skip on
machines without a toolchain."""

import numpy as np
import pytest

from theroundtaible_tpu.native import lcp, native_available, read_safetensors

needs_native = pytest.mark.skipif(
    not native_available(), reason="native lib unavailable (no g++?)")


class TestLcp:
    def test_basic(self):
        assert lcp([1, 2, 3, 4], [1, 2, 9]) == 2
        assert lcp([], [1, 2]) == 0
        assert lcp([7, 8], [7, 8]) == 2
        assert lcp([1], [2]) == 0

    def test_long_sequences(self):
        a = list(range(8192))
        b = list(range(8192))
        assert lcp(a, b) == 8192
        b[4096] = -1
        assert lcp(a, b) == 4096

    def test_kvcache_uses_it(self):
        from theroundtaible_tpu.engine.paging import PagedKVCache
        assert PagedKVCache.common_prefix_len([1, 2, 3], [1, 2, 5]) == 2


@needs_native
class TestSafetensorsReader:
    def test_dtype_conversions_match_reference(self, tmp_path):
        import ml_dtypes
        from safetensors.numpy import save_file

        rng = np.random.default_rng(0)
        tensors = {
            "f32": rng.standard_normal((64, 32)).astype(np.float32),
            "f16": rng.standard_normal((33, 7)).astype(np.float16),
            "bf16": rng.standard_normal((128, 16)).astype(ml_dtypes.bfloat16),
            "i64": rng.integers(-5, 5, (11,)).astype(np.int64),
        }
        p = tmp_path / "m.safetensors"
        save_file(tensors, str(p))
        out = read_safetensors(p)
        assert out is not None
        for name, ref in tensors.items():
            assert out[name].dtype == np.float32
            np.testing.assert_array_equal(out[name],
                                          ref.astype(np.float32))

    def test_f16_subnormals_and_specials(self, tmp_path):
        from safetensors.numpy import save_file

        specials = np.asarray(
            [0.0, -0.0, np.inf, -np.inf, np.nan, 6.1e-5, 5.96e-8, 65504.0,
             -65504.0, 1.0, -2.5], np.float16)
        p = tmp_path / "s.safetensors"
        save_file({"x": specials}, str(p))
        out = read_safetensors(p)
        np.testing.assert_array_equal(out["x"], specials.astype(np.float32))

    def test_checkpoint_loader_path(self, tmp_path):
        """load_hf_checkpoint goes through the native reader end to end."""
        import jax.numpy as jnp
        from safetensors.numpy import save_file

        from theroundtaible_tpu.engine.checkpoint import load_hf_checkpoint
        from theroundtaible_tpu.engine.models.registry import (
            get_model_config)

        cfg = get_model_config("tiny-llama")
        rng = np.random.default_rng(3)
        e, h, k, d, f, v = (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads,
                            cfg.head_dim, cfg.mlp_dim, cfg.vocab_size)
        tensors = {
            "model.embed_tokens.weight":
                rng.standard_normal((v, e)).astype(np.float32),
            "model.norm.weight": np.ones((e,), np.float32),
            "lm_head.weight":
                rng.standard_normal((v, e)).astype(np.float32),
        }
        for i in range(cfg.num_layers):
            p = f"model.layers.{i}"
            tensors.update({
                f"{p}.self_attn.q_proj.weight":
                    rng.standard_normal((h * d, e)).astype(np.float16),
                f"{p}.self_attn.k_proj.weight":
                    rng.standard_normal((k * d, e)).astype(np.float16),
                f"{p}.self_attn.v_proj.weight":
                    rng.standard_normal((k * d, e)).astype(np.float16),
                f"{p}.self_attn.o_proj.weight":
                    rng.standard_normal((e, h * d)).astype(np.float16),
                f"{p}.mlp.gate_proj.weight":
                    rng.standard_normal((f, e)).astype(np.float32),
                f"{p}.mlp.up_proj.weight":
                    rng.standard_normal((f, e)).astype(np.float32),
                f"{p}.mlp.down_proj.weight":
                    rng.standard_normal((e, f)).astype(np.float32),
                f"{p}.input_layernorm.weight": np.ones((e,), np.float32),
                f"{p}.post_attention_layernorm.weight":
                    np.ones((e,), np.float32),
            })
        save_file(tensors, str(tmp_path / "model.safetensors"))
        params = load_hf_checkpoint(tmp_path, cfg, jnp.float32)
        got = np.asarray(params["layers"][0]["q_proj"])
        want = (tensors["model.layers.0.self_attn.q_proj.weight"]
                .astype(np.float32).T.reshape(e, h, d))
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_missing_file_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            read_safetensors(tmp_path / "absent.safetensors")

    def test_shape_offsets_mismatch_rejected(self, tmp_path):
        """A header whose data_offsets disagree with shape must fail
        loudly, never silently read the neighbor tensor's bytes."""
        import json as _json
        import struct as _struct

        from theroundtaible_tpu.native.loader import iter_safetensors

        header = {"w": {"dtype": "F32", "shape": [16],
                        "data_offsets": [0, 32]}}  # 16 f32 needs 64 bytes
        raw = _json.dumps(header).encode()
        blob = _struct.pack("<Q", len(raw)) + raw + b"\x00" * 64
        p = tmp_path / "bad.safetensors"
        p.write_bytes(blob)
        with pytest.raises(ValueError, match="disagree"):
            list(iter_safetensors(p))

    def test_truncated_file_falls_back_cleanly(self, tmp_path):
        from theroundtaible_tpu.native.loader import native_can_read
        p = tmp_path / "trunc.safetensors"
        p.write_bytes(b"\x04")  # shorter than the 8-byte header length
        assert native_can_read(p) is False


@needs_native
@pytest.mark.parametrize("source_newer,origin", [(True, "built"),
                                                 (False, "loaded")])
def test_stale_library_is_rebuilt_from_source(tmp_path, monkeypatch,
                                              source_newer, origin):
    """The .so is git-ignored, so a working tree may carry a build of an
    earlier rt_native.cc: a source newer than the library rebuilds it
    (ISSUE 22), a fresh library just loads — and native_origin() says
    which happened."""
    import os
    import shutil

    from theroundtaible_tpu.native import loader

    so = tmp_path / "librt_native.so"
    shutil.copy(loader._SO_PATH, so)
    so_mtime = so.stat().st_mtime
    src = tmp_path / "rt_native.cc"
    shutil.copy(loader._SRC_PATH, src)
    delta = 60 if source_newer else -60
    os.utime(src, (so_mtime + delta, so_mtime + delta))
    monkeypatch.setattr(loader, "_SO_PATH", so)
    monkeypatch.setattr(loader, "_SRC_PATH", src)
    monkeypatch.setattr(loader, "_lib", None)
    monkeypatch.setattr(loader, "_lib_tried", False)
    monkeypatch.setattr(loader, "_lib_origin", None)

    assert loader._stale() is source_newer
    # The latency-sensitive path never builds: stale → nothing, unlatched.
    assert (loader._get_lib(build=False) is None) is source_newer
    assert loader.native_available()
    assert loader.native_origin() == origin
    assert (so.stat().st_mtime > so_mtime) is source_newer
    assert loader.lcp([1, 2, 3, 4], [1, 2, 9]) == 2
