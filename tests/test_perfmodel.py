"""Shared roofline/perf-attribution model suite (ISSUE 6).

Covers utils/perfmodel.py units (hand-computed ceilings, chip specs,
streamed bytes over quantized trees, span-overhead folding), the
bench-constant dedupe drift test (bench.py / bench_microquant import
the ONE model), live EnginePerf + memory-ledger gauge publication on a
real tiny engine, and the `roundtable status --perf` render.
"""

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from theroundtaible_tpu.utils import perfmodel, telemetry


@pytest.mark.perf_obs(allow_quiet=True)
class TestChipSpecs:
    def test_v5e_constants_are_the_bench_constants(self):
        assert perfmodel.V5E_HBM_GBPS == 819.0
        assert perfmodel.V5E_BF16_PEAK_TFLOPS == 197.0

    def test_lookup_by_device_kind_and_prefix(self):
        assert perfmodel.chip_spec("TPU v5 lite").name == "v5e"
        assert perfmodel.chip_spec("TPU v4").name == "v4"
        # plugins append steppings — prefix match still resolves
        assert perfmodel.chip_spec("TPU v5 lite chip").name == "v5e"
        assert perfmodel.chip_spec("Radeon") is None
        assert perfmodel.chip_spec(None) is None

    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv(perfmodel.CHIP_ENV, "v5p")
        assert perfmodel.chip_spec("TPU v5 lite").name == "v5p"
        spec, source = perfmodel.detect_chip()
        assert spec.name == "v5p" and source == "env"


@pytest.mark.perf_obs(allow_quiet=True)
class TestCeilingMath:
    def test_hand_computed_tiny_model_ceiling(self):
        # 4 GB streamed / v5e 819 GB/s → 204.75 tok/s ceiling;
        # 2e9 params → 197e12 / 4e9 FLOPs/tok = 49250 tok/s peak.
        chip = perfmodel.V5E
        assert perfmodel.decode_ceiling_tps(4_000_000_000, chip) \
            == pytest.approx(204.75)
        assert perfmodel.prefill_peak_tps(2_000_000_000, chip) \
            == pytest.approx(49250.0)
        # mesh scaling: both ceilings are per-chip additive
        assert perfmodel.decode_ceiling_tps(4_000_000_000, chip, 4) \
            == pytest.approx(819.0)

    def test_roofline_block_values_and_keys(self):
        block = perfmodel.roofline_block(
            param_bytes=4_000_000_000, num_params=2_000_000_000,
            n_devices=1, decode_tps=150.0, prefill_tps=9850.0,
            chip=perfmodel.V5E)
        assert block["decode_ceiling_tps"] == 204.8  # round(204.75, 1)
        assert block["decode_frac"] == pytest.approx(0.733)
        assert block["prefill_mfu"] == pytest.approx(0.2)
        assert "819" in block["assumptions"]
        # The DRIFT PIN: bench.py embeds this dict verbatim, so these
        # keys ARE the bench-record roofline schema. Changing them here
        # without updating the consumers is a reviewable event.
        assert set(block) == {"chip", "chip_source",
                              "decode_ceiling_tps", "decode_frac",
                              "prefill_mfu", "assumptions"}

    def test_unknown_chip_assumes_v5e_and_says_so(self, monkeypatch):
        monkeypatch.delenv(perfmodel.CHIP_ENV, raising=False)
        block = perfmodel.roofline_block(
            param_bytes=1_000_000_000, num_params=500_000_000)
        assert block["chip"] == "v5e"
        assert block["chip_source"] == "assumed-v5e"

    def test_int4_fallbacks_ride_along(self):
        block = perfmodel.roofline_block(
            param_bytes=1_000, num_params=2_000, chip=perfmodel.V5E,
            int4_fallbacks=3)
        assert block["int4_fallback_dispatches"] == 3


@pytest.mark.perf_obs(allow_quiet=True)
class TestBenchDedupe:
    """Satellite: the bench scripts import the ONE shared model."""

    def test_bench_constants_are_perfmodel_objects(self):
        import bench
        assert bench.V5E_HBM_GBPS is perfmodel.V5E_HBM_GBPS
        assert bench.V5E_BF16_PEAK_TFLOPS \
            is perfmodel.V5E_BF16_PEAK_TFLOPS

    def test_bench_microquant_roofline_from_perfmodel(self):
        import bench_microquant
        assert bench_microquant._DEFAULT_HBM_GBPS \
            == perfmodel.V5E_HBM_GBPS
        assert bench_microquant._hbm_roofline_gbps("TPU v4") \
            == perfmodel.chip_spec("TPU v4").hbm_gbps
        assert bench_microquant._hbm_roofline_gbps("") \
            == perfmodel.V5E_HBM_GBPS


@pytest.mark.perf_obs(allow_quiet=True)
class TestStreamedBytes:
    def test_plain_tree(self):
        tree = {"a": np.zeros((4, 8), np.float32),
                "b": np.zeros((16,), np.int8)}
        assert perfmodel.streamed_param_bytes(tree) == 4 * 8 * 4 + 16

    def test_int4_leaf_counts_packed_bytes(self):
        from theroundtaible_tpu.engine.models.common import Int4Leaf
        leaf = Int4Leaf(q4=np.zeros((8, 16), np.int8),
                        s4=np.zeros((8, 2), np.float32),
                        axis=1, group=16)
        # q4 streams 1 B/byte (two params), s4 streams 4 B/scale —
        # exactly what the memory bus sees, NOT the logical count.
        assert perfmodel.streamed_param_bytes({"w": leaf}) \
            == 8 * 16 + 8 * 2 * 4

    def test_kv_bytes_per_token(self):
        from theroundtaible_tpu.engine.models.registry import \
            get_model_config
        cfg = get_model_config("tiny-gemma")
        assert perfmodel.kv_bytes_per_token(cfg, 2) \
            == cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim * 2


@pytest.mark.perf_obs(allow_quiet=True)
class TestSpanOverheads:
    def test_folds_dispatch_host_sync_and_gap(self):
        spans = [
            {"span_id": "d1", "parent_id": "", "rung": "decode",
             "dur_s": 1.0},
            {"span_id": "x1", "parent_id": "d1", "rung": "dispatch",
             "dur_s": 0.5, "stage": "decode"},
            {"span_id": "x2", "parent_id": "d1", "rung": "dispatch",
             "dur_s": 0.2, "op": "host_sync"},
            {"span_id": "t1", "parent_id": "", "rung": "turn",
             "dur_s": 2.0, "attrs": {"queue_wait_s": 0.25}},
        ]
        over = perfmodel.span_overheads(spans)
        d = over["decode"]
        assert d["dispatch_frac"] == pytest.approx(0.5)
        assert d["host_sync_frac"] == pytest.approx(0.2)
        assert d["gap_frac"] == pytest.approx(0.3)
        assert over["queue_wait_s"] == pytest.approx(0.25)

    def test_handles_both_record_shapes(self):
        # ring records flatten attrs; spans.jsonl nests them — both
        # must classify host_sync children identically.
        base = [{"span_id": "p", "parent_id": "", "rung": "prefill",
                 "dur_s": 1.0}]
        flat = base + [{"span_id": "c", "parent_id": "p",
                        "rung": "dispatch", "dur_s": 0.4,
                        "op": "host_sync"}]
        nested = base + [{"span_id": "c", "parent_id": "p",
                          "rung": "dispatch", "dur_s": 0.4,
                          "attrs": {"op": "host_sync"}}]
        assert perfmodel.span_overheads(flat)["prefill"][
            "host_sync_frac"] == perfmodel.span_overheads(nested)[
            "prefill"]["host_sync_frac"] == pytest.approx(0.4)

    def test_empty_spans(self):
        assert perfmodel.span_overheads([]) == {}


def _tiny_engine(monkeypatch, **kw):
    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import \
        get_model_config
    monkeypatch.setenv(perfmodel.CHIP_ENV, "v5e")
    cfg = get_model_config("tiny-gemma", max_seq_len=256)
    kw.setdefault("num_slots", 2)
    return InferenceEngine(cfg, **kw)


@pytest.mark.perf_obs
class TestLiveGauges:
    def test_generate_publishes_roofline_gauges(self, monkeypatch):
        eng = _tiny_engine(monkeypatch)
        assert eng.perf.chip.name == "v5e"
        eng.generate("the roundtable convenes at dawn",
                     slot_name="g", max_new_tokens=8)
        # the ceilings and the measured rate; no utilization over the
        # host's wall clock (ISSUE 37)
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_decode_tps", engine=eng.cfg.name) > 0.0
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_decode_ceiling_tps", engine=eng.cfg.name) \
            == pytest.approx(eng.perf.decode_ceiling)

    def test_memory_ledger_gauges(self, monkeypatch):
        eng = _tiny_engine(monkeypatch)
        eng.generate("knights discuss the eastern gate",
                     slot_name="m", max_new_tokens=4)
        name = eng.cfg.name
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_kv_slots_in_use", engine=name) >= 1
        occ = telemetry.REGISTRY.gauge_value(
            "roundtable_kv_slot_occupancy", engine=name)
        assert 0 < occ <= 1
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_kv_hbm_bytes", engine=name) > 0
        # CPU has no memory_stats → the ESTIMATE gauge carries HBM.
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_hbm_bytes_estimated", engine=name) > 0

    def test_memory_ledger_paged_pool(self, monkeypatch):
        from theroundtaible_tpu.engine import trace_hooks
        eng = _tiny_engine(monkeypatch, kv_layout="paged",
                           page_size=64)
        eng.generate("a long discussion about the moat and walls",
                     slot_name="p", max_new_tokens=4)
        led = trace_hooks.publish_memory_ledger(eng)
        assert led["layout"] == "paged"
        assert led["pages_in_use"] >= 1
        assert 0 < led["page_utilization"] <= 1
        # Fragmentation = held page cells not backing cached tokens
        # (decode reserve + tail) — bounded and nonzero right after a
        # short generation that reserved whole segments.
        assert 0 <= led["fragmentation"] <= 1
        name = eng.cfg.name
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_kv_pages_in_use", engine=name) \
            == led["pages_in_use"]
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_kv_fragmentation", engine=name) \
            == led["fragmentation"]

    def test_session_kv_series_removed_on_retire(self):
        perf = perfmodel.EnginePerf(
            "kv-unit", param_bytes=100, num_params=50,
            chip=perfmodel.V5E, kv_token_bytes=4)
        perf.publish_session_kv("sX", 100)
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_session_kv_bytes", engine="kv-unit",
            session="sX") == 400.0
        perf.publish_session_kv("sX", 0)
        # REMOVED, not zeroed: uuid-tagged session ids must not grow
        # the registry one dead series per session ever served.
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_session_kv_bytes", engine="kv-unit",
            session="sX") is None

    def test_attribution_snapshot_shape(self, monkeypatch):
        eng = _tiny_engine(monkeypatch)
        eng.generate("one more turn", slot_name="a",
                     max_new_tokens=4)
        snap = perfmodel.attribution_snapshot()
        assert any(k.startswith("roundtable_kv_")
                   for k in snap["series"])
        assert snap["compiles"]["mode"] == "monitoring"


@pytest.mark.perf_obs(allow_quiet=True)
class TestStatusPerfRender:
    def test_renders_roofline_compile_and_memory(self, tmp_path,
                                                 capsys):
        sess = tmp_path / ".roundtable" / "sessions" / "sess-001"
        (sess / "telemetry").mkdir(parents=True)
        (sess / "telemetry" / "metrics.prom").write_text(
            '# TYPE roundtable_decode_ceiling_tps gauge\n'
            'roundtable_decode_ceiling_tps{engine="knight"} 204.8\n'
            'roundtable_sched_starved_seconds_total{engine="knight",'
            'phase="build"} 1.25\n'
            'roundtable_sched_starved_seconds_total{engine="knight",'
            'phase="dispatch",replica="r0"} 2.5\n'
            'roundtable_sched_starved_seconds_total{engine="knight",'
            'phase="dispatch",replica="r1"} 0.5\n'
            'roundtable_sched_starved_seconds_total{engine="knight",'
            'phase="sync"} 0\n'
            'roundtable_page_copies_total{cause="share",'
            'engine="knight"} 60\n'
            'roundtable_page_copies_total{cause="alias",'
            'engine="knight"} 90\n'
            'roundtable_page_copy_programs_total{engine="knight",'
            'path="dma"} 20\n'
            'roundtable_kv_pages_in_use{engine="knight"} 12\n'
            'roundtable_session_kv_bytes{engine="knight",'
            'session="s0"} 4194304\n')
        (sess / "telemetry" / "spans.jsonl").write_text(
            json.dumps({"span_id": "d", "parent_id": "",
                        "rung": "decode", "dur_s": 1.0}) + "\n"
            + json.dumps({"span_id": "x", "parent_id": "d",
                          "rung": "dispatch", "dur_s": 0.7}) + "\n")
        from theroundtaible_tpu.commands.status import status_command
        rc = status_command(project_root=str(tmp_path), perf_view=True)
        out = capsys.readouterr().out
        assert rc == 0
        assert "Roofline" in out
        assert "knight" in out and "204.8" in out
        # the starved seconds by loop phase, largest first, replicas
        # added up, a phase that never starved left out
        assert "starved_s" in out
        assert "dispatch=3.000 build=1.250" in out and "sync=" not in out
        assert "bw_util" not in out and "mfu" not in out
        # what each program of the page cache's copier gathered
        assert "Page copies" in out and "copies/program" in out
        assert "knight 150 20 7.5 alias=90 share=60 dma" in " ".join(
            out.split())
        assert "Compile observatory" in out
        assert "Memory ledger" in out
        assert "roundtable_kv_pages_in_use" in out
        assert "Per-session KV footprint" in out
        assert "Overhead breakdown" in out

    @pytest.mark.parametrize("says", [
        "seconds the scheduler left the device unfed by loop phase",
        "page copies a program of the page cache's copier",
        "the scheduler's loop and starved seconds by phase"])
    def test_the_help_text_follows_the_columns(self, says, capsys):
        from theroundtaible_tpu.cli import build_parser
        with pytest.raises(SystemExit):
            build_parser().parse_args(["status", "--help"])
        out = " ".join(capsys.readouterr().out.split())
        assert says in out
        assert "utilization" not in out and "MFU" not in out

    def test_quiet_without_any_capture(self, tmp_path, capsys):
        (tmp_path / ".roundtable" / "sessions" / "s1").mkdir(
            parents=True)
        from theroundtaible_tpu.commands.status import status_command
        rc = status_command(project_root=str(tmp_path), perf_view=True)
        assert rc == 0
        assert "Performance" in capsys.readouterr().out
