"""Shared roofline & performance-attribution model (ISSUE 6).

Roofline math lived twice in bench scripts with copy-pasted constants
(bench.py's `V5E_HBM_GBPS` / ceiling formulas, bench_microquant's 819
GB/s literal) and nowhere in the serving path — a number could be slow
in production with no live gauge saying how far from the hardware
ceiling it was, or why. This module is the ONE definition:

- **Chip specs** — per-chip HBM bandwidth and bf16 peak FLOP/s from
  public TPU specs, keyed by `device_kind` (the string the runtime
  reports) and by short name (`ROUNDTABLE_PERF_CHIP=v5e` overrides
  detection — CPU smoke runs and unknown plugin device_kinds still get
  a ceiling, explicitly marked as assumed).
- **Ceiling math** — decode is weight-streaming bound at low batch, so
  `decode_ceiling_tps = n_devices * HBM / streamed_param_bytes`
  (measured from the ACTUAL quantized tree, so int8/int4 automatically
  get their smaller-bytes ceilings); prefill is compute bound,
  `prefill_peak_tps = n_devices * peak_flops / (2 * params)`.
  `roofline_block()` packages both the way bench records carry them —
  bench.py embeds this dict verbatim, and the drift test pins its keys
  here so the bench schema and the live gauges can never fork again.
- **EnginePerf** — a per-engine instance built once at engine
  construction (param bytes + ceilings + KV bytes/token): the two
  ceiling gauges, and per-session KV-footprint gauges
  (`publish_session_kv`). It publishes no utilization: a rate over
  the host's wall clock against an assumed peak said nothing the
  device's own trace did not contradict (ISSUE 37); how long the
  device waits for the host is the scheduler's
  `roundtable_sched_starved_seconds_total`.
- **Span overheads** — `span_overheads()` folds the PR-5 span tree
  into a per-rung breakdown: how much of a decode/prefill/segment
  span's wall was inside device dispatches, host syncs, or the
  unaccounted dispatch gap between them — the "where did the
  milliseconds go" table `status --perf` renders.
- **attribution_snapshot()** — the perf block embedded in bench
  records and flight-recorder dumps: perf/compile/memory registry
  series + span overheads + the compile observatory's summary.

Host-only by design: no jax import at module load (the lazy imports in
`streamed_param_bytes`/`detect_chip` are the only backend touches), so
bench parents, tests and the telemetry spine can import this freely.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Optional

from . import telemetry


@dataclass(frozen=True)
class ChipSpec:
    """Per-chip roofline constants (public TPU specs)."""

    name: str                 # short name (env-override key)
    hbm_gbps: float           # HBM bandwidth, GB/s per chip
    bf16_peak_tflops: float   # peak bf16 TFLOP/s per chip


# Keyed by the runtime's device_kind string. Sources: public TPU specs
# (the v5e row is the pair bench.py carried since round 1).
CHIP_SPECS: dict[str, ChipSpec] = {
    "TPU v5 lite": ChipSpec("v5e", 819.0, 197.0),
    "TPU v5e": ChipSpec("v5e", 819.0, 197.0),
    "TPU v5": ChipSpec("v5p", 2765.0, 459.0),
    "TPU v5p": ChipSpec("v5p", 2765.0, 459.0),
    "TPU v4": ChipSpec("v4", 1228.0, 275.0),
    "TPU v6 lite": ChipSpec("v6e", 1640.0, 918.0),
    "TPU v6e": ChipSpec("v6e", 1640.0, 918.0),
    "TPU v3": ChipSpec("v3", 900.0, 123.0),
    "TPU v2": ChipSpec("v2", 700.0, 46.0),
}

_BY_SHORT_NAME: dict[str, ChipSpec] = {}
for _spec in CHIP_SPECS.values():
    _BY_SHORT_NAME.setdefault(_spec.name, _spec)

V5E = CHIP_SPECS["TPU v5e"]
# Back-compat names (bench.py re-exports these — ONE definition now).
V5E_HBM_GBPS = V5E.hbm_gbps
V5E_BF16_PEAK_TFLOPS = V5E.bf16_peak_tflops

CHIP_ENV = "ROUNDTABLE_PERF_CHIP"


def chip_spec(device_kind: Optional[str] = None) -> Optional[ChipSpec]:
    """The ChipSpec for a device_kind (or the env override), else None.

    ROUNDTABLE_PERF_CHIP (short name like "v5e", or a device_kind)
    wins over the argument — it is how CPU smoke runs and tests force
    a known roofline."""
    forced = os.environ.get(CHIP_ENV)
    if forced:
        return _BY_SHORT_NAME.get(forced) or CHIP_SPECS.get(forced)
    if not device_kind:
        return None
    spec = CHIP_SPECS.get(device_kind)
    if spec is not None:
        return spec
    # Prefix match: plugins append steppings ("TPU v5 lite chip" etc.).
    for kind, spec in CHIP_SPECS.items():
        if device_kind.startswith(kind):
            return spec
    return None


def detect_chip() -> tuple[Optional[ChipSpec], str]:
    """(spec, source) for the local device 0. source is one of
    "env" | "detected" | "none" — callers that refuse to run
    ceiling-less (bench on hardware) fall back to V5E and mark the
    block "assumed-v5e"."""
    if os.environ.get(CHIP_ENV):
        return chip_spec(), "env"
    try:
        import jax
        kind = getattr(jax.devices()[0], "device_kind", "")
    except Exception:  # noqa: BLE001 — no backend is a valid state
        return None, "none"
    spec = chip_spec(kind)
    return spec, ("detected" if spec else "none")


def streamed_param_bytes(params: Any) -> int:
    """Bytes decode streams from HBM per token: the summed on-device
    size of the ACTUAL (possibly quantized) param tree — Int4Leaf's
    packed q4 bytes and its scales count as stored, which is exactly
    what the memory bus sees."""
    import jax
    return sum(int(x.size) * int(x.dtype.itemsize)
               for x in jax.tree_util.tree_leaves(params))


def flops_per_token(num_params: int) -> float:
    """Dense-decoder forward FLOPs per token ≈ 2 · params (the
    standard roofline approximation both bench scripts used)."""
    return 2.0 * num_params


def decode_ceiling_tps(param_bytes: int, chip: ChipSpec,
                       n_devices: int = 1,
                       kv_stream_bytes: int = 0) -> float:
    """Weight-streaming decode ceiling: with TP over n chips each chip
    streams param_bytes/n per token. `kv_stream_bytes` (ISSUE 11) is
    the per-token KV read — context_tokens x resident cell bytes (data
    + scales on a quantized pool) — folded into the streamed term;
    0 keeps the historical weights-only ceiling (MQA at short serving
    context reads <1% of the weight bytes, but long contexts and batch
    don't, and quantized pages shrink exactly this term)."""
    return (n_devices * chip.hbm_gbps * 1e9
            / max(param_bytes + kv_stream_bytes, 1))


def prefill_peak_tps(num_params: int, chip: ChipSpec,
                     n_devices: int = 1) -> float:
    """Compute-bound prefill ceiling: peak bf16 FLOP/s over
    2·params FLOPs/token, scaled by the mesh size."""
    return (n_devices * chip.bf16_peak_tflops * 1e12
            / max(flops_per_token(num_params), 1.0))


def _assumptions(chip: ChipSpec) -> str:
    return (f"decode: HBM {chip.hbm_gbps:g} GB/s / streamed param "
            "bytes (KV traffic excluded); prefill: 2·params "
            f"FLOPs/token vs {chip.bf16_peak_tflops:g} bf16 TFLOP/s")


def roofline_block(*, param_bytes: int, num_params: int,
                   n_devices: int = 1,
                   decode_tps: Optional[float] = None,
                   prefill_tps: Optional[float] = None,
                   chip: Optional[ChipSpec] = None,
                   int4_fallbacks: Optional[int] = None,
                   kv_stream_bytes: int = 0,
                   kv_dtype: Optional[str] = None) -> dict:
    """The bench-record `roofline` dict — produced HERE and only here
    (bench.py embeds it verbatim; the drift test pins these keys).

    When no chip is given or detectable, the block assumes v5e and
    says so in `chip_source` — a hardware-window record must never
    silently drop its ceiling because a plugin renamed device_kind.

    `kv_stream_bytes`/`kv_dtype` (ISSUE 11): per-token KV bytes the
    decode step streams on top of the weights (context x resident cell
    bytes — data + scales on a quantized pool). Nonzero folds into the
    ceiling and rides the block as explicit keys, so an int8-KV record
    carries its own higher ceiling next to the dtype that earned it;
    0 keeps the historical weights-only block byte-identical."""
    source = "given"
    if chip is None:
        chip, source = detect_chip()
        if chip is None:
            chip, source = V5E, "assumed-v5e"
    ceiling = decode_ceiling_tps(param_bytes, chip, n_devices,
                                 kv_stream_bytes)
    peak = prefill_peak_tps(num_params, chip, n_devices)
    block = {
        "chip": chip.name,
        "chip_source": source,
        "decode_ceiling_tps": round(ceiling, 1),
        "decode_frac": (round(decode_tps / ceiling, 3)
                        if decode_tps is not None else None),
        "prefill_mfu": (round(prefill_tps / peak, 3)
                        if prefill_tps is not None else None),
        "assumptions": _assumptions(chip),
    }
    if int4_fallbacks:
        # XLA-dequant fallbacks materialize bf16 weights per token, so
        # the packed-bytes ceiling above is optimistic for that share
        # of dispatches — the count rides along so the reader knows.
        block["int4_fallback_dispatches"] = int(int4_fallbacks)
    if kv_stream_bytes:
        block["kv_stream_bytes_per_token"] = int(kv_stream_bytes)
        block["kv_dtype"] = kv_dtype or "bf16"
    return block


def kv_bytes_per_token(cfg: Any, dtype_bytes: int = 2,
                       quant_spec: Any = None) -> int:
    """Resident KV bytes one cached token costs this model:
    layers × (K + V) × kv_heads × head_dim × dtype. `quant_spec`
    (ISSUE 11, a kv_quant.KVQuantSpec) switches the cell to the
    quantized layout — int8/int4 payload PLUS the per-cell scale
    arrays, the closed form engine/kv_quant.cell_bytes_per_token owns
    (lazy import keeps this module host-only at load)."""
    if quant_spec is not None:
        from ..engine.kv_quant import cell_bytes_per_token
        return int(cell_bytes_per_token(cfg, quant_spec, dtype_bytes))
    return int(len(cfg.attention_layers) * cfg.page_cells * dtype_bytes)


# --- gauge-publication counter (tests/conftest.py `perf_obs` guard) ---

_published = 0
_published_lock = threading.Lock()


def note_published(n: int = 1) -> None:
    global _published
    with _published_lock:
        _published += n


def gauges_published() -> int:
    return _published


class EnginePerf:
    """One engine's live roofline model: built once at construction,
    published through at event rate (per call / per segment), embedded
    in describe(). `chip` may be None (CPU, unknown plugin) — ceilings
    are then None and publish_* become no-ops for the roofline gauges
    (memory/session gauges don't need a chip and publish elsewhere)."""

    def __init__(self, engine_name: str, *, param_bytes: int,
                 num_params: int, n_devices: int = 1,
                 chip: Optional[ChipSpec] = None,
                 chip_source: str = "given",
                 kv_token_bytes: int = 0):
        self.engine_name = engine_name
        self.param_bytes = param_bytes
        self.num_params = num_params
        self.n_devices = n_devices
        self.chip = chip
        self.chip_source = chip_source
        self.kv_token_bytes = kv_token_bytes
        # Multi-LoRA streamed-bytes overhead (ISSUE 10): a persona row
        # streams its adapter's A/B bytes on top of the base weights
        # every decode token, so the weight-streaming ceiling drops.
        # The engine's LoraStore keeps this at the per-adapter cost
        # while any adapter is resident (a conservative default).
        self.lora_row_bytes = 0.0
        # Quantized-KV streamed term (ISSUE 11): decode streams each
        # row's whole context from the page pool every token on top of
        # the weights. kv_token_bytes is already the RESIDENT cell cost
        # (data + scales on a quantized pool — from_engine resolves the
        # spec), so set_kv_decode_context(mean context tokens) is all
        # the ceiling needs to price the pool dtype; 0 (the default)
        # keeps the historical weights-only ceiling.
        self.kv_decode_context = 0
        self.decode_ceiling = (decode_ceiling_tps(param_bytes, chip,
                                                  n_devices)
                               if chip else None)
        self.prefill_peak = (prefill_peak_tps(num_params, chip,
                                              n_devices)
                             if chip else None)
        if self.decode_ceiling:
            telemetry.set_gauge("roundtable_decode_ceiling_tps",
                                self.decode_ceiling,
                                engine=engine_name)
            telemetry.set_gauge("roundtable_prefill_peak_tps",
                                self.prefill_peak, engine=engine_name)
            note_published(2)

    @classmethod
    def from_engine(cls, engine) -> "EnginePerf":
        """Build from a live engine: streamed bytes from its ACTUAL
        (quantized) tree, chip from its mesh's device 0."""
        kind = ""
        try:
            kind = getattr(engine.mesh.devices.flatten()[0],
                           "device_kind", "")
        except Exception:  # noqa: BLE001 — spec detection best-effort
            pass
        chip = chip_spec(kind)
        source = ("env" if os.environ.get(CHIP_ENV)
                  else "detected" if chip else "none")
        quant_spec = getattr(engine, "kv_quant_spec", None)
        kv_itemsize = 2
        kv = getattr(engine, "kv", None)
        pools = getattr(kv, "pools", None)
        layers = getattr(kv, "layers", None)
        if pools and quant_spec is None:
            kv_itemsize = pools[0][0].dtype.itemsize
        elif pools:
            # Quantized pools store int8 payload — itemsize 1 would
            # miss the scales; the spec's closed cell form below
            # charges both, against the engine's LOGICAL kv dtype
            # (the allocator records it — quantize-off round-trips
            # to exactly that width).
            kv_itemsize = getattr(kv, "_kv_dtype_bytes", 2)
        elif layers:
            kv_itemsize = layers[0][0].dtype.itemsize
        return cls(
            engine.cfg.name,
            param_bytes=streamed_param_bytes(engine.params),
            num_params=engine.num_params,
            n_devices=int(engine.mesh.devices.size),
            chip=chip, chip_source=source,
            kv_token_bytes=kv_bytes_per_token(engine.cfg, kv_itemsize,
                                              quant_spec=quant_spec))

    def set_lora_row_bytes(self, n: float) -> None:
        self.lora_row_bytes = float(max(n, 0.0))

    def set_kv_decode_context(self, tokens: int) -> None:
        """Mean per-row context length the decode ceiling should charge
        KV streaming for (ISSUE 11) — tokens x kv_token_bytes joins the
        streamed term. 0 restores the weights-only ceiling."""
        self.kv_decode_context = int(max(tokens, 0))

    def _decode_ceiling(self, lora_bytes_per_token=None) -> float:
        """The weight-streaming ceiling with LoRA bytes folded in
        (ISSUE 10): a K-adapter batch streams base + adapter bytes per
        token, so the base-only ceiling would flatter a persona batch.
        The quantized-KV streamed term (ISSUE 11) folds in the same
        way: context x resident cell bytes per decoded token — int8
        pages halve it, which RAISES the ceiling this gauge divides by
        (the explicit decode-ceiling correction the bench A/B prices)."""
        extra = (self.lora_row_bytes if lora_bytes_per_token is None
                 else lora_bytes_per_token)
        kv_extra = self.kv_decode_context * self.kv_token_bytes
        if not extra and not kv_extra:
            return self.decode_ceiling
        return decode_ceiling_tps(self.param_bytes + int(extra),
                                  self.chip, self.n_devices,
                                  kv_stream_bytes=int(kv_extra))

    # --- live publication seams ---

    def publish_session_kv(self, session: str, cached_tokens: int) -> None:
        """Per-session KV-footprint gauge (the memory ledger's
        per-session series). Retirement passes 0, which REMOVES the
        series: session ids are uuid-tagged per serve call, so a
        zeroed-but-kept series per session ever served would grow the
        registry (and every metrics.prom export) without bound in a
        long-lived serving process."""
        if cached_tokens <= 0:
            telemetry.REGISTRY.remove_gauge(
                "roundtable_session_kv_bytes",
                engine=self.engine_name, session=session)
            return
        telemetry.set_gauge("roundtable_session_kv_bytes",
                            cached_tokens * self.kv_token_bytes,
                            engine=self.engine_name, session=session)
        note_published(1)

    def describe(self) -> dict[str, Any]:
        return {
            "chip": self.chip.name if self.chip else None,
            "chip_source": self.chip_source,
            "param_bytes": self.param_bytes,
            "n_devices": self.n_devices,
            "decode_ceiling_tps": (round(self.decode_ceiling, 1)
                                   if self.decode_ceiling else None),
            "prefill_peak_tps": (round(self.prefill_peak, 1)
                                 if self.prefill_peak else None),
            "kv_bytes_per_token": self.kv_token_bytes,
            "kv_decode_context": self.kv_decode_context,
            "lora_row_bytes": int(self.lora_row_bytes),
        }


# --- span-tree overhead attribution ---


def _span_attr(rec: dict, key: str):
    """Span records come in two shapes: the flight-recorder ring
    flattens attrs into the record, spans.jsonl nests them."""
    if key in rec:
        return rec[key]
    return rec.get("attrs", {}).get(key)


def span_overheads(spans: list[dict]) -> dict[str, dict]:
    """Per-rung overhead breakdown from finished-span records (the
    PR-5 ring or spans.jsonl): for every parent rung, what fraction of
    its wall sat inside device dispatches, host syncs, or the
    unaccounted dispatch GAP between children — the host-overhead
    number the hardware-window tok/s needs an explanation from.

    Returns {rung: {total_s, dispatch_s, host_sync_s, gap_s,
    dispatch_frac, host_sync_frac, gap_frac, count}} for rungs that
    have children, plus a "queue_wait_s" roll-up from turn spans."""
    children: dict[str, list[dict]] = {}
    for s in spans:
        pid = s.get("parent_id")
        if pid:
            children.setdefault(pid, []).append(s)
    agg: dict[str, dict] = {}
    queue_wait = 0.0
    for s in spans:
        qw = _span_attr(s, "queue_wait_s")
        if s.get("rung") == "turn" and qw:
            queue_wait += float(qw)
        kids = children.get(s.get("span_id") or "", ())
        if not kids:
            continue
        rung = s.get("rung", "?")
        a = agg.setdefault(rung, {"total_s": 0.0, "dispatch_s": 0.0,
                                  "host_sync_s": 0.0, "gap_s": 0.0,
                                  "count": 0})
        dur = float(s.get("dur_s", 0.0))
        child_total = 0.0
        for k in kids:
            kdur = float(k.get("dur_s", 0.0))
            child_total += kdur
            if k.get("rung") == "dispatch":
                if _span_attr(k, "op") == "host_sync":
                    a["host_sync_s"] += kdur
                else:
                    a["dispatch_s"] += kdur
        a["total_s"] += dur
        a["gap_s"] += max(dur - child_total, 0.0)
        a["count"] += 1
    for a in agg.values():
        total = a["total_s"] or 1.0
        a["dispatch_frac"] = round(a["dispatch_s"] / total, 3)
        a["host_sync_frac"] = round(a["host_sync_s"] / total, 3)
        a["gap_frac"] = round(a["gap_s"] / total, 3)
        for key in ("total_s", "dispatch_s", "host_sync_s", "gap_s"):
            a[key] = round(a[key], 4)
    if queue_wait:
        agg["queue_wait_s"] = round(queue_wait, 4)
    return agg


# --- the embedded perf-attribution block ---

# Registry series the perf block collects (prefix match on the series
# name): roofline gauges, compile observatory, memory ledger.
PERF_SERIES_PREFIXES = (
    "roundtable_decode_ceiling_tps", "roundtable_prefill_peak_tps",
    "roundtable_decode_tps",
    "roundtable_sched_starved_seconds",  # ISSUE 37: the feed bit
    "roundtable_page_cop",  # ISSUE 38: page copies and their programs
    "roundtable_dispatch_",  # ISSUE 53: buffers and launches a program
    "roundtable_compile", "roundtable_steady_state",
    "roundtable_setup_",  # ISSUE 54: a start's seconds, stage and phase
    "roundtable_gc_",     # ISSUE 54: the collector's pauses
    "roundtable_kv_", "roundtable_hbm_", "roundtable_session_kv_",
    "roundtable_prefix_",   # ISSUE 7: prefix-cache hit/miss/size series
    "roundtable_spec_",     # ISSUE 9: speculation accept/rate series
    "roundtable_lora_",     # ISSUE 10: multi-LoRA residency/apply series
)


def perf_series(snapshot: Optional[dict] = None) -> dict[str, float]:
    """The perf slice of a compact registry snapshot."""
    snap = snapshot if snapshot is not None \
        else telemetry.REGISTRY.snapshot_compact()
    return {k: v for k, v in snap.items()
            if k.split("{", 1)[0].startswith(PERF_SERIES_PREFIXES)}


def attribution_snapshot() -> dict[str, Any]:
    """The perf-attribution block bench records and flight dumps embed:
    perf registry series + span-tree overheads (from the flight ring)
    + the compile observatory's summary. Never raises — an attribution
    block must not add a failure to the record it explains."""
    out: dict[str, Any] = {"series": perf_series()}
    try:
        out["overheads"] = span_overheads(
            telemetry.recorder().span_events())
    except Exception:  # noqa: BLE001 — best-effort block
        pass
    try:
        from ..engine import compile_watch
        out["compiles"] = compile_watch.summary(recent=8)
    except Exception:  # noqa: BLE001 — engine layer may be absent
        pass
    return out
