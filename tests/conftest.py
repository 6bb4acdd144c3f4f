"""Test bootstrap.

Engine/sharding tests run on a virtual 8-device CPU mesh (SURVEY.md §4):
JAX must see the flags before first import, so they are set here at conftest
import time — before any test module imports jax.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("ROUNDTABLE_DISABLE_TPU_DETECT", "1")

# Tests run on the CPU whatever the environment says: force the platform
# through jax.config before any device lookup — it initializes ONLY the
# cpu backend (xla_bridge._backends == ['cpu']), so no test process ever
# reaches for an accelerator (one process per chip: a test run beside a
# chip run must not take it).
import jax

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache shared across test processes and runs
# (VERDICT r4 weak #7: the full suite outgrew a 10-minute single-command run;
# most of the engine-test time is XLA:CPU re-compiling the same tiny-shape
# programs in every process). Entries are always produced on the machine that
# reads them (the dir starts empty on a fresh checkout), so XLA's cross-
# machine AOT-feature warning does not apply; it may still log a spurious
# "prefer-no-scatter ... could lead to SIGILL" error about its own pseudo-
# features on load — cosmetic, and pytest's capture hides it for passing
# tests. The directory is placed from outside: JAX_COMPILATION_CACHE_DIR
# where set (JAX reads it itself), else the checkout's .pytest_xla_cache.
# Opt out with ROUNDTABLE_TEST_NO_XLA_CACHE=1.
if not os.environ.get("ROUNDTABLE_TEST_NO_XLA_CACHE"):
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _cache_dir = os.path.join(os.path.dirname(__file__), os.pardir,
                                  ".pytest_xla_cache")
        os.makedirs(_cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import signal
import threading
import time

import pytest

# The static-analysis fixture corpus (ISSUE 15) is lint INPUT — seeded
# rule violations and mini test trees the analyzer runs over — never
# test code to collect (its deliberate test_*.py twins would otherwise
# collide at import time and carry unregistered fixture markers).
collect_ignore = ["fixtures"]

# Per-test wall-clock guard (ISSUE 2 tooling satellite): a regression
# that reintroduces an unbounded device wait must fail ITS test fast
# with a named culprit instead of eating the whole 870 s tier-1 budget
# as a silent rc=124. SIGALRM-based (main-thread, POSIX — exactly the
# tier-1 environment); `slow`-marked tests get a 3x allowance, and
# ROUNDTABLE_TEST_TIMEOUT=0 disables the guard. The alarm interrupts
# only interruptible Python — a wait truly stuck in C is the engine
# watchdog's job (engine/deadlines.py), not this one's.
_TEST_ALARM_S = int(os.environ.get("ROUNDTABLE_TEST_TIMEOUT", "300"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    use_alarm = (_TEST_ALARM_S > 0 and hasattr(signal, "SIGALRM")
                 and threading.current_thread()
                 is threading.main_thread())
    old_handler = None
    if use_alarm:
        budget = _TEST_ALARM_S * (3 if item.get_closest_marker("slow")
                                  else 1)

        def _on_alarm(signum, frame):
            pytest.fail(
                f"{item.nodeid} exceeded the {budget}s per-test guard "
                "(conftest alarm) — an unbounded wait would otherwise "
                "consume the whole tier-1 clock", pytrace=False)

        old_handler = signal.signal(signal.SIGALRM, _on_alarm)
        signal.setitimer(signal.ITIMER_REAL, budget)
    try:
        yield
    finally:
        if use_alarm:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, old_handler)


@pytest.fixture(autouse=True)
def _quant_kernel_guard(request, monkeypatch):
    """Tier-1 guard for @pytest.mark.quant_kernels (ISSUE 3 satellite):
    a test that CLAIMS w4a16 kernel-path coverage must not silently run
    the XLA dequant fallback — every declined dispatch is recorded and
    any reason outside the marker's `allow=(...)` whitelist fails the
    test loud with the fallback_reason. Unmarked tests are untouched."""
    marker = request.node.get_closest_marker("quant_kernels")
    if marker is None:
        yield
        return
    from theroundtaible_tpu.engine.pallas import int4mm

    declines: list[tuple] = []
    orig_single = int4mm.einsum_int4_or_reason
    orig_spmd = int4mm.einsum_int4_spmd

    def spy_single(spec, a, leaf):
        y, reason = orig_single(spec, a, leaf)
        if y is None:
            declines.append((spec, tuple(a.shape), reason))
        return y, reason

    def spy_spmd(mesh, spec, a, leaf, tp=None):
        y, reason = orig_spmd(mesh, spec, a, leaf, tp=tp)
        if y is None:
            declines.append((spec, tuple(a.shape), reason))
        return y, reason

    monkeypatch.setattr(int4mm, "einsum_int4_or_reason", spy_single)
    monkeypatch.setattr(int4mm, "einsum_int4_spmd", spy_spmd)
    yield
    allowed = tuple(marker.kwargs.get("allow", ()))
    unexpected = [d for d in declines
                  if not any(a in (d[2] or "") for a in allowed)]
    assert not unexpected, (
        "quant_kernels-marked test silently fell back to xla_dequant "
        f"(spec, a_shape, fallback_reason): {unexpected}")


@pytest.fixture(autouse=True)
def _compile_watch_isolation():
    """Steady-state isolation (ISSUE 6): `warmup_complete` flips GLOBAL
    process state (any later compile counts as a mid-serve recompile),
    and module-scoped engines outlive their tests — without a per-test
    reset, one test's warmup would classify every later test's compiles
    as steady-state violations (and, under the scheduler suite's strict
    arming, fail them). Cheap: two attribute clears, no jax import."""
    from theroundtaible_tpu.engine import compile_watch

    compile_watch.reset_steady_state()
    yield
    compile_watch.reset_steady_state()


@pytest.fixture(autouse=True)
def _scheduler_guard(request, monkeypatch):
    """Tier-1 guard for @pytest.mark.scheduler (ISSUE 4 satellite): a
    test that CLAIMS continuous-batching coverage must not silently fall
    back to serial serving — if no decode segment during the test ever
    carried >= 2 rows, the sessions were served one-at-a-time and the
    test's concurrency claims are vacuous; fail LOUD. Unit tests of the
    scheduler's non-batching surfaces mark allow_serial=True.

    Every scheduler-marked test additionally runs with
    ROUNDTABLE_RECOMPILE_STRICT=1 armed (ISSUE 6): once a test declares
    warmup complete, a mid-serve recompile RAISES instead of hiding in
    the latency tail — the pow2-bucket invariant is enforced, not
    assumed. Tests that never declare steady state are unaffected."""
    marker = request.node.get_closest_marker("scheduler")
    if marker is None:
        yield
        return
    monkeypatch.setenv("ROUNDTABLE_RECOMPILE_STRICT", "1")
    if marker.kwargs.get("allow_serial"):
        yield
        return
    from theroundtaible_tpu.engine import scheduler as sched_mod

    sched_mod.reset_test_counters()
    yield
    assert sched_mod.max_rows_seen() >= 2, (
        "scheduler-marked test silently fell back to serial serving: no "
        "decode segment carried more than "
        f"{sched_mod.max_rows_seen()} row(s) — continuous batching "
        "never happened (mark allow_serial=True only for unit tests)")


@pytest.fixture(autouse=True)
def _perf_obs_guard(request):
    """Tier-1 guard for @pytest.mark.perf_obs (ISSUE 6): a test that
    CLAIMS performance-attribution coverage must actually exercise the
    observability — if neither the compile observatory recorded an
    event nor any perf gauge was published during the test, the seams
    silently no-op'd (uninstalled observatory, disconnected publish
    path); fail LOUD. allow_quiet=True waives the check for pure-math
    units (ceiling formulas, span folding)."""
    marker = request.node.get_closest_marker("perf_obs")
    if marker is None:
        yield
        return
    from theroundtaible_tpu.engine import compile_watch
    from theroundtaible_tpu.utils import perfmodel

    compile_watch.install()
    c0 = compile_watch.compiles_seen()
    g0 = perfmodel.gauges_published()
    yield
    if marker.kwargs.get("allow_quiet"):
        return
    assert (compile_watch.compiles_seen() > c0
            or perfmodel.gauges_published() > g0), (
        "perf_obs-marked test recorded NO compile events and published "
        "NO perf gauges: the performance-attribution seams silently "
        "no-op'd (mark allow_quiet=True only for pure-math units)")


@pytest.fixture(autouse=True)
def _prefix_cache_guard(request):
    """Tier-1 guard for @pytest.mark.prefix_cache (ISSUE 7 satellite):
    a test that CLAIMS cross-session prefix-cache coverage must not
    silently run cache-off serving — if no attach() hit was recorded
    during the test, every row prefilled from scratch and the test's
    reuse claims are vacuous; fail LOUD. Eviction/miss/offload unit
    tests (which legitimately serve cold) mark allow_cold=True."""
    marker = request.node.get_closest_marker("prefix_cache")
    if marker is None:
        yield
        return
    from theroundtaible_tpu.engine import prefix_cache as pc

    pc.reset_test_counters()
    yield
    if marker.kwargs.get("allow_cold"):
        return
    assert pc.hits_seen() > 0, (
        "prefix_cache-marked test recorded ZERO cache attach hits: the "
        "cross-session prefix cache silently served nothing (cache-off "
        "fallback?) — mark allow_cold=True only for eviction/miss/"
        "offload units")


@pytest.fixture(autouse=True)
def _ragged_attn_guard(request):
    """Tier-1 guard for @pytest.mark.ragged_attn (ISSUE 8 satellite): a
    test that CLAIMS ragged mixed-dispatch coverage must not silently
    serve the prologue or the XLA fallback — if the provenance sink
    recorded ZERO ragged KERNEL dispatches during the test, the ragged
    path never ran (kill-switch left on, shape silently declined, join
    never deferred); fail LOUD. XLA-fallback units mark
    allow_fallback=True, which still requires SOME ragged dispatch."""
    marker = request.node.get_closest_marker("ragged_attn")
    if marker is None:
        yield
        return
    from theroundtaible_tpu.engine.pallas import attention as pattn

    pattn.reset_ragged_counters()
    yield
    if marker.kwargs.get("allow_fallback"):
        assert (pattn.ragged_kernel_dispatches()
                + pattn.ragged_fallback_dispatches()) > 0, (
            "ragged_attn-marked test issued NO ragged dispatches at "
            "all — the mixed-dispatch path silently never ran")
        return
    assert pattn.ragged_kernel_dispatches() > 0, (
        "ragged_attn-marked test recorded ZERO ragged-kernel "
        "dispatches: the ragged path silently fell back or never ran "
        "(mark allow_fallback=True only for XLA-path units)")


@pytest.fixture(autouse=True)
def _spec_decode_guard(request):
    """Tier-1 guard for @pytest.mark.spec_decode (ISSUE 9 satellite):
    a test that CLAIMS speculative-decoding coverage must not silently
    serve 1-token decode — if no verify dispatch during the test ever
    ACCEPTED a drafted token, speculation either never ran (kill-switch
    left on, drafter never proposed) or never paid off, and the test's
    multi-token claims are vacuous; fail LOUD. Rejection/throttle unit
    tests (which legitimately accept nothing) mark allow_cold=True."""
    marker = request.node.get_closest_marker("spec_decode")
    if marker is None:
        yield
        return
    from theroundtaible_tpu.engine import spec_decode as spec_mod

    spec_mod.reset_test_counters()
    yield
    if marker.kwargs.get("allow_cold"):
        return
    assert spec_mod.accepted_seen() > 0, (
        "spec_decode-marked test never ACCEPTED a drafted token "
        f"({spec_mod.dispatches_seen()} verify dispatches, "
        f"{spec_mod.drafted_seen()} drafted): speculation silently "
        "served 1-token decode — mark allow_cold=True only for "
        "rejection/throttle units")
    if marker.kwargs.get("tree") and not marker.kwargs.get("allow_chain"):
        # ISSUE 13: a test CLAIMING tree-verify coverage must have
        # walked a MULTI-NODE accepted path (>= 2 edges) at least once
        # — single-edge acceptance is indistinguishable from a lucky
        # chain, so a silent degrade-to-chain (no free pages, no
        # root-distinct proposals) would make the tree claims vacuous.
        assert spec_mod.tree_accepted_paths_seen() > 0, (
            "spec_decode(tree=True)-marked test never accepted a "
            f"multi-node tree path ({spec_mod.tree_nodes_seen()} tree "
            "nodes packed): tree verify silently degraded to chain — "
            "mark allow_chain=True only for chain-only units")


@pytest.fixture(autouse=True)
def _lora_guard(request):
    """Tier-1 guard for @pytest.mark.lora (ISSUE 10 satellite): a test
    that CLAIMS multi-LoRA co-batching coverage must not silently serve
    one adapter (or the base) at a time — if no dispatch during the
    test ever carried >= 2 DISTINCT non-base adapters in one program,
    the grouped-batched path never actually mixed personas and the
    test's co-batching claims are vacuous; fail LOUD. Store/evict/
    kernel unit tests (which legitimately run single-adapter) mark
    allow_single=True."""
    marker = request.node.get_closest_marker("lora")
    if marker is None:
        yield
        return
    from theroundtaible_tpu.engine import lora as lora_mod

    lora_mod.reset_test_counters()
    yield
    if marker.kwargs.get("allow_single"):
        return
    assert lora_mod.max_mixed_seen() >= 2, (
        "lora-marked test never mixed >= 2 distinct adapters in one "
        f"dispatch (max {lora_mod.max_mixed_seen()} across "
        f"{lora_mod.dispatches_seen()} dispatches): grouped batched "
        "LoRA silently served per-adapter — mark allow_single=True "
        "only for store/evict/kernel units")


@pytest.fixture(autouse=True)
def _kv_quant_guard(request):
    """Tier-1 guard for @pytest.mark.kv_quant (ISSUE 11 satellite): a
    test that CLAIMS quantized-KV-page coverage must not silently serve
    bf16 pools — if no serving dispatch during the test ever READ a
    quantized page (kernel-dequant or XLA-dequant), the `kv_quant:`
    config silently resolved off (kill-switch left armed, spec declined
    at construction) and the test's compression
    claims are vacuous; fail LOUD. Decline/fallback/kill-switch unit
    tests (which legitimately serve bf16) mark allow_bf16=True."""
    marker = request.node.get_closest_marker("kv_quant")
    if marker is None:
        yield
        return
    from theroundtaible_tpu.engine import kv_quant as kvq_mod

    kvq_mod.reset_test_counters()
    yield
    if marker.kwargs.get("allow_bf16"):
        return
    assert kvq_mod.quant_dispatches() > 0, (
        "kv_quant-marked test recorded ZERO quantized-page dispatches: "
        "serving silently ran bf16 pools (kill-switch armed? spec "
        "declined?) — mark allow_bf16=True only for "
        "decline/fallback/kill-switch units")


@pytest.fixture(autouse=True)
def _supervision_guard(request):
    """Tier-1 guard for @pytest.mark.supervision (ISSUE 12 satellite):
    a test that CLAIMS engine-supervision coverage must actually cross
    an engine restart — if the supervisor never ran a restart cycle
    (successful OR budgeted-failed) during the test, the quiesce →
    evacuate → rebuild → restore machinery silently never engaged
    (kill-switch left on, detection never triggered) and the test's
    recovery claims are vacuous; fail LOUD. Detection/journal/gate unit
    tests (which legitimately never rebuild) mark allow_norestart=True.
    The guard also restores the process supervisor singleton, so one
    test's dead-engine verdict can never poison another's submits."""
    marker = request.node.get_closest_marker("supervision")
    if marker is None:
        yield
        return
    from theroundtaible_tpu.engine import supervisor as sup_mod

    sup_mod.set_supervisor(None)
    sup_mod.reset_test_counters()
    yield
    restarts = sup_mod.restarts_seen()
    sup_mod.set_supervisor(None)
    if marker.kwargs.get("allow_norestart"):
        return
    assert restarts > 0, (
        "supervision-marked test never crossed an engine restart: the "
        "supervisor's quiesce/evacuate/rebuild/restore cycle silently "
        "never ran (mark allow_norestart=True only for detection/"
        "journal/gate units)")


@pytest.fixture(autouse=True)
def _gateway_guard(request):
    """Tier-1 guard for @pytest.mark.gateway (ISSUE 16 satellite): a
    test that CLAIMS serving-gateway coverage must actually stream
    tokens over a REAL socket — if no SSE token event was written (and
    drained) to a connection during the test, the HTTP front door
    silently never served (in-memory shortcuts, dead pump, unopened
    stream) and the test's serving claims are vacuous; fail LOUD.
    Admission/journal/event-id unit tests (which legitimately never
    open a socket) mark allow_no_stream=True."""
    marker = request.node.get_closest_marker("gateway")
    if marker is None:
        yield
        return
    from theroundtaible_tpu.gateway import streams as streams_mod

    streams_mod.reset_test_counters()
    yield
    if marker.kwargs.get("allow_no_stream"):
        return
    assert streams_mod.tokens_streamed() > 0, (
        "gateway-marked test streamed ZERO tokens over a real socket: "
        "the SSE serving path silently never ran (mark "
        "allow_no_stream=True only for admission/journal/event-id "
        "units)")


@pytest.fixture(autouse=True)
def _router_guard(request):
    """Tier-1 guard for @pytest.mark.router (ISSUE 17 satellite): a
    test that CLAIMS multi-replica routing coverage must actually cross
    a replica boundary — if no session's KV pages were adopted onto
    another replica (migration) and no journal replay ran on a survivor
    (failover) during the test, the evacuate → adopt → restore transfer
    fabric silently never engaged (everything stayed on one engine) and
    the test's fleet claims are vacuous; fail LOUD. Scoring/signals/
    assignment unit tests (which legitimately never move KV) mark
    allow_local=True. The guard also clears the process-wide active
    router, so one test's fleet can never leak into another's
    fleet_health()/status view."""
    marker = request.node.get_closest_marker("router")
    if marker is None:
        yield
        return
    from theroundtaible_tpu.router import core as router_core

    router_core.set_active_router(None)
    router_core.reset_test_counters()
    yield
    crossings = router_core.boundary_crossings()
    router_core.set_active_router(None)
    if marker.kwargs.get("allow_local"):
        return
    assert crossings > 0, (
        "router-marked test never crossed a replica boundary: no "
        "migration adopt and no failover replay ran — the evacuate/"
        "adopt/restore fabric silently never engaged (mark "
        "allow_local=True only for scoring/signals/assignment units)")


@pytest.fixture(autouse=True)
def _loadgen_guard(request):
    """Tier-1 guard for @pytest.mark.loadgen (ISSUE 19 satellite): a
    test that CLAIMS offered-load harness coverage must actually OFFER
    load — if the driver never held >= 2 concurrent open-loop sessions
    in flight during the test, the harness silently served closed-loop
    (or one-at-a-time), arrivals waited on completions, and the test's
    open-loop capacity claims are vacuous; fail LOUD. Arrival/workload/
    capacity-math unit tests (which never drive a scheduler) mark
    allow_closed=True."""
    marker = request.node.get_closest_marker("loadgen")
    if marker is None:
        yield
        return
    from theroundtaible_tpu.loadgen import driver as lg_driver

    lg_driver.reset_test_counters()
    yield
    if marker.kwargs.get("allow_closed"):
        return
    assert lg_driver.open_loop_peak() >= 2, (
        "loadgen-marked test never drove >= 2 concurrent OPEN-LOOP "
        f"sessions (peak {lg_driver.open_loop_peak()}): arrivals "
        "silently waited on completions — closed-loop in disguise "
        "(mark allow_closed=True only for arrival/workload/"
        "capacity-math units)")


@pytest.fixture(autouse=True)
def _telemetry_guard(request):
    """Tier-1 guard for @pytest.mark.telemetry (ISSUE 5 satellite): a
    test that CLAIMS span-tracing coverage runs with telemetry armed,
    and if NO span was emitted during it the tracing silently no-op'd
    (disarm regression, broken seam) — fail LOUD. Registry/flight-
    recorder-only unit tests mark allow_no_spans=True. The guard
    restores the armed flag so unmarked tests keep measuring the
    disarmed (zero-overhead) hot path."""
    marker = request.node.get_closest_marker("telemetry")
    if marker is None:
        yield
        return
    from theroundtaible_tpu.utils import telemetry

    was_active = telemetry.ACTIVE
    telemetry.arm()
    telemetry.reset_spans_emitted()
    yield
    emitted = telemetry.spans_emitted()
    if not was_active:
        telemetry.disarm()
    if not marker.kwargs.get("allow_no_spans"):
        assert emitted > 0, (
            "telemetry-marked test emitted NO spans: the span seams "
            "silently no-op'd (mark allow_no_spans=True only for "
            "registry/recorder unit tests)")


@pytest.fixture(autouse=True)
def _tracing_guard(request):
    """Tier-1 guard for @pytest.mark.tracing (ISSUE 20): a test that
    CLAIMS end-to-end trace-propagation coverage must actually link the
    layers — if no trace id during the test appeared on BOTH a serving-
    layer span (rung request/resume, the gateway/driver root) and an
    engine-side span (turn/segment/dispatch), context propagation
    silently broke at the gateway→scheduler seam (detached submit,
    dropped parent, unthreaded ctx) and the test's tracing claims are
    vacuous; fail LOUD. Parser/stage-math/retention unit tests (which
    never cross the seam) mark allow_local=True. The guard arms
    telemetry (spans gate on ACTIVE) and clears the trace ring so
    retention assertions see only this test's traces."""
    marker = request.node.get_closest_marker("tracing")
    if marker is None:
        yield
        return
    from theroundtaible_tpu.utils import telemetry, tracing

    was_active = telemetry.ACTIVE
    telemetry.arm()
    tracing.store().reset()
    # The span ring is bounded: once a worker's earlier files have
    # filled it its length stands still, so this test's spans are the
    # ones after the newest one there now — found by identity.
    ring = telemetry.recorder().span_events()
    newest = ring[-1] if ring else None

    def spans_since():
        ring = telemetry.recorder().span_events()
        for i in range(len(ring) - 1, -1, -1):
            if ring[i] is newest:
                return ring[i + 1:]
        return ring             # turned over: all of it is newer

    yield
    # The request/turn spans end asynchronously (pump thread, scheduler
    # loop) after the client reads its terminal event — give them a
    # moment to land in the flight ring before judging.
    deadline = time.monotonic() + 3.0
    while True:
        spans = spans_since()
        if (tracing.cross_layer_count(spans) > 0
                or time.monotonic() > deadline):
            break
        time.sleep(0.05)
    if not was_active:
        telemetry.disarm()
    if marker.kwargs.get("allow_local"):
        return
    assert tracing.cross_layer_count(spans) > 0, (
        "tracing-marked test never produced a CROSS-LAYER trace: no "
        "trace id appeared on both a serving span (request/resume) and "
        "an engine span (turn/segment/dispatch) — context propagation "
        "silently broke at the gateway→scheduler seam (mark "
        "allow_local=True only for parser/stage-math/retention units)")


@pytest.fixture
def project_root(tmp_path):
    """A scratch project dir with a .roundtable skeleton."""
    (tmp_path / ".roundtable" / "sessions").mkdir(parents=True)
    return tmp_path


# Real-checkpoint recipe shared by test_e2e_checkpoint (HF-parity serving)
# and test_emergent_consensus (constructed-weights discuss): one place
# owns the tokenizer training + transformers-Llama save layout.

CKPT_CORPUS = [
    "the knights debate the session store design at the roundtable",
    "caching and consensus and chronicles and decrees",
    "a verify command runs in the sandbox with a timeout"] * 50


def save_trained_tokenizer(d, vocab_size=300, extra_tokens=()):
    """Train a real BPE tokenizer on CKPT_CORPUS and save it to `d` in HF
    layout (pad/bos/eos/unk = 0/1/2/3). `extra_tokens` are added as
    NON-special tokens (their content survives decode). Returns the
    PreTrainedTokenizerFast."""
    from tokenizers import Tokenizer, models, pre_tokenizers, trainers
    from transformers import PreTrainedTokenizerFast

    tok = Tokenizer(models.BPE(unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.train_from_iterator(CKPT_CORPUS, trainers.BpeTrainer(
        vocab_size=vocab_size,
        special_tokens=["<pad>", "<bos>", "<eos>", "<unk>"]))
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tok, bos_token="<bos>", eos_token="<eos>",
        pad_token="<pad>", unk_token="<unk>")
    if extra_tokens:
        assert fast.add_tokens(list(extra_tokens)) == len(extra_tokens)
    fast.save_pretrained(d)
    return fast


def make_tiny_hf_llama(vocab_size, *, hidden_size=64, seed=None,
                       max_position_embeddings=256):
    """A transformers LlamaForCausalLM in the tiny-llama shape family
    (2 layers, 4 heads / 2 kv, mlp 128) — the real HF modeling code the
    checkpoint loader and tokenizer pipeline are tested against."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    if seed is not None:
        torch.manual_seed(seed)
    hf = LlamaForCausalLM(LlamaConfig(
        vocab_size=vocab_size, hidden_size=hidden_size,
        intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=max_position_embeddings,
        rms_norm_eps=1e-6, rope_theta=10_000.0, tie_word_embeddings=False,
        attention_bias=False, mlp_bias=False,
        bos_token_id=1, eos_token_id=2, pad_token_id=0))
    hf.eval()
    return hf
