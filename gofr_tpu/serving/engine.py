"""Continuous-batching inference engine — the TPU serving hot loop.

The component BASELINE.json's north star adds on top of the GoFr
surface: requests from any transport (HTTP handler, gRPC stream,
pub/sub worker) are coalesced in front of the device.

Architecture (one device or one mesh):

- A dedicated **engine thread** owns all device calls, so the asyncio
  serving loop never blocks on the TPU. Handlers ``submit()`` requests
  and consume an ``asyncio.Queue`` of tokens bridged via
  ``loop.call_soon_threadsafe``.
- **Decode is one fixed-shape jitted step** over ``max_batch`` slots
  (inactive slots are masked), so XLA compiles exactly one decode
  graph. KV caches are donated — updated in place in HBM.
- **Prefill is bucketed** (prompt padded to power-of-two lengths) to
  bound recompiles; each bucket compiles once.
- Per-slot sampling params ride as arrays; greedy rows use argmax,
  stochastic rows use gumbel sampling, selected with ``jnp.where`` so
  one graph serves every mix.
- Scheduling: waiting prefills are admitted whenever a slot is free
  (prefill-priority keeps TTFT low; decode continues for everyone else
  next step).

The KV cache is ONE page pool behind per-slot block tables
(``ops/paged_kv.py``): pages are allocated on admission and freed on
retire, page-aligned prompt prefixes are shared by refcount, and the
newest request is preempted by recompute when the pool runs dry — KV
capacity is decoupled from ``max_batch x max_seq`` (``kv_pages=None``
reserves exactly that much). Two attention paths read it
(``EngineConfig.paged_attention``): the native path, where the model's
paged steps write rows through the tables and the ragged kernels read
pages in place, and the view path, where a dense per-slot view is
gathered for the family's dense steps — the path of a mesh-sharded
engine and of a family without paged steps.

Scheduler state is **device-resident**: per-slot lengths, sampling
params, page tables and the active mask live as persistent device
arrays, re-uploaded only when an admission/retirement/preemption
event changes them (``_sync_decode_state``). The decode graph advances
lengths and the sampling-rng counter on device, so a steady-state
decode dispatch performs ZERO host->device transfers — re-uploading
unchanged scheduler state every pass is now considered a bug (it was
the measured bottleneck of the overhead-bound round-5 chip decode).
"""

from __future__ import annotations

import asyncio
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.annotations import hot_path, hot_path_boundary
from .faults import NO_FAULTS, resolve_plan
from .spec import (MAX_TREE_NODES, DraftTree, NgramIndex, SpecController,
                   build_draft_tree)

NEG_INF = -1e30


@dataclass
class SamplingParams:
    temperature: float = 0.7
    top_p: float = 1.0
    #: 0 disables the *explicit* top-k filter; stochastic sampling is
    #: always bounded to the ``TOPK_BOUND`` (64) most likely tokens —
    #: the engine's sampling graph never materialises the full-vocab
    #: distribution (see ``_sample_batch``).
    top_k: int = 0
    max_new_tokens: int = 128


@dataclass
class GenRequest:
    prompt_tokens: list[int]
    params: SamplingParams
    submitted_at: float = field(default_factory=time.time)
    first_token_at: float | None = None
    finished_at: float | None = None
    # engine-internal
    slot: int = -1
    generated: list[int] = field(default_factory=list)
    out_queue: Any = None          # asyncio.Queue[int | None]
    loop: Any = None               # the submitting event loop
    error: str | None = None
    cancelled: bool = False        # consumer gone: retire, don't decode
    admit_order: int = -1          # paged preemption picks the newest;
                                   # assigned once at first admission and
                                   # kept across preemption-requeues so a
                                   # re-admitted old request stays old
    pending_prefill: bool = False  # mid chunked-prefill OR awaiting a
                                   # dispatched batch prefill: holds a
                                   # slot but must not decode yet
    prefill_offset: int = 0        # next chunk's start position
    prefill_epoch: int = 0         # bumps per batch-prefill dispatch so
                                   # a stale in-flight result can never
                                   # attach to a requeued request
    # -- observability (host-side only; see serving/observability.py)
    trace: Any = None              # (trace_id, parent_span_id) when the
                                   # submitter's trace is sampled — the
                                   # engine.* spans assemble at retire
    admitted_at: float | None = None  # first slot assignment (queue end)
    rid: int = -1                  # engine-local request id, set in
                                   # submit(): the request's spans and
                                   # flight-log entry carry it, and a
                                   # pass record names the rids it served
    events: list = field(default_factory=list)  # (name, t0, t1, attrs)
    _obs_done: bool = False        # finalize-once guard (retire + fail)
    tenant: str | None = None      # bounded tenant label from the auth
                                   # principal (TenantResolver); stamped
                                   # into spans/usage, accounted by the
                                   # UsageLedger at retire
    device_s: float = 0.0          # this request's share of each pass's
                                   # busy span (busy/occupancy per pass,
                                   # accumulated at collect — host float
                                   # adds on an existing loop)
    waste_recompute_s: float = 0.0  # slice of device_s re-prefilling KV
                                    # this request already computed once
                                    # (preemption-by-recompute) — the
                                    # per-tenant "who pays for
                                    # preemption" column
    waste_spec_s: float = 0.0       # slice of device_s spent on this
                                    # request's REJECTED draft tokens
    spec_index: Any = None          # per-request NgramIndex (lazy; fed
                                    # incrementally by _draft_proposals,
                                    # rebuilt when the token stream is
                                    # rewritten by preempt/recover)
    lane: str = "interactive"      # scheduler lane (interactive |
                                   # background); explicit submit() lane
                                   # wins over the config's tenant->lane
                                   # mapping
    reject: Any = None             # scheduler.SchedReject stamped when
                                   # admission refused the request —
                                   # handlers turn it into 429/503 with
                                   # Retry-After instead of a blanket 503
    recovered: bool = False        # salvaged across an engine restart
                                   # before its first token: the replay
                                   # prefill recomputes KV it already
                                   # paid for once, priced under the
                                   # preempt_recompute goodput cause
    digest: str | None = None      # output fingerprint, stamped once at
                                   # the retire boundary by the
                                   # integrity plane's digest fold
                                   # (serving/integrity.py)
    probe: str = ""                # golden-canary id when this request
                                   # IS an integrity probe — its device
                                   # time re-prices to integrity_probe
                                   # waste and its digest is judged
                                   # against probe_expected at retire
    probe_expected: str = ""       # the sealed golden digest a probe
                                   # must reproduce bit-for-bit

    def _emit(self, token: int | None) -> None:
        if self.out_queue is not None and self.loop is not None:
            try:
                self.loop.call_soon_threadsafe(self.out_queue.put_nowait,
                                               token)
            except RuntimeError:
                # the submitter's event loop died (client disconnect,
                # worker reload): stop emitting to it — one dead client
                # must never take down the engine hot loop
                self.out_queue = None
                self.loop = None

    @property
    def ttft_ms(self) -> float | None:
        if self.first_token_at is None:
            return None
        return (self.first_token_at - self.submitted_at) * 1000.0


@dataclass
class RestartPolicy:
    """Crash-recovery budget for the in-thread engine supervisor: on a
    hot-loop exception the loop salvages what it safely can (see
    ``Engine._recover``), rebuilds runtime state on the resident
    weights and compiled graphs, sleeps a deterministic exponential
    backoff, and resumes — up to ``max_restarts`` times, after which
    the crash is terminal (health DOWN, the old ``_crash`` semantics).
    """
    max_restarts: int = 3       # lifetime restart budget; 0 = disabled
    backoff_s: float = 0.05     # sleep before restart #1
    backoff_mult: float = 2.0   # growth per successive restart
    max_backoff_s: float = 5.0  # backoff ceiling

    def backoff_for(self, attempt: int) -> float:
        """Deterministic backoff before restart ``attempt`` (1-based)."""
        return min(self.max_backoff_s,
                   self.backoff_s * self.backoff_mult ** max(0, attempt - 1))


@dataclass
class EngineConfig:
    max_batch: int = 8          # decode slots
    max_seq: int = 1024         # per-slot kv capacity
    prefill_buckets: tuple = (32, 64, 128, 256, 512, 1024)
    eos_id: int = -1            # -1: never stop on eos
    #: decode steps fused into one device call (lax.scan). Each host
    #: round-trip then yields K tokens per slot instead of 1 — the
    #: per-token host/dispatch overhead divides by K. Tokens stream in
    #: bursts of K and admission happens between passes, so large K
    #: trades TTFT/streaming granularity for throughput.
    decode_steps_per_pass: int = 8
    #: fused multi-pass decode: how many K-step passes the on-device
    #: decode loop runs per dispatch (M). One dispatch then yields
    #: K x M tokens per slot with device-side token feedback and
    #: length advancement — the Python dispatch/collect overhead per
    #: token divides by another factor of M. Admission, retirement and
    #: draft checks still happen only between dispatches, so large M
    #: trades scheduling granularity (and wasted steps past a
    #: finishing request's budget) for throughput. 1 = the classic
    #: single-pass dispatch.
    decode_passes_per_dispatch: int = 1
    #: widths of the VIEW path's gather: extra decode-graph variants
    #: that gather and scatter back only the table columns covering
    #: the first ``window`` rows of each slot (the native path walks
    #: live pages only and ignores this). Each pass picks the smallest
    #: listed window covering every live length + K; none covering ->
    #: the full-max_seq graph. The view's HBM traffic becomes
    #: O(longest live row), not O(max_seq). Each window is one extra
    #: compile (warmed in warmup()). () = off.
    decode_windows: tuple = ()
    #: waiting requests prefilled per device call. The prefill graph is
    #: a fixed [P, bucket] shape (short groups ride with masked dummy
    #: rows, which cost nothing extra — the shapes are static either
    #: way), so a burst of arrivals costs ceil(n/P) device round-trips
    #: instead of n. Keep modest: P multiplies per-call prefill FLOPs.
    prefill_batch: int = 8
    #: sampling RNG seed; None draws entropy from ``os.urandom`` so two
    #: engines started in the same millisecond never share streams. Set
    #: for reproducible generation in tests/evals.
    seed: int | None = None
    #: admission bound: waiting requests beyond this fail immediately
    #: with "engine overloaded" (surfaced as a 503 by the handlers)
    #: instead of growing an unbounded queue where every TTFT degrades
    #: together. 0 = unbounded. Already-admitted work that bounces
    #: back (preemption, slot races) bypasses the bound.
    max_waiting: int = 0
    #: chunked-prefill pacing: how many bucket-width chunks of a long
    #: prompt run per engine pass. Decode for every other slot
    #: interleaves between passes, so one giant prompt cannot
    #: head-of-line block the whole batch.
    prefill_chunks_per_pass: int = 2
    #: stall detection: with work in flight, a loop that has not
    #: completed a pass for this long (a wedged device runtime)
    #: flips health to DEGRADED so orchestrators can act —
    #: exceptions are contained separately (health DOWN). 0 disables.
    stall_threshold_s: float = 120.0
    #: stall ESCALATION cadence: a watchdog thread polls
    #: ``health_check()`` every this many seconds and, when the stall
    #: flag flips, dumps the flight recorder, emits an ``engine.stall``
    #: span + ``app_engine_stalls`` counter, and leaves health DEGRADED
    #: for the next control-plane heartbeat so the leader can evict
    #: instead of waiting for heartbeat silence. Pure host-side
    #: polling off the hot loop. 0 disables the watchdog.
    watchdog_interval_s: float = 5.0
    #: the one KV layout; any other value is refused (the contiguous
    #: "slot" layout was removed in PR 30). The field is still accepted
    #: only because the benchmark's configuration files name it as an
    #: ``EngineConfig`` key: it goes when they drop the key (ROADMAP C2).
    kv_layout: str = "paged"
    #: rows per KV page
    page_size: int = 64
    #: pool size in pages; None sizes the pool to the full contiguous
    #: capacity (max_batch x ceil(max_seq/page_size)). Smaller values
    #: overcommit: more concurrent short requests in the same HBM.
    kv_pages: int | None = None
    #: KV page storage dtype. "bf16" (default)
    #: stores pages in the model dtype — bit-identical to the classic
    #: pool. "int8" stores narrow codes plus one f32 scale per row
    #: (ops/paged_kv.py quantized pool): pages quantize on write
    #: inside the jitted scatters and the ragged kernels dequantize
    #: in-register after each per-page DMA, so per-row HBM cost falls
    #: from 2·hd to hd+4 bytes — at the same byte budget the pool
    #: holds ~2x the pages (1.88x at hd=64, 1.94x at hd=128).
    kv_dtype: str = "bf16"
    #: explicit KV pool HBM budget in bytes (K and V together). None
    #: derives the budget from ``kv_pages`` (or the full contiguous
    #: capacity) at the NATIVE page cost, so switching
    #: ``kv_dtype`` to int8 under the same budget grows the page count
    #: instead of shrinking the footprint — capacity is the point.
    kv_pool_bytes: int | None = None
    #: retain retired requests' page-aligned prompt prefixes and share
    #: them with later requests bearing the same prefix (the common
    #: system prompt) — the suffix prefills through
    #: the chunk-with-history path, skipping the shared compute
    #: entirely. Shared pages are read-only by construction (decode
    #: and suffix writes land past the aligned prefix) and refcounted;
    #: cache entries evict LRU under pool pressure.
    prefix_cache: bool = True
    #: cap on pages pinned by the prefix cache; None = a quarter of
    #: the pool.
    prefix_cache_pages: int | None = None
    #: prefix-cache digest published to the fleet: the newest N cache
    #: keys are hashed (serving/router.py prefix_hash) at the throttled
    #: gauge boundary and attached to heartbeat summaries so the
    #: leader's router can score hosts by longest resident prefix.
    #: 0 disables the digest (heartbeats carry no prefix_digest key).
    prefix_digest_hashes: int = 64
    #: speculative decoding (opt-in): draft tokens by prompt-lookup
    #: (an n-gram of the recent context matched earlier in
    #: prompt+generated proposes its continuation) and verify them in
    #: ONE parallel pass — accepted drafts + one bonus token land per
    #: pass instead of one token. Greedy outputs are identical to
    #: vanilla decode; non-greedy slots never accept drafts (their
    #: bonus token still samples with their own params).
    speculative: bool = False
    #: max draft tokens verified per pass
    spec_draft: int = 4
    #: n-gram width the prompt-lookup draft matches on
    spec_ngram: int = 3
    #: candidate continuations drafted per pass: the n-gram index
    #: proposes up to this many distinct continuations, trie-merged
    #: into ONE draft tree and verified together under a packed
    #: ancestor bitmask (1 + spec_draft * spec_branches <= 32 nodes).
    spec_branches: int = 2
    #: goodput-driven draft controller: per-slot accept-rate EWMA
    #: priced against fitted decode sec/token and verify row cost —
    #: drafting shrinks/stops per slot when expected accepted tokens
    #: stop paying for the marginal verify rows. False = the static
    #: always-full-depth policy.
    spec_adaptive: bool = True
    #: accept-rate EWMA floor under which a slot's drafting is
    #: disabled (re-probed every spec_probe_interval passes)
    spec_accept_floor: float = 0.1
    #: passes between single-node probes of a disabled slot
    spec_probe_interval: int = 32
    #: attention path over the pool: "auto" = the ragged
    #: paged-attention kernel on TPU (pages read in place, no per-pass
    #: view materialisation) and the gather/scatter view path
    #: elsewhere; "kernel" / "interpret" / "xla" force the native path
    #: with that paged-attention implementation; "view" forces
    #: gather/scatter. A family that supplies no ``paged_decode_fn``
    #: (the Mixtral-style MoE, any engine under a mesh) serves through
    #: the view whatever this says.
    paged_attention: str = "auto"
    #: decode-pipeline depth: dispatched passes left uncollected after
    #: each iteration. 1 overlaps the host round-trip (token download,
    #: stream emission, admissions) with device compute — but tokens
    #: arrive one pass late, each retirement wastes the pass its slot
    #: rides out, and freshly admitted requests see their first token
    #: behind a decode pass. None = adaptive: depth 1 only while at
    #: least ``pipeline_min_slots`` slots are actively decoding (the
    #: saturated regime where overlap pays for the waste); depth 0
    #: otherwise, where the waste dominates (the r4 tiny-config CPU
    #: bench ran ~9x slower always-pipelined: 381.6 -> 41.6 req/s).
    pipeline_depth: int | None = None
    #: adaptive-pipelining threshold (``pipeline_depth=None`` only):
    #: minimum actively-decoding slots before a pass is left in flight.
    pipeline_min_slots: int = 8
    #: flight recorder ring size: per-pass records (kind, enqueue and
    #: result times, the rows' request ids and context lengths,
    #: occupancy, queue depth, tokens, h2d count, preemptions) kept in
    #: a fixed ring beside the ring of the loop's phase spans, served
    #: at ``/debug/engine``, summarized by ``health_check()`` and
    #: dumped on a loop crash. Sized for a minute at several times the
    #: pass rate the chip shows today. Recording is append-only host
    #: work — zero device perturbation. 0 disables passes, requests
    #: and spans alike.
    flight_recorder_size: int = 4096
    #: retired-request event logs kept alongside the pass ring
    flight_recorder_requests: int = 512
    #: workload capture: arm the WorkloadRecorder at construction so
    #: every retired request lands in the capture ring (arrival time,
    #: prompt ids, gen params, seed, tenant, outcome) — the replayable
    #: workload file behind ``GET /debug/workload``. Off by default;
    #: ``POST /debug/workload/start`` arms it at runtime regardless.
    #: Recording is retire-time host work — zero hot-path perturbation
    #: (transfer-guard + greedy bit-identity hold with capture ON).
    workload_capture: bool = False
    #: capture ring bound: retired-request records kept (oldest drop,
    #: counted). 0 disables the recorder entirely.
    workload_capture_requests: int = 4096
    #: redact captured workloads: prompt/completion token ids are
    #: replaced by salted hashes (lengths kept) — shippable off-box,
    #: not bit-identity-replayable (serving/observability.py)
    capture_redact: bool = False
    #: goodput accounting + memory watermarks: classify every pass's
    #: busy device time into useful vs. waste causes (padding,
    #: preempt_recompute, spec_rejected, bubble) at collect/retire,
    #: with useful + sum(waste) == busy conserved, and track KV/prefix/
    #: host-RSS high-water marks. Host float arithmetic on existing
    #: collect paths — zero hot-path perturbation (transfer-guard +
    #: greedy bit-identity hold with it ON). Surfaced as
    #: app_engine_goodput_ratio / app_engine_waste_seconds{cause} /
    #: app_engine_*_watermark and GET /debug/efficiency.
    goodput: bool = True
    #: recompile sentinel: after warmup() seals the expected shape set,
    #: a dispatch whose (kind, shape) signature warmup never compiled
    #: bumps app_engine_recompiles and WARNs once with the offending
    #: signature — a shape-induced recompile storm names itself before
    #: p99 does. O(1) host set lookups; engines that never warm up
    #: never seal, so cold compiles stay silent.
    recompile_sentinel: bool = True
    #: pass-cost observatory (serving/costmodel.py): per-dispatch-
    #: signature EWMA + variance of pass device time and per-row/
    #: per-token cost, fed host-side at the existing collect
    #: boundaries with the same durations the goodput ledger bills —
    #: zero hot-path perturbation (transfer-guard + greedy
    #: bit-identity hold with it ON). Surfaced at GET /debug/costs,
    #: in /debug/efficiency, on heartbeat summaries (fleet
    #: federation) and in workload headers (replay divergence).
    cost_model: bool = True
    #: EWMA weight for the per-signature cost mean/variance
    cost_alpha: float = 0.2
    #: serving-path passes per signature before its drift baseline
    #: seals (warmup never feeds the model — its timings are
    #: compile-laden)
    cost_baseline_passes: int = 32
    #: drift sentinel thresholds: an episode opens when a signature's
    #: EWMA exceeds BOTH baseline * cost_drift_ratio and baseline +
    #: cost_drift_sigma * baseline_std (ratio guards near-zero-std
    #: baselines, sigma guards noisy ones); fires one obs.cost_drift
    #: event + app_engine_cost_drift{kind} + one incident bundle per
    #: episode
    cost_drift_ratio: float = 2.0
    cost_drift_sigma: float = 6.0
    #: anomaly-triggered profiling (serving/costmodel.AutoProfiler):
    #: cost drift, SLO fast-burn or a goodput-floor breach arms a
    #: single-flight ProfilerCapture that auto-stops after
    #: autoprof_passes collected passes or autoprof_max_capture_s;
    #: arms are debounced and GOFR_AUTOPROF=0 is the kill-switch.
    #: The artifact path + cost table attach to the incident bundle.
    autoprof: bool = True
    autoprof_passes: int = 64
    autoprof_max_capture_s: float = 30.0
    autoprof_debounce_s: float = 300.0
    #: goodput-ratio floor that arms the autoprofiler (checked at the
    #: throttled gauge cadence once busy_s > 1); 0 disables the floor
    autoprof_goodput_floor: float = 0.0
    autoprof_dir: str = "/tmp/gofr_tpu_profiles"
    #: output-integrity observatory (serving/integrity.py): fold every
    #: retired request into a blake2b fingerprint at the retire
    #: boundary — stamped into GenRequest/flight recorder/workload
    #: records and judged by golden canary probes + fleet divergence
    #: voting. Zero hot-path perturbation: greedy outputs stay
    #: bit-identical with the plane ON.
    integrity: bool = True
    #: golden canary corpus (gofr-golden JSONL sealed from the replay
    #: corpus by GoldenSet.seal) — None disables probing; the
    #: fingerprint fold alone needs no corpus
    integrity_golden_path: str | None = None
    #: cap on golden entries loaded/probed (the corpus is meant to be
    #: tiny — a handful of short greedy prompts)
    integrity_golden_max: int = 8
    #: launch one golden probe on the scheduler's background lane
    #: every N collected passes (pass-count cadence, never wall
    #: clock); 0 disables probing
    integrity_probe_passes: int = 0
    #: consecutive clean probes that close a mismatch episode so a
    #: later mismatch alarms again (hysteresis, mirroring the
    #: cost-drift sentinel)
    integrity_rearm_probes: int = 2
    #: admission/scheduling/shedding policy (serving/scheduler.py):
    #: weighted fair-share dequeue over per-tenant sub-queues,
    #: interactive/background lanes with starvation preemption,
    #: token-bucket rate limits, burn-rate-driven shedding. None =
    #: default SchedulerConfig (fair-share ON — single-tenant traffic
    #: is strict FIFO, bit-identical to the old queue).
    scheduler: Any = None
    #: deterministic fault injection (serving/faults.py): a FaultPlan,
    #: a plan string ("pass_raise:at=3;..."), or None = read the
    #: ``GOFR_FAULTS`` env (unset -> the NO_FAULTS no-op singleton).
    #: Sites are compiled into the hot loop behind an identity
    #: comparison against NO_FAULTS, so the disabled default costs
    #: nothing and transfer-guard/bit-identity invariants hold.
    faults: Any = None
    #: the fleet flight data recorder (serving/events.py): an
    #: EventLedgerConfig, an EventLedger, True/False, or None = default
    #: ledger unless the ``GOFR_EVENTS`` env disables it. Emission only
    #: happens at already-declared @hot_path_boundary sites, so the
    #: zero-hot-path invariant holds with the ledger ON; False wires
    #: the NO_EVENTS no-op singleton everywhere.
    events: Any = None
    #: crash recovery: a RestartPolicy arms the in-thread supervisor —
    #: a hot-loop exception salvages pre-first-token requests into the
    #: recovery buffer, fails mid-stream ones with a typed retryable
    #: error, rebuilds runtime state on the resident weights/compile
    #: cache and resumes after a deterministic backoff. None (default)
    #: keeps the historical fail-fast semantics: any loop exception is
    #: terminal (health DOWN).
    restart_policy: Any = None


class Engine:
    """Continuous batching over a model family's step functions.

    prefill_fn(params, tokens[P, S], kv_lengths[P]) -> (logits,
        (k [L,P,S,Hkv,hd], v)) where logits is [P, V] (last-position,
        e.g. ``llama_prefill_last``) or [P, S, V] (full; the engine
        gathers each row's last prompt position).
    make_cache(batch, max_seq) -> (k, v) dense caches: how the family
        states its row; the engine builds the page pool from one page
        of it (``_pool_probe``).
    The view path's steps run on a dense per-slot view gathered from
    the pool: decode_fn(params, tokens[B], k_view, v_view, lengths[B])
        -> (logits[B, V], k_view, v_view) — e.g. ``llama_decode_step``;
    ``prefill_chunk_fn`` / ``spec_verify_fn`` likewise. The native
    path's steps take the pools and the block tables
    (``paged_decode_fn``, ``paged_chunk_fn``, ``paged_verify_fn`` — e.g.
    ``llama_decode_step_paged``). A family passes the steps it has.
    A ``paged_decode_fn`` may return a fourth value, a small int32
    vector of counters it took on the device; ``decode_facts`` then
    names them, in the vector's order: name -> reducer of that
    counter's column over a pass's steps (numpy int32 [T]) to the
    number the decode pass record carries under the name.
    """

    def __init__(self, params: Any, config: EngineConfig, *,
                 prefill_fn: Callable, decode_fn: Callable | None = None,
                 make_cache: Callable, prefill_chunk_fn: Callable
                 | None = None, spec_verify_fn: Callable | None = None,
                 paged_decode_fn: Callable | None = None,
                 paged_chunk_fn: Callable | None = None,
                 paged_verify_fn: Callable | None = None,
                 decode_facts: dict[str, Callable] | None = None,
                 metrics: Any = None,
                 logger: Any = None, tracer: Any = None) -> None:
        self.params = params
        self.config = config
        self._decode_facts = dict(decode_facts or {})
        self.metrics = metrics
        self.logger = logger
        #: tracer for engine.* request spans (assembled at retire from
        #: host timestamps); None = no spans. ``app.serve_model`` wires
        #: the container's tracer here.
        self.tracer = tracer
        from .observability import (FlightRecorder, GoodputMeter,
                                    RecompileSentinel, UsageLedger,
                                    WatermarkTracker, WorkloadRecorder)
        self.recorder = FlightRecorder(config.flight_recorder_size,
                                       config.flight_recorder_requests)
        self._rids = itertools.count(1)  # GenRequest.rid (next() is atomic)
        #: device-time waste attribution (useful vs padding/
        #: preempt_recompute/spec_rejected/bubble, conserved against
        #: busy time); fed at collect/retire on the engine thread
        self.goodput = GoodputMeter(config.goodput)
        #: KV/prefix/host-RSS high-water marks (throttled gauge cadence)
        self.watermarks = WatermarkTracker(config.goodput)
        #: post-warmup recompile detection by dispatch shape signature
        self.sentinel = RecompileSentinel(config.recompile_sentinel)
        #: pass-cost observatory: per-signature EWMA/variance cost
        #: model + drift sentinel, fed at the collect boundaries with
        #: the same durations the goodput ledger bills
        from .costmodel import AutoProfiler, CostModel
        self.costs = CostModel(config.cost_model,
                               alpha=config.cost_alpha,
                               baseline_passes=config.cost_baseline_passes,
                               drift_ratio=config.cost_drift_ratio,
                               drift_sigma=config.cost_drift_sigma)
        if self.costs.enabled:
            # heartbeat summaries carry the cost table: the leader's
            # straggler math compares hosts on the SAME signature
            self.recorder.cost_source = self.costs.table
        #: anomaly-triggered profiling: drift / fast-burn / goodput
        #: floor arm a bounded single-flight ProfilerCapture
        _capture = None
        if config.autoprof:
            from .observability import ProfilerCapture
            _capture = ProfilerCapture(base_dir=config.autoprof_dir,
                                       logger=logger)
        self.autoprof = AutoProfiler(
            _capture, enabled=config.autoprof,
            passes=config.autoprof_passes,
            max_capture_s=config.autoprof_max_capture_s,
            debounce_s=config.autoprof_debounce_s, logger=logger)
        #: output-integrity observatory: digest folds at the retire
        #: boundary, golden canary probes on the background lane at a
        #: pass-count cadence, heartbeat digest block for the leader's
        #: divergence vote (serving/integrity.py)
        from .integrity import GoldenSet, IntegrityPlane
        _golden = None
        if config.integrity and config.integrity_golden_path:
            # a missing/corrupt corpus must fail at construction, not
            # silently disable probing mid-incident
            _golden = GoldenSet.load(config.integrity_golden_path,
                                     limit=config.integrity_golden_max)
        self.integrity = IntegrityPlane(
            config.integrity, golden=_golden,
            probe_passes=config.integrity_probe_passes,
            rearm_probes=config.integrity_rearm_probes)
        if self.integrity.enabled:
            # heartbeat summaries carry the probe digests: the
            # leader's divergence vote compares hosts on the SAME
            # golden prompt
            self.recorder.integrity_source = self.integrity.summary
        if self.goodput.enabled:
            # heartbeats and workload headers carry the waste digest
            self.recorder.goodput_source = self.goodput.summary
        #: prefix-cache digest for the fleet router: assembled at the
        #: throttled gauge boundary (dirty-flagged by cache mutation
        #: sites), read by the heartbeat thread via an atomic reference
        self._prefix_digest: dict | None = None
        self._prefix_digest_dirty = True
        if config.prefix_digest_hashes > 0:
            self.recorder.prefix_digest_source = self.prefix_digest
        #: workload capture ring (armed lazily — see EngineConfig.
        #: workload_capture); engine_seed is stamped below once the
        #: sampling seed resolves
        self.workload = WorkloadRecorder(config.workload_capture_requests,
                                         redact=config.capture_redact)
        if self.goodput.enabled:
            self.workload.goodput_source = self.goodput.summary
        if self.costs.enabled:
            # captured workloads carry the recording side's cost table
            # (additive header field) so replay can report per-
            # signature divergence next to efficiency_divergence
            self.workload.cost_source = self.costs.table
        #: per-tenant usage metering, fed at retire (_finalize_obs);
        #: always present (host dicts only) — attach_metrics points it
        #: at the metrics manager so app_tenant_* series populate
        self.usage_ledger = UsageLedger()
        #: SLO burn-rate tracker (serving/observability.SLOTracker);
        #: wired by app.serve_model (or set directly) — None = off
        self.slo = None
        #: MFU basis, derived once at compile time in warmup() from the
        #: decode graph's cost_analysis — None until then (gauge stays 0)
        self._flops_per_token: float | None = None
        self._peak_flops: float | None = None
        self._gauge_wall = time.time()
        self._gauge_tokens = 0
        self._make_cache = make_cache
        # chunked prefill: long prompts in bucket-width chunks against
        # the growing cache (pages written in place via paged_chunk_fn
        # on the native path, else the slot's view is gathered and the
        # chunk scattered back)
        self._prefill_chunk_fn = prefill_chunk_fn
        self._spec_verify_fn = spec_verify_fn
        self._paged_chunk_fn = paged_chunk_fn
        self._paged_verify_fn = paged_verify_fn
        self._spec_enabled = (config.speculative
                              and spec_verify_fn is not None)
        self._spec_toggle = True  # mixed-batch alternation state
        #: goodput-priced speculation policy (serving/spec.py); always
        #: constructed so /debug/efficiency can report it, only
        #: consulted when _spec_enabled
        self._spec_ctrl = SpecController(
            config.max_batch, draft=config.spec_draft,
            branches=config.spec_branches,
            adaptive=config.spec_adaptive,
            accept_floor=config.spec_accept_floor,
            probe_interval=config.spec_probe_interval)
        #: request each controller slot's state belongs to — the
        #: drafting loop resets a slot's EWMA when its tenant changes
        #: (cheaper than hooking every admit/retire site)
        self._spec_ctrl_owner: list = [None] * config.max_batch

        cfg = config
        if cfg.kv_layout != "paged":
            raise ValueError(
                f"kv_layout={cfg.kv_layout!r}: the page pool is the one "
                f"KV layout (the contiguous 'slot' layout was removed in "
                f"PR 30); leave kv_layout unset — kv_pages=None reserves "
                f"the same max_batch x max_seq capacity")
        if cfg.paged_attention not in ("auto", "kernel", "interpret",
                                       "xla", "view"):
            raise ValueError(
                f"paged_attention must be one of auto/kernel/interpret/"
                f"xla/view, got {cfg.paged_attention!r}")
        if cfg.kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8', "
                             f"got {cfg.kv_dtype!r}")
        if cfg.spec_branches < 1:
            raise ValueError(f"spec_branches must be >= 1, got "
                             f"{cfg.spec_branches}")
        if 1 + cfg.spec_draft * cfg.spec_branches > MAX_TREE_NODES:
            raise ValueError(
                f"1 + spec_draft * spec_branches = "
                f"{1 + cfg.spec_draft * cfg.spec_branches} exceeds the "
                f"{MAX_TREE_NODES}-node packed ancestor bitmask; shrink "
                f"spec_draft or spec_branches")
        if not 0.0 < cfg.spec_accept_floor < 1.0:
            raise ValueError(f"spec_accept_floor must be in (0, 1), "
                             f"got {cfg.spec_accept_floor}")
        if cfg.spec_probe_interval < 1:
            raise ValueError(f"spec_probe_interval must be >= 1, got "
                             f"{cfg.spec_probe_interval}")
        #: dtype the dequantized view/model side of a quantized pool
        #: uses (set by _alloc_pool from the probe allocation); None
        #: until a pool exists — plain pools ignore it entirely
        self._kv_view_dtype = None
        #: the model's head_dim — what the view fallback unpacks the
        #: pool's 128-lane rows back to (set by _alloc_pool)
        self._kv_head_dim = None
        #: allocated KV bytes (both caches, scale leaves included) —
        #: quant.quantized_bytes over the cache pytree, set post-alloc
        self._kv_bytes_total = 0

        # persistent XLA compilation cache BEFORE any graph compiles:
        # warmup's compile wall amortizes across processes and
        # restarts instead of being re-paid by each (config/env.py has
        # the one rule for where it lives)
        from ..config.env import enable_compile_cache
        enable_compile_cache()

        # decode + sampling fused into ONE graph returning just the
        # sampled token ids [B] — the per-step host transfer is 4B/slot
        # instead of the full [B, vocab] logits, and none of the
        # sampling math dispatches eagerly (each eager op is its own
        # host-to-device dispatch)
        import os as _os
        seed = (cfg.seed if cfg.seed is not None
                else int.from_bytes(_os.urandom(4), "little"))
        #: the RESOLVED sampling seed (explicit or entropy-drawn) —
        #: captured into workload records so a replay engine built with
        #: EngineConfig(seed=header["engine_seed"]) reproduces the rng
        #: stream; greedy replay is bit-identical either way (argmax)
        self.seed = seed
        self.workload.engine_seed = seed
        if cfg.workload_capture:
            self.workload.start()
        base_key = jax.random.key(seed % (2**31))
        # disjoint rng streams: prefill and decode fold into separate
        # subkeys so their per-step indices can never collide
        decode_key = jax.random.fold_in(base_key, 0)
        prefill_key = jax.random.fold_in(base_key, 1)

        K = max(1, int(cfg.decode_steps_per_pass))
        M = max(1, int(cfg.decode_passes_per_dispatch))
        T = K * M  # tokens per dispatch

        def _fused_decode(step_fn, rng_key, tokens, kc, vc, lengths,
                          step, temps, top_ps, top_ks):
            # T = K x M decode steps in ONE lax.scan: sampled tokens
            # feed back into the next step on-device; rng derives
            # in-graph from the device-resident step counter (no eager
            # random.split, no host scalar upload per pass). The outer
            # passes-per-dispatch loop is fused into the same scan —
            # M multiplies the trip count while the compiled body stays
            # identical, so greedy outputs match M sequential
            # single-pass dispatches bit for bit. rng_key rides as an
            # ARGUMENT (not a captured constant) so the compiled HLO is
            # seed-independent — unseeded engines still hit the
            # persistent compile cache across processes.
            # A family's step may return a fourth value: a small int32
            # vector of counters it took on the device (the sparse-
            # expert families' routing facts). They ride the token
            # array as extra COLUMNS [T, B + n], so the collect's one
            # download brings them: no second read, no sync.
            def one(carry, t):
                toks, kc, vc, lens = carry
                key = jax.random.fold_in(rng_key, step * T + t)
                logits, kc, vc, *facts = step_fn(toks, kc, vc, lens)
                nxt = _sample_batch(logits, key, temps, top_ps, top_ks)
                return (nxt, kc, vc, lens + 1), (nxt, *facts)

            carry, (toks, *facts) = jax.lax.scan(
                one, (tokens, kc, vc, lengths), jnp.arange(T))
            if facts:
                toks = jnp.concatenate(
                    [toks, facts[0].astype(toks.dtype)], axis=1)
            return carry, toks

        def _advance_lengths(lengths, active):
            # persistent device lengths: advance active rows exactly as
            # the host mirror does (clamped at the cache ceiling);
            # pending-prefill sentinels and inactive rows pass through
            return jnp.where(active,
                             jnp.minimum(lengths + T, cfg.max_seq),
                             lengths)

        self._decode_windows: tuple = ()
        self._decode_by_window: dict = {}
        cfg_windows = tuple(sorted(
            w for w in (cfg.decode_windows or ()) if 0 < w < cfg.max_seq))
        #: raw configured windows — the view path's chunk walks use
        #: these; the native path's _decode_windows stays empty
        self._cfg_windows = cfg_windows
        from ..ops.paged_kv import scatter_decode
        from ..ops.attention import is_tpu
        impl = cfg.paged_attention
        if impl == "auto":
            impl = "kernel" if is_tpu() else "view"
        if paged_decode_fn is None:
            impl = "view"
        if impl == "view" and decode_fn is None:
            raise ValueError(
                "the view path needs the family's dense decode_fn and "
                "the native path its paged_decode_fn; neither was given")
        #: what ``paged_attention`` resolved to — "kernel" (compiled
        #: Pallas), "interpret", "xla" (native writes, gather
        #: reference attention) or "view" (gather/scatter round trip)
        self.paged_attention_impl: str = impl
        self._paged_decode_fn = paged_decode_fn
        use_native = impl != "view"
        #: native paged hot paths: the model family writes rows/chunks
        #: through the block tables and attends with the ragged paged
        #: kernels — no per-pass dense view of the pool. Chunked
        #: prefill, prefix-suffix reattachment and speculative verify
        #: follow decode onto the native path whenever the kernel path
        #: is active and the family supplies the paged chunk step.
        self._native_chunk = use_native and paged_chunk_fn is not None
        self._native_verify = use_native and paged_verify_fn is not None
        #: whether the path that resolved can walk chunks with history
        #: (long prompts, prefix-cache suffixes, preemption recomputes):
        #: the native path through ``paged_chunk_fn``, else the view
        #: through ``prefill_chunk_fn``
        self._chunk_walks = (self._native_chunk
                             or prefill_chunk_fn is not None)

        if use_native:
            def _decode_sample(params, tokens, use_prev, prev,
                               k_pool, v_pool, tables, lengths,
                               active, step, temps, top_ps, top_ks,
                               rng_key):
                # native paged path: the model's paged decode step
                # writes each new row through the table and attends
                # with the ragged kernel — the pool is only ever
                # touched in place, no per-pass view (VERDICT r3 #2)
                toks_in = jnp.where(use_prev, prev, tokens)

                def step_fn(toks, kp, vp, lens):
                    return paged_decode_fn(params, toks, kp, vp,
                                           tables, lens)

                (last, k_pool, v_pool, _), toks = _fused_decode(
                    step_fn, rng_key, toks_in, k_pool, v_pool,
                    lengths, step, temps, top_ps, top_ks)
                return (toks, last, k_pool, v_pool,  # [T,B(+n)],[B]
                        _advance_lengths(lengths, active), step + 1)
            self._decode = jax.jit(_decode_sample,
                                   donate_argnums=(4, 5))
        else:
            pg_rows = max(1, int(cfg.page_size))

            def _make_decode(window=None):
                # windowed variant: gather (and scatter back) only
                # the first ceil(window/pg) table columns — the
                # materialised view is O(window) rows per slot, not
                # O(max_seq). This is the path mesh-sharded serving
                # runs (the ragged kernel is single-device).
                mp_w = (None if window is None
                        else -(-window // pg_rows))

                def _decode_sample(params, tokens, use_prev, prev,
                                   k_pool, v_pool, tables, lengths,
                                   active, step, temps, top_ps,
                                   top_ks, rng_key):
                    # ONE gather per T-step pass builds the
                    # slot-contiguous view the dense decode step
                    # runs on; only the T fresh rows scatter back —
                    # the model family never sees pages
                    toks_in = jnp.where(use_prev, prev, tokens)
                    tb = tables if mp_w is None else tables[:, :mp_w]
                    k_view = self._gather_view(k_pool, tb)
                    v_view = self._gather_view(v_pool, tb)

                    def step_fn(toks, kc, vc, lens):
                        return decode_fn(params, toks, kc, vc, lens)

                    (_, k_view, v_view, _), toks = _fused_decode(
                        step_fn, rng_key, toks_in, k_view, v_view,
                        lengths, step, temps, top_ps, top_ks)
                    k_pool = scatter_decode(k_pool, tb, k_view,
                                            lengths, T)
                    v_pool = scatter_decode(v_pool, tb, v_view,
                                            lengths, T)
                    return (toks, toks[-1], k_pool, v_pool,
                            _advance_lengths(lengths, active),
                            step + 1)
                return jax.jit(_decode_sample, donate_argnums=(4, 5))

            self._decode = _make_decode()
            self._decode_windows = cfg_windows
            self._decode_by_window = {
                w: _make_decode(w) for w in self._decode_windows}
        self._decode_k = K
        #: tokens one decode dispatch yields per slot (K x M)
        self._tokens_per_pass = T
        #: rng keys ride as device-array ARGUMENTS, not jit constants,
        #: so compiled graphs are seed-independent and unseeded
        #: engines still share the persistent compile cache
        self._dev_decode_key = decode_key
        self._prefill_base_key = prefill_key
        self._prefill_cache: dict[Any, Callable] = {}
        self._prefill_fn = prefill_fn

        self._failed: str | None = None
        self._last_beat = time.time()
        self._watchdog: Any = None  # StallWatchdog, started with start()
        #: deterministic fault plan; the disabled default IS the
        #: NO_FAULTS singleton, so every site guards with one identity
        #: comparison (``self.faults is not NO_FAULTS``)
        self.faults = resolve_plan(config.faults)
        # fleet flight data recorder: the causal event ledger every
        # state transition is recorded on, plus the incident detector
        # that snapshots a diagnostic bundle when the fleet does
        # something an operator will be asked about (serving/events.py)
        from .events import IncidentDetector, resolve_ledger
        self.events = resolve_ledger(config.events, metrics=metrics)
        if self.faults is not NO_FAULTS:
            self.faults.events = self.events
        self.watermarks.events = self.events
        self.incidents = IncidentDetector(self.events.config,
                                          ledger=self.events,
                                          logger=logger)
        self.incidents.sources.update({
            "slo": lambda: (self.slo.state()
                            if self.slo is not None else None),
            "scheduler": lambda: self.waiting.state(),
            "goodput": self.goodput.state,
            "watermarks": self.watermarks.state,
            # the newest passes and spans (what the whole ring was
            # before it grew to a minute's worth): a bundle stays small
            "recorder": lambda: self.recorder.snapshot(256),
            "config": self.config_digest,
            # every bundle ships the per-signature cost table + the
            # autoprofiler state ("which kernel class got slower, and
            # where is the trace") — the cost_drift reason's bundle
            # additionally carries the capture dir in its attrs
            "costs": self.cost_state,
            # ... and the integrity plane's probe/episode state, so an
            # integrity bundle names which golden prompt diverged
            "integrity": self.integrity_state,
        })
        # crash-recovery supervisor state (see _recover / RestartPolicy)
        self._restarts = 0
        self._last_crash: str | None = None
        self._stranded_slots = 0   # active slots a timed-out stop() left
        self._draining = False     # drain(): admission closed, work runs

        # admission queue: the tenant/SLO-aware Scheduler (same
        # put/pop_batch/qsize/close contract as native/batch_queue) —
        # fair-share DRR over per-tenant sub-queues, lanes, rate
        # limits and burn-rate shedding, all at admission boundaries.
        # Single-tenant traffic is strict FIFO, bit-identical to the
        # old queue. Built before attach_metrics so its gauges wire up.
        from .scheduler import Scheduler, SchedulerConfig
        sched_cfg = (config.scheduler if config.scheduler is not None
                     else SchedulerConfig())
        self.waiting = Scheduler(sched_cfg, config.max_waiting,
                                 ledger=self.usage_ledger,
                                 slo_source=lambda: self.slo,
                                 metrics=metrics, logger=logger)
        self.waiting.events = self.events

        if self.metrics is not None:
            self.attach_metrics(self.metrics)

        # prefill buckets wider than the cache would scatter K/V slabs
        # that cannot fit the [.., max_seq, ..] cache axis
        self._usable_buckets = tuple(sorted(
            b for b in cfg.prefill_buckets if b <= cfg.max_seq)) \
            or (cfg.max_seq,)

        pg = max(1, int(cfg.page_size))
        self._pages_per_slot = -(-cfg.max_seq // pg)        # ceil
        base_pages = (cfg.kv_pages if cfg.kv_pages is not None
                      else cfg.max_batch * self._pages_per_slot)
        # pools are sized in BYTES, not rows: the page count is
        # budget // per-page-cost for the configured kv_dtype, so
        # an int8 pool at the same budget holds ~2x the pages.
        # The bf16 default without an explicit budget resolves to
        # exactly base_pages (no probe, no arithmetic drift).
        self._n_pages = self._sized_pool_pages(pg, base_pages)
        self.k_cache, self.v_cache = self._alloc_pool(pg)
        if self.paged_attention_impl == "kernel":
            # a shape the compiled kernel cannot take fails HERE,
            # naming the constraint — not as a Mosaic trace out of
            # warmup, and never by quietly taking another path
            from ..ops.paged_attention import check_kernel_layout
            check_kernel_layout(self.k_cache)
        self._free_pages = list(range(self._n_pages))
        #: per-slot ordered page ids; OOB id ``n_pages`` = unallocated
        self._tables = np.full((cfg.max_batch, self._pages_per_slot),
                               self._n_pages, np.int32)
        self._slot_pages = np.zeros(cfg.max_batch, np.int32)
        self._admit_seq = 0
        #: page refcounts: slots and the prefix cache each hold one
        self._page_refs = np.zeros(self._n_pages, np.int32)
        self._prefix_cache: dict[tuple, list[int]] = {}
        #: pins held by the cache (entries may overlap on shared
        #: pages, so this counts references, not distinct pages)
        self._cached_pages = 0
        #: cached key lengths -> entry count: probes test only
        #: these lengths instead of every aligned prefix
        self._prefix_lens: dict[int, int] = {}
        # reattachment needs the chunk-with-history walk; without
        # it a populated cache could never produce a hit
        self._prefix_enabled = cfg.prefix_cache and self._chunk_walks
        self._prefix_budget = (cfg.prefix_cache_pages
                               if cfg.prefix_cache_pages is not None
                               else max(1, self._n_pages // 4))
        # allocated KV footprint (K + V, scale leaves included):
        # quantized_bytes walks the pytree so the quantized pool's q/s
        # split needs no special casing here
        from ..ops.quant import quantized_bytes
        self._kv_bytes_total = int(quantized_bytes(
            (self.k_cache, self.v_cache)))
        #: bytes one token's cache row takes as stored, all layers, both
        #: sides (a one-vector family's V side counts nought)
        from ..ops.paged_kv import pool_row_bytes
        self._kv_row_bytes = pool_row_bytes(self.k_cache) \
            + pool_row_bytes(self.v_cache)
        self.lengths = np.zeros(cfg.max_batch, np.int32)       # kv length per slot
        self.active: list[GenRequest | None] = [None] * cfg.max_batch
        # already-admitted work bounced back (preemption, slot races,
        # chunk-walk pacing): re-enters ahead of the public queue and
        # NEVER counts against the admission bound — engine-thread
        # only, no lock needed
        self._requeued: list[GenRequest] = []
        self._requeued_set: set[int] = set()  # id() dedup: a request
        #                       preempted in the same pass it requeued
        #                       itself must not enter twice

        # decode pipeline: dispatched-but-uncollected passes (FIFO,
        # depth <= 2), plus the newest pass's last sampled token per
        # slot as a DEVICE array — the next pass's input rides it
        # without a host sync (see the decode section comment)
        from collections import deque
        self._pending: Any = deque()
        self._pending_prefills: Any = deque()
        self._dev_last: Any = None
        # committed device-resident stand-in for "no previous token":
        # building it fresh at dispatch would be an eager op per pass
        self._dev_zero = jnp.zeros(cfg.max_batch, jnp.int32)
        self._dev_last_reqs: list = [None] * cfg.max_batch
        # device-resident scheduler state: the per-slot arrays every
        # decode pass consumes (tokens/use_prev/active/lengths/temps/
        # top_ps/top_ks) live on device and are re-uploaded ONLY when
        # an admission/retirement/preemption/prefill/spec event flips
        # _sched_dirty — steady-state dispatches reuse them with zero
        # host->device transfers. Lengths and the rng step advance
        # on-device inside the decode graph, mirrored on the host.
        self._dev_sched: dict | None = None
        self._sched_dirty = True
        self._active_np = np.zeros(cfg.max_batch, bool)
        self._fresh_rows: list[int] = []
        self._dev_tables: Any = None     # device block tables
        self._tables_dirty = True
        self._dev_rng_step = jnp.zeros((), jnp.int32)
        self._decode_busy_until = 0.0
        self._prefill_busy_until = 0.0

        self._rng_step = 0
        self._running = False
        self._cleaned = False
        self._thread: threading.Thread | None = None
        self._step_count = 0
        self.total_generated = 0
        #: per-phase wall time (device call + sync) for perf accounting.
        #: dispatch_s/collect_s are the summed durations of the decode
        #: passes' ``engine.decode_dispatch`` and ``engine.emit`` spans
        #: (sweep + arg prep + async dispatch / post-sync emission);
        #: h2d_transfers counts scheduler-state uploads performed by
        #: decode dispatches — steady-state passes must add zero.
        self.stats = {"prefill_calls": 0, "prefill_s": 0.0,
                      "decode_passes": 0, "decode_s": 0.0,
                      "dispatch_s": 0.0, "collect_s": 0.0,
                      "h2d_transfers": 0, "sched_syncs": 0,
                      "view_bytes_avoided": 0,
                      "prefix_hits": 0, "spec_passes": 0,
                      "spec_accepted": 0, "spec_drafted": 0,
                      "spec_rows": 0, "preemptions": 0,
                      "requeues": 0, "prefix_evictions": 0,
                      "stalls": 0, "recompiles": 0, "cost_drifts": 0,
                      "integrity_failures": 0}
        #: waste-counter watermark already published to the metrics
        #: manager (the throttled gauge pass emits deltas)
        self._waste_published: dict[str, float] = {}

    # ----------------------------------------------------------- lifecycle
    def start(self) -> None:
        """Start (or RESTART) the engine thread. An engine stopped with
        ``stop()``/``drain()`` restarts in place: weights and every
        compiled graph are still resident, so the restart skips
        warmup entirely — only KV bookkeeping and the admission queue
        reset (the queue reopens; tenant/rate-limit state survives)."""
        if self._running:
            return
        prev = self._thread
        if prev is not None and prev.is_alive():
            # a timed-out stop() left the old loop mid device call; a
            # second loop over the same donated caches would corrupt
            # them — the caller must wait the pass out first
            raise RuntimeError(
                "previous engine thread is still in a device call "
                "(stop() timed out); wait for it to exit before start()")
        if self._cleaned:
            # restart after a clean stop (or a terminal crash): stand
            # the runtime back up on the resident weights/compile cache
            self._reset_runtime_state()
            self._cleaned = False
            self._failed = None
            self._stranded_slots = 0
            if hasattr(self.waiting, "reopen"):
                self.waiting.reopen()
        self._draining = False
        self._last_beat = time.time()
        self._running = True
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="gofr-engine")
        self._thread.start()
        if self.config.watchdog_interval_s > 0 and self._watchdog is None:
            from .observability import StallWatchdog
            self._watchdog = StallWatchdog(
                self, interval_s=self.config.watchdog_interval_s)
            self._watchdog.start()

    def stop(self, join_timeout_s: float = 30.0) -> None:
        watchdog, self._watchdog = self._watchdog, None
        if watchdog is not None:
            watchdog.stop()
        self._running = False
        # snapshot: concurrent stop() calls are legal (handler + app
        # shutdown hook), and another stopper may null self._thread
        # between our check and use
        thread = self._thread
        if thread is not None:
            # the engine thread runs _shutdown_cleanup itself when the
            # loop exits, so a slow in-flight pass (e.g. a first-hit
            # compile outliving the join timeout) can never race
            # host-side cleanup: whoever finishes the loop retires the
            # streams, exactly once
            thread.join(timeout=join_timeout_s)
            if thread.is_alive():
                # still mid device call (slow compile or wedged
                # runtime): fail the *queued* requests now — the live
                # thread only touches the queue via pop_batch, which
                # returns None once closed — but leave active slots to
                # the thread's own cleanup at pass end, so a stream
                # can never see tokens after its terminal None. The
                # thread handle stays set so repeated stop()/close()
                # never run the full cleanup concurrently with it.
                stranded_active = sum(
                    1 for r in self.active if r is not None)
                self._stranded_slots = stranded_active
                if self.logger:
                    self.logger.warn(
                        f"engine thread still in a device call; "
                        f"{stranded_active} active slot(s) stranded — "
                        "streams retire when the pass completes")
                self.events.emit("engine.stranded_slot",
                                 severity="warn",
                                 cause="stop timed out mid device call",
                                 slots=stranded_active)
                self.waiting.close()
                stranded = self.waiting.pop_batch(1 << 16, first_wait_s=0.0)
                for req in stranded or []:
                    self._fail(req, "engine stopped")
                return
            self._thread = None
        if not self._cleaned:  # loop never started (or crashed mid-start)
            self._shutdown_cleanup("engine stopped")

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful shutdown: close admission (new submits are refused
        with a typed ``draining`` 503 + Retry-After), let queued and
        in-flight requests run to completion, then ``stop()``. Returns
        True when everything retired inside the budget; False when the
        deadline cut stragglers off (they fail with "engine stopped",
        like a plain stop). The engine can ``start()`` again after."""
        deadline = time.time() + timeout_s
        self._draining = True
        self.events.emit("engine.drain", cause="admission closed",
                         timeout_s=timeout_s)
        try:
            drained = False
            while True:
                # engine-thread-owned state read racily from here: all
                # plain loads under the GIL, and the quiesce condition
                # is stable once reached (admission is closed)
                if (self.waiting.qsize() == 0 and not self._requeued
                        and not self._pending
                        and not self._pending_prefills
                        and all(r is None for r in self.active)):
                    drained = True
                    break
                if not self._running or time.time() >= deadline:
                    break
                time.sleep(0.01)
            self.stop(join_timeout_s=max(1.0, deadline - time.time()))
            return drained and not self._stranded_slots
        finally:
            self._draining = False

    def _shutdown_cleanup(self, reason: str) -> None:
        """Terminal teardown: refuse new submissions, fail anything
        stranded in the queue AND anything still holding a slot — no
        submitter may be left waiting on a request nothing will run.
        Runs on whichever thread finishes the loop, exactly once."""
        self._cleaned = True
        self.waiting.close()
        stranded = self.waiting.pop_batch(1 << 16, first_wait_s=0.0)
        for req in stranded or []:
            self._fail(req, reason)
        requeued, self._requeued = self._requeued, []
        self._requeued_set.clear()
        for req in requeued:
            self._fail(req, reason)
        for i, req in enumerate(self.active):
            if req is not None:
                self.active[i] = None
                self.lengths[i] = 0
                self._fail(req, reason)

    def _reset_runtime_state(self) -> None:
        """Stand the runtime back up on the resident weights: no
        in-flight passes, empty KV bookkeeping, a pristine page
        allocator, device scheduler state marked for re-upload.
        Weights and every compiled graph are untouched — a restarted
        engine serves its first request without recompiling. Shared by
        ``start()``-after-``stop()`` and the crash-recovery supervisor
        (``_recover``); donated caches are re-allocated only when a
        crashing pass actually consumed them."""
        cfg = self.config
        self._pending.clear()
        self._pending_prefills.clear()
        self._dev_last = None
        self._dev_last_reqs = [None] * cfg.max_batch
        self._dev_sched = None
        self._sched_dirty = True
        self._tables_dirty = True
        self._decode_busy_until = 0.0
        self._prefill_busy_until = 0.0
        if self._kv_lost():
            self.k_cache, self.v_cache = self._alloc_pool(
                max(1, int(cfg.page_size)))
        self._reset_allocator()
        self.lengths[:] = 0
        # speculation: slot ownership is void (every slot re-admits),
        # so the next drafting pass re-seeds each slot's accept EWMA;
        # the controller's fitted costs and lifetime totals survive —
        # restart doesn't change what a token costs
        self._spec_ctrl_owner = [None] * cfg.max_batch

    def health_check(self) -> dict:
        status = "DOWN" if (self._failed or not self._running) else "UP"
        active = sum(r is not None for r in self.active)
        waiting = self.waiting.qsize()
        out = {
            "status": status,
            "active_slots": active,
            "waiting": waiting,
            "steps": self._step_count,
            "total_generated": self.total_generated,
        }
        threshold = self.config.stall_threshold_s
        stalled_for = time.time() - self._last_beat
        if (status == "UP" and threshold > 0 and (active or waiting)
                and stalled_for > threshold):
            # work in flight but no pass completing: a wedged device
            # call (a hung runtime) — exceptions would have gone
            # through _crash, so this is the only way to see a hang
            out["status"] = "DEGRADED"
            out["stalled_for_s"] = round(stalled_for, 1)
        if self.stats.get("stalls"):
            out["stalls"] = self.stats["stalls"]
        if self._restarts:
            out["restarts"] = self._restarts
        if self._last_crash:
            out["last_crash"] = self._last_crash
        if self._stranded_slots:
            out["stranded_slots"] = self._stranded_slots
        if self._failed:
            out["error"] = self._failed
        if self.recorder.enabled:
            out["flight"] = self.recorder.summary()
        return out

    def close(self) -> None:
        # the app-shutdown path: a wedged device call must not hold
        # graceful shutdown for the full join budget — the daemon
        # thread dies with the process, queued requests fail now
        self.stop(join_timeout_s=2.0)

    def attach_metrics(self, metrics: Any) -> None:
        """Point the engine at a metrics manager, registering the
        serving gauges if absent — engines are often built before the
        app exists (``app.serve_model`` attaches the container's
        manager post-hoc; a bare assignment would leave every
        ``set_gauge`` logging 'not registered')."""
        self.metrics = metrics
        for name, desc in (
            ("app_engine_active_slots", "occupied decode slots"),
            ("app_engine_waiting", "requests queued for admission"),
            ("app_engine_kv_pool_utilization",
             "fraction of KV capacity in use (slots + prefix cache)"),
            ("app_engine_kv_pool_fragmentation",
             "fraction of allocated KV page capacity holding no rows"),
            ("app_engine_prefix_cache_entries",
             "prefix-cache entries pinned"),
            ("app_engine_prefix_cache_pages",
             "page references pinned by the prefix cache"),
            ("app_engine_tokens_per_second",
             "generated tokens per second (quarter-second window)"),
            ("app_engine_mfu",
             "decode-path model FLOPs utilization (cost_analysis FLOPs "
             "x tokens/s over the chip peak; 0 when the peak or the "
             "compiled cost is unknown)"),
            ("app_engine_goodput_ratio",
             "useful device time over total busy device time "
             "(1 - waste; see app_engine_waste_seconds for the causes)"),
            ("app_engine_kv_pages_watermark",
             "high-water mark of KV pool pages in use"),
            ("app_engine_prefix_pages_watermark",
             "high-water mark of page references pinned by the prefix "
             "cache"),
            ("app_engine_kv_bytes_watermark",
             "high-water mark of KV-pool HBM bytes held by in-use "
             "pages (scale leaves included for int8 pools)"),
            ("app_engine_host_rss_bytes_watermark",
             "host process RSS high-water mark (ru_maxrss)"),
            ("app_engine_spec_accept_rate",
             "lifetime speculative draft acceptance rate "
             "(accepted/drafted; 1.0 before any drafting)"),
        ):
            if metrics.get(name) is None:
                metrics.new_gauge(name, desc)
        for name, desc in (
            ("app_engine_h2d_transfers",
             "host->device scheduler-state uploads by the decode "
             "path (event-driven; zero per steady-state pass)"),
            ("app_engine_preemptions",
             "requests preempted (vLLM-style recompute requeue)"),
            ("app_engine_prefix_evictions",
             "prefix-cache entries evicted under pool pressure"),
            ("app_engine_requeues",
             "admitted work bounced back to the requeue list "
             "(chunk-walk pacing, slot races, preemption)"),
            ("app_engine_spec_drafted",
             "draft tokens offered to speculative verify"),
            ("app_engine_spec_accepted",
             "draft tokens accepted by speculative verify"),
            ("app_engine_stalls",
             "stall episodes escalated by the watchdog (work in "
             "flight, no pass for stall_threshold_s)"),
            ("app_replay_divergence",
             "replayed requests whose token stream diverged from the "
             "recorded completion (serving/replay.py)"),
            ("app_tenant_requests",
             "retired requests by tenant and status (ok/error/"
             "cancelled)"),
            ("app_tenant_prompt_tokens", "prompt tokens by tenant"),
            ("app_tenant_completion_tokens",
             "generated tokens by tenant"),
            ("app_tenant_device_seconds",
             "device busy time attributed to each tenant (per-request "
             "share of every pass's busy span)"),
            ("app_tenant_waste_seconds",
             "per-tenant attributable waste device time by cause "
             "(preempt_recompute, spec_rejected)"),
            ("app_engine_waste_seconds",
             "busy device time classified as waste, by cause (padding/"
             "preempt_recompute/spec_rejected/bubble); useful + waste "
             "== busy is conserved"),
            ("app_engine_recompiles",
             "unexpected post-warmup XLA recompiles detected by the "
             "dispatch-shape sentinel"),
            ("app_engine_cost_drift",
             "pass-cost drift episodes by dispatch kind: a signature's "
             "cost EWMA departed its sealed baseline past the "
             "configured ratio/sigma thresholds (serving/costmodel.py)"),
            ("app_engine_integrity_failures",
             "golden canary probe digest mismatch episodes by kind: "
             "this host produced output whose fingerprint departed the "
             "sealed golden digest (serving/integrity.py)"),
            ("app_engine_restarts",
             "engine loop restarts by the in-thread crash-recovery "
             "supervisor (EngineConfig.restart_policy)"),
            ("app_engine_requests_recovered",
             "pre-first-token requests salvaged into the recovery "
             "buffer and replayed across an engine restart"),
        ):
            if metrics.get(name) is None:
                metrics.new_counter(name, desc)
        for name, desc in (
            ("app_slo_burn_rate",
             "error-budget burn rate by window (1 = spending the "
             "budget at exactly the sustainable pace)"),
            ("app_slo_error_budget_remaining",
             "fraction of the availability error budget left over "
             "SLOConfig.budget_window_s"),
            ("app_sched_lane_depth",
             "queued requests per scheduler lane (interactive/"
             "background)"),
            ("app_sched_tenant_share",
             "per-tenant fraction of windowed device time "
             "(the fair-share dequeue signal)"),
            ("app_sched_shed_active",
             "1 while a burn-rate shed episode is active"),
        ):
            if metrics.get(name) is None:
                metrics.new_gauge(name, desc)
        for name, desc in (
            ("app_sched_rejections",
             "admission refusals by cause (queue_full/rate_limited/"
             "shed) and tenant"),
            ("app_sched_preemptions",
             "scheduler-initiated background preemptions to unstarve "
             "the interactive lane (priced by the preempt_recompute "
             "goodput ledger)"),
            ("app_events_total",
             "event-ledger records by kind (serving/events.py)"),
            ("app_events_dropped",
             "event-ledger ring evictions by kind — a truncated "
             "timeline is visible, never silent"),
        ):
            if metrics.get(name) is None:
                metrics.new_counter(name, desc)
        ttft_buckets = (0.005, 0.01, 0.025, 0.05, 0.075, 0.1, 0.15,
                        0.25, 0.5, 1, 2, 5)
        for name, desc, buckets in (
            ("app_chat_ttft_seconds", "time to first token",
             ttft_buckets),
            ("app_chat_queue_seconds",
             "submit -> first slot assignment (admission queue wait)",
             ttft_buckets),
            ("app_chat_e2e_seconds", "submit -> finish wall time",
             (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60)),
            ("app_chat_tpot_seconds",
             "per-request mean inter-token latency (time per output "
             "token past the first)",
             (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
              0.25, 0.5, 1)),
            ("app_engine_batch_occupancy",
             "active decode slots per pass",
             (1, 2, 4, 8, 16, 32, 64, 128, 256)),
            ("app_tpu_execute_seconds", "device execute wall time",
             (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
              0.25, 0.5, 1, 5)),
            ("app_tenant_queue_seconds",
             "admission queue wait by tenant", ttft_buckets),
            ("app_tenant_e2e_seconds",
             "submit -> finish wall time by tenant",
             (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60)),
        ):
            if metrics.get(name) is None:
                metrics.new_histogram(name, desc, buckets=buckets)
        if self.usage_ledger is not None \
                and self.usage_ledger.metrics is None:
            self.usage_ledger.metrics = metrics
        if self.slo is not None and self.slo.metrics is None:
            self.slo.metrics = metrics
        if getattr(self.waiting, "metrics", None) is None \
                and hasattr(self.waiting, "publish_gauges"):
            self.waiting.metrics = metrics
        if self.events.enabled and self.events.metrics is None:
            self.events.metrics = metrics

    def config_digest(self) -> dict:
        """JSON-safe engine-config summary for incident bundles: plain
        scalars pass through, everything else stringifies (a bundle
        must always serialize)."""
        from dataclasses import fields as _fields
        out = {}
        for f in _fields(self.config):
            value = getattr(self.config, f.name)
            out[f.name] = value if isinstance(
                value, (bool, int, float, str, type(None))) \
                else repr(value)
        out["resolved_seed"] = self.seed
        return out

    def warmup(self, prompt_lens: tuple = (1,), decode: bool = True,
               chunked: bool = False) -> None:
        """Compile serving graphs ahead of traffic: every power-of-two
        prefill group size for each bucket covering ``prompt_lens``,
        plus the decode pass. Pass ``chunked=True`` when prompts longer
        than the widest bucket are expected, so the chunked-prefill
        graph compiles here instead of inline on the first long
        prompt. Dummy rows carry all-OOB block tables, so every cache
        write drops — real state is untouched. Call before
        ``start()`` (it exercises the donated caches)."""
        cfg = self.config

        def oob_tables(rows: int):
            return jnp.full((rows, self._pages_per_slot), self._n_pages,
                            jnp.int32)

        buckets = {self._bucket_for(int(n)) for n in prompt_lens}
        for bucket in sorted(buckets):
            for g in self._group_sizes():
                self.sentinel.observe(self._sig("prefill", bucket, g))
                fn = self._get_prefill(bucket, g)
                toks, self.k_cache, self.v_cache = fn(
                    self.params, jnp.zeros((g, bucket), jnp.int32),
                    jnp.ones(g, jnp.int32), self.k_cache, self.v_cache,
                    oob_tables(g), np.int32(0),
                    jnp.zeros(g, jnp.float32), jnp.ones(g, jnp.float32),
                    jnp.zeros(g, jnp.int32), self._prefill_base_key)
                jax.block_until_ready(toks)
        if decode:
            b = cfg.max_batch
            tables = oob_tables(b)
            for w in (0, *self._decode_windows):
                self.sentinel.observe(self._sig("decode", w))
            variants = [self._decode] + [
                self._decode_by_window[w] for w in self._decode_windows]
            for fn in variants:
                toks, _, self.k_cache, self.v_cache, _, _ = fn(
                    self.params, jnp.zeros(b, jnp.int32),
                    jnp.zeros(b, bool), self._dev_zero,
                    self.k_cache, self.v_cache, tables,
                    jnp.ones(b, jnp.int32), jnp.zeros(b, bool),
                    jnp.zeros((), jnp.int32),
                    jnp.zeros(b, jnp.float32), jnp.ones(b, jnp.float32),
                    jnp.zeros(b, jnp.int32), self._dev_decode_key)
                jax.block_until_ready(toks)
            # MFU basis: ONE cost_analysis of the (already compiled)
            # decode graph, here at compile time — serve-time MFU gauge
            # updates are pure host arithmetic, never a device sync
            try:
                from .observability import (device_peak_flops,
                                            jit_cost_flops)
                pass_flops = jit_cost_flops(
                    self._decode, self.params, jnp.zeros(b, jnp.int32),
                    jnp.zeros(b, bool), self._dev_zero,
                    self.k_cache, self.v_cache, tables,
                    jnp.ones(b, jnp.int32), jnp.zeros(b, bool),
                    jnp.zeros((), jnp.int32),
                    jnp.zeros(b, jnp.float32), jnp.ones(b, jnp.float32),
                    jnp.zeros(b, jnp.int32), self._dev_decode_key)
                if pass_flops:
                    self._flops_per_token = pass_flops / float(
                        b * self._tokens_per_pass)
                self._peak_flops = device_peak_flops()
            except Exception:  # cost analysis is best-effort, never fatal
                pass
        if chunked and self._chunk_walks:
            # compile the chunk-walk graph at every bucket width for
            # both group sizes the walk uses (solo and full wave) —
            # all rows dummy (OOB tables): every cache write drops,
            # the samples are discarded
            P = max(1, cfg.prefill_batch)
            # full graph always; plus the single windowed chunk
            # variant the view path's walk dispatcher may select (the
            # native chunk path is length-bounded and never picks a
            # windowed variant)
            chunk_windows = [None]
            if self._cfg_windows and not self._native_chunk:
                chunk_windows.append(self._cfg_windows[-1])
            for cw in chunk_windows:
                fn = self._get_chunk_prefill(cw)
                for width in self._usable_buckets:
                    if cw is not None and width > cw:
                        continue  # the dispatcher never picks cw then
                    for g in sorted({1, P}):
                        self.sentinel.observe(
                            self._sig("chunk", width, g, cw))
                        toks, self.k_cache, self.v_cache = fn(
                            self.params, jnp.zeros((g, width), jnp.int32),
                            self.k_cache, self.v_cache, oob_tables(g),
                            jnp.zeros(g, jnp.int32),
                            jnp.zeros(g, jnp.int32),
                            np.int32(0), jnp.zeros(g, jnp.float32),
                            jnp.ones(g, jnp.float32),
                            jnp.zeros(g, jnp.int32),
                            self._prefill_base_key)
                        jax.block_until_ready(toks)
        if self._spec_enabled:
            # tree-verify graphs: one per pow-2 width bucket
            # (_spec_pass picks the smallest bucket holding the pass's
            # widest tree). Observe AND eagerly compile every bucket —
            # the sealed sentinel treats any unseen post-warmup
            # signature as a regression, and a lazy first compile
            # would stall the serving loop mid-stream. All rows are
            # dummies (OOB offsets/tables): every cache write drops.
            b = cfg.max_batch
            fn = self._get_spec_verify()
            cap = 1 + cfg.spec_draft * cfg.spec_branches
            w = 2
            while True:
                self.sentinel.observe(self._sig("spec_verify", w))
                _, bonus, _, self.k_cache, self.v_cache = fn(
                    self.params, jnp.zeros((b, w), jnp.int32),
                    jnp.zeros((b, w), jnp.int32),
                    jnp.zeros((b, w), jnp.int32),
                    jnp.ones((b, w), jnp.int32),
                    self.k_cache, self.v_cache, oob_tables(b),
                    jnp.full(b, cfg.max_seq, jnp.int32),
                    jnp.ones(b, jnp.int32), np.int32(0),
                    jnp.zeros(b, jnp.float32),
                    jnp.ones(b, jnp.float32),
                    jnp.zeros(b, jnp.int32), self._prefill_base_key)
                jax.block_until_ready(bonus)
                if w >= cap:
                    break
                w *= 2
        self.sentinel.seal()

    def _clamp_prompt(self, tokens: list[int], max_new: int) -> list[int]:
        """Keep the tail of an over-long prompt, reserving room to
        generate. With chunked prefill the cache is the only cap;
        without it the widest prefill graph also bounds admission.
        (Preemption-requeue clamps less aggressively: see ``_preempt``
        — its continuation already fit the cache.)"""
        room = max(1, min(max_new, self.config.max_seq // 2))
        limit = max(1, self.config.max_seq - room - 1)
        if not self._chunk_walks:
            limit = min(limit, max(self._usable_buckets))
        return tokens[-limit:] if len(tokens) > limit else tokens

    # -------------------------------------------------------------- submit
    def submit(self, prompt_tokens: list[int],
               params: SamplingParams | None = None, *,
               traceparent: str | None = None,
               tenant: str | None = None,
               lane: str = "interactive") -> GenRequest:
        """Called from the asyncio loop; returns a request whose
        ``out_queue`` yields token ids and then ``None``.

        When a tracer is attached, the request carries the caller's
        trace identity — the active span on the submitting thread/task
        (the HTTP/gRPC middleware span), else a W3C ``traceparent``
        header — and the engine.* child spans assemble at retire.
        ``tenant`` is the resolved bounded-cardinality accounting
        label (handlers pass it from the auth principal); it rides the
        request into spans, the flight-recorder log and the usage
        ledger. ``lane`` routes the request into the scheduler's
        interactive or background lane (the config's
        ``background_tenants`` mapping applies when left default)."""
        params = params or SamplingParams()
        prompt_tokens = self._clamp_prompt(list(prompt_tokens),
                                           params.max_new_tokens)
        req = GenRequest(prompt_tokens=prompt_tokens, params=params,
                         tenant=tenant, lane=lane, rid=next(self._rids))
        if self.tracer is not None:
            parent = self.tracer.current_span()
            if parent is not None:
                if parent.sampled:
                    req.trace = (parent.trace_id, parent.span_id)
            elif traceparent:
                from ..tracing.tracer import (_traceparent_sampled,
                                              extract_traceparent)
                remote = extract_traceparent(traceparent)
                if remote is not None and _traceparent_sampled(traceparent):
                    req.trace = remote
        try:
            req.loop = asyncio.get_running_loop()
            req.out_queue = asyncio.Queue()
        except RuntimeError:  # submitted from a plain thread (tests/bench)
            req.loop = None
            req.out_queue = None
        if self.faults is not NO_FAULTS \
                and self.faults.trip("page_exhaustion",
                                     request_id=req.tenant):
            # injected KV-pool exhaustion: refused at admission with a
            # typed retryable 503 — the engine keeps serving
            self._refuse(req, "kv_exhausted",
                         "kv page pool exhausted; retry shortly",
                         retry_after_s=1.0)
            return req
        if self._draining:
            self._refuse(req, "draining",
                         "engine draining for shutdown; retry against "
                         "another replica", retry_after_s=5.0)
            return req
        if not self.waiting.put(req):  # refused/closed: fail loudly,
            # never hang. The scheduler stamps a typed reject
            # (queue_full / rate_limited / shed) for policy refusals;
            # a closed queue stamps nothing — lifecycle refusals
            # (stopped or crashed engine) get their own typed code so
            # clients see 503 + Retry-After + details.code, not a bare
            # string.
            if req.reject is not None and self._running:
                self._fail(req, req.reject.message)
            elif self._running:
                self._fail(req, "engine overloaded: waiting queue full")
            else:
                policy = self.config.restart_policy
                retry = (policy.backoff_for(self._restarts + 1)
                         if policy is not None else 1.0)
                self._refuse(
                    req, "engine_down",
                    "engine not accepting requests"
                    + (f" (last crash: {self._last_crash})"
                       if self._last_crash else ""),
                    retry_after_s=max(1.0, retry))
        return req

    def submit_sync(self, prompt_tokens: list[int],
                    params: SamplingParams | None = None) -> GenRequest:
        """Blocking submit for non-async callers; returns when finished."""
        req = self.submit(prompt_tokens, params)
        while req.finished_at is None and req.error is None:
            time.sleep(0.002)
        return req

    def cancel(self, req: GenRequest) -> None:
        """Abandon a request: a disconnected client must not keep
        burning decode slots. Waiting requests are dropped at
        admission; active slots retire at the next pass."""
        req.cancelled = True

    async def stream_request(self, req: GenRequest):
        """Async iterator of a submitted request's token ids. Closing
        the iterator early (client disconnect) cancels the request."""
        try:
            while True:
                token = await req.out_queue.get()
                if token is None:
                    break
                yield token
        finally:
            if req.finished_at is None:
                self.cancel(req)

    # ---------------------------------------------------------- scheduling
    def _group_sizes(self) -> tuple:
        """Compiled prefill group sizes: powers of two up to
        ``prefill_batch``, plus ``prefill_batch`` itself when it is not
        one — the admission chunk size always has an exact graph."""
        cap = max(1, self.config.prefill_batch)
        sizes = []
        g = 1
        while g < cap:
            sizes.append(g)
            g *= 2
        sizes.append(cap)
        return tuple(sizes)

    def _bucket_for(self, n: int) -> int:
        for b in self._usable_buckets:
            if n <= b:
                return b
        return self._usable_buckets[-1]

    def _get_prefill(self, bucket: int, group: int) -> Callable:
        """Fused group prefill per (bucket, group-size) — ONE device
        call per group: forward [P, bucket], sample each row's first
        token, and scatter the prompt K/V straight into the donated
        pools (dummy rows carry all-OOB block tables, dropped by the
        scatter). The host pulls back 4·P bytes of token ids, nothing
        else. Group sizes are powers of two up to ``prefill_batch`` so
        a lone arrival runs a [1, bucket] graph, not the full-width
        one, at the cost of ≤log2(P) extra compiles per bucket."""
        fn = self._prefill_cache.get((bucket, group))
        if fn is None:
            prefill_fn = self._prefill_fn
            from ..ops.paged_kv import scatter_chunk

            def fused(params, tokens, kv_len, kc, vc, slots, step,
                      temps, top_ps, top_ks, rng_key):
                key = jax.random.fold_in(rng_key, step)
                logits, (k, v) = prefill_fn(params, tokens, kv_len)
                if logits.ndim == 3:  # full [P, S, V]: keep last position
                    logits = jnp.take_along_axis(
                        logits, jnp.maximum(kv_len - 1, 0)[:, None, None],
                        axis=1)[:, 0]
                toks = _sample_batch(logits, key, temps, top_ps, top_ks)
                # ``slots`` carries each row's block table [P, Mp];
                # scatter_chunk (offset 0, per-row prompt length)
                # writes only the pages each prompt spans — pad
                # rows past kv_len drop instead of round-tripping
                # the scatter owns the pool representation: plain
                # pools cast internally, quantized pools quantize
                # on write (no .astype on the pool here)
                zeros = jnp.zeros_like(kv_len)
                kc = scatter_chunk(kc, slots, k, zeros, kv_len)
                vc = scatter_chunk(vc, slots, v, zeros, kv_len)
                return toks, kc, vc
            fn = jax.jit(fused, donate_argnums=(3, 4))
            self._prefill_cache[(bucket, group)] = fn
        return fn

    def _get_chunk_prefill(self, window: int | None = None) -> Callable:
        """Fused G-slot chunk step: run one [G, width] chunk forward
        against each walking slot's history — through the block tables
        on the native path, on a gathered per-slot view whose written
        rows are spliced back on the view path — and sample (only each
        row's final chunk's sample is used). The jit retraces per
        (G, width) — an admission wave of prefix-cache suffixes shares
        ONE dispatch instead of one per request, and a short tail pays
        for its own bucket, not the widest. Dummy pad rows carry OOB
        tables, so their writes drop.

        ``window`` (view path only): gather/scatter only the table
        columns covering the first ``window`` rows — prefix-suffix
        walks with short histories stop paying O(max_seq) view
        traffic. The walk dispatcher uses the LARGEST configured decode
        window (one extra compile per (G, width)) and falls back to the
        full graph when a walker's history outgrows it."""
        fn = self._prefill_cache.get(("chunk", window))
        if fn is None:
            chunk_fn = self._prefill_chunk_fn

            if self._native_chunk:
                # native paged chunk: the model writes only the pages
                # the chunk spans through the block tables and attends
                # with the ragged chunk kernel — no gather/scatter of
                # a dense per-slot view, so a chunk's HBM traffic is
                # O(history + chunk), not O(pool allocation). The walk
                # is length-bounded by construction; windowed variants
                # exist only to bound the VIEW path's gather.
                native_fn = self._paged_chunk_fn

                def fused(params, tokens, kp, vp, tables, offsets,
                          chunk_lens, step, temps, top_ps, top_ks,
                          rng_key):
                    logits, kp, vp = native_fn(
                        params, tokens, kp, vp, tables, offsets,
                        chunk_lens)
                    key = jax.random.fold_in(rng_key, step)
                    toks = _sample_batch(logits, key, temps,
                                         top_ps, top_ks)
                    return toks, kp, vp
            else:
                from ..ops.paged_kv import scatter_decode
                pg_rows = max(1, int(self.config.page_size))
                mp_w = None if window is None else -(-window // pg_rows)

                def fused(params, tokens, kp, vp, tables, offsets,
                          chunk_lens, step, temps, top_ps, top_ks,
                          rng_key):
                    width = tokens.shape[1]
                    tables = (tables if mp_w is None
                              else tables[:, :mp_w])
                    k_view = self._gather_view(kp, tables)
                    v_view = self._gather_view(vp, tables)
                    logits, k_view, v_view = chunk_fn(
                        params, tokens, k_view, v_view, offsets,
                        chunk_lens)
                    # write back exactly each row's chunk range; rows
                    # beyond chunk_len round-trip their gathered values
                    # and unallocated (dummy) pages drop (the scatter
                    # owns the pool dtype/quantization)
                    kp = scatter_decode(kp, tables, k_view,
                                        offsets, width)
                    vp = scatter_decode(vp, tables, v_view,
                                        offsets, width)
                    key = jax.random.fold_in(rng_key, step)
                    toks = _sample_batch(logits, key, temps,
                                         top_ps, top_ks)
                    return toks, kp, vp
            fn = jax.jit(fused, donate_argnums=(2, 3))
            self._prefill_cache[("chunk", window)] = fn
        return fn

    def _chunk_window(self, needed: int, width: int) -> int | None:
        """Largest configured decode window, if it covers ``needed``
        rows AND the chunk width (warmup only compiles windowed
        variants for widths <= window — the gates must agree or the
        first wide-bucket suffix walk compiles on the serving path);
        else None (full graph). The native chunk path needs no windows
        at all — the ragged kernel walks only the pages covering each
        row's history + chunk."""
        if not self._cfg_windows or self._native_chunk:
            return None
        w = self._cfg_windows[-1]
        return w if needed <= w and width <= w else None

    def _finish_walk(self, req: GenRequest, first: int) -> None:
        """A chunk walk covered its whole prompt: emit the first
        sampled token and open the slot for decode."""
        self._sched_dirty = True  # slot flips pending -> decoding
        req.pending_prefill = False
        if self.faults is not NO_FAULTS and \
                self.faults.trip("logit_corrupt", req.tenant):
            first = self._corrupt_token(first)
        now = time.time()  # gofrlint: allow(hot-path-purity) -- first-token boundary of a finished walk: once per request lifetime
        if req.first_token_at is None:  # not a preemption recompute
            req.first_token_at = now
            if self.metrics is not None:
                self.metrics.record_histogram(  # gofrlint: allow(hot-path-purity) -- TTFT observation at the walk's collect boundary, once per request lifetime
                    "app_chat_ttft_seconds", now - req.submitted_at,
                    exemplar_trace_id=req.trace[0] if req.trace else None)
        req.generated.append(first)
        req._emit(first)
        self.total_generated += 1
        self.lengths[req.slot] = len(req.prompt_tokens)
        if self._finished(req, first):
            self._retire(req.slot)

    @hot_path
    def _walk_chunks(self, pairs: list) -> None:
        """Admit (or resume) prompts through the chunk-with-history
        walk — prompts longer than the widest bucket, prefix-cache
        suffixes, preemption recomputes — BATCHED: walkers entering
        together share [G, width] device calls grouped by chunk width,
        so an admission wave of same-system-prompt suffixes costs
        ceil(G/prefill_batch) dispatches instead of G (each dispatch
        is a host round trip). At most ``prefill_chunks_per_pass``
        chunk rounds run per call; unfinished walks requeue so decode
        for every other slot interleaves instead of head-of-line
        blocking."""
        cfg = self.config
        widest = max(self._usable_buckets)
        P = max(1, cfg.prefill_batch)
        walkers: list[GenRequest] = []
        if pairs:  # slots change occupancy/pending state below
            self._sched_dirty = True
        for req, slot in pairs:
            prompt = req.prompt_tokens
            if -(-(len(prompt) + 1) // cfg.page_size) > self._n_pages:
                # an attached prefix (incref'd before this call) must
                # not leak into the slot's table for the next occupant
                self._release_pages(slot)
                if self.active[slot] is req:  # admit-time reservation
                    self.active[slot] = None
                req.prefill_offset = 0
                self._fail(req, "prompt exceeds kv pool")
                continue
            self._dev_last_reqs[slot] = None  # fresh/resumed occupant
            req.prefill_epoch += 1  # orphan any in-flight batch prefill
            self.active[slot] = req
            req.slot = slot
            req.pending_prefill = True
            self._note_admitted(req)
            if req.admit_order < 0:
                req.admit_order = self._admit_seq
                self._admit_seq += 1
            walkers.append(req)
        if not walkers:
            return

        def owns_slot(r: GenRequest) -> bool:
            return (r.finished_at is None and r.slot >= 0
                    and self.active[r.slot] is r)

        start = time.perf_counter()
        dispatched: list[GenRequest] = []  # rows of the in-flight call
        try:
            fn = self._get_chunk_prefill()
            for _ in range(max(1, int(cfg.prefill_chunks_per_pass))):
                live = [r for r in walkers if owns_slot(r)
                        and r.prefill_offset < len(r.prompt_tokens)]
                if not live:
                    break
                # smallest bucket covering each walker's remainder —
                # the last chunk of a walk and prefix-cache suffixes
                # run a graph their own size, not the widest
                by_width: dict[int, list[GenRequest]] = {}
                for r in live:
                    remaining = len(r.prompt_tokens) - r.prefill_offset
                    width = next((b for b in self._usable_buckets
                                  if b >= remaining), widest)
                    by_width.setdefault(width, []).append(r)
                for width, group in by_width.items():
                    for i in range(0, len(group), P):
                        ready = []
                        for r in group[i:i + P]:
                            if not owns_slot(r):
                                continue  # a peer's headroom preempted it
                            chunk_len = min(
                                width,
                                len(r.prompt_tokens) - r.prefill_offset)
                            rows = min(r.prefill_offset + chunk_len + 1,
                                       cfg.max_seq)
                            if not self._ensure_headroom(r.slot, rows):
                                # the pool can't cover this walk even
                                # after preempting younger requests:
                                # release and restart from scratch
                                # once pages free up
                                self._release_pages(r.slot)
                                self._dev_last_reqs[r.slot] = None
                                self.active[r.slot] = None
                                r.prefill_offset = 0
                                self._requeue(r)
                                continue
                            ready.append(r)
                        ready = [r for r in ready if owns_slot(r)]
                        if not ready:
                            continue
                        pass_id = self.recorder.new_pass()
                        with self.recorder.span("engine.chunk_walk",
                                                pass_id) as sp:
                            # pad to the full group: only (1, P)
                            # variants ever compile per width
                            G = 1 if len(ready) == 1 else P
                            tokens = np.zeros((G, width), np.int32)
                            offs = np.zeros(G, np.int32)
                            lens = np.zeros(G, np.int32)
                            temps = np.zeros(G, np.float32)
                            top_ps = np.ones(G, np.float32)
                            top_ks = np.zeros(G, np.int32)
                            # dummy rows all-OOB: writes drop
                            slots_arg = np.full(
                                (G, self._pages_per_slot),
                                self._n_pages, np.int32)
                            for row, r in enumerate(ready):
                                chunk = r.prompt_tokens[
                                    r.prefill_offset:
                                    r.prefill_offset + width]
                                tokens[row, :len(chunk)] = chunk
                                offs[row] = r.prefill_offset
                                lens[row] = len(chunk)
                                temps[row] = r.params.temperature
                                top_ps[row] = r.params.top_p
                                top_ks[row] = r.params.top_k
                                slots_arg[row] = self._tables[r.slot]
                            self._rng_step += 1
                            dispatched = ready
                            cw = self._chunk_window(
                                int((offs + lens).max()), width)
                            call = (self._get_chunk_prefill(cw) if cw
                                    else fn)
                            self._note_dispatch_shape("chunk", width, G, cw)
                            c0 = time.perf_counter()
                            self.goodput.note_dispatch(c0)
                            w0 = time.time()  # gofrlint: allow(hot-path-purity) -- span timestamps use wall clock; once per chunk dispatch (the walk is synchronous by design)
                            toks, self.k_cache, self.v_cache = call(
                                self.params, jnp.asarray(tokens),
                                self.k_cache, self.v_cache,
                                jnp.asarray(slots_arg), jnp.asarray(offs),
                                jnp.asarray(lens), np.int32(self._rng_step),
                                jnp.asarray(temps), jnp.asarray(top_ps),
                                jnp.asarray(top_ks),
                                self._prefill_base_key)
                            self.stats["prefill_calls"] += 1
                            if self._native_chunk:
                                self._note_view_avoided(G)
                        # the call is asynchronous: this times the
                        # enqueue, not the chunk on the device
                        c_dur = sp.t1 - c0
                        chunk_sig = self._sig_str("chunk", width, G, cw)
                        # goodput: a walker with a first token already
                        # emitted is re-prefilling KV it computed once
                        # (preemption recompute); pad rows are padding
                        recomp = sum(1 for r in ready
                                     if r.first_token_at is not None
                                     or r.recovered)
                        self.goodput.add_prefill(
                            "prefill_chunk", c_dur, G,
                            len(ready) - recomp, recomp)
                        # cost observatory: same duration the ledger
                        # just billed; tokens = the compiled shape's
                        # G x width positions (what the graph costs)
                        self._note_pass_cost(
                            "chunk", chunk_sig, c_dur,
                            rows=len(ready), tokens=G * width)
                        w1 = time.time()  # gofrlint: allow(hot-path-purity) -- span timestamps use wall clock; once per chunk dispatch
                        for r in ready:
                            r.device_s += c_dur / len(ready)
                            if r.first_token_at is not None or r.recovered:
                                r.waste_recompute_s += c_dur / len(ready)
                            # the enqueue of an asynchronous call, named
                            # for what it is; the chunk's device time is
                            # in the profiler's trace, not on this clock
                            self._req_event(
                                r, "prefill_dispatch", w0, w1,
                                {"bucket": width,
                                 "offset": int(r.prefill_offset),
                                 "pass_id": pass_id,
                                 "view_avoided": self._native_chunk})
                        toks_np = None
                        t1 = None  # nobody waited: the walk goes on
                        for row, r in enumerate(ready):
                            r.prefill_offset += int(lens[row])
                            if r.prefill_offset >= len(r.prompt_tokens):
                                if toks_np is None:
                                    with self.recorder.span(
                                            "engine.chunk_wait",
                                            pass_id) as wait:
                                        toks_np = np.asarray(toks)  # gofrlint: allow(hot-path-purity) -- this sync IS the walk's collect: finished walkers' first tokens cross to host here
                                    t1 = wait.t1
                                self._finish_walk(r, int(toks_np[row]))
                        if self.recorder.enabled:
                            n = len(ready)
                            self.recorder.record_pass(
                                "prefill_chunk", pass_id, t0=c0, t1=t1,
                                rids=[r.rid for r in ready],
                                offsets=offs[:n].tolist(),
                                lens=lens[:n].tolist(),
                                width=width, window=cw, sig=chunk_sig,
                                dur=round(c_dur, 6),
                                view_avoided=self._native_chunk,
                                queue_depth=self.waiting.qsize())
                        dispatched = []
        except Exception as exc:
            # fail the rows of the crashing dispatch; walkers that
            # were not in it keep their state and requeue below
            for r in (dispatched or
                      [w for w in walkers if owns_slot(w)
                       and w.pending_prefill]):
                if r.slot >= 0 and self.active[r.slot] is r:
                    self.active[r.slot] = None
                    self._release_pages(r.slot)
                r.pending_prefill = False
                self._fail(r, str(exc))
            if self.logger:
                self.logger.error(f"chunked prefill failed: {exc!r}")  # gofrlint: allow(hot-path-purity) -- failure path: the chunk dispatch raised; rows are being failed, not served
            self._recover_lost_cache(exc)
        self._note_prefill_span(start)
        self._update_kv_watermarks()
        self._note_device_idle()
        for r in walkers:  # more chunks next pass
            if owns_slot(r) and r.pending_prefill \
                    and r.prefill_offset < len(r.prompt_tokens):
                self._requeue(r)

    def _free_slot(self) -> int:
        for i, r in enumerate(self.active):
            if r is None:
                return i
        return -1

    # ------------------------------------------------------ paged alloc
    def _decref_page(self, page: int) -> None:
        self._page_refs[page] -= 1
        if self._page_refs[page] <= 0:
            self._page_refs[page] = 0
            self._free_pages.append(page)

    @hot_path_boundary(
        "pool-pressure eviction event; runs only after an allocation already missed")
    def _evict_prefix_entries(self, pages_needed: int) -> None:
        """Drop LRU prefix-cache entries (insertion order IS the LRU
        order — touches reinsert) until the free list can cover
        ``pages_needed`` or the cache is empty."""
        while len(self._free_pages) < pages_needed and self._prefix_cache:
            key = next(iter(self._prefix_cache))
            pages = self._prefix_cache.pop(key)
            self.stats["prefix_evictions"] += 1
            if self.metrics is not None:
                self.metrics.increment_counter("app_engine_prefix_evictions")
            count = self._prefix_lens.get(len(key), 0) - 1
            if count > 0:
                self._prefix_lens[len(key)] = count
            else:
                self._prefix_lens.pop(len(key), None)
            self._cached_pages -= len(pages)
            for page in pages:
                self._decref_page(page)
        self._prefix_digest_dirty = True

    def _alloc_pages(self, slot: int, rows: int) -> bool:
        """Grow ``slot``'s block table to cover ``rows`` logical rows;
        False when the free list cannot even after evicting cached
        prefixes (caller preempts or defers)."""
        pg = self.config.page_size
        need = min(-(-rows // pg), self._pages_per_slot)
        have = int(self._slot_pages[slot])
        if need <= have:
            return True
        if need - have > len(self._free_pages):
            self._evict_prefix_entries(need - have)
        if need - have > len(self._free_pages):
            return False
        for i in range(have, need):
            page = self._free_pages.pop()
            self._tables[slot, i] = page
            self._page_refs[page] = 1
        self._slot_pages[slot] = need
        self._tables_dirty = True
        return True

    def _release_pages(self, slot: int) -> None:
        n = int(self._slot_pages[slot])
        if n:
            self._tables_dirty = True
        for i in range(n):
            self._decref_page(int(self._tables[slot, i]))
        self._tables[slot, :] = self._n_pages
        self._slot_pages[slot] = 0

    # ------------------------------------------------------ prefix cache
    def _probe_prefix(self, prompt: list[int]) -> int:
        """-> covered rows of the longest cached page-aligned prefix
        of ``prompt`` (0 = miss). Always leaves >= 1 suffix token so
        the first sample has a position to come from. Only lengths
        that actually exist in the cache are tested."""
        if not self._prefix_enabled or not self._prefix_cache:
            return 0
        limit = len(prompt) - 1
        for length in sorted(self._prefix_lens, reverse=True):
            if length <= limit \
                    and tuple(prompt[:length]) in self._prefix_cache:
                return length
        return 0

    def _attach_prefix(self, slot: int, prompt: list[int],
                       covered: int) -> None:
        """Point ``slot``'s table at the cached pages for
        ``prompt[:covered]`` (increfs them) — the slot starts with the
        shared prefix KV already in place."""
        key = tuple(prompt[:covered])
        pages = self._prefix_cache.pop(key)   # LRU touch: reinsert at
        self._prefix_cache[key] = pages       # the fresh end
        for i, page in enumerate(pages):
            self._tables[slot, i] = page
            self._page_refs[page] += 1
        self._slot_pages[slot] = len(pages)
        self._tables_dirty = True
        self.stats["prefix_hits"] += 1

    def _register_prefix(self, slot: int, req: GenRequest) -> None:
        """At retire: pin the page-aligned prompt prefix for reuse.
        Decode wrote only past the prompt, so these pages hold exactly
        the prefix KV."""
        cfg = self.config
        if not self._prefix_enabled:
            return
        pg = cfg.page_size
        prompt = req.prompt_tokens
        aligned = ((len(prompt) - 1) // pg) * pg
        n = aligned // pg
        if n < 1 or int(self._slot_pages[slot]) < n:
            return
        # when the full prefix exceeds the budget, pin the longest
        # aligned prefix that fits — partial reuse beats none
        n = min(n, self._prefix_budget - self._cached_pages)
        if n < 1:
            return
        aligned = n * pg
        key = tuple(prompt[:aligned])
        if key in self._prefix_cache:
            return
        pages = [int(self._tables[slot, i]) for i in range(n)]
        for page in pages:
            self._page_refs[page] += 1
        self._prefix_cache[key] = pages
        self._prefix_lens[aligned] = self._prefix_lens.get(aligned, 0) + 1
        self._cached_pages += n
        self._prefix_digest_dirty = True

    @hot_path_boundary(
        "event-driven eviction; its host work is amortized over the recompute prefill it schedules, not paid per pass")
    def _preempt(self, slot: int) -> None:
        """Evict a request, keeping its stream open: pages return to
        the pool now, the request re-enters the queue with prompt =
        original prompt + everything generated, and the next prefill
        recomputes its KV and samples its next token — vLLM-style
        preemption-by-recompute, which on TPU costs one extra bucketed
        prefill instead of a cache swap to host memory."""
        req = self.active[slot]
        if req is None:
            return
        self.stats["preemptions"] += 1
        if self.metrics is not None:
            self.metrics.increment_counter("app_engine_preemptions")
        _now = time.time()
        self._req_event(req, "preempt", _now, _now,
                        {"slot": slot, "generated": len(req.generated)})
        # the request re-enters by recompute with host-side state; a
        # surviving _dev_last entry from its old life in this slot must
        # never match it again (its generated[] diverges from the
        # discarded in-flight pass), and neither may an in-flight batch
        # prefill's first token (epoch bump) — the recompute re-admits
        # through whichever prefill path fits its new prompt
        self._dev_last_reqs[slot] = None
        self._sched_dirty = True
        req.pending_prefill = False
        req.prefill_epoch += 1
        self.active[slot] = None
        self.lengths[slot] = 0
        self._release_pages(slot)
        # the continuation IS the cache content at eviction (<= max_seq
        # rows by construction): re-prefilling it reproduces the exact
        # token positions, so greedy outputs cannot diverge. Only the
        # widest prefill bucket truncates (divergence then unavoidable
        # without chunked prefill — requires buckets narrower than
        # max_seq, non-default).
        req.prompt_tokens = list(req.prompt_tokens) + list(req.generated)
        limit = min(max(self._usable_buckets), self.config.max_seq)
        if self._chunk_walks:
            # chunked prefill re-admits any continuation the cache can
            # hold — no bucket truncation
            limit = self.config.max_seq
        if len(req.prompt_tokens) > limit:
            req.prompt_tokens = req.prompt_tokens[-limit:]
        # any chunk/suffix progress is gone with the pages: restart
        # from zero (a cached prefix can re-attach at re-admission)
        req.prefill_offset = 0
        self._requeue(req)

    def _ensure_headroom(self, slot: int, rows: int) -> bool:
        """Allocate pages for ``rows`` logical rows, preempting the
        newest *younger* active request as needed — an older request
        (closer to completion) is never evicted for a newer one. False
        when no younger victim remains and the pool still cannot cover
        this slot (the caller preempts ``slot`` itself)."""
        mine = self.active[slot].admit_order
        while not self._alloc_pages(slot, rows):
            victims = [i for i, r in enumerate(self.active)
                       if r is not None and i != slot
                       and r.admit_order > mine]
            if not victims:
                return False
            self._preempt(max(
                victims, key=lambda i: self.active[i].admit_order))
        return True

    @hot_path_boundary(
        "starvation-triggered preemption decision at the admission boundary; rate-capped by the scheduler, not steady-state")
    def _sched_starvation_preempt(self) -> bool:
        """When the scheduler reports interactive starvation with the
        batch full, preempt the newest background slot through the
        existing preemption-by-recompute machinery (the
        ``preempt_recompute`` goodput ledger prices it) and route the
        victim back through the scheduler instead of the ``_requeued``
        fast lane — which bypasses admission and would hand the freed
        slot straight back to the victim."""
        sched = self.waiting
        if not hasattr(sched, "starving_interactive") \
                or not sched.starving_interactive():
            return False
        victims = [i for i, r in enumerate(self.active)
                   if r is not None and not r.pending_prefill
                   and not r.cancelled
                   and getattr(r, "lane", None) == "background"]
        if not victims:
            return False
        # newest victim loses
        slot = max(victims, key=lambda i: self.active[i].admit_order)
        req = self.active[slot]
        self._preempt(slot)
        if id(req) in self._requeued_set:
            self._requeued_set.discard(id(req))
            self._requeued = [r for r in self._requeued if r is not req]
            sched.readmit(req)  # head of its background sub-queue
        if hasattr(sched, "note_preempted"):
            sched.note_preempted()
        return True

    @hot_path_boundary(
        "event-driven backpressure bookkeeping (admission races, pool pressure), not steady-state")
    def _requeue(self, req: GenRequest) -> None:
        if id(req) not in self._requeued_set:
            self._requeued_set.add(id(req))
            self._requeued.append(req)
            self.stats["requeues"] += 1
            if self.metrics is not None:
                self.metrics.increment_counter("app_engine_requeues")

    def _pool_probe(self, page: int):
        """A ONE-page allocation from the model family's cache
        constructor, each side re-laid head-major [L, Hkv, 1, pg, hd]:
        the dims, dtype and (under a mesh) head-axis sharding the pool
        constructor reads. The family states its row here — K and V of
        ``Hkv`` heads, or one latent vector and a V side of no lanes."""
        from ..ops.paged_kv import pool_from_cache_shape
        k, v = self._make_cache(1, page)
        return pool_from_cache_shape(k), pool_from_cache_shape(v)

    def _alloc_pool(self, page: int):
        """Allocate the paged pool in its final representation
        (ops/paged_kv.py: head-major, kv heads packed into 128-lane
        rows, ``kv_dtype="int8"`` as the ``{"q", "s"}`` pytree) — built
        in place from the probe's dims, so no unpacked or unquantized
        transient the size of the pool ever exists. Every later write
        packs/quantizes inside the jitted scatters; this is the only
        place the representation is chosen."""
        from ..ops.paged_kv import empty_pool
        k_probe, v_probe = self._pool_probe(page)
        # what the view fallback unpacks / dequantizes back to
        self._kv_view_dtype = k_probe.dtype
        self._kv_head_dim = k_probe.shape[-1]
        quantized = self.config.kv_dtype == "int8"
        return (empty_pool(k_probe, self._n_pages, quantized),
                empty_pool(v_probe, self._n_pages, quantized))

    def _gather_view(self, pool, tables):
        """Dense per-slot view [L, B, S, Hkv, hd] of the pool — the view
        fallback's read side (traced inside the jitted closures)."""
        from ..ops.paged_kv import gather_view
        return gather_view(pool, tables, dtype=self._kv_view_dtype,
                           head_dim=self._kv_head_dim)

    def _sized_pool_pages(self, page: int, base_pages: int) -> int:
        """Resolve the pool's page count from its BYTE budget. The
        budget is ``kv_pool_bytes`` when set, else ``base_pages`` at
        the native per-page cost — so flipping ``kv_dtype`` to int8
        keeps the footprint and roughly doubles the pages. The bf16
        default with no explicit budget short-circuits to
        ``base_pages`` exactly (no probe allocation, no rounding)."""
        cfg = self.config
        if cfg.kv_dtype == "bf16" and cfg.kv_pool_bytes is None:
            return max(1, int(base_pages))
        from ..ops.paged_kv import empty_pool, pool_row_bytes
        probes = self._pool_probe(page)

        def page_bytes(quantized: bool) -> int:
            # K + V, one page each, as allocated (scale rows included)
            return sum(probe.shape[3] * pool_row_bytes(
                empty_pool(probe, 1, quantized)) for probe in probes)

        budget = (cfg.kv_pool_bytes if cfg.kv_pool_bytes is not None
                  else base_pages * page_bytes(False))
        return max(1, int(budget) // page_bytes(cfg.kv_dtype == "int8"))

    def _reset_allocator(self) -> None:
        """Every page free, every table unallocated, the prefix cache
        empty: what a restart and a lost pool both come back to."""
        self._free_pages = list(range(self._n_pages))
        self._tables[:] = self._n_pages
        self._slot_pages[:] = 0
        self._page_refs[:] = 0
        self._prefix_cache.clear()
        self._prefix_lens.clear()
        self._cached_pages = 0
        self._prefix_digest_dirty = True

    def _kv_lost(self) -> bool:
        """True when a failed donated dispatch consumed either cache —
        pytree-aware (a quantized pool is multiple leaves)."""
        return any(leaf.is_deleted() for leaf in
                   jax.tree_util.tree_leaves((self.k_cache,
                                              self.v_cache)))

    @hot_path_boundary(
        "device-loss recovery path: the engine is already off the fast path when this runs")
    def _recover_lost_cache(self, exc: BaseException) -> None:
        """A failed prefill may have consumed the donated caches; if
        so every active slot's KV went with them — fail those streams
        honestly and stand up fresh caches so the engine keeps serving
        new requests."""
        if not self._kv_lost():
            return
        for i, other in enumerate(self.active):
            if other is not None:
                self.active[i] = None
                self._fail(other, f"kv cache lost to failed prefill: "
                                  f"{exc}")
        self.lengths[:] = 0
        self._sched_dirty = True
        self._tables_dirty = True
        # same geometry, pristine allocator
        self.k_cache, self.v_cache = self._alloc_pool(
            max(1, int(self.config.page_size)))
        self._reset_allocator()

    def _sig(self, *parts: Any) -> tuple:
        """Sentinel shape signature for a dispatch site. A non-default
        ``kv_dtype`` changes every compiled graph (quantized pools are
        a different pytree), so it is folded into the signature — bf16
        signatures stay seed-identical."""
        if self.config.kv_dtype != "bf16":
            return (*parts, self.config.kv_dtype)
        return parts

    @hot_path_boundary(
        "O(1) host set probe per dispatch; the metric/log fire only on an anomalous post-warmup recompile")
    def _note_dispatch_shape(self, *sig: Any) -> None:
        """Recompile-sentinel hook at every device dispatch site: a
        novel post-warmup shape signature means XLA is lowering a new
        graph on the serving path — count it and WARN once with the
        offending shape (O(1) host set lookup otherwise)."""
        sig = self._sig(*sig)
        if not self.sentinel.dispatch(sig):
            return
        self.stats["recompiles"] += 1
        if self.metrics is not None:
            self.metrics.increment_counter("app_engine_recompiles")
        if self.logger is not None:
            self.logger.warn(
                "unexpected post-warmup recompile: dispatch shape was "
                "never compiled during warmup",
                signature="/".join(str(p) for p in sig))
        self.events.emit(
            "obs.recompile", severity="warn",
            signature="/".join(str(p) for p in sig))

    def _sig_str(self, *parts: Any) -> str:
        """The sentinel's rendered signature string — the join key the
        cost table, flight-recorder pass records, /debug/costs and the
        fleet federation all share."""
        return "/".join(str(p) for p in self._sig(*parts))

    @hot_path_boundary(
        "cost-model fold at the collect boundary: host float EWMA "
        "updates over the pass duration the collect already measured; "
        "the event/metric/WARN/incident and the profiler arm fire only "
        "on a rare drift-episode entry")
    def _note_pass_cost(self, kind: str, sig_str: str, dur: float, *,
                        rows: int = 0, tokens: int = 0) -> None:
        """Feed one collected pass to the cost observatory. Called at
        every collect site with the SAME duration the goodput ledger
        bills, so /debug/costs conserves against busy seconds. A drift
        episode entry (CostModel.observe returns a record once per
        episode) emits obs.cost_drift, WARNs once, bumps
        app_engine_cost_drift{kind}, arms the autoprofiler and opens a
        cost_drift incident bundle carrying the capture dir. The
        integrity plane's probe cadence ticks here too — one int
        compare per pass when probing is off, a background-lane submit
        when it fires (pass-count-driven, never wall clock)."""
        self.autoprof.note_pass()
        probe = self.integrity.note_pass()
        if probe is not None:
            self._launch_probe(probe)
        if not self.costs.enabled:
            return
        skew = 0.0
        if self.faults is not NO_FAULTS \
                and self.faults.trip("cost_skew", sig_str):
            # deterministic drift induction: inflate the OBSERVED
            # duration only — no sleep, no token perturbation, greedy
            # outputs stay bit-identical (serving/faults.py)
            skew = self.faults.payload("cost_skew")
        drift = self.costs.observe(kind, sig_str, dur, rows=rows,
                                   tokens=tokens, skew_s=skew)
        if drift is None:
            return
        self.stats["cost_drifts"] += 1
        if self.metrics is not None:
            self.metrics.increment_counter("app_engine_cost_drift",
                                           kind=kind)
        if self.logger is not None:
            self.logger.warn(
                "pass cost drifted off its sealed baseline",
                signature=sig_str, ewma_s=drift["ewma_s"],
                baseline_s=drift["baseline_s"], ratio=drift["ratio"])
        self.events.emit("obs.cost_drift", severity="warn",
                         signature=sig_str, pass_kind=kind,
                         ratio=drift["ratio"], ewma_s=drift["ewma_s"],
                         baseline_s=drift["baseline_s"])
        capture = self.autoprof.arm(
            "cost_drift", f"pass cost drift: {sig_str}")
        self.incidents.trigger(
            "cost_drift", cause=f"pass cost drift: {sig_str}",
            attrs={**drift,
                   "autoprof_dir": (capture or {}).get("dir")})

    def cost_state(self) -> dict:
        """The per-model ``GET /debug/costs`` payload: the full cost
        table plus the autoprofiler's state — also an incident-bundle
        source, so every bundle names which kernel class got slower."""
        return {"costs": self.costs.state(),
                "autoprof": self.autoprof.state()}

    def integrity_state(self) -> dict:
        """The per-model ``GET /debug/integrity`` payload: digest-fold
        totals, golden corpus, probe results and the mismatch-episode
        latch — also an incident-bundle source, so an integrity bundle
        names which golden prompt diverged."""
        return self.integrity.state()

    def _launch_probe(self, entry) -> None:
        """Submit one golden canary through the normal admission path
        on the scheduler's BACKGROUND lane — a probe must never crowd
        out interactive traffic (it yields to it by lane policy), and
        it must exercise exactly the serving path users ride, or a
        clean probe would prove nothing. The GenRequest is built
        directly (not via ``submit``) so the probe marker is stamped
        before any admission refusal can retire the request."""
        p = entry.params
        params = SamplingParams(temperature=p["temperature"],
                                top_p=p["top_p"], top_k=p["top_k"],
                                max_new_tokens=p["max_new_tokens"])
        req = GenRequest(
            prompt_tokens=self._clamp_prompt(list(entry.prompt_tokens),
                                             params.max_new_tokens),
            params=params, tenant="_integrity", lane="background")
        req.probe = entry.id
        req.probe_expected = entry.digest
        if self._draining or not self.waiting.put(req):
            # refused at admission (drain window, queue_full, shed):
            # release the in-flight latch — the cadence retries later
            self.integrity.probe_aborted()

    @hot_path_boundary(
        "integrity fold at the retire boundary: one blake2b over token "
        "ids the collects already emitted plus host dict bookkeeping "
        "for probe results; the WARN/event/metric/incident fire only "
        "on a rare probe-mismatch episode entry — runs once per "
        "request, never per pass")
    def _note_integrity(self, req: GenRequest) -> None:
        """Feed one retired request to the integrity plane: stamp the
        output fingerprint (flight recorder and workload records pick
        it up downstream in ``_finalize_obs``), re-price golden-probe
        device time to the ``integrity_probe`` waste cause, emit the
        probe's ``obs.integrity`` event, and on a mismatch episode
        entry (IntegrityPlane.fold returns a record once per episode)
        WARN once, bump ``app_engine_integrity_failures{kind}`` and
        open an incident bundle."""
        mismatch = self.integrity.fold(req)
        if req.probe:
            # canary device time is correctness verification, not
            # serving goodput — move it to the conserving ledger's
            # integrity_probe cause (busy unchanged)
            self.goodput.reprice_probe(req.device_s)
            self.integrity.probe_device_s += req.device_s
            rec = self.integrity.last.get(req.probe)
            if rec is not None and req.error is None \
                    and not req.cancelled:
                self.events.emit(
                    "obs.integrity",
                    severity="info" if rec["ok"] else "warn",
                    golden_id=req.probe, digest=rec["digest"],
                    expected=req.probe_expected, ok=rec["ok"],
                    seq=rec["seq"])
        if mismatch is None:
            return
        self.stats["integrity_failures"] += 1
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_engine_integrity_failures", kind="probe_mismatch")
        if self.logger is not None:
            self.logger.warn(
                "golden probe digest mismatch: this host's greedy "
                "output diverged from its sealed expectation",
                golden_id=mismatch["golden_id"],
                digest=mismatch["digest"],
                expected=mismatch["expected"])
        self.incidents.trigger(
            "integrity",
            cause=f"golden probe digest mismatch: "
                  f"{mismatch['golden_id']}",
            attrs=dict(mismatch))

    def _corrupt_token(self, token: int) -> int:
        """The ``logit_corrupt`` fault site's host-visible effect: the
        device's sampled token is replaced deterministically, as a
        corrupted logit row would have sampled a different id (the
        real logits never cross to the host — the zero-h2d invariant —
        so the collected token IS where device corruption becomes
        observable). The perturbed id never lands on ``eos_id``:
        stream lengths are preserved, nothing crashes, only digests
        diverge."""
        alt = token ^ 1
        if alt == self.config.eos_id:
            alt = token ^ 2
        return alt

    def _note_device_idle(self) -> None:
        """Goodput bubble tracking: a synchronous collect finished and
        no dispatched pass remains in flight — from the host's view the
        device is idle. Record whether work was waiting (queued,
        requeued, or active slots mid-generation) so the gap until the
        next dispatch can be classified as bubble waste."""
        if not self.goodput.enabled:
            return
        if self._pending or self._pending_prefills:
            return  # a pass is still in flight: the device isn't idle
        backlog = (bool(self._requeued) or self.waiting.qsize() > 0
                   or any(r is not None and not r.pending_prefill
                          for r in self.active))
        self.goodput.note_pass_end(time.perf_counter(), backlog)

    def _req_event(self, req: GenRequest, name: str, t0: float,
                   t1: float, attrs: dict | None = None) -> None:
        """Append a lifecycle event (bounded) — spans and the flight
        recorder's request log assemble from these at retire."""
        if len(req.events) < 64:
            req.events.append((name, t0, t1, attrs or {}))

    @hot_path_boundary(
        "admission boundary: closes the queue-wait span exactly once per request")
    def _note_admitted(self, req: GenRequest) -> None:
        """First slot assignment: the queue span ends here. Recompute
        re-admissions (preemption, pool-exhaustion restarts) keep the
        original admission time — the queue wait was paid once."""
        if req.admitted_at is None:
            now = time.time()
            req.admitted_at = now
            if self.metrics is not None:
                self.metrics.record_histogram(
                    "app_chat_queue_seconds", now - req.submitted_at,
                    exemplar_trace_id=req.trace[0] if req.trace else None)

    def _finalize_obs(self, req: GenRequest) -> None:
        """Terminal observability for a request (exactly once): latency
        histograms, the flight-recorder request log, and the engine.*
        span assembly. All host arithmetic over timestamps already
        collected — called before the terminal None is emitted so a
        drained stream implies the spans are exported."""
        if req._obs_done:
            return
        req._obs_done = True
        end = req.finished_at or time.time()
        exemplar = req.trace[0] if req.trace else None
        n = len(req.generated)
        ttft_s = ((req.first_token_at - req.submitted_at)
                  if req.first_token_at is not None else None)
        tpot_s = ((end - req.first_token_at) / (n - 1)
                  if req.first_token_at is not None and n > 1 else None)
        e2e_s = end - req.submitted_at
        if self.metrics is not None and req.error is None \
                and not req.cancelled:
            self.metrics.record_histogram("app_chat_e2e_seconds", e2e_s,
                                          exemplar_trace_id=exemplar)
            if tpot_s is not None:
                self.metrics.record_histogram(
                    "app_chat_tpot_seconds", tpot_s,
                    exemplar_trace_id=exemplar)
        if self.usage_ledger is not None:
            status = ("cancelled" if req.cancelled
                      else "error" if req.error is not None else "ok")
            queue_s = ((req.admitted_at - req.submitted_at)
                       if req.admitted_at is not None else 0.0)
            self.usage_ledger.record(
                tenant=req.tenant or "anonymous", status=status,
                prompt_tokens=len(req.prompt_tokens),
                completion_tokens=n, queue_s=queue_s, e2e_s=e2e_s,
                device_s=req.device_s,
                waste_recompute_s=req.waste_recompute_s,
                waste_spec_s=req.waste_spec_s, t=end)
        if self.slo is not None and not req.cancelled \
                and getattr(req, "reject", None) is None \
                and not req.probe:
            # golden canary probes are synthetic traffic: a corrupted
            # host's probes must alarm the INTEGRITY plane, not burn
            # the availability error budget into a shed episode.
            # Likewise, typed admission refusals (429/shed) are policy, not
            # service failures: counting them as SLO errors would let
            # one tenant's flood burn the global budget and trip the
            # shedder against everyone else (a rejection -> burn ->
            # shed feedback loop). They are priced by
            # app_sched_rejections instead.
            good = self.slo.judge(error=req.error, ttft_s=ttft_s,
                                  tpot_s=tpot_s, e2e_s=e2e_s)
            self.slo.record(good, t=end)
            # the same verdict feeds the scheduler's per-tenant burn
            # column (the /debug/scheduler victim/offender view)
            if hasattr(self.waiting, "note_retire"):
                self.waiting.note_retire(req.tenant, good, t=end)
        if self.integrity.enabled:
            # digest fold BEFORE the recorder/workload writes below,
            # so both records carry the fingerprint
            self._note_integrity(req)
        if self.recorder.enabled:
            from .observability import request_summary
            self.recorder.record_request(request_summary(req))
        if self.workload.capturing and not req.probe:
            # golden probes stay out of the capture ring: the replay
            # corpus (and any golden set sealed from it) must hold
            # real traffic, not the canaries checking it
            self.workload.record(req)
        if self.tracer is not None and req.trace is not None:
            try:
                from .observability import emit_engine_spans
                emit_engine_spans(self.tracer, req)
            except Exception:  # tracing must never take down a stream
                pass

    @hot_path_boundary(
        "terminal error path; observability assembly mirrors _retire")
    def _fail(self, req: GenRequest, error: str) -> None:
        req.error = error
        req.finished_at = time.time()
        self._finalize_obs(req)
        req._emit(None)

    @hot_path_boundary(
        "lifecycle refusal path (drain/crash window), not steady-state")
    def _refuse(self, req: GenRequest, code: str, detail: str, *,
                retry_after_s: float = 1.0) -> None:
        """Fail ``req`` with a typed, machine-readable reject — the
        same :class:`~.scheduler.SchedReject` shape the scheduler
        stamps for policy refusals, so the handlers' structured-error
        path (503 + ``Retry-After`` + ``details.code``, OpenAI-compat
        included) covers lifecycle refusals (drain, crash window, KV
        exhaustion) too. Typed rejects are policy, not service
        failures: ``_finalize_obs`` keeps them out of the SLO burn."""
        from .scheduler import SchedReject
        req.reject = SchedReject(code=code, tenant=req.tenant,
                                 retry_after_s=retry_after_s,
                                 detail=detail)
        self._fail(req, req.reject.message)

    def _admit_live(self, batch: list[GenRequest]) -> None:
        """Drop what was cancelled while it waited, admit the rest."""
        live = []
        for r in batch:
            if r.cancelled:  # dropped before prefill
                if (r.pending_prefill and r.slot >= 0
                        and self.active[r.slot] is r):
                    # mid chunk-walk: free the slot too
                    self._retire(r.slot)
                elif r.finished_at is None:
                    r.finished_at = time.time()
                    r._emit(None)
            else:
                live.append(r)
        if live:
            self._admit_batch(live)

    def _admit_batch(self, reqs: list[GenRequest]) -> None:
        """Admit a burst: group by prompt bucket, prefill each group in
        chunks of ``prefill_batch`` with one device call per chunk.
        Prompts wider than every bucket take the chunked path."""
        by_bucket: dict[int, list[GenRequest]] = {}
        walkers: list = []
        widest = max(self._usable_buckets)

        def reserve_for_walk(req: GenRequest, slot: int) -> None:
            # hold the slot NOW: walkers dispatch together after the
            # bucket groups, and _free_slot must not hand their slot
            # to a later request in this same batch
            self.active[slot] = req
            req.slot = slot
            walkers.append((req, slot))

        for req in reqs:
            if req.finished_at is not None:
                continue  # failed/retired while queued
            if (not req.pending_prefill and req.slot >= 0
                    and self.active[req.slot] is req):
                continue  # already serving (stale duplicate entry)
            if req.pending_prefill:  # resuming a chunk walk
                if req.slot >= 0 and self.active[req.slot] is req:
                    walkers.append((req, req.slot))
                elif req.finished_at is None:
                    # slot lost (pool-exhaustion restart / preemption):
                    # re-admit from scratch
                    slot = self._free_slot()
                    if slot < 0:
                        self._requeue(req)
                    else:
                        reserve_for_walk(req, slot)
                continue
            if self._prefix_enabled and req.prefill_offset == 0:
                covered = self._probe_prefix(req.prompt_tokens)
                if covered:
                    slot = self._free_slot()
                    if slot < 0:
                        self._requeue(req)
                    else:
                        # shared prefix KV attaches; only the suffix
                        # computes, through the chunk-with-history walk
                        self._attach_prefix(slot, req.prompt_tokens,
                                            covered)
                        req.prefill_offset = covered
                        _now = time.time()
                        self._req_event(req, "prefill", _now, _now,
                                        {"prefix_hit": True,
                                         "covered_rows": covered})
                        reserve_for_walk(req, slot)
                    continue
            if self._chunk_walks and len(req.prompt_tokens) > widest:
                slot = self._free_slot()
                if slot < 0:  # raced out of slots; try next pass
                    self._requeue(req)
                else:
                    reserve_for_walk(req, slot)
                continue
            bucket = self._bucket_for(len(req.prompt_tokens))
            by_bucket.setdefault(bucket, []).append(req)
        P = max(1, self.config.prefill_batch)
        for bucket, group in by_bucket.items():
            for i in range(0, len(group), P):
                self._prefill_group(bucket, group[i:i + P])
        if walkers:
            # after the bucket dispatches: their device work overlaps
            # the walk's synchronous rounds
            self._walk_chunks(walkers)
        # below the pipelining threshold the decode pass these prefills
        # would hide behind is cheap and TTFT is the scarce resource —
        # sync first tokens out now instead of after the next pass
        if self._pending_prefills and self._pipeline_depth() == 0:
            self._collect_prefills()

    @hot_path
    def _prefill_group(self, bucket: int, chunk: list[GenRequest]) -> None:
        pg = self.config.page_size
        placed: list[GenRequest] = []
        for req in chunk:
            slot = self._free_slot()
            if slot < 0:  # raced out of slots; back to the requeue list
                self._requeue(req)
                continue
            if -(-(len(req.prompt_tokens) + 1) // pg) > self._n_pages:
                # can never fit, no matter what retires
                self._fail(req, "prompt exceeds kv pool")
                continue
            if not self._alloc_pages(slot, len(req.prompt_tokens) + 1):
                # pool busy: requeue and wait for retires to free pages
                self._requeue(req)
                continue
            if req.admit_order < 0:
                req.admit_order = self._admit_seq
                self._admit_seq += 1
            req.slot = slot
            self._dev_last_reqs[slot] = None  # fresh occupant: host token
            self.active[slot] = req       # reserve before the next scan
            self._note_admitted(req)
            placed.append(req)
        if not placed:
            return

        # smallest compiled group size that fits: sparse traffic pays
        # for a [1..2, bucket] forward, bursts amortise the full width
        P = next(g for g in self._group_sizes() if g >= len(placed))
        self._rng_step += 1
        self._note_dispatch_shape("prefill", bucket, P)
        pass_id = self.recorder.new_pass()
        with self.recorder.span("engine.prefill_dispatch", pass_id) as sp:
            self._enqueue_prefill(bucket, P, placed, pass_id, sp.t0)

    @hot_path
    def _enqueue_prefill(self, bucket: int, P: int,
                         placed: list[GenRequest], pass_id: int,
                         start: float) -> None:
        """Build the rows of one bucket prefill and enqueue it."""
        self.goodput.note_dispatch(start)
        try:
            tokens = np.zeros((P, bucket), np.int32)
            kv_len = np.ones(P, np.int32)                # dummy rows: length 1
            # per-row block tables; dummy rows all-OOB: dropped
            slots = np.full((P, self._pages_per_slot), self._n_pages,
                            np.int32)
            temps = np.zeros(P, np.float32)
            top_ps = np.ones(P, np.float32)
            top_ks = np.zeros(P, np.int32)
            for row, req in enumerate(placed):
                n = len(req.prompt_tokens)
                tokens[row, :n] = req.prompt_tokens
                kv_len[row] = n
                slots[row] = self._tables[req.slot]
                temps[row] = req.params.temperature
                top_ps[row] = req.params.top_p
                top_ks[row] = req.params.top_k

            prefill = self._get_prefill(bucket, P)
            toks, self.k_cache, self.v_cache = prefill(
                self.params, jnp.asarray(tokens), jnp.asarray(kv_len),
                self.k_cache, self.v_cache, jnp.asarray(slots),
                np.int32(self._rng_step), jnp.asarray(temps),
                jnp.asarray(top_ps), jnp.asarray(top_ks),
                self._prefill_base_key)
            self.stats["prefill_calls"] += 1
        except Exception as exc:
            for req in placed:
                self.active[req.slot] = None
                self._release_pages(req.slot)
                self._fail(req, str(exc))
            if self.logger:
                self.logger.error(f"prefill failed: {exc!r}")  # gofrlint: allow(hot-path-purity) -- failure path: the prefill already raised; the engine is off the fast path
            self._recover_lost_cache(exc)
            return

        # PIPELINED: don't block on the first tokens here — the decode
        # pass for everyone else dispatches first, and the tokens are
        # collected when the device gets there (_collect_prefills).
        # Until then the slots hold their requests but don't decode.
        self._sched_dirty = True  # freshly occupied slots go pending
        for req in placed:
            req.pending_prefill = True
            req.prefill_epoch += 1
        self._pending_prefills.append({
            "toks": toks,
            "placed": list(placed),
            "slots": [r.slot for r in placed],
            "epochs": [r.prefill_epoch for r in placed],
            "t0": start,
            "wall0": time.time(),  # span timestamps use wall clock  # gofrlint: allow(hot-path-purity) -- span timestamps use wall clock; once per prefill dispatch, never per decode pass
            "bucket": bucket,
            "pass_id": pass_id,
        })

    @hot_path
    def _collect_prefills(self) -> None:
        """Sync dispatched batch prefills: emit first tokens, open the
        slots for decode. Requests whose slot changed hands or that
        were re-dispatched since (epoch mismatch) are discarded — their
        current life owns its own prefill."""
        if not self._pending_prefills:
            self._note_device_idle()
            return
        with self.recorder.span("engine.prefill_collect"):
            self._collect_pending_prefills()

    @hot_path
    def _collect_pending_prefills(self) -> None:
        # collected slots flip pending -> decoding with new lengths
        self._sched_dirty = True
        while self._pending_prefills:
            rec = self._pending_prefills.popleft()
            try:
                with self.recorder.span("engine.prefill_wait",
                                        rec["pass_id"]) as wait:
                    toks_np = np.asarray(rec["toks"])  # gofrlint: allow(hot-path-purity) -- this sync IS the prefill collect: first tokens cross to host here by design
            except Exception as exc:
                for req, slot, epoch in zip(rec["placed"], rec["slots"],
                                            rec["epochs"]):
                    if req.prefill_epoch != epoch:
                        continue  # re-dispatched elsewhere since
                    req.pending_prefill = False
                    if self.active[slot] is req:
                        self.active[slot] = None
                        self._release_pages(slot)
                    if req.finished_at is None:
                        self._fail(req, str(exc))
                if self.logger:
                    self.logger.error(f"prefill failed: {exc!r}")  # gofrlint: allow(hot-path-purity) -- failure path: device collect raised; slots are being failed, not served
                self._recover_lost_cache(exc)
                continue
            self._note_prefill_span(rec["t0"])
            now = time.time()  # gofrlint: allow(hot-path-purity) -- wall-clock span assembly at the prefill collect boundary, once per batch
            pass_dur = wait.t1 - rec["t0"]
            pass_share = pass_dur / max(1, len(rec["placed"]))
            # the dispatch's (bucket, group) signature: group size is
            # the padded batch axis the graph compiled for
            prefill_sig = self._sig_str("prefill", rec.get("bucket"),
                                        int(toks_np.shape[0]))
            if self.recorder.enabled:
                self.recorder.record_pass(
                    "prefill", rec["pass_id"], t0=rec["t0"], t1=wait.t1,
                    rids=[r.rid for r in rec["placed"]],
                    lens=[len(r.prompt_tokens) for r in rec["placed"]],
                    bucket=rec.get("bucket"),
                    group=int(toks_np.shape[0]), sig=prefill_sig,
                    dur=round(pass_dur, 6),
                    occupancy=sum(r is not None for r in self.active),
                    queue_depth=self.waiting.qsize())
            fresh_rows = recompute_rows = 0
            for row, (req, slot, epoch) in enumerate(
                    zip(rec["placed"], rec["slots"], rec["epochs"])):
                if (req.prefill_epoch != epoch
                        or self.active[slot] is not req
                        or req.finished_at is not None):
                    # preempted/retired/re-admitted since: the row's
                    # compute is discarded — preemption-class waste
                    recompute_rows += 1
                    continue
                req.pending_prefill = False
                req.device_s += pass_share
                if req.first_token_at is not None or req.recovered:
                    # a recompute row: the KV it just prefilled was
                    # already computed in its pre-preemption (or
                    # pre-restart) life
                    recompute_rows += 1
                    req.waste_recompute_s += pass_share
                else:
                    fresh_rows += 1
                self._req_event(req, "prefill", rec.get("wall0", now),
                                now, {"bucket": rec.get("bucket"),
                                      "rows": len(rec["placed"])})
                first = int(toks_np[row])
                if self.faults is not NO_FAULTS and \
                        self.faults.trip("logit_corrupt", req.tenant):
                    first = self._corrupt_token(first)
                if req.first_token_at is None:  # not a recompute
                    req.first_token_at = now
                    if self.metrics is not None:
                        self.metrics.record_histogram(  # gofrlint: allow(hot-path-purity) -- TTFT observation at the collect boundary, once per request lifetime
                            "app_chat_ttft_seconds",
                            now - req.submitted_at,
                            exemplar_trace_id=req.trace[0]
                            if req.trace else None)
                req.generated.append(first)
                req._emit(first)
                self.total_generated += 1
                self.lengths[slot] = len(req.prompt_tokens)
                if self._finished(req, first):
                    self._retire(slot)
            self.goodput.add_prefill("prefill", pass_dur,
                                     int(toks_np.shape[0]), fresh_rows,
                                     recompute_rows)
            self._note_pass_cost(
                "prefill", prefill_sig, pass_dur,
                rows=int(toks_np.shape[0]),
                tokens=int(toks_np.shape[0]) * (rec.get("bucket") or 0))
            self._update_kv_watermarks()
        self._note_device_idle()

    def _note_view_avoided(self, n_rows: int) -> None:
        """Account HBM bytes a dense-view round trip would have moved
        for a dispatch of ``n_rows`` slots that ran on the native
        path instead (gather of the K and V per-slot views; the
        write-back scatter is smaller and not counted). Surfaced in
        ``stats`` next to ``h2d_transfers``: steady native serving
        grows it every chunk/verify dispatch, the view path leaves it
        flat."""
        from ..ops.paged_kv import pool_shape
        pg = pool_shape(self.k_cache)[3]
        self.stats["view_bytes_avoided"] += \
            n_rows * self._pages_per_slot * pg * self._kv_row_bytes

    def _note_prefill_span(self, start: float) -> None:
        """prefill_s accumulates a UNION of dispatch→sync spans: two
        bucket groups dispatched back-to-back and collected after the
        same decode pass cover nearly the same wall interval — naive
        sums would double-count (same watermark trick as decode_s)."""
        end = time.perf_counter()
        self.stats["prefill_s"] += end - max(start,
                                             self._prefill_busy_until)
        self._prefill_busy_until = end

    def _retire_unservable(self) -> None:
        """Shared pre-pass sweep: cancelled or at-ceiling slots leave
        before any device compute (decode and verify passes alike)."""
        for i, req in enumerate(self.active):
            if req is None:
                continue
            if req.cancelled:
                # a cancelled slot's in-flight tokens are discarded by
                # design — retire now, the collect discard-check holds
                self._retire(i)
            elif self.lengths[i] >= self.config.max_seq:
                # lengths advance at DISPATCH, so an uncollected pass
                # may still hold this slot's final tokens — settle it
                # (which usually retires the slot via valid < K) before
                # declaring the slot spent
                if any(rec["mask"][i] and rec["reqs"][i] is req
                       for rec in self._pending):
                    self._drain_pending()
                if (self.active[i] is req
                        and self.lengths[i] >= self.config.max_seq):
                    self._retire(i)

    def _note_pass(self, stat_key: str, start: float) -> None:
        """Per-device-pass accounting shared by decode and verify."""
        elapsed = time.perf_counter() - start
        self.stats[stat_key] += 1
        self.stats["decode_s"] += elapsed
        if self.metrics is not None:
            self.metrics.record_histogram("app_tpu_execute_seconds",
                                          elapsed)
        self._step_count += 1

    def _finished(self, req: GenRequest, token: int) -> bool:
        if token == self.config.eos_id:
            return True
        return len(req.generated) >= req.params.max_new_tokens

    @hot_path_boundary(
        "terminal per-request path: host-side span/metric/ledger assembly at retire is the architecture (PRs 3-5); runs once per request, never per pass")
    def _retire(self, slot: int) -> None:
        req = self.active[slot]
        if req is None:
            return
        self._dev_last_reqs[slot] = None  # device-token lineage ends here
        self._sched_dirty = True
        req.finished_at = time.time()
        with self.recorder.span("engine.finalize"):
            self._finalize_obs(req)  # before the terminal None: a drained
            #                          stream implies spans are exported
        req._emit(None)
        self.active[slot] = None
        self.lengths[slot] = 0
        if req.error is None and not req.cancelled:
            self._register_prefix(slot, req)
        self._release_pages(slot)

    # -------------------------------------------------------------- decode
    #
    # The decode path is PIPELINED: each iteration dispatches pass N+1
    # to the device and only then blocks on pass N's tokens, so the
    # host round trip (token download, stream emission, admission
    # bookkeeping) overlaps device compute instead of serialising with
    # it.  Pass N+1's input tokens come straight from pass N's device
    # output (``_dev_last``) — no host sync sits between passes.  The
    # cost: a slot that finishes in pass N still rides pass N+1 with
    # garbage output (discarded at collect), one wasted pass per
    # retirement.  Anything that mutates request state an uncollected
    # pass still owns (_retire, _preempt, spec passes) settles the
    # pipeline first.

    def _pipeline_depth(self) -> int:
        """How many dispatched passes to leave in flight right now.

        Adaptive by default: overlap only pays at saturation, where
        per-pass host work is large (many streams) and retirements are
        rare relative to passes; below ``pipeline_min_slots`` decoding
        slots the wasted pass per retirement and the one-pass token lag
        cost more than the overlap buys (VERDICT r4 weak #2)."""
        cfg = self.config
        if cfg.pipeline_depth is not None:
            return max(0, int(cfg.pipeline_depth))
        decoding = sum(1 for r in self.active
                       if r is not None and not r.pending_prefill)
        return 1 if decoding >= cfg.pipeline_min_slots else 0

    @hot_path
    def _decode_step(self) -> None:
        before = len(self._pending)
        self._decode_dispatch()
        if len(self._pending) == before:
            # nothing dispatched (every slot mid chunk-walk): settle
            # whatever is in flight so those streams don't stall
            self._drain_pending()
        else:
            depth = self._pipeline_depth()
            while len(self._pending) > depth:
                self._decode_collect()

    @hot_path
    def _drain_pending(self) -> None:
        while self._pending:
            self._decode_collect()

    @hot_path
    def _sync_decode_state(self) -> None:
        """Rebuild + upload the per-slot scheduler arrays the decode
        graph consumes. Called ONLY when an event (admission, retire,
        preemption, prefill transition, spec pass) flipped
        ``_sched_dirty`` — steady-state passes reuse the device copies
        untouched, and the decode graph itself advances lengths and
        the rng counter on device."""
        cfg = self.config
        b = cfg.max_batch
        tokens = np.zeros(b, np.int32)
        use_prev = np.zeros(b, bool)
        temps = np.zeros(b, np.float32)
        top_ps = np.ones(b, np.float32)
        top_ks = np.zeros(b, np.int32)
        active = np.zeros(b, bool)
        device_lengths = self.lengths.copy()
        fresh: list[int] = []
        for i, req in enumerate(self.active):
            if req is None:
                continue
            if req.pending_prefill:
                # mid chunked-prefill: the slot holds real KV rows the
                # chunk walk wrote — the decode pass must neither write
                # into them (length = max_seq makes the scatter drop)
                # nor emit its garbage samples
                device_lengths[i] = cfg.max_seq
                continue
            active[i] = True
            if (self._dev_last is not None
                    and self._dev_last_reqs[i] is req):
                # continuing slot: its true last token is pass N's
                # device output — feed it without syncing
                use_prev[i] = True
            else:
                tokens[i] = req.generated[-1]
                fresh.append(i)
            temps[i] = req.params.temperature
            top_ps[i] = req.params.top_p
            top_ks[i] = req.params.top_k
        self._dev_sched = {
            "tokens": jnp.asarray(tokens),
            "use_prev": jnp.asarray(use_prev),
            "active": jnp.asarray(active),
            "lengths": jnp.asarray(device_lengths),
            "temps": jnp.asarray(temps),
            "top_ps": jnp.asarray(top_ps),
            "top_ks": jnp.asarray(top_ks),
        }
        self._active_np = active
        self._fresh_rows = fresh
        self._sched_dirty = False
        self.stats["sched_syncs"] += 1
        self.stats["h2d_transfers"] += 7
        if self.metrics is not None:
            self.metrics.add_counter("app_engine_h2d_transfers", 7.0)  # gofrlint: allow(hot-path-purity) -- event-driven sched sync: this write records the h2d-invariant counter (zero per steady-state pass)

    @hot_path
    def _tables_arg(self):
        """Device-resident block tables, re-uploaded only when the
        host tables changed (page alloc/free/prefix attach) — page
        growth is the one mid-steady-state table event, every
        ``page_size // tokens_per_pass`` passes per slot."""
        if self._tables_dirty or self._dev_tables is None:
            self._dev_tables = jnp.asarray(self._tables)
            self._tables_dirty = False
            self.stats["h2d_transfers"] += 1
            if self.metrics is not None:
                self.metrics.add_counter("app_engine_h2d_transfers", 1.0)  # gofrlint: allow(hot-path-purity) -- event-driven table upload: page growth, not steady state; the write records the h2d invariant
        return self._dev_tables

    @hot_path
    def _decode_dispatch(self) -> None:
        # the id is taken before anything is known to decode, so the
        # span and the profiler's annotation carry it; a dispatch that
        # finds no active row leaves an id with spans and no record
        pass_id = self.recorder.new_pass()
        with self.recorder.span("engine.decode_dispatch", pass_id) as sp:
            rec = self._enqueue_decode(pass_id)
        if rec is not None:
            rec["disp"] = sp.t1 - sp.t0
            self.stats["dispatch_s"] += rec["disp"]

    @hot_path
    def _enqueue_decode(self, pass_id: int) -> dict | None:
        """Sweep, make room, sync the scheduler arrays if an event
        dirtied them, enqueue one decode pass; the pending record, or
        None when no row decodes."""
        cfg = self.config
        T = self._tokens_per_pass
        h2d0 = self.stats["h2d_transfers"]  # this pass's upload delta
        # pre-pass sweep retires cancelled/at-ceiling slots, which
        # settles the pipeline per-slot via _retire
        self._retire_unservable()
        # grow each slot's block table to cover this pass, evicting
        # the newest requests when the pool runs dry (they resume
        # by recompute); iterate oldest-first so survivors are the
        # requests closest to completion
        order = sorted(
            (i for i, r in enumerate(self.active) if r is not None),
            key=lambda i: self.active[i].admit_order)
        for i in order:
            if self.active[i] is None:  # preempted by an earlier slot
                continue
            if self.active[i].pending_prefill:
                continue  # chunk walk allocates its own pages
            rows = min(int(self.lengths[i]) + T, cfg.max_seq)
            if not self._ensure_headroom(i, rows):
                self._preempt(i)  # pool can't hold even this one now

        if self._sched_dirty:
            self._sync_decode_state()
        active_mask = self._active_np
        if not active_mask.any():
            return None
        st = self._dev_sched

        # steps whose cache write would land past max_seq-1 are dropped
        # by the device scatter and attend to stale rows; their samples
        # are garbage — account the valid prefix NOW on the host mirror
        # (the graph advances the device lengths with the same clamp)
        decode = self._decode
        win = 0
        if self._decode_windows:
            # smallest compiled window covering every live row this
            # pass will touch (len + T); pending-prefill slots carry
            # the max_seq drop sentinel and decode garbage either way,
            # so only active slots bound the window
            needed = int(self.lengths[active_mask].max()) + T
            for w in self._decode_windows:
                if needed <= w:
                    decode = self._decode_by_window[w]
                    win = w
                    break
        self._note_dispatch_shape("decode", win)
        valid = np.where(active_mask,
                         np.minimum(T, cfg.max_seq - self.lengths),
                         0).astype(np.int32)
        self.lengths += valid

        start = time.perf_counter()
        self.goodput.note_dispatch(start)
        prev = (self._dev_last if self._dev_last is not None
                else self._dev_zero)
        (step_tokens, self._dev_last, self.k_cache, self.v_cache,
         new_lengths, self._dev_rng_step) = decode(
            self.params, st["tokens"], st["use_prev"], prev,
            self.k_cache, self.v_cache, self._tables_arg(), st["lengths"],
            st["active"], self._dev_rng_step, st["temps"],
            st["top_ps"], st["top_ks"], self._dev_decode_key)
        st["lengths"] = new_lengths  # device mirror of self.lengths
        self._dev_last_reqs = [
            req if active_mask[i] else None
            for i, req in enumerate(self.active)]
        if self._fresh_rows:
            # rows fed from host tokens this pass continue from the
            # device output next pass: their use_prev flips — one more
            # sync, then steady state
            self._sched_dirty = True
        rows = np.flatnonzero(active_mask)
        rec = {
            "toks": step_tokens,
            "reqs": list(self.active),
            "mask": active_mask,
            "valid": valid,
            "t0": start,
            "pass_id": pass_id,
            # what the pass works on, for its record: request id and
            # context length after the pass (lengths advanced above)
            "rids": [self.active[i].rid for i in rows],
            "ctx": self.lengths[rows].tolist(),
            "win": win,
            "h2d": self.stats["h2d_transfers"] - h2d0,
        }
        self._pending.append(rec)
        return rec

    @hot_path
    def _decode_collect(self) -> None:
        """Sync the oldest in-flight pass: emit its tokens, retire
        finished slots.  Slots whose request was retired or preempted
        since dispatch are discarded (their rows decoded garbage)."""
        if not self._pending:
            return
        if self.faults is not NO_FAULTS:
            # corrupt-pass injection: a pass HAS dispatched, so tokens
            # are in flight — recovery must take the mid-stream
            # typed-retryable branch, never the bit-identical replay
            self.faults.trip("nan_logits")
        rec = self._pending.popleft()
        pass_id = rec["pass_id"]
        with self.recorder.span("engine.decode_wait", pass_id) as wait:
            step_np = np.asarray(rec["toks"])  # [T, B] — blocks on device  # gofrlint: allow(hot-path-purity) -- this sync IS the decode collect: the token download is the pass's one sanctioned device read
        # decode_s = wall time with a decode pass in flight (dispatch →
        # sync complete), accumulated as a UNION of spans — consecutive
        # passes overlap (N+1 dispatches before N collects), and host/
        # prefill work overlapping a pass still counts as decode here
        end = wait.t1
        with self.recorder.span("engine.emit", pass_id) as emit:
            busy = end - max(rec["t0"], self._decode_busy_until)
            self._decode_busy_until = end
            self.stats["decode_passes"] += 1
            self.stats["decode_s"] += busy
            occupancy = int(rec["mask"].sum())
            if self.metrics is not None:
                self.metrics.record_histogram("app_tpu_execute_seconds", busy)  # gofrlint: allow(hot-path-purity) -- per-pass observation at the collect sync point, host floats already paid for
                self.metrics.record_histogram("app_engine_batch_occupancy",  # gofrlint: allow(hot-path-purity) -- per-pass observation at the collect sync point, host floats already paid for
                                              float(occupancy))
            self._step_count += 1
            # KV watermark BEFORE retires zero the finishing slots: the
            # dispatch already advanced lengths, so this is the pass peak
            self._update_kv_watermarks()
            emitted = 0
            credited = 0  # rows whose request actually kept this pass
            share = busy / occupancy if occupancy else 0.0
            for i, req in enumerate(rec["reqs"]):
                if req is None or not rec["mask"][i]:
                    continue
                if self.active[i] is not req or req.finished_at is not None:
                    continue  # retired/preempted since dispatch: discard
                # device-time attribution: this pass's busy span split
                # evenly across its occupied rows — the per-tenant
                # device_seconds the usage ledger accounts at retire
                req.device_s += share
                credited += 1
                done = False
                for k in range(int(rec["valid"][i])):
                    token = int(step_np[k, i])
                    if self.faults is not NO_FAULTS and \
                            self.faults.trip("logit_corrupt", req.tenant):
                        token = self._corrupt_token(token)
                    req.generated.append(token)
                    req._emit(token)
                    self.total_generated += 1
                    emitted += 1
                    if self._finished(req, token):
                        done = True
                        break
                if done or rec["valid"][i] < self._tokens_per_pass:
                    self._retire(i)
        collect = emit.t1 - end
        self.stats["collect_s"] += collect
        with self.recorder.span("engine.planes", pass_id):
            # goodput: rows that kept the pass are useful; empty slots,
            # pending-prefill sentinels and retired requests riding out a
            # pipelined pass are padding waste
            self.goodput.add_decode(busy, credited, self.config.max_batch)
            # fit the controller's sec/token price from the same busy span
            # the goodput ledger bills — an accepted draft token is worth
            # exactly what a plain-decode token costs
            self._spec_ctrl.note_decode(busy, emitted)
            decode_sig = self._sig_str("decode", rec.get("win", 0))
            self._note_pass_cost("decode", decode_sig, busy,
                                 rows=credited, tokens=emitted)
            if self.recorder.enabled:
                # the pass record: everything here is a host int/float the
                # collect already computed — no device reads beyond the
                # token sync that IS the collect. A family whose step
                # counts on the device sent the counters as extra columns
                # of the token array (_fused_decode) and named them
                # (``decode_facts``)
                facts = step_np[:, self.config.max_batch:]
                counted = {name: read(facts[:, i]) for i, (name, read)
                           in enumerate(self._decode_facts.items())}
                if counted:
                    counted["kv_row_bytes"] = self._kv_row_bytes
                self.recorder.record_pass(
                    "decode", rec["pass_id"], t0=rec["t0"], t1=end,
                    **counted,
                    rids=rec["rids"], ctx=rec["ctx"],
                    steps=self._tokens_per_pass, win=rec.get("win", 0),
                    dur=round(busy, 6),
                    # the durations of this pass's engine.decode_dispatch
                    # and engine.emit spans
                    dispatch_s=round(rec.get("disp", 0.0), 6),
                    collect_s=round(collect, 6), occupancy=occupancy,
                    sig=decode_sig,
                    queue_depth=self.waiting.qsize(), tokens=emitted,
                    h2d=rec.get("h2d", 0),
                    preemptions=self.stats["preemptions"])
            self._note_device_idle()

    # ------------------------------------------------- speculative decode
    def _get_spec_verify(self) -> Callable:
        """Fused tree-verify pass over all slots: feed each row's
        draft tree (node 0 = the committed last token, topological
        packing) at its cache offset, greedy-predict every node under
        the packed ancestor bitmask, resolve the longest fully
        accepted root-to-leaf path in-graph, compact the accepted
        path's KV rows into contiguous cache positions, and emit one
        bonus token sampled at the deepest accepted node — per-row
        sampling params decide the bonus (greedy rows take the argmax
        path inside _sample_batch). Returns (accepted[B], bonus[B],
        path[B, W]): ``path[b, k]`` is the node index at depth k of
        the accepted path, valid for k <= accepted[b]. One jitted
        closure serves every pow-2 width bucket (jit re-traces per
        bucket; warmup pre-observes and pre-compiles them)."""
        fn = self._prefill_cache.get("spec")
        if fn is None:
            verify_fn = self._spec_verify_fn
            from ..ops.paged_kv import pool_move_rows, scatter_decode
            max_seq = self.config.max_seq

            def _resolve_tree(logits, tokens, parents, depths,
                              chunk_lens, step, temps, top_ps, top_ks,
                              rng_key):
                b, w = tokens.shape
                pred = jnp.argmax(logits, axis=-1)         # [B, W]
                # node j is accepted iff its parent is accepted and
                # its token equals the parent's greedy prediction;
                # node 0 (the committed root) always is. Topological
                # packing (parents[j] < j) makes one forward sweep
                # over the static width exact.
                acc = jnp.zeros((b, w), bool).at[:, 0].set(True)
                for j in range(1, w):
                    pj = parents[:, j:j + 1]               # [B, 1]
                    p_acc = jnp.take_along_axis(acc, pj, axis=1)[:, 0]
                    p_pred = jnp.take_along_axis(pred, pj, axis=1)[:, 0]
                    ok = p_acc & (tokens[:, j] == p_pred) \
                        & (j < chunk_lens)
                    acc = acc.at[:, j].set(ok)
                # deepest accepted node; argmax ties break to the
                # LOWEST node index = the earliest-proposed chain
                score = jnp.where(acc, depths, -1)
                best = jnp.argmax(score, axis=1).astype(jnp.int32)
                n_acc = jnp.take_along_axis(
                    depths, best[:, None], axis=1)[:, 0]
                # root-first path-by-depth: walk parents w static
                # steps from best, scattering each visited node index
                # at its own depth (the walk idles at the root once it
                # arrives — rewrites of path[:, 0] with 0 are no-ops)
                path = jnp.zeros((b, w), jnp.int32)
                cur = best
                for _ in range(w):
                    d_cur = jnp.take_along_axis(
                        depths, cur[:, None], axis=1)      # [B, 1]
                    hit = jnp.arange(w)[None, :] == d_cur
                    path = jnp.where(hit, cur[:, None], path)
                    cur = jnp.take_along_axis(
                        parents, cur[:, None], axis=1)[:, 0]
                bonus_logits = jnp.take_along_axis(
                    logits, best[:, None, None], axis=1)[:, 0]
                key = jax.random.fold_in(rng_key, step)
                bonus = _sample_batch(bonus_logits, key, temps,
                                      top_ps, top_ks)
                return n_acc, bonus, path

            def _path_moves(offsets, path, n_acc, w):
                # KV compaction plan: the accepted node at depth k was
                # written at row offsets + path[k] and belongs at
                # offsets + k. k = 0 is an in-place no-op (path[0] is
                # the root); k > n_acc rows get an out-of-bounds dst
                # and drop. Inactive slots (offsets = max_seq) drop
                # everything the same way.
                k_arange = jnp.arange(w, dtype=jnp.int32)[None, :]
                src = offsets[:, None] + path              # [B, W]
                dst = jnp.where(k_arange <= n_acc[:, None],
                                offsets[:, None] + k_arange, max_seq)
                return src, dst

            def _move_rows_dense(cache, src, dst):
                # gather ALL src rows, then scatter — overlap-safe
                # compaction on [L, B, S, H, D] views; OOB dst drops
                s = cache.shape[2]
                src_c = jnp.clip(src, 0, s - 1)
                rows = jnp.take_along_axis(
                    cache, src_c[None, :, :, None, None], axis=2)
                bidx = jnp.arange(cache.shape[1])[:, None]
                return cache.at[:, bidx, dst].set(rows, mode="drop")

            if self._native_verify:
                # native paged verify: the model writes the fed node
                # rows through the tables and attends with the ragged
                # tree kernel — verify reads only the pages each row's
                # history + tree window spans, no dense view; the
                # accepted path compacts by moving RAW pool rows
                # (quantized pools move codes+scales untouched, so the
                # commit is exact — no requantization)
                native_verify = self._paged_verify_fn

                def fused(params, tokens, parents, depths, tree_masks,
                          kc, vc, tables, offsets, chunk_lens, step,
                          temps, top_ps, top_ks, rng_key):
                    logits, kc, vc = native_verify(
                        params, tokens, kc, vc, tables, offsets,
                        chunk_lens, tree_depths=depths,
                        tree_masks=tree_masks)
                    n_acc, bonus, path = _resolve_tree(
                        logits, tokens, parents, depths, chunk_lens,
                        step, temps, top_ps, top_ks, rng_key)
                    src, dst = _path_moves(offsets, path, n_acc,
                                           tokens.shape[1])
                    kc = pool_move_rows(kc, tables, src, dst)
                    vc = pool_move_rows(vc, tables, src, dst)
                    return n_acc, bonus, path, kc, vc
            else:
                def fused(params, tokens, parents, depths, tree_masks,
                          kc, vc, tables, offsets, chunk_lens, step,
                          temps, top_ps, top_ks, rng_key):
                    s_width = tokens.shape[1]
                    k_view = self._gather_view(kc, tables)
                    v_view = self._gather_view(vc, tables)
                    logits, k_view, v_view = verify_fn(
                        params, tokens, k_view, v_view, offsets,
                        chunk_lens, tree_depths=depths,
                        tree_masks=tree_masks)
                    n_acc, bonus, path = _resolve_tree(
                        logits, tokens, parents, depths, chunk_lens,
                        step, temps, top_ps, top_ks, rng_key)
                    src, dst = _path_moves(offsets, path, n_acc,
                                           s_width)
                    k_view = _move_rows_dense(k_view, src, dst)
                    v_view = _move_rows_dense(v_view, src, dst)
                    kc = scatter_decode(kc, tables, k_view,
                                        offsets, s_width)
                    vc = scatter_decode(vc, tables, v_view,
                                        offsets, s_width)
                    return n_acc, bonus, path, kc, vc
            fn = jax.jit(fused, donate_argnums=(5, 6))
            self._prefill_cache["spec"] = fn
        return fn

    @hot_path_boundary(
        "drafting policy: O(1)-amortized n-gram index maintenance plus "
        "controller pricing, host work that runs only for greedy slots "
        "on a speculation pass — never inside the plain decode pass")
    def _draft_proposals(self, req: GenRequest):
        """Prompt-lookup drafting on the request's incremental n-gram
        index: the stream's final n-gram proposes up to
        ``spec_branches`` distinct continuations (newest occurrences
        first), trie-merged into one :class:`DraftTree`. The
        controller prices each slot's depth/branching per pass; a
        (0, 0) plan skips drafting entirely. Returns a DraftTree with
        at least one draft node, or [] when this pass shouldn't
        draft. The index replaces the old per-pass O(context) rescan
        with O(new tokens) maintenance + O(branches) dict probes."""
        cfg = self.config
        slot = req.slot
        ctrl = self._spec_ctrl
        if 0 <= slot < ctrl.max_batch:
            if self._spec_ctrl_owner[slot] is not req:
                # new tenant in this slot: its predecessor's
                # accept-rate history doesn't transfer
                ctrl.reset_slot(slot)
                self._spec_ctrl_owner[slot] = req
            depth, branches = ctrl.plan(slot)
        else:
            depth, branches = cfg.spec_draft, cfg.spec_branches
        # never draft past the token budget: the bonus token always
        # lands, so at most remaining-1 drafts can be kept
        remaining = req.params.max_new_tokens - len(req.generated)
        depth = min(depth, max(0, remaining - 1))
        if depth <= 0 or branches <= 0:
            return []
        n = max(1, cfg.spec_ngram)
        idx = req.spec_index
        if (idx is None or idx.n != n
                or idx.prompt_len != len(req.prompt_tokens)):
            # first drafting pass — or the token stream was rewritten
            # under the index (preemption/recovery fold generated
            # tokens back into the prompt): rebuild from scratch
            idx = NgramIndex(n)
            idx.extend(req.prompt_tokens)
            idx.prompt_len = len(req.prompt_tokens)
            req.spec_index = idx
        stream_len = idx.prompt_len + len(req.generated)
        if idx.size < stream_len:
            idx.extend(req.generated[idx.size - idx.prompt_len:])
        chains = idx.propose(depth, branches)
        if not chains:
            return []
        tree = build_draft_tree(
            req.generated[-1], chains,
            max_nodes=1 + cfg.spec_draft * cfg.spec_branches)
        return tree if tree.n_draft else []

    @hot_path_boundary(
        "speculative verify collect: the accept/path/bonus download IS "
        "the pass's sanctioned device sync, and the controller/ledger "
        "bookkeeping is priced against the multi-token verify pass it "
        "rides, not per decode pass")
    def _spec_pass(self, proposals: dict) -> None:
        """One speculative tree-verify pass over every active slot.
        Slots without drafts ride along as a lone root node — for
        them this is exactly a single decode step."""
        cfg = self.config
        # verify feeds each row's true last token from host state and
        # appends host-side — the decode pipeline must be settled, its
        # device-resident last token invalidated, and the scheduler
        # state resynced before the next decode dispatch (lengths
        # advance host-side below)
        self._drain_pending()
        self._dev_last = None
        self._sched_dirty = True
        self._retire_unservable()
        b = cfg.max_batch
        # normalize: monkeypatched _draft_proposals hooks may return a
        # plain token list (the historical single-chain shape)
        trees: dict[int, DraftTree] = {}
        for i, drafted in proposals.items():
            req = self.active[i]
            if req is None or req.pending_prefill:
                continue
            if not isinstance(drafted, DraftTree):
                drafted = DraftTree.from_chain(req.generated[-1],
                                               drafted)
            if drafted.n_draft:
                trees[i] = drafted
        # pow-2 width buckets: the widest tree this pass picks the
        # verify graph, so the compiled-shape set stays small and
        # warmup can observe/compile every bucket up front
        widest = max((t.n_nodes for t in trees.values()), default=1)
        width = 2
        while width < widest:
            width *= 2
        tokens = np.zeros((b, width), np.int32)
        parents = np.zeros((b, width), np.int32)
        depths = np.zeros((b, width), np.int32)
        masks = np.ones((b, width), np.int32)
        chunk_lens = np.ones(b, np.int32)
        offsets = np.full(b, cfg.max_seq, np.int32)  # inactive: drop
        temps = np.zeros(b, np.float32)
        top_ps = np.ones(b, np.float32)
        top_ks = np.zeros(b, np.int32)
        rows = []
        for i, req in enumerate(self.active):
            if req is None or req.pending_prefill:
                continue
            tokens[i, 0] = req.generated[-1]
            tree = trees.get(i)
            if tree is not None:
                n = tree.n_nodes
                tokens[i, :n] = tree.tokens
                parents[i, :n] = tree.parents
                depths[i, :n] = tree.depths
                masks[i, :n] = tree.masks
                chunk_lens[i] = n
            offsets[i] = int(self.lengths[i])
            temps[i] = req.params.temperature
            top_ps[i] = req.params.top_p
            top_ks[i] = req.params.top_k
            rows.append(i)
        if not rows:
            return
        # headroom for every fed row (draft nodes write cache rows
        # too); an earlier row's headroom may preempt a later one
        for i in list(rows):
            if self.active[i] is None:  # preempted as a victim
                continue
            rows_needed = min(int(self.lengths[i])
                              + int(chunk_lens[i]), cfg.max_seq)
            if not self._ensure_headroom(i, rows_needed):
                self._preempt(i)
        tables = self._tables_arg()
        self._rng_step += 1
        self._note_dispatch_shape("spec_verify", width)
        start = time.perf_counter()
        self.goodput.note_dispatch(start)
        w0 = time.time()
        fn = self._get_spec_verify()
        accepted_dev, bonus_dev, path_dev, self.k_cache, \
            self.v_cache = fn(
                self.params, jnp.asarray(tokens), jnp.asarray(parents),
                jnp.asarray(depths), jnp.asarray(masks), self.k_cache,
                self.v_cache, tables, jnp.asarray(offsets),
                jnp.asarray(chunk_lens), np.int32(self._rng_step),
                jnp.asarray(temps), jnp.asarray(top_ps),
                jnp.asarray(top_ks), self._prefill_base_key)
        accepted = np.asarray(accepted_dev)
        bonus = np.asarray(bonus_dev)
        path = np.asarray(path_dev)
        if self._native_verify:
            self._note_view_avoided(b)
        self._note_pass("spec_passes", start)
        t1 = time.perf_counter()
        spec_dur = t1 - start
        w1 = time.time()
        pass_drafted = pass_accepted = pass_rows = 0
        rids: list[int] = []  # the rows verified, for the pass record
        row_stats: list[tuple[int, int]] = []  # (drafted, accepted)
        live = sum(1 for r in self.active
                   if r is not None and not r.pending_prefill)
        verify_share = (spec_dur / live) if live else 0.0
        for i, req in enumerate(self.active):
            if req is None or req.pending_prefill:
                continue
            req.device_s += verify_share
            rids.append(req.rid)
            tree = trees.get(i)
            n_drafted = tree.n_draft if tree is not None else 0
            n_acc = min(int(accepted[i]), n_drafted)
            if n_drafted:
                # the rejected-draft slice of this row's device time:
                # positions computed and thrown away, billed to the
                # tenant that drafted them
                req.waste_spec_s += verify_share \
                    * (n_drafted - n_acc) / (1 + n_drafted)
                self._spec_ctrl.note_result(i, n_drafted, n_acc)
            row_stats.append((n_drafted, n_acc))
            pass_drafted += n_drafted
            pass_accepted += n_acc
            pass_rows += 1
            if n_drafted:
                self._req_event(req, "spec_verify", w0, w1,
                                {"drafted": n_drafted,
                                 "accepted": n_acc})
            # the accepted root-to-leaf path's tokens, in depth order,
            # then the bonus sampled at the deepest accepted node
            emitted = [tree.tokens[int(path[i, k])]
                       for k in range(1, n_acc + 1)] if tree else []
            emitted.append(int(bonus[i]))
            self.stats["spec_accepted"] += n_acc
            # offered drafts this row — the honest acceptance-rate
            # denominator (spec_passes counts batched passes, so
            # accepted/passes*draft overstates with G rows per pass);
            # spec_rows counts row-participations: each emits exactly
            # one bonus token, the per-row tokens-per-verify base
            self.stats["spec_drafted"] += n_drafted
            self.stats["spec_rows"] += 1
            # rows for the fed tokens were written at offsets..; only
            # the accepted prefix (plus the already-cached last token)
            # counts — rejected rows are overwritten by later passes
            # and never attended (length-masked)
            ceiling = cfg.max_seq - int(self.lengths[i])
            done = False
            kept = 0
            for token in emitted:
                if kept >= ceiling:
                    done = True
                    break
                if self.faults is not NO_FAULTS and \
                        self.faults.trip("logit_corrupt", req.tenant):
                    token = self._corrupt_token(token)
                req.generated.append(token)
                req._emit(token)
                self.total_generated += 1
                kept += 1
                if self._finished(req, token):
                    done = True
                    break
            self.lengths[i] += kept
            if done or kept >= ceiling:
                self._retire(i)
        if self.metrics is not None and pass_drafted:
            self.metrics.add_counter("app_engine_spec_drafted",
                                     float(pass_drafted))
            self.metrics.add_counter("app_engine_spec_accepted",
                                     float(pass_accepted))
        self.goodput.add_spec(spec_dur, b, row_stats)
        # fit the controller's verify row cost from the same span the
        # ledger bills, so policy and waste accounting can't diverge
        self._spec_ctrl.note_verify(spec_dur, pass_rows, width)
        spec_sig = self._sig_str("spec_verify", width)
        self._note_pass_cost("spec_verify", spec_sig, spec_dur,
                             rows=pass_rows,
                             tokens=pass_accepted + pass_rows)
        self._update_kv_watermarks()
        if self.recorder.enabled:
            self.recorder.record_pass(
                "spec_verify", t0=start, t1=t1, rids=rids,
                drafted=pass_drafted,
                accepted=pass_accepted,
                dur=round(time.perf_counter() - start, 6),
                occupancy=pass_rows, sig=spec_sig,
                queue_depth=self.waiting.qsize())
        self._note_device_idle()

    def _update_kv_watermarks(self) -> None:
        """KV high-water marks, sampled at collect sites so a short
        burst's peak is caught before its slots retire — an O(1) page
        count, pure host compares."""
        wm = self.watermarks
        if not wm.enabled:
            return
        used = self._n_pages - len(self._free_pages)
        wm.update("kv_pages", float(used))
        wm.update("prefix_pages", float(self._cached_pages))
        wm.update("kv_bytes",
                  used * self._kv_bytes_total / max(1, self._n_pages))

    def _update_watermarks(self) -> None:
        """Advance every memory high-water mark (throttled cadence):
        the KV marks plus host RSS (one getrusage syscall)."""
        wm = self.watermarks
        if not wm.enabled:
            return
        self._update_kv_watermarks()
        wm.update_rss()

    def efficiency_state(self) -> dict:
        """The ``GET /debug/efficiency`` payload for this engine:
        goodput classification, memory watermarks, recompile sentinel
        state — all host-side reads."""
        self._update_watermarks()
        cap_tokens = self._n_pages * max(1, int(self.config.page_size))
        return {"goodput": self.goodput.state(),
                "watermarks": self.watermarks.state(),
                "recompiles": self.sentinel.state(),
                "spec": self._spec_ctrl.state(),
                "costs": self.costs.state(),
                "kv_bytes": self._kv_bytes_total,
                "kv_bytes_per_token": round(
                    self._kv_bytes_total / max(1, cap_tokens), 3)}

    def _update_gauges(self) -> None:
        m = self.metrics
        if m is not None:
            m.set_gauge(
                "app_engine_active_slots",
                float(sum(r is not None for r in self.active)))
            m.set_gauge("app_engine_waiting",
                        float(self.waiting.qsize()))
        # derived gauges + watermarks, throttled: pure host arithmetic
        # over counters the loop already maintains — never a device sync
        now = time.time()
        dt = now - self._gauge_wall
        if dt < 0.25:
            return
        self._update_watermarks()
        self._refresh_prefix_digest()
        tps = (self.total_generated - self._gauge_tokens) / dt
        self._gauge_wall = now
        self._gauge_tokens = self.total_generated
        if m is None:
            return
        m.set_gauge("app_engine_tokens_per_second", round(tps, 2))
        gp = self.goodput
        if gp.enabled and gp.busy_s > 0:
            ratio = gp.useful_s / gp.busy_s
            m.set_gauge("app_engine_goodput_ratio", round(ratio, 6))
            # goodput-floor breach arms a bounded auto-capture; off by
            # default (floor 0.0), and the 1s busy guard keeps a cold
            # engine's first noisy ratio from tripping it
            floor = self.config.autoprof_goodput_floor
            if floor > 0.0 and gp.busy_s > 1.0 and ratio < floor:
                self.autoprof.arm(
                    "goodput_floor",
                    f"goodput ratio {ratio:.3f} below floor {floor:.3f}")
            for cause, total in gp.waste_s.items():
                delta = total - self._waste_published.get(cause, 0.0)
                if delta > 0:  # counters take deltas, the meter totals
                    m.add_counter("app_engine_waste_seconds", delta,
                                  cause=cause)
                    self._waste_published[cause] = total
        wm = self.watermarks
        if wm.enabled:
            for mark, gauge in (
                ("kv_pages", "app_engine_kv_pages_watermark"),
                ("kv_bytes", "app_engine_kv_bytes_watermark"),
                ("prefix_pages", "app_engine_prefix_pages_watermark"),
                ("host_rss_bytes",
                 "app_engine_host_rss_bytes_watermark"),
            ):
                value = wm.get(mark)
                if value is not None:
                    m.set_gauge(gauge, value)
        mfu = (tps * self._flops_per_token / self._peak_flops
               if self._flops_per_token and self._peak_flops else 0.0)
        m.set_gauge("app_engine_mfu", round(mfu, 6))
        if self._spec_enabled:
            m.set_gauge("app_engine_spec_accept_rate",
                        round(self._spec_ctrl.accept_rate(), 6))
        if hasattr(self.waiting, "publish_gauges"):
            self.waiting.publish_gauges(m)
        used = self._n_pages - len(self._free_pages)
        m.set_gauge("app_engine_kv_pool_utilization",
                    round(used / max(1, self._n_pages), 4))
        # fragmentation: allocated page capacity not holding live
        # rows (pending-prefill slots report their walk progress)
        cap_rows = int(self._slot_pages.sum()) * self.config.page_size
        live = int(self.lengths.sum()) + sum(
            r.prefill_offset for r in self.active
            if r is not None and r.pending_prefill)
        frag = 1.0 - live / cap_rows if cap_rows else 0.0
        m.set_gauge("app_engine_kv_pool_fragmentation",
                    round(min(1.0, max(0.0, frag)), 4))
        m.set_gauge("app_engine_prefix_cache_entries",
                    float(len(self._prefix_cache)))
        m.set_gauge("app_engine_prefix_cache_pages",
                    float(self._cached_pages))

    @hot_path_boundary(
        "prefix-digest assembly at the throttled gauge cadence: host-side "
        "hashing over cache keys already resident, skipped entirely unless "
        "a cache mutation set the dirty flag, published by atomic "
        "reference swap for the heartbeat thread")
    def _refresh_prefix_digest(self) -> None:
        """Rebuild the fleet-router digest when the prefix cache
        changed since the last gauge pass: one truncated content hash
        per resident cache key (newest ``prefix_digest_hashes``
        entries — the LRU end the router should bet on)."""
        if not self._prefix_digest_dirty:
            return
        self._prefix_digest_dirty = False
        limit = max(0, int(self.config.prefix_digest_hashes))
        if not self._prefix_enabled or not limit:
            self._prefix_digest = None
            return
        from .router import prefix_hash
        keys = list(self._prefix_cache)
        if len(keys) > limit:
            keys = keys[-limit:]
        self._prefix_digest = {
            "page": int(self.config.page_size),
            "entries": len(self._prefix_cache),
            "pages": int(self._cached_pages),
            "hashes": [prefix_hash(k) for k in keys],
        }

    def prefix_digest(self) -> dict | None:
        """Latest published digest (atomic reference read — safe from
        the heartbeat thread); None when disabled or cache-less."""
        return self._prefix_digest

    # ---------------------------------------------------------------- loop
    def _loop(self) -> None:
        try:
            while self._running:
                self._last_beat = time.time()
                if self.faults is not NO_FAULTS:
                    # deterministic chaos (serving/faults.py), armed
                    # only when a plan is loaded: pass_raise throws
                    # into the recovery path below, pass_stall /
                    # pass_latency wedge the loop so the watchdog and
                    # the control plane see a genuine stall
                    self.faults.trip("pass_raise")
                    self.faults.trip("pass_stall")
                    self.faults.trip("pass_latency")
                free = sum(1 for r in self.active if r is None)
                busy = free < self.config.max_batch
                if free == 0 and not self._requeued:
                    # full batch, nothing bounced back: if the
                    # interactive lane is starving behind background
                    # work, preempt-by-recompute frees a slot for it
                    # (rate-capped by the scheduler)
                    if self._sched_starvation_preempt():
                        free = sum(1 for r in self.active if r is None)
                if free > 0 or self._requeued:
                    # requeued (already-admitted) work goes first,
                    # bypasses the admission bound, and drains even
                    # with zero free slots — mid-walk chunked prefills
                    # HOLD their slot and must keep resuming; then one
                    # batched pop per pass (TTFT priority): blocks
                    # while fully idle — in the native queue the
                    # engine thread sleeps in C with the GIL released
                    # — and is a zero-wait drain between decode steps
                    # while busy
                    batch, self._requeued = self._requeued, []
                    self._requeued_set.clear()
                    # mid-walk resumes already hold their slot: they
                    # must not eat capacity meant for waiting requests
                    needing_slots = sum(
                        1 for r in batch
                        if not (r.pending_prefill and r.slot >= 0
                                and self.active[r.slot] is r))
                    take = free - needing_slots
                    if take > 0 and not (busy or batch):
                        # the one pop that may block: its span is the
                        # loop waiting for requests, not host work
                        with self.recorder.span("engine.wait"):
                            batch = self.waiting.pop_batch(
                                take, first_wait_s=0.05,
                                drain_wait_s=0.0) or []
                        take = 0
                    if take > 0 or batch:
                        with self.recorder.span("engine.admit"):
                            if take > 0:
                                batch = batch + (self.waiting.pop_batch(
                                    take, first_wait_s=0.0,
                                    drain_wait_s=0.0) or [])
                            self._admit_live(batch)
                if any(r is not None for r in self.active):
                    proposals: dict[int, Any] = {}  # slot -> DraftTree
                    decoding = 0
                    if self._spec_enabled:
                        # drafting reads each stream's tail on the
                        # host: a pass still in flight holds tokens the
                        # tree's root and n-gram must not miss
                        self._drain_pending()
                        for i, r in enumerate(self.active):
                            if (r is None or r.pending_prefill
                                    or r.cancelled):
                                continue
                            decoding += 1
                            if r.params.temperature == 0.0:
                                drafted = self._draft_proposals(r)
                                if drafted:
                                    proposals[i] = drafted
                    # mixed batches alternate: a verify pass advances
                    # non-drafting slots by ONE token, so they get a
                    # full K-step decode pass every other iteration —
                    # bounding their slowdown instead of starving them
                    # while a peer keeps drafting
                    run_spec = bool(proposals) and (
                        len(proposals) == decoding or self._spec_toggle)
                    if run_spec:
                        self._spec_toggle = False
                        self._spec_pass(proposals)
                    else:
                        self._spec_toggle = True
                        self._decode_step()
                    self._collect_prefills()
                else:
                    # nothing active: settle any in-flight pass so its
                    # final tokens reach their streams
                    self._drain_pending()
                    self._collect_prefills()
                with self.recorder.span("engine.gauges"):
                    self._update_gauges()
            # clean stop with work still in flight: the tokens are
            # real — emit them before failing what remains
            self._drain_pending()
            self._collect_prefills()
        except Exception as exc:  # containment: never die silently
            if self._recover(exc):
                # runtime state rebuilt on the resident weights and
                # compile cache: resume serving and replay the recovery
                # buffer. Recursion depth is bounded by
                # restart_policy.max_restarts.
                self._loop()
            else:
                self._crash(exc)
        else:
            self._shutdown_cleanup("engine stopped")

    def _recover(self, exc: BaseException) -> bool:
        """In-thread crash-recovery supervisor: when
        ``config.restart_policy`` has budget left, salvage what can be
        salvaged, rebuild the runtime on the resident weights and
        compiled graphs, sleep a deterministic exponential backoff and
        report True so ``_loop`` resumes. False = no policy, budget
        exhausted, or the engine was stopping anyway — the crash is
        terminal (``_crash``).

        Salvage rules (the no-duplicate-token invariant): a request
        that has NOT emitted its first token replays invisibly — it
        goes to the recovery buffer (the ``_requeued`` fast lane, which
        bypasses the admission bound) and re-prefills from its prompt,
        priced as ``preempt_recompute`` waste via the ``recovered``
        flag. A MID-STREAM request already holds tokens the engine
        cannot un-send, so replaying it risks duplicates — it fails
        with a typed retryable ``engine_restart`` reject (503 +
        Retry-After + details.code through the handlers)."""
        policy = self.config.restart_policy
        if policy is None or not self._running:
            return False
        if self._restarts >= policy.max_restarts:
            # budget exhausted: this crash is terminal — snapshot an
            # incident bundle before _crash tears down (the bundle's
            # timeline seals with the engine.crash event _crash emits)
            self.incidents.trigger(
                "restart_budget",
                cause=f"{self._restarts} restarts >= budget "
                      f"{policy.max_restarts}; last crash: "
                      f"{type(exc).__name__}: {exc}")
            return False
        self._restarts += 1
        self._last_crash = f"{type(exc).__name__}: {exc}"
        backoff = policy.backoff_for(self._restarts)
        if self.logger:
            self.logger.error(
                f"engine loop crashed ({self._last_crash}); restarting "
                f"{self._restarts}/{policy.max_restarts} after "
                f"{backoff:.2f}s backoff")
            self.recorder.dump(self.logger, reason=self._last_crash)
        if self.metrics is not None:
            self.metrics.increment_counter("app_engine_restarts")
        self.events.emit("engine.restart", severity="error",
                         cause=self._last_crash,
                         restart=self._restarts,
                         max_restarts=policy.max_restarts,
                         backoff_s=round(backoff, 3))
        from .scheduler import SchedReject
        recovered = 0
        for i, req in enumerate(self.active):
            if req is None:
                continue
            self.active[i] = None
            self.lengths[i] = 0
            if req.finished_at is not None:
                continue
            if req.cancelled:  # consumer gone: just close the stream
                req.finished_at = time.time()
                self._finalize_obs(req)
                req._emit(None)
            elif req.first_token_at is None:
                req.pending_prefill = False
                req.prefill_epoch += 1
                req.prefill_offset = 0
                req.slot = -1
                req.recovered = True
                self._requeue(req)
                recovered += 1
            else:
                req.reject = SchedReject(
                    code="engine_restart", tenant=req.tenant,
                    retry_after_s=max(1.0, backoff),
                    detail="engine restarted mid-stream; the partial "
                           "output is stale — retry the request")
                self._fail(req, req.reject.message)
        # dispatched-but-uncollected passes died with the crash; the
        # recovery buffer (_requeued) survives untouched and replays
        # first once the loop resumes
        self._reset_runtime_state()
        if recovered:
            if self.metrics is not None:
                self.metrics.add_counter("app_engine_requests_recovered",
                                         float(recovered))
            if self.logger:
                self.logger.warn(
                    f"recovery buffer: {recovered} request(s) replay "
                    "after restart")
        deadline = time.time() + backoff
        while self._running and time.time() < deadline:
            # interruptible backoff: stop() during the sleep resumes
            # the loop, which then exits through the CLEAN path
            time.sleep(min(0.05, max(0.0, deadline - time.time())))
        self._last_beat = time.time()
        self.events.emit("engine.recovery",
                         cause=self._last_crash,
                         restart=self._restarts, recovered=recovered)
        return True

    def _crash(self, exc: BaseException) -> None:
        """The hot loop threw: fail every in-flight request, refuse new
        ones, and flip health DOWN so orchestrators can see it.

        The reference refuses to let one request take the process down
        (panic recovery, /root/reference/pkg/gofr/handler.go:141); for
        an engine thread the equivalent blast-radius control is failing
        fast and loudly rather than hanging every stream forever."""
        self._failed = f"{type(exc).__name__}: {exc}"
        self._running = False
        self.events.emit("engine.crash", severity="error",
                         cause=self._failed, restarts=self._restarts)
        if self.logger:
            self.logger.error(f"engine loop crashed: {exc!r}")
            # post-mortem: the last N pass records tell you what the
            # loop was doing when it died
            self.recorder.dump(self.logger, reason=self._failed)
        self._shutdown_cleanup(f"engine crashed: {self._failed}")


#: static cap on the candidate set per row. ``lax.top_k`` over this many
#: columns replaces a full-vocab bitonic sort (128k wide on Llama-3 —
#: measured as the single largest cost in the fused decode graph). Any
#: realistic top-k/top-p nucleus fits in 64 candidates; rows whose
#: nucleus would be wider are truncated to the 64 most likely tokens.
TOPK_BOUND = 64


def _sample_batch(logits: jnp.ndarray, key: jax.Array,
                  temperatures: jnp.ndarray, top_ps: jnp.ndarray,
                  top_ks: jnp.ndarray | None = None) -> jnp.ndarray:
    """Per-row sampling in one graph: greedy rows (temp==0) take the
    top-1 candidate; stochastic rows gumbel-sample within the
    ``TOPK_BOUND`` most likely tokens after the row's top-k filter and
    a top-p filter applied *on the top-k-renormalised* distribution
    (``top_ks`` row value 0 disables top-k for that row).

    All-greedy batches (the common serving case and every benchmark)
    take a ``lax.cond`` fast path: a plain argmax, skipping the
    vocab-wide ``lax.top_k`` whose cost scales with B x V and is pure
    waste when no row samples. The predicate is traced, so one compile
    covers both regimes.
    """
    logits = logits.astype(jnp.float32)
    bound = min(TOPK_BOUND, logits.shape[-1])

    def _greedy(_):
        # tie-break assumption: argmax here and idx[:, 0] from the
        # mixed branch's lax.top_k both resolve exact logit ties to
        # the LOWEST index in XLA — if either ever changes, the same
        # greedy row could emit different tokens depending on whether
        # a batchmate samples (ADVICE r5)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _full(_):
        safe_t = jnp.maximum(temperatures, 1e-6)[:, None]
        vals, idx = jax.lax.top_k(logits / safe_t, bound)  # sorted desc

        # top-k first: mask candidates beyond each row's k (0 = disabled)
        pos = jnp.arange(bound)[None, :]
        if top_ks is not None:
            k_eff = jnp.where(top_ks > 0, jnp.minimum(top_ks, bound),
                              bound)
            vals = jnp.where(pos < k_eff[:, None], vals, NEG_INF)

        # then top-p on the renormalised survivor distribution
        probs = jax.nn.softmax(vals, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = jnp.roll(cum, 1, axis=-1) < top_ps[:, None]
        keep = keep.at[..., 0].set(True)
        filtered = jnp.where(keep, vals, NEG_INF)

        gumbel = -jnp.log(-jnp.log(
            jax.random.uniform(key, vals.shape, minval=1e-20,
                               maxval=1.0) + 1e-20))
        choice = jnp.argmax(filtered + gumbel, axis=-1)
        sampled = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]
        # temperature scaling is monotonic, so idx[:, 0] IS the argmax
        return jnp.where(temperatures <= 0.0, idx[:, 0],
                         sampled).astype(jnp.int32)

    return jax.lax.cond(jnp.all(temperatures <= 0.0), _greedy, _full, None)
