"""Device-resident decode state + fused multi-pass decode.

The decode hot path keeps per-slot scheduler state (lengths, sampling
params, active mask, page tables) as persistent DEVICE arrays that are
re-uploaded only on admission/retirement/preemption events; lengths and
the sampling-rng counter advance on-device inside the decode graph.
These tests pin the contract:

  * steady-state dispatches perform ZERO host->device transfers
    (enforced with ``jax.transfer_guard_host_to_device``);
  * scheduler events trigger exactly one resync;
  * ``decode_passes_per_dispatch`` (M) is a pure throughput knob —
    greedy outputs are bit-identical to the single-pass path on both
    attention paths, in fewer dispatches.
"""

import time

import jax
import pytest

from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.glue import demo_llama_engine


def _admit(eng, prompts, **sp):
    """Drive the engine WITHOUT its thread: pop + admit on this thread
    so the test controls exactly when decode passes run."""
    params = SamplingParams(**sp)
    reqs = [eng.submit(p, params) for p in prompts]
    batch = eng.waiting.pop_batch(len(reqs), first_wait_s=0.5)
    assert batch and len(batch) == len(reqs)
    eng._admit_batch(batch)
    eng._collect_prefills()
    return reqs


def _run_threaded(eng, prompts, n):
    eng.start()
    sp = SamplingParams(temperature=0.0, max_new_tokens=n)
    reqs = [eng.submit(p, sp) for p in prompts]
    deadline = time.time() + 120
    while time.time() < deadline and any(
            r.finished_at is None and r.error is None for r in reqs):
        time.sleep(0.005)
    eng.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    assert all(len(r.generated) == n for r in reqs)
    return [r.generated for r in reqs]


def test_steady_state_decode_uploads_nothing():
    """Consecutive decode passes with no admission/retirement events
    must not upload ANY scheduler state — the graph runs entirely on
    device-resident arrays (tokens feed back on device, lengths and
    the rng counter advance in-graph)."""
    eng = demo_llama_engine(EngineConfig(max_batch=4, max_seq=256,
                                         seed=0))
    reqs = _admit(eng, [[1 + i, 2, 3] for i in range(3)],
                  temperature=0.0, max_new_tokens=200)
    # two unguarded passes: the first uploads the freshly admitted
    # state, the second re-uploads once as the fresh rows flip to
    # device-side token feedback (use_prev) — then steady state
    eng._decode_step()
    eng._drain_pending()
    eng._decode_step()
    eng._drain_pending()
    transfers = eng.stats["h2d_transfers"]
    syncs = eng.stats["sched_syncs"]
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(3):
            eng._decode_step()
            eng._drain_pending()
    assert eng.stats["h2d_transfers"] == transfers
    assert eng.stats["sched_syncs"] == syncs
    K = eng.config.decode_steps_per_pass
    assert all(len(r.generated) == 1 + 5 * K for r in reqs)


def test_admission_event_triggers_exactly_one_resync():
    """A scheduler event (new admission) costs one state upload, then
    the path returns to zero-transfer steady state."""
    eng = demo_llama_engine(EngineConfig(max_batch=4, max_seq=256,
                                         seed=1))
    _admit(eng, [[7, 8, 9]], temperature=0.0, max_new_tokens=200)
    for _ in range(3):
        eng._decode_step()
        eng._drain_pending()
    syncs = eng.stats["sched_syncs"]
    _admit(eng, [[4, 5, 6]], temperature=0.0, max_new_tokens=200)
    eng._decode_step()          # admission -> resync
    eng._drain_pending()
    eng._decode_step()          # fresh row flips to use_prev -> resync
    eng._drain_pending()
    assert eng.stats["sched_syncs"] == syncs + 2
    with jax.transfer_guard_host_to_device("disallow"):
        eng._decode_step()      # steady again
        eng._drain_pending()
    assert eng.stats["sched_syncs"] == syncs + 2


def test_dispatch_and_collect_spans_accounted():
    """The per-pass host-side phase accounting must populate — the
    bench uses it to prove dispatch overhead fell."""
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64,
                                         seed=2))
    eng.start()
    req = eng.submit_sync([1, 2, 3], SamplingParams(
        temperature=0.0, max_new_tokens=12))
    eng.stop()
    assert req.error is None
    assert eng.stats["decode_passes"] >= 1
    # both come from the passes' engine.decode_dispatch / engine.emit
    # spans in the flight recorder, not from clock reads of their own
    spans = list(eng.recorder.log.spans)
    dispatch = [t1 - t0 for name, t0, t1, _ in spans
                if name == "engine.decode_dispatch"]
    emit = [t1 - t0 for name, t0, t1, _ in spans if name == "engine.emit"]
    assert len(emit) == eng.stats["decode_passes"] <= len(dispatch)
    assert 0.0 < eng.stats["dispatch_s"] <= sum(dispatch) + 1e-9
    assert eng.stats["collect_s"] >= sum(emit) > 0.0
    assert eng.stats["sched_syncs"] >= 1
    assert eng.stats["h2d_transfers"] >= 7


@pytest.mark.parametrize("layout_kw", [
    {},      # the default: the pool through the dense view off the TPU
    {"page_size": 16, "paged_attention": "xla"},    # the native path
])
def test_multi_pass_decode_greedy_identical(layout_kw):
    """decode_passes_per_dispatch is a pure dispatch-overhead knob:
    K x M fused steps must reproduce the single-pass token streams
    bit for bit (both attention paths), in fewer dispatches."""
    prompts = [[5 + i, 2, 9] for i in range(3)]
    n = 32

    def build(m):
        return demo_llama_engine(EngineConfig(
            max_batch=4, max_seq=128, seed=11,
            decode_passes_per_dispatch=m, **layout_kw))

    single = build(1)
    want = _run_threaded(single, prompts, n)
    fused = build(4)
    got = _run_threaded(fused, prompts, n)
    assert got == want
    assert fused.stats["decode_passes"] < single.stats["decode_passes"]


def test_multi_pass_respects_max_seq_ceiling():
    """A fused pass crossing the cache ceiling emits only the valid
    prefix and retires the slot — no overrun, no hang."""
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64,
                                         seed=3,
                                         decode_passes_per_dispatch=4))
    eng.start()
    req = eng.submit_sync(list(range(1, 40)), SamplingParams(
        temperature=0.0, max_new_tokens=100))
    eng.stop()
    assert req.error is None
    assert 0 < len(req.generated) <= 100
