"""Output-integrity observatory (serving/integrity.py): digest
folding at the retire boundary, golden canary probes priced in the
goodput ledger, mismatch-episode hysteresis, and the leader's fleet
divergence vote with router quarantine."""

import time

import pytest

from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.integrity import (DIGEST_VERSION, GoldenSet,
                                        IntegrityPlane, request_digest)


def _drain(reqs, timeout=180):
    deadline = time.time() + timeout
    while time.time() < deadline and any(
            r.finished_at is None and r.error is None for r in reqs):
        time.sleep(0.01)
    return reqs


def _greedy(max_new_tokens=8):
    return SamplingParams(temperature=0.0, max_new_tokens=max_new_tokens)


# ------------------------------------------------------ the fingerprint

class _Params:
    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


def test_request_digest_deterministic_and_sensitive():
    p = _Params(temperature=0.0, top_p=1.0, top_k=0, max_new_tokens=8)
    a = request_digest([1, 2, 3], p, [9, 8, 7])
    assert a == request_digest([1, 2, 3], p, [9, 8, 7])
    # one emitted token flips the fingerprint
    assert a != request_digest([1, 2, 3], p, [9, 8, 6])
    # prompt and params are folded too
    assert a != request_digest([1, 2, 4], p, [9, 8, 7])
    hot = _Params(temperature=0.7, top_p=1.0, top_k=0, max_new_tokens=8)
    assert a != request_digest([1, 2, 3], hot, [9, 8, 7])
    # ... but a cosmetic float round-trip (JSON replay) lands in the
    # same 1e-4 quantization bucket
    jittered = _Params(temperature=1e-9, top_p=1.0 - 1e-9, top_k=0,
                       max_new_tokens=8)
    assert a == request_digest([1, 2, 3], jittered, [9, 8, 7])


def test_digest_identical_across_attention_paths():
    """The view path (dense step functions) and the native path
    (the ragged kernel, interpreted) produce bit-identical greedy
    tokens (test_paged_attention pins that) — the fingerprint must
    agree too, or a fleet of sharded and single-chip hosts would vote
    against itself."""
    prompts = [[5 + i, 2, 9] for i in range(2)]
    digests = {}
    for name, extra in (
            ("view", dict(page_size=16, paged_attention="view")),
            ("native", dict(page_size=16,
                            paged_attention="interpret"))):
        engine = demo_llama_engine(EngineConfig(
            max_batch=2, max_seq=128, seed=23, **extra))
        engine.start()
        reqs = [engine.submit(p, _greedy()) for p in prompts]
        _drain(reqs)
        engine.stop()
        assert all(r.error is None for r in reqs)
        digests[name] = [r.digest for r in reqs]
        assert all(digests[name])
    assert digests["view"] == digests["native"]


def test_digest_deterministic_on_int8_pool():
    """The int8 page pool legitimately shifts numerics vs bf16 — the
    contract is run-to-run determinism (same host, same config, same
    digest), which is what the golden probes lean on."""
    engine = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=128, seed=23, kv_layout="paged",
        page_size=32, kv_dtype="int8", paged_attention="interpret"))
    engine.start()
    first, second = _drain([engine.submit([5, 2, 9], _greedy()),
                            engine.submit([5, 2, 9], _greedy())])
    engine.stop()
    assert first.error is None and second.error is None
    assert first.digest and first.digest == second.digest


def test_greedy_bit_identity_with_plane_on():
    """The plane is pure host arithmetic at the retire boundary:
    switching it off must not change one emitted token."""
    prompts = [[7, 3, 1], [4, 4, 2]]
    outs = {}
    for flag in (True, False):
        engine = demo_llama_engine(EngineConfig(
            max_batch=2, max_seq=128, seed=29, integrity=flag))
        engine.start()
        reqs = [engine.submit(p, _greedy()) for p in prompts]
        _drain(reqs)
        engine.stop()
        assert all(r.error is None for r in reqs)
        outs[flag] = [r.generated for r in reqs]
        # the digest is stamped exactly when the plane is on
        assert all(bool(r.digest) == flag for r in reqs)
    assert outs[True] == outs[False]


# ------------------------------------------------------- golden corpus

def _capture_golden(tmp_path, *, seed=23, n=3):
    """Run greedy traffic with workload capture on and seal a golden
    set from the records — the operator's sealing flow."""
    engine = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=128, seed=seed,
        workload_capture=True))
    engine.start()
    reqs = [engine.submit([5 + i, 2, 9], _greedy(6)) for i in range(n)]
    _drain(reqs)
    records = engine.workload.snapshot()["records"]
    engine.stop()
    golden = GoldenSet.seal(records)
    assert len(golden) == n
    path = str(tmp_path / "golden.jsonl")
    golden.save(path)
    return path, golden, [r.digest for r in reqs]


def test_golden_seal_load_roundtrip_and_loud_failures(tmp_path):
    path, golden, digests = _capture_golden(tmp_path)
    loaded = GoldenSet.load(path)
    assert [e.to_dict() for e in loaded.entries] == \
        [e.to_dict() for e in golden.entries]
    assert sorted(e.digest for e in loaded.entries) == sorted(digests)
    # wrong header contracts fail loudly: probing against the wrong
    # corpus would alarm on every probe, or on none
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"format": "not-golden", "version": 1}\n')
    with pytest.raises(ValueError, match="format"):
        GoldenSet.load(str(bad))
    bad.write_text('{"format": "gofr-golden", "version": 1, '
                   f'"digest_version": {DIGEST_VERSION + 1}}}\n')
    with pytest.raises(ValueError, match="digest_version"):
        GoldenSet.load(str(bad))


def test_probe_pricing_conserves_goodput(tmp_path):
    """Golden probes run on the background lane, their device time
    re-prices to the integrity_probe waste cause, and the goodput
    conservation identity stays exact with the cadence live."""
    path, _, _ = _capture_golden(tmp_path)
    engine = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=128, seed=23,
        integrity_golden_path=path, integrity_probe_passes=2,
        workload_capture=True))
    engine.start()
    _drain([engine.submit([5, 2, 9], _greedy(6))])
    deadline = time.time() + 60
    while time.time() < deadline and \
            engine.integrity.probes["run"] < 2:
        time.sleep(0.02)
    state = engine.integrity_state()
    goodput = engine.goodput.state()
    records = engine.workload.snapshot()["records"]
    engine.stop()
    assert state["probes"]["run"] >= 2
    assert state["probes"]["mismatch"] == 0 and not state["episode"]
    assert state["probe_device_s"] > 0.0
    assert goodput["waste_s"]["integrity_probe"] > 0.0
    assert goodput["conservation_error_s"] == 0.0
    # canaries are synthetic traffic: never captured as workload
    assert all(r.get("tenant") != "_integrity" for r in records)


# -------------------------------------------- mismatch-episode hysteresis

class _FakeReq:
    def __init__(self, *, probe=None, expected=None, generated=(9,)):
        self.prompt_tokens = [1, 2]
        self.params = _Params(temperature=0.0, top_p=1.0, top_k=0,
                              max_new_tokens=4)
        self.generated = list(generated)
        self.probe = probe
        self.probe_expected = expected
        self.error = None
        self.cancelled = False


def test_mismatch_episode_fires_once_then_rearms():
    plane = IntegrityPlane(True, rearm_probes=2)
    good = request_digest([1, 2], _FakeReq().params, [9])

    def probe(generated):
        return plane.fold(_FakeReq(probe="g000", expected=good,
                                   generated=generated))

    assert probe([9]) is None and plane.probes["ok"] == 1
    # first mismatch opens the episode: exactly one alarm record
    rec = probe([8])
    assert rec and rec["episode"] == 1 and rec["expected"] == good
    # further mismatches inside the episode stay silent
    assert probe([8]) is None and probe([7]) is None
    assert plane.probes["mismatch"] == 3 and plane.episodes == 1
    # one clean probe is not enough to re-arm (hysteresis) ...
    assert probe([9]) is None and plane.episode
    # ... two consecutive clean probes close the episode ...
    assert probe([9]) is None and not plane.episode
    # ... and the NEXT mismatch alarms again as a fresh episode
    rec = probe([8])
    assert rec and rec["episode"] == 2


def test_failed_probe_is_not_judged():
    plane = IntegrityPlane(True)
    req = _FakeReq(probe="g000", expected="feed", generated=[])
    req.error = "queue_full"
    assert plane.fold(req) is None
    assert plane.probes == {"run": 0, "ok": 0, "mismatch": 0,
                            "error": 1}
    assert not plane.episode


# ----------------------------------------- fleet divergence + quarantine

def _leader(**kw):
    from gofr_tpu.serving.control_plane import (ControlPlaneLeader,
                                                FleetConfig)
    fleet = FleetConfig(**kw) if kw else None
    return ControlPlaneLeader(coordinator="10.0.0.1:8476", fleet=fleet)


def _beat(leader, host, digests, seq, *, busy_s=10.0):
    """One heartbeat carrying an integrity digest block; busy_s lets a
    test make one host's traffic mix look much heavier."""
    leader.heartbeat(host, leader.generation, summary={
        "busy_s": busy_s, "useful_s": busy_s * 0.9,
        "waste_s": {"padding": busy_s * 0.1},
        "integrity": {"digest_version": 1, "seq": seq,
                      "probe_digests": dict(digests),
                      "probe_ok": True}})


def test_vote_names_outlier_and_spares_heavier_mix_host():
    leader = _leader()
    for h in ("a", "b", "c"):
        leader.join(h, f"http://{h}:1", 4)
    # host b carries 10x the traffic of its siblings — load must not
    # look like divergence; host c disagrees on g000's digest
    _beat(leader, "a", {"g000": "aaaa", "g001": "cccc"}, 1)
    _beat(leader, "b", {"g000": "aaaa", "g001": "cccc"}, 1,
          busy_s=100.0)
    _beat(leader, "c", {"g000": "ffff", "g001": "cccc"}, 1)
    vote = leader._vote_integrity()
    assert vote["votes"]["g000"]["majority"] == "aaaa"
    assert sorted(vote["quarantined"]) == ["c"]
    assert vote["quarantined"]["c"]["golden_id"] == "g000"
    assert vote["quarantined"]["c"]["digest"] == "ffff"
    statuses = {m["host_id"]: m["status"]
                for m in leader.routing_view()}
    assert statuses == {"a": "UP", "b": "UP", "c": "QUARANTINED"}
    assert leader.fleet_status()["hosts"]["c"]["status"] == "QUARANTINED"
    # exactly ONE divergence event + incident for the whole episode,
    # however many heartbeats repeat the same bad digest
    _beat(leader, "c", {"g000": "ffff", "g001": "cccc"}, 1)
    divergences = leader.events.snapshot(
        kind="fleet.integrity_divergence")
    assert len(divergences) == 1
    assert divergences[0]["attrs"]["outlier"] == "c"
    assert divergences[0]["attrs"]["majority"] == "aaaa"
    assert len([b for b in leader.incidents.list()
                if b["reason"] == "integrity_divergence"]) == 1


def test_no_vote_below_quorum_or_without_strict_majority():
    leader = _leader()
    for h in ("a", "b"):
        leader.join(h, f"http://{h}:1", 4)
    _beat(leader, "a", {"g000": "aaaa"}, 1)
    _beat(leader, "b", {"g000": "ffff"}, 1)
    # two hosts disagreeing is a tie, not a verdict
    vote = leader._vote_integrity()
    assert vote["votes"] == {} and vote["quarantined"] == {}
    # a 2-2 split above quorum records the split, never guesses
    # (quorum=4 so no intermediate 3-ballot majority forms while the
    # heartbeats trickle in)
    leader = _leader(integrity_quorum=4)
    for h in ("a", "b", "c", "d"):
        leader.join(h, f"http://{h}:1", 4)
    _beat(leader, "a", {"g000": "aaaa"}, 2)
    _beat(leader, "b", {"g000": "ffff"}, 2)
    _beat(leader, "c", {"g000": "aaaa"}, 2)
    _beat(leader, "d", {"g000": "ffff"}, 2)
    vote = leader._vote_integrity()
    assert vote["votes"]["g000"]["majority"] is None
    assert vote["quarantined"] == {}


def test_quarantine_rejoins_after_seq_advanced_clean_probes():
    leader = _leader(integrity_clean_probes=2)
    for h in ("a", "b", "c"):
        leader.join(h, f"http://{h}:1", 4)
    _beat(leader, "a", {"g000": "aaaa"}, 1)
    _beat(leader, "b", {"g000": "aaaa"}, 1)
    _beat(leader, "c", {"g000": "ffff"}, 1)
    assert "c" in leader._vote_integrity()["quarantined"]
    # clean digest but the SAME probe seq: a repeated heartbeat is not
    # new evidence, the streak counts probes
    _beat(leader, "c", {"g000": "aaaa"}, 1)
    assert "c" in leader._vote_integrity()["quarantined"]
    _beat(leader, "c", {"g000": "aaaa"}, 2)
    assert "c" in leader._vote_integrity()["quarantined"]
    _beat(leader, "c", {"g000": "aaaa"}, 3)
    vote = leader._vote_integrity()
    assert vote["quarantined"] == {}
    assert {m["host_id"]: m["status"] for m in leader.routing_view()} \
        == {"a": "UP", "b": "UP", "c": "UP"}
    actions = [e["attrs"]["action"] for e in
               leader.events.snapshot(kind="fleet.quarantine")]
    assert actions == ["quarantine", "rejoin"]


def test_router_drops_quarantined_host_and_sweeps_affinity():
    from gofr_tpu.serving.router import FleetRouter, RouterConfig

    leader = _leader()
    for h in ("a", "b", "c"):
        leader.join(h, f"http://{h}:1", 4)
    router = FleetRouter(leader, RouterConfig(affinity_size=8))
    router.affinity.put("sess-1", "c")
    assert {m["host_id"] for m in router._members()} == {"a", "b", "c"}
    _beat(leader, "a", {"g000": "aaaa"}, 1)
    _beat(leader, "b", {"g000": "aaaa"}, 1)
    _beat(leader, "c", {"g000": "ffff"}, 1)
    # quarantined: routed share goes to zero on the next plan and the
    # pinned session must re-plan onto a healthy sibling
    assert {m["host_id"] for m in router._members()} == {"a", "b"}
    assert router.affinity.get("sess-1") is None
    assert router.debug_state()["quarantines"] == {"quarantine": 1}
    _beat(leader, "c", {"g000": "aaaa"}, 2)
    _beat(leader, "c", {"g000": "aaaa"}, 3)
    assert {m["host_id"] for m in router._members()} == {"a", "b", "c"}
    assert router.debug_state()["quarantines"] == \
        {"quarantine": 1, "rejoin": 1}


# ------------------------------------------------ fault-driven divergence

def test_logit_corrupt_diverges_digest_without_crashing():
    """The deterministic corruption drill: exact invocation window,
    stream lengths preserved, nothing crashes — only bytes (and so
    the fingerprint) change."""
    engine = demo_llama_engine(EngineConfig(
        max_batch=1, max_seq=128, seed=23,
        faults="logit_corrupt:at=1"))
    engine.start()
    # at=1 fires on the first emitted token only: request 1 is
    # corrupted, request 2 (same prompt) is the clean reference
    dirty = _drain([engine.submit([5, 2, 9], _greedy(6))])[0]
    clean = _drain([engine.submit([5, 2, 9], _greedy(6))])[0]
    engine.stop()
    assert dirty.error is None and clean.error is None
    assert len(dirty.generated) == len(clean.generated)
    assert dirty.generated != clean.generated
    diff = [i for i, (d, c) in enumerate(
        zip(dirty.generated, clean.generated)) if d != c]
    assert diff[0] == 0  # the corrupted emit is the faulted one
    assert dirty.digest != clean.digest
