"""CI smoke: the fleet survives losing its leader.

Boots TWO leader candidates — rank 0 active, rank 1 standby — and two
engine workers configured with the ranked candidate list, then drills
the full HA story end to end:

1. **Reference run.** 6 greedy prompts through the active leader
   record bit-exact token references.
2. **Kill the leader mid-traffic.** With a stream in flight, the
   active leader is stopped. The workers' missed-ack failover elects
   the standby deterministically (lease-with-epoch: epoch bumps to 2),
   within 2 heartbeat intervals. The in-flight stream either finishes
   or is retried typed — and the retried output carries zero
   duplicated tokens.
3. **Bit-identical service resumes.** The same 6 prompts through the
   new leader (with a Retry-After-honoring client, absorbing any
   ``leader_takeover``/``no_members`` 503s during convergence) match
   the references token for token.
4. **A revived stale leader is fenced.** A fresh rank-0 leader boots
   believing epoch 1; a control write carrying epoch 2 is refused with
   a typed 409 ``stale_leader``, the write is NOT applied, the reject
   is counted on ``app_fleet_stale_leader_rejects``, and the revived
   leader demotes (``GET /control/leader`` shows active=false).

Exits nonzero on any failure; one line per check on success.
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from gofr_tpu.serving.control_plane import FleetConfig
from gofr_tpu.serving.engine import EngineConfig
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.router import RouterConfig
from gofr_tpu.serving.tokenizer import ByteTokenizer
from router_smoke import AppThread, chat, make_app, request, sse_tokens

WORKERS = ("ha-w0", "ha-w1")
SYSTEM = "You are the gofr-tpu HA smoke. Answer in one short line. "
HEARTBEAT = 0.5


def boot_leader(name, rank, candidates=()):
    app = make_app(name)
    leader = app.serve_fleet_leader(
        host_id=name, rank=rank,
        fleet=FleetConfig(leader_candidates=tuple(candidates)),
        router=RouterConfig(max_retries=2, affinity_size=64),
        heartbeat_interval_s=HEARTBEAT)
    return leader, AppThread(app).start()


def chat_retry(port, prompt, *, max_tokens=12, stream=False,
               deadline_s=30):
    """A well-behaved HA client: honor Retry-After on the typed 503s a
    takeover window serves, then retry — the contract that keeps
    greedy outputs bit-identical through a failover."""
    deadline = time.time() + deadline_s
    while True:
        status, headers, payload = chat(
            port, prompt, max_tokens=max_tokens, stream=stream)
        if status != 503:
            return status, headers, payload
        if time.time() > deadline:
            raise AssertionError(
                f"retries never converged for {prompt!r}: {payload[:200]}")
        retry_after = next((v for k, v in headers.items()
                            if k.lower() == "retry-after"), "1")
        time.sleep(min(float(retry_after), 1.0))


def main() -> int:
    leader0, thread0 = boot_leader("ha-leader0", 0)
    leader1, thread1 = boot_leader("ha-leader1", 1)
    urls = (f"http://127.0.0.1:{thread0.port}",
            f"http://127.0.0.1:{thread1.port}")
    for lead in (leader0, leader1):
        lead.fleet.leader_candidates = urls

    workers = []
    for host in WORKERS:
        app = make_app(host)
        engine = demo_llama_engine(EngineConfig(
            max_batch=4, max_seq=256,
            page_size=8, prefill_buckets=(8,), seed=5))
        app.serve_model("llm", engine, ByteTokenizer())
        app.join_fleet(urls[0], host_id=host,
                       heartbeat_interval_s=HEARTBEAT,
                       fleet=FleetConfig(leader_candidates=urls,
                                         missed_acks_before_failover=1))
        workers.append((host, AppThread(app).start()))

    revived = None
    try:
        deadline = time.time() + 30
        while time.time() < deadline:
            view = leader0.routing_view()
            if len(view) == 2 and all(m["address"] for m in view):
                break
            time.sleep(0.1)
        else:
            raise AssertionError("workers never became routable")
        assert leader0.epoch == 1 and not leader1.active
        print("ok: rank-0 leader active at epoch 1, standby fenced, "
              "both workers routable")

        # ------------------------------------------ phase 0: references
        prompts = [SYSTEM + f"ha {i}" for i in range(6)]
        stream_prompt = SYSTEM + "ha stream"
        refs = {}
        for p, n in [(p, 12) for p in prompts] + [(stream_prompt, 48)]:
            status, _, data = chat(thread0.port, p, max_tokens=n)
            assert status == 201, (status, data[:200])
            refs[p] = json.loads(data)["data"]["tokens"]
            assert refs[p], p
        print("ok: recorded 7 greedy references through leader0")

        # ----------------------- phase 1: kill the leader mid-traffic
        stream_result = {}

        def run_stream():
            try:
                stream_result["response"] = chat(
                    thread0.port, stream_prompt, max_tokens=48,
                    stream=True)
            except Exception as exc:  # connection died with the leader
                stream_result["error"] = exc

        stream_thread = threading.Thread(target=run_stream)
        stream_thread.start()
        time.sleep(0.05)  # let the stream reach a worker
        thread0.stop()
        t_down = time.time()
        while not leader1.leadership()["active"]:
            if time.time() - t_down > 30:
                raise AssertionError("standby never took over")
            time.sleep(0.005)
        elapsed = time.time() - t_down
        assert elapsed <= 2 * HEARTBEAT, (
            f"takeover took {elapsed:.2f}s > 2 heartbeat intervals")
        assert leader1.epoch == 2, leader1.epoch
        print(f"ok: standby took over in {elapsed:.2f}s "
              f"(< {2 * HEARTBEAT}s) at epoch 2")

        # both workers re-register with the new leader (stateless
        # rebuild off their next heartbeat round)
        deadline = time.time() + 30
        while time.time() < deadline:
            view = leader1.routing_view()
            if len(view) == 2 and all(m["address"] for m in view):
                break
            time.sleep(0.05)
        else:
            raise AssertionError("workers never reached the new leader")
        print("ok: new leader rebuilt membership + routing from "
              "heartbeats alone")

        # the in-flight stream finished, or draws a typed retry whose
        # output is bit-identical with zero duplicated tokens
        stream_thread.join(30)
        response = stream_result.get("response")
        finished = False
        if response is not None and response[0] == 200:
            got, done = sse_tokens(response[2])
            if done and got == refs[stream_prompt]:
                finished = True
        if not finished:
            status, _, payload = chat_retry(
                thread1.port, stream_prompt, max_tokens=48, stream=True)
            assert status == 200, (status, payload[:200])
            got, done = sse_tokens(payload)
            assert done, "retried stream lost its terminal event"
        assert got == refs[stream_prompt], "stream tokens diverged"
        assert len(got) == len(refs[stream_prompt]), "duplicated tokens"
        print("ok: in-flight stream "
              + ("finished" if finished else "retried typed")
              + " — bit-identical, zero duplicated tokens")

        # --------------------- phase 2: bit-identical post-takeover run
        for p in prompts:
            status, _, data = chat_retry(thread1.port, p)
            assert status == 201, (status, data[:200])
            got = json.loads(data)["data"]["tokens"]
            assert got == refs[p], (p, got, refs[p])
        print("ok: 6/6 greedy outputs via the new leader bit-identical "
              "to the undisturbed references")

        status, _, data = request(thread1.metrics_port, "GET",
                                  "/metrics")
        assert status == 200
        text = data.decode()
        assert "app_fleet_leader_epoch 2" in text, \
            "leader epoch gauge did not advance"
        print("ok: app_fleet_leader_epoch=2 on the new leader's "
              "/metrics")

        # ---------------------- phase 3: revived stale leader is fenced
        stale, revived = boot_leader("ha-leader0-revived", 0, urls)
        assert stale.epoch == 1  # believes its old lease
        status, _, data = request(
            revived.port, "POST", "/control/heartbeat",
            body={"host_id": WORKERS[0], "generation": 1, "epoch": 2})
        assert status == 409, (status, data[:200])
        doc = json.loads(data)
        assert doc["error"]["details"]["code"] == "stale_leader", doc
        assert stale.topology()["world_size"] == 0, \
            "stale-epoch write was accepted"
        status, _, data = request(revived.port, "GET", "/control/leader")
        assert status == 200
        assert json.loads(data)["data"]["active"] is False, \
            "revived stale leader did not demote"
        status, _, data = request(revived.metrics_port, "GET",
                                  "/metrics")
        assert "app_fleet_stale_leader_rejects 1" in data.decode(), \
            "stale reject was not counted"
        print("ok: revived stale leader fenced — 409 stale_leader, "
              "zero accepted writes, demoted, reject counted")
    except AssertionError as exc:
        print(f"FAIL: {exc}", file=sys.stderr)
        return 1
    finally:
        for _host, thread in workers:
            thread.stop()
        if revived is not None:
            revived.stop()
        thread1.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
