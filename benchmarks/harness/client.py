"""The load generator: one thread, one asyncio loop, raw sockets.

It sends ``POST /chat`` with ``"stream": true`` to the app's own HTTP
server and reads the chunked server-sent events as they arrive, one
host-clock reading per token. Open loop: every request goes out when it
is due, whether or not earlier ones have answered, and once the window
has closed the generator waits for what is in flight (up to DRAIN_S).
Closed loop: each of N clients sends its next request when the last one
has answered; at the close the streams in flight are dropped.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time

DRAIN_S = 60.0   # how long past the close an answer is waited for


def _body(req: dict) -> bytes:
    payload = json.dumps({"prompt": " ".join(map(str, req["prompt"])),
                          "max_tokens": req["max_tokens"],
                          "temperature": 0, "stream": True}).encode()
    head = (f"POST /chat HTTP/1.1\r\nHost: bench\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\nConnection: close\r\n\r\n")
    return head.encode() + payload


async def _one(port: int, req: dict, rec: dict) -> None:
    """Send one request and read its stream into ``rec``."""
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port,
                                                       limit=1 << 20)
        rec["sent"] = time.perf_counter()
        writer.write(_body(req))
        await writer.drain()
        status = await reader.readline()
        rec["status"] = int(status.split()[1])
        while (await reader.readline()) not in (b"\r\n", b""):
            pass
        if rec["status"] != 200:
            rec["error"] = f"HTTP {rec['status']}: " + (
                await reader.read(500)).decode("latin-1")
            return
        while True:
            line = await reader.readline()
            if not line:
                if not rec["done"]:
                    rec["error"] = rec["error"] or "stream cut short"
                return
            if not line.startswith(b"data: "):
                continue
            if line.startswith(b"data: [DONE]"):
                rec["done"] = True
                continue
            event = json.loads(line[6:])
            if "token" in event:
                rec["token_times"].append(time.perf_counter())
                rec["tokens"].append(event["token"])
            else:
                rec["error"] = str(event.get("error", event))[:300]
    except asyncio.CancelledError:
        rec["error"] = rec["error"] or "no answer by the time limit"
        raise
    except (OSError, ValueError, IndexError) as exc:
        rec["error"] = f"{type(exc).__name__}: {exc}"[:300]
    finally:
        rec["ended"] = time.perf_counter()
        if writer is not None:
            writer.close()


def _record(req: dict, due: float | None) -> dict:
    return {"due": due, "sent": None, "ended": None, "status": None,
            "token_times": [], "tokens": [], "done": False, "error": None,
            "dropped": False,
            "prompt": req["prompt"], "max_tokens": req["max_tokens"]}


async def _open_loop(port, requests, t0, seconds, records):
    async def fire(req, rec):
        delay = rec["due"] - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        await _one(port, req, rec)

    tasks = []
    for req in requests:
        rec = _record(req, t0 + req["due_s"])
        records.append(rec)
        tasks.append(asyncio.ensure_future(fire(req, rec)))
    left = t0 + seconds + DRAIN_S - time.perf_counter()
    _, pending = await asyncio.wait(tasks, timeout=max(0.0, left))
    for task in pending:   # never answered: failed, and says so
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def _closed_loop(port, requests, clients, t0, seconds, records):
    nxt = iter(requests)

    async def client():
        for req in nxt:
            if time.perf_counter() >= t0 + seconds:
                return
            rec = _record(req, time.perf_counter())
            records.append(rec)
            await _one(port, req, rec)

    tasks = [asyncio.ensure_future(client()) for _ in range(clients)]
    _, pending = await asyncio.wait(
        tasks, timeout=max(0.0, t0 + seconds - time.perf_counter()))
    for rec in records:    # in flight at the close: dropped, not failed
        rec["dropped"] = rec["ended"] is None
    for task in pending:
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


def run_window(port: int, traffic: dict, seconds: float,
               during=None) -> tuple[list, float]:
    """Drive one window from a thread of its own; returns the records
    and the window's start (``time.perf_counter()``). ``during(t0)``,
    if given, runs in the caller's thread while the window is open (the
    traced run starts and stops the profiler there). A closed loop's
    requests that were in flight at the close are marked ``dropped``:
    their tokens count, they are neither attempted nor failed."""
    records: list = []
    box: dict = {}
    started = threading.Event()

    def main():
        t0 = box["t0"] = time.perf_counter() + 0.05
        started.set()
        if traffic["loop"] == "open":
            coro = _open_loop(port, traffic["requests"], t0, seconds, records)
        else:
            coro = _closed_loop(port, traffic["requests"],
                                traffic["clients"], t0, seconds, records)
        try:
            asyncio.run(coro)
        except BaseException as exc:   # reported by the caller's thread
            box["error"] = exc

    thread = threading.Thread(target=main, name="bench-client")
    thread.start()
    started.wait()
    try:
        if during is not None:
            during(box["t0"])
    finally:
        thread.join()
    if "error" in box:
        raise box["error"]
    return records, box["t0"]
