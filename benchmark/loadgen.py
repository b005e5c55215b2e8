"""The load generator: a child process that never imports JAX.

``python -m benchmark.loadgen --traffic F --seed N --seconds S --url U
--vocab V --out FILE`` sends the requests of one run to a server
over HTTP (``/v1/completions``, ``stream: true``, token-id prompts) and
writes one JSON file of stamps. All stamps are ``time.monotonic()`` seconds,
which on Linux is one clock for every process of the host, so the parent
that holds the chip reads them against its own.

When its requests are drawn the child prints ``READY`` and reads from its
standard input the instant at which the traffic starts; the first ``warmup_s`` of it (from the
traffic file) are sent and not measured, then the window of ``--seconds``.
Open loop: each request is sent when it is DUE, whatever the server is
doing, and its stamps are kept against that due time. Closed loop: ``clients``
callers each send their next request when the last one has answered, and
stop at the window's close: what is in flight then is dropped, not failed.
"""

from __future__ import annotations

import argparse
import asyncio
import itertools
import json
import sys
import time

import aiohttp

from benchmark import traffic as traffic_mod


async def _one(session, url: str, req: dict, rec: dict, sampling: dict):
    """Send one request, stamp every streamed chunk."""
    body = {"prompt": req["prompt"], "max_tokens": req["max_tokens"],
            "stream": True, **sampling}
    rec["sent"] = time.monotonic()
    try:
        async with session.post(url + "/v1/completions", json=body) as resp:
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = (await resp.text())[:200]
                return
            async for line in resp.content:
                if not line.startswith(b"data: "):
                    continue
                now = time.monotonic()
                data = line[6:].strip()
                if data == b"[DONE]":
                    rec["done"] = now
                    break
                event = json.loads(data)
                rec["id"] = event["id"]
                reason = event["choices"][0]["finish_reason"]
                if reason is None:
                    rec["chunks"].append(now)
                else:
                    rec["finish_reason"] = reason
    except (aiohttp.ClientError, asyncio.TimeoutError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]


def _record(i: int, req: dict, due) -> dict:
    return {"i": i, "due": due, "sent": None, "status": None, "id": None,
            "chunks": [], "done": None, "finish_reason": None, "error": None,
            "prompt_tokens": len(req["prompt"]),
            "max_tokens": req["max_tokens"]}


async def drive(traffic: dict, reqs: list, url: str, start_at: float,
                seconds: float) -> list:
    warm = float(traffic.get("warmup_s", 0.0))
    stop_at = start_at + warm + seconds
    open_loop = traffic["kind"] == "serve-open"
    # an open loop's late requests are waited for (a backlog is the
    # server's fault); a closed loop's callers just stop at the close
    deadline = stop_at + (float(traffic.get("drain_s", 20.0)) if open_loop
                          else 0.5)
    sampling = dict(traffic.get("sampling", {"temperature": 0.0}))
    records: list = []
    tasks: list = []
    timeout = aiohttp.ClientTimeout(total=None)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as s:
        if open_loop:
            for i, req in enumerate(reqs):
                due = start_at + req["due"]
                if due >= stop_at:
                    break
                await asyncio.sleep(max(due - time.monotonic(), 0.0))
                rec = _record(i, req, due)
                records.append(rec)
                tasks.append(asyncio.create_task(
                    _one(s, url, req, rec, sampling)))
        else:
            # the pool is taken in order, and again from its start if a run
            # outlasts it
            todo = enumerate(itertools.cycle(reqs))

            async def client():
                for i, req in todo:
                    if time.monotonic() >= stop_at:
                        return
                    rec = _record(i, req, None)
                    records.append(rec)
                    await _one(s, url, req, rec, sampling)

            await asyncio.sleep(max(start_at - time.monotonic(), 0.0))
            tasks = [asyncio.create_task(client())
                     for _ in range(int(traffic["clients"]))]
        if tasks:
            _, pending = await asyncio.wait(
                tasks, timeout=max(deadline - time.monotonic(), 0.0))
            for t in pending:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
    for rec in records:
        if rec["done"] is None and rec["error"] is None:
            rec["error"] = ("unfinished when the drain ended" if open_loop
                            else "in flight when the window closed")
            rec["in_flight"] = not open_loop
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("benchmark.loadgen")
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--url", required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rate", type=float, default=None,
                    help="override arrivals.rate_per_s (the sweep's knob)")
    a = ap.parse_args(argv)
    traffic = traffic_mod.load(a.traffic)
    if a.rate is not None:
        traffic["arrivals"]["rate_per_s"] = a.rate
    horizon = float(traffic.get("warmup_s", 0.0)) + a.seconds
    reqs = traffic_mod.requests(traffic, a.seed, horizon, a.vocab)
    # ready: the parent answers with the instant the traffic starts
    print("READY", flush=True)
    start_at = float(sys.stdin.readline())
    records = asyncio.run(drive(traffic, reqs, a.url, start_at, a.seconds))
    with open(a.out, "w") as f:
        json.dump({"start_at": start_at,
                   "warmup_s": float(traffic.get("warmup_s", 0.0)),
                   "seconds": a.seconds, "kind": traffic["kind"],
                   "drawn": traffic_mod.describe(reqs),
                   "records": records}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
