"""The load generator's child for multi-turn SESSIONS (traffic ``kind``
``sessions-closed``): ``benchmark/loadgen.py``'s protocol (READY, the start
instant on standard input, one JSON file of stamps with the same records),
with callers that build each request from the reply to the last. No JAX.

One session a caller. A session's text starts as its FIRST history (length
and ids from the file's ``shape_seed``: ``first_histories``, which the
runner sends once in set-up); every turn sends the text so far plus a new
message, and the reply's ids, as the stream gives them
(``choices[0].token_ids`` of each chunk), join the text behind the message.
A server whose stream gives no ids (one from before this kind existed) has
ids drawn from the seed at the reply's asked length put there instead: the
next turn's prefix hit ends at the same page either way. A session whose
next turn would pass ``session_max_tokens`` begins again from its first
history.

- the histories, and ``turns_drawn_per_session`` (message, output) LENGTH
  pairs a session, come from ``shape_seed``: the same for every ``--seed``;
- the run's seed permutes which row of pairs goes with which session and
  draws the messages' token ids.

``python -m benchmark.loadgen_sessions`` takes ``benchmark.loadgen``'s
arguments; the runner (``runners/sessions.py``) starts it where the serving
runner starts ``benchmark.loadgen``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import aiohttp
import numpy as np

from benchmark import loadgen
from benchmark.traffic import _FIRST_PLAIN_ID, draw_lengths, load

__all__ = ["first_histories", "load", "turn_shapes"]


def first_histories(traffic: dict, vocab: int) -> list:
    """The sessions' first histories as lists of token ids, from
    ``shape_seed``: what set-up makes resident."""
    rng = np.random.default_rng([int(traffic.get("shape_seed", 0)), 1])
    lengths = draw_lengths(traffic["first_history_tokens"],
                           int(traffic["clients"]), rng)
    return [rng.integers(_FIRST_PLAIN_ID, vocab, size=int(n)).tolist()
            for n in lengths]


def turn_shapes(traffic: dict, seed: int) -> list:
    """[session][turn] -> (message tokens, output tokens): the rows drawn
    from ``shape_seed``, handed to the sessions in the order of ``seed``."""
    n, turns = int(traffic["clients"]), int(traffic["turns_drawn_per_session"])
    shape = np.random.default_rng(int(traffic.get("shape_seed", 0)))
    m_len = draw_lengths(traffic["message_tokens"], n * turns, shape)
    o_len = draw_lengths(traffic["output_tokens"], n * turns, shape)
    rows = [[(int(m), int(o)) for m, o in zip(m_len[i * turns:(i + 1) * turns],
                                              o_len[i * turns:(i + 1) * turns])]
            for i in range(n)]
    return [rows[i] for i in np.random.default_rng(int(seed)).permutation(n)]


async def _turn(session, url: str, req: dict, rec: dict, sampling: dict
                ) -> list:
    """``loadgen._one`` that also keeps the ids the stream gives."""
    ids: list = []
    body = {"prompt": req["prompt"], "max_tokens": req["max_tokens"],
            "stream": True, **sampling}
    rec["sent"] = time.monotonic()
    try:
        async with session.post(url + "/v1/completions", json=body) as resp:
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = (await resp.text())[:200]
                return ids
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
                choice = event["choices"][0]
                if choice["finish_reason"] is None:
                    rec["chunks"].append(now)
                    ids.extend(choice.get("token_ids") or ())
                else:
                    rec["finish_reason"] = choice["finish_reason"]
    except (aiohttp.ClientError, asyncio.TimeoutError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    return ids


async def drive(traffic: dict, histories: list, shapes: list, seed: int,
                vocab: int, url: str, start_at: float, seconds: float
                ) -> list:
    stop_at = start_at + float(traffic.get("warmup_s", 0.0)) + seconds
    sampling = dict(traffic.get("sampling", {"temperature": 0.0}))
    longest = int(traffic["session_max_tokens"])
    records: list = []
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as s:
        async def caller(i: int):
            rng = np.random.default_rng([int(seed), 3, i])
            text, turn = list(histories[i]), 0
            while time.monotonic() < stop_at:
                m, o = shapes[i][turn % len(shapes[i])]
                if len(text) + m + o > longest:
                    text = list(histories[i])       # the session begins again
                prompt = text + rng.integers(_FIRST_PLAIN_ID, vocab,
                                             size=m).tolist()
                req = {"prompt": prompt, "max_tokens": o}
                rec = dict(loadgen._record(len(records), req, None),
                           session=i, turn=turn)
                records.append(rec)
                ids = await _turn(s, url, req, rec, sampling)
                if rec["error"] is not None or rec["done"] is None:
                    return
                if len(ids) != o:
                    # a stream without ids: ids from the seed at the length
                    ids = rng.integers(_FIRST_PLAIN_ID, vocab, size=o).tolist()
                    rec["reply_ids_drawn"] = True
                text, turn = prompt + ids, turn + 1

        await asyncio.sleep(max(start_at - time.monotonic(), 0.0))
        tasks = [asyncio.create_task(caller(i))
                 for i in range(int(traffic["clients"]))]
        _, pending = await asyncio.wait(
            tasks, timeout=max(stop_at + 0.5 - time.monotonic(), 0.0))
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    for rec in records:
        if rec["done"] is None and rec["error"] is None:
            rec["error"] = "in flight when the window closed"
            rec["in_flight"] = True
    return records


def describe(histories: list, shapes: list) -> dict:
    def five(xs):
        q = np.percentile(xs, [0, 50, 95, 100])
        return {"n": len(xs), "min": float(q[0]), "p50": float(q[1]),
                "p95": float(q[2]), "max": float(q[3]),
                "sum": float(np.sum(xs))}
    return {"first_history_tokens": five([len(h) for h in histories]),
            "message_tokens": five([m for row in shapes for m, _ in row]),
            "output_tokens": five([o for row in shapes for _, o in row])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("benchmark.loadgen_sessions")
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--url", required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    traffic = load(a.traffic)
    histories = first_histories(traffic, a.vocab)
    shapes = turn_shapes(traffic, a.seed)
    # ready: the parent answers with the instant the traffic starts
    print("READY", flush=True)
    start_at = float(sys.stdin.readline())
    records = asyncio.run(drive(traffic, histories, shapes, a.seed, a.vocab,
                                a.url, start_at, a.seconds))
    with open(a.out, "w") as f:
        json.dump({"start_at": start_at,
                   "warmup_s": float(traffic.get("warmup_s", 0.0)),
                   "seconds": a.seconds, "kind": traffic["kind"],
                   "drawn": describe(histories, shapes),
                   "records": records}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
