#!/usr/bin/env python3
"""The load generator: a child process of run.py that never imports jax
(a chip belongs to the one process that holds the engine; real clients
do not share the server's GIL). One thread, one asyncio loop, one
aiohttp session.

    python loadgen.py --url http://127.0.0.1:P --model NAME \
        --traffic traffic/<mix>.json --seed N --seconds S

stdout: one line ``{"open": <time.monotonic()>}`` when the window opens
(CLOCK_MONOTONIC is one clock for every process of the machine), then,
when every request has ended, one JSON line per request (times are
seconds after the window opened, on this process's monotonic clock):

    i, due_s, sent_s   due_s == sent_s in a closed loop
    status, done, finish, tokens, usage   as the server answered
    chunk_s, chunk_n   arrival time and token count of every content chunk
    end_s, cut, error  cut = stopped by the end of a closed-loop window

The SSE reader is chip_smoke._sse_chat with arrival times kept. A
request is what a chat client sends: no logprobs. The benchmark's
weights (harness/weights.py) make every greedy token one printable ASCII
character under the byte tokenizer, so each engine emission arrives as a
content chunk and a chunk's characters number its tokens; a token that
decoded to no text would leave the count short of ``max_tokens`` and the
request counts as failed.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import traffic  # noqa: E402

# how long an open-loop request may run on after the window, and how long
# any response may stay silent, before it counts as failed
DRAIN_S = 90.0


async def one_request(http, url: str, model: str, msgs: list, req: dict,
                      t0: float, row: dict) -> None:
    body = {"model": model, "stream": True, "max_tokens": req["output_len"],
            "messages": msgs, "temperature": 0,
            "stream_options": {"include_usage": True},
            "ext": {"ignore_eos": True, "use_raw_prompt": True}}
    row["sent_s"] = time.monotonic() - t0
    try:
        async with http.post(url, json=body) as resp:
            row["status"] = resp.status
            if resp.status != 200:
                row["error"] = (await resp.text())[:300]
                return
            async for raw in resp.content:
                line = raw.strip()
                if not line.startswith(b"data: "):
                    continue
                if line == b"data: [DONE]":
                    row["done"] = True
                    break
                now = time.monotonic() - t0
                chunk = json.loads(line[len(b"data: "):])
                if chunk.get("usage"):
                    row["usage"] = chunk["usage"]
                for c in chunk.get("choices", []):
                    n = len((c.get("delta") or {}).get("content") or "")
                    if n:
                        row["chunk_s"].append(now)
                        row["chunk_n"].append(n)
                        row["tokens"] += n
                    if c.get("finish_reason"):
                        row["finish"] = c["finish_reason"]
    except asyncio.CancelledError:
        row["cut"] = True
        raise
    except Exception as e:  # noqa: BLE001 — a failed request is a row
        row["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        row["end_s"] = time.monotonic() - t0


def new_row(req: dict) -> dict:
    return {"i": req["i"], "due_s": req["due_s"], "sent_s": None,
            "prompt_len": req["prompt_len"], "output_len": req["output_len"],
            "status": None, "done": False, "finish": None, "tokens": 0,
            "usage": None, "chunk_s": [], "chunk_n": [], "end_s": None,
            "cut": False, "error": None}


async def run(a) -> list:
    import aiohttp

    with open(a.traffic) as f:
        params = json.load(f)
    if a.rate is not None:
        params["rate_rps"] = a.rate
    sched = traffic.schedule(params, a.seconds)
    msgs = [traffic.messages(params, a.seed, r) for r in sched]
    url = a.url + "/v1/chat/completions"
    rows: list = []
    timeout = aiohttp.ClientTimeout(total=None, sock_read=DRAIN_S)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as http:
        t0 = time.monotonic()
        print(json.dumps({"open": t0}), flush=True)

        def start(req):
            row = new_row(req)
            rows.append(row)
            return asyncio.ensure_future(one_request(
                http, url, a.model, msgs[req["i"]], req, t0, row))

        if params["loop"] == "open":
            tasks = []
            for req in sched:
                delay = t0 + req["due_s"] - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                tasks.append(start(req))
            left = t0 + a.seconds - time.monotonic()
            if left > 0:
                await asyncio.sleep(left)
            _, pending = await asyncio.wait(tasks, timeout=DRAIN_S)
        else:
            nxt = iter(sched)

            async def client():
                for req in nxt:
                    row = new_row(req)
                    row["due_s"] = time.monotonic() - t0
                    rows.append(row)
                    await one_request(http, url, a.model, msgs[req["i"]],
                                      req, t0, row)

            tasks = [asyncio.ensure_future(client())
                     for _ in range(params["clients"])]
            _, pending = await asyncio.wait(tasks, timeout=a.seconds)
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    if params["loop"] == "open":
        # only a closed-loop window cuts requests; here a request still
        # running when the drain ran out has failed
        for row in rows:
            if row["cut"]:
                row["cut"] = False
                row["error"] = f"not ended {DRAIN_S} s after the window"
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", required=True)
    ap.add_argument("--model", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rate", type=float, default=None,
                    help="builder's sweep: overrides the file's rate_rps")
    a = ap.parse_args(argv)
    rows = asyncio.run(run(a))
    out = "\n".join(json.dumps(r) for r in rows)
    sys.stdout.write(out + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
