"""Closed-loop load over HTTP: streaming chat completions, timed per token.

JAX-free on purpose: the harness holds the chip and the server's loop,
and this module runs as its child process (``python3 -m chipbench.loadgen
plan.json out.json``). Clocks are
``time.monotonic()`` on both sides — CLOCK_MONOTONIC is one clock for
every process of a host, so the plan's absolute times mean the same here.

The plan::

    {"base": "http://127.0.0.1:<port>", "model": "...", "traffic": {...},
     "seed": 7, "t_begin": <mono>, "window": [<mono>, <mono>],
     "request_timeout_s": 120}

Clients start staggered over the ramp (``t_begin`` to ``window[0]``,
not measured). Each client takes the next request of the shared list,
pauses its think time, sends, reads the stream to ``[DONE]``, and goes
round until the window has closed; whatever is in flight then is read to
its end (the drain). Nothing is cancelled: every request sent counts.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

from chipbench import traffic

#: requests made ready for one run: enough for the ramp, the window and
#: the drain at any plausible rate
LIST_LENGTH = 8192


async def _one(session, plan, rec: dict, text: str) -> None:
    body = {
        "model": plan["model"],
        "messages": [{"role": "user", "content": text}],
        "max_tokens": rec["output_tokens"],
        "stream": True,
        "temperature": 0.0,
        # Random weights may sample <eos> at any step.
        "nvext": {"ignore_eos": True},
    }
    times: list[float] = []
    rec["sent"] = time.monotonic()
    try:
        async with session.post(
            plan["base"] + "/v1/chat/completions", json=body
        ) as resp:
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = (await resp.text())[:300]
                return
            async for raw in resp.content:
                if not raw.startswith(b"data: "):
                    continue
                now = time.monotonic()
                data = raw[6:].strip()
                if data == b"[DONE]":
                    rec["done"] = now
                    break
                chunk = json.loads(data)
                if "error" in chunk:
                    rec["error"] = json.dumps(chunk["error"])[:300]
                elif chunk.get("choices"):
                    times.append(now)
                elif chunk.get("usage"):
                    rec["usage_prompt"] = chunk["usage"]["prompt_tokens"]
                    rec["usage_completion"] = chunk["usage"][
                        "completion_tokens"
                    ]
    except Exception as exc:  # noqa: BLE001 — a failed request is a result
        rec["error"] = f"{type(exc).__name__}: {exc}"[:300]
    finally:
        rec["token_times"] = times


async def drive(plan: dict) -> dict:
    import aiohttp

    spec = plan["traffic"]
    seed = plan["seed"]
    clients = int(spec["clients"])
    t_begin = plan["t_begin"]
    w0, w1 = plan["window"]
    reqs = traffic.requests(spec, seed, LIST_LENGTH)
    cursor = 0
    records: list[dict] = []
    late: list[float] = []
    lag: list[float] = []

    async def client(c: int, session) -> None:
        nonlocal cursor
        await asyncio.sleep(
            max(0.0, t_begin + (w0 - t_begin) * c / clients - time.monotonic())
        )
        while time.monotonic() < w1:
            i, cursor = cursor, cursor + 1
            if i >= len(reqs):
                raise RuntimeError("request list exhausted: LIST_LENGTH")
            rec = dict(reqs[i], index=i, client=c)
            due = time.monotonic() + rec["think_s"]
            if rec["think_s"] > 0:
                if due >= w1:
                    return
                await asyncio.sleep(rec["think_s"])
            text = traffic.prompt_text(seed, i, rec["prompt_tokens"])
            late.append(time.monotonic() - due)
            records.append(rec)
            await _one(session, plan, rec, text)

    async def lag_probe() -> None:
        """How late this loop wakes from a 10 ms sleep: a starved
        generator must not be read as a fast server."""
        while time.monotonic() < w1:
            t = time.monotonic()
            await asyncio.sleep(0.01)
            lag.append(time.monotonic() - t - 0.01)

    timeout = aiohttp.ClientTimeout(total=plan.get("request_timeout_s", 120))
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as s:
        probe = asyncio.ensure_future(lag_probe())
        await asyncio.gather(*(client(c, s) for c in range(clients)))
        await probe
    return {
        "records": records,
        "generator_late_s": late,
        "loop_lag_s": lag,
        "finished": time.monotonic(),
    }


def main(argv: list[str]) -> None:
    plan_path, out_path = argv
    with open(plan_path) as f:
        plan = json.load(f)
    result = asyncio.run(drive(plan))
    with open(out_path, "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main(sys.argv[1:])
