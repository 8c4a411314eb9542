"""The frontend's streamed path alone: what one streamed token costs the
asyncio loop of a server process, with no engine thread and no device.

The served chain of ``dynamo-tpu run --in http --out echo_core`` in this
process (``HttpService`` -> preprocessor -> detokenizer -> failover ->
router -> the local call -> ``EchoEngineCore``, which hands out a token a
step as fast as it is asked), driven by ``--streams`` streaming chat
completions of ``--tokens`` tokens each from a CHILD process over
loopback, so the client's parsing is not on the server's loop. The loop is
saturated throughout: seconds / events is the loop's cost a token.

Not part of the benchmark; it is where a frontend PR sizes each step on
the host it will be measured on (the chip machine's host is slower than
the sandbox's, and ``PERF.md`` quotes only runs made there):

    chiprun -- python -m tools.frontend_stream_bench --streams 64 --tokens 400
    python -m tools.frontend_stream_bench --profile      # top by own time

A line of JSON on stdout: events, events a second, microseconds an event,
and the two counters of ``llm/metrics.py`` the streamed path keeps
(``frontend_stream_events_total`` by rendering,
``frontend_stream_busy_seconds_total``; ``null`` on a tree without them).
``--profile`` runs the same load under cProfile and prints the top of it
by own time on stderr: shares, not a rate.

``--feed token|step|paced`` puts a SECOND THREAD under the same chain, as
a served process has one (``TpuEngine``'s). It makes the steps: each is
``--busy-ms`` of pure Python with the interpreter held (compose and
deliver; an amount of work sized while nothing else runs, so it stretches
when the loop takes its share of the interpreter), ``--wait-ms`` asleep
with it released (the wait for the device), then one token for every live
stream, handed to the loop a token at a time (one
``call_soon_threadsafe(put_nowait)`` each), a step at a time (one
``call_soon_threadsafe`` for the step's frames, however far behind the
loop is), or ``paced``: a step at a time once the loop has written the
step before it (what ``TpuEngine._flush_outbox`` does: one step in the
channel). The hand-off alone, on the host it will be measured on: events
a second, the gap between a stream's events on the client's clock
(``gap_ms_p50`` / ``_p95`` / ``_p99``), the steps, the wake-ups of the
loop, the frames handed over and what the thread waited for the loop a
step. ``--switch-ms`` sets the interpreter's switch interval for the run
(how long the loop waits for the interpreter while the thread holds it).

    chiprun -- python -m tools.frontend_stream_bench --streams 128 \
        --tokens 200 --busy-ms 10 --wait-ms 4 --feed token --feed step \
        --feed paced
"""

from __future__ import annotations

import argparse
import asyncio
import cProfile
import io
import json
import pstats
import statistics
import subprocess
import sys
import threading
import time
from collections import deque

MODEL = "echo"


async def _client_stream(port: int, body: bytes, gaps: list) -> int:
    """One streaming request over a raw socket; the `data:` events with
    choices it carried (what ``chipbench/loadgen.py`` counts a token).
    ``gaps`` takes the seconds between its events as they were read (0
    for an event that came in one read with the one before it)."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(
        b"POST /v1/chat/completions HTTP/1.1\r\nHost: x\r\n"
        b"Content-Type: application/json\r\nContent-Length: "
        + str(len(body)).encode() + b"\r\n\r\n" + body
    )
    await writer.drain()
    events = 0
    tail = b""
    last = None
    try:
        while True:
            chunk = await reader.read(1 << 16)
            if not chunk:
                raise RuntimeError("stream closed before [DONE]")
            now = time.monotonic()
            # An event ends in a blank line and is never split by the
            # chunked framing: count in what is whole, keep the rest.
            buf = tail + chunk
            cut = buf.rfind(b"\n\n") + 2
            whole, tail = buf[:cut], buf[cut:]
            n = whole.count(b'data: {"id"')
            if n:
                if last is not None:
                    gaps.append(now - last)
                gaps.extend([0.0] * (n - 1))
                last = now
            events += n
            if b"data: [DONE]" in whole:
                return events
    finally:
        writer.close()


async def _client(port: int, streams: int, tokens: int) -> dict:
    text = ("The quick brown fox jumps over the lazy dog. " * (tokens // 40 + 1))
    body = json.dumps({
        "model": MODEL, "stream": True, "max_tokens": tokens,
        "messages": [{"role": "user", "content": text[:tokens]}],
    }).encode()
    t0 = time.monotonic()
    gaps: list[float] = []
    counts = await asyncio.gather(
        *[_client_stream(port, body, gaps) for _ in range(streams)]
    )
    seconds = time.monotonic() - t0
    cuts = statistics.quantiles(gaps, n=100)
    return {
        "events": sum(counts), "seconds": seconds,
        "gap_ms_p50": 1e3 * cuts[49], "gap_ms_p95": 1e3 * cuts[94],
        "gap_ms_p99": 1e3 * cuts[98],
    }


def _spin(n: int) -> int:
    x = 0
    for i in range(n):
        x += i & 3
    return x


def _drain(batch: list, taken=None) -> None:
    for out_q, item in batch:
        out_q.put_nowait(item)
    if taken is not None:
        # Behind the streams the puts woke: they have written by then.
        asyncio.get_running_loop().call_soon(taken)


class ThreadFedEcho:
    """The echo engine fed as ``TpuEngine`` feeds its streams: a second
    thread makes the steps (module docstring, ``--feed``)."""

    def __init__(self, by: str, busy_s: float, wait_s: float) -> None:
        self.by, self.wait_s = by, wait_s
        # A step's busy stretch is an amount of WORK, sized here with the
        # interpreter uncontended: a loop that takes its share stretches
        # the step, as it stretches the engine's.
        t0 = time.perf_counter()
        _spin(200_000)
        self.spins = int(200_000 * busy_s / (time.perf_counter() - t0))
        self.steps = self.wakeups = self.items = 0
        self.wait_for_loop_s = 0.0
        self._taken = threading.Event()
        self._taken.set()
        self._new: deque = deque()
        self._stop = threading.Event()
        self._loop = asyncio.get_running_loop()
        self._thread = threading.Thread(target=self._feed, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._taken.set()
        self._thread.join(5.0)

    def _feed(self) -> None:
        live: list[tuple] = []  # (the tokens left, the stream's queue)
        call = self._loop.call_soon_threadsafe
        while not self._stop.is_set():
            while self._new:
                live.append(self._new.popleft())
            if not live:
                time.sleep(0.001)
                continue
            _spin(self.spins)  # the interpreter is held, as by compose
            if self.wait_s:
                time.sleep(self.wait_s)
            batch, left = [], []
            for tokens, out_q in live:
                tok = next(tokens, None)
                batch.append((out_q, tok))
                if tok is not None:
                    left.append((tokens, out_q))
            live = left
            self.steps += 1
            self.items += len(batch)
            if self.by == "token":
                self.wakeups += len(batch)
                for out_q, tok in batch:
                    call(out_q.put_nowait, tok)
            elif self.by == "step":
                self.wakeups += 1
                call(_drain, batch)
            else:
                # One step in the channel: this one leaves when the loop
                # has written the one before it.
                t0 = time.perf_counter()
                self._taken.wait()
                self.wait_for_loop_s += time.perf_counter() - t0
                self._taken.clear()
                self.wakeups += 1
                call(_drain, batch, self._taken.set)

    async def generate(self, request):
        from dynamo_tpu.llm.protocols.common import (
            EngineOutput,
            FinishReason,
            PreprocessedRequest,
        )

        pre = PreprocessedRequest.from_wire(request.payload)
        out_q: asyncio.Queue = asyncio.Queue()
        self._new.append(
            (iter(pre.token_ids[: pre.stop.max_tokens]), out_q)
        )
        count = 0
        while True:
            tok = await out_q.get()
            if tok is None:
                yield EngineOutput(
                    token_ids=[], finish_reason=FinishReason.STOP,
                    cum_tokens=count,
                ).to_wire()
                return
            count += 1
            # The frame as ``TpuEngine._stream`` spells it.
            yield {
                "token_ids": [tok], "text": None, "finish_reason": None,
                "cum_tokens": count, "kv_transfer_params": None,
            }


async def _serve_and_drive(opts, feed: str | None = None) -> dict:
    from dynamo_tpu.llm.discovery import (
        ModelManager,
        ModelWatcher,
        register_llm,
    )
    from dynamo_tpu.llm.engines import EchoEngineCore
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    drt = await DistributedRuntime.in_process()
    endpoint = drt.namespace("dyn").component("echo").endpoint("generate")
    engine = (
        ThreadFedEcho(feed, opts.busy_ms / 1e3, opts.wait_ms / 1e3)
        if feed else EchoEngineCore()
    )
    await endpoint.serve(engine, offer_local=True)
    await register_llm(
        drt, endpoint, ModelDeploymentCard(name=MODEL, model_path=None)
    )
    manager = ModelManager()
    await ModelWatcher(drt, manager).start()
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    profile = cProfile.Profile() if opts.profile else None
    try:
        child = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "tools.frontend_stream_bench",
            "--client", str(service.port), "--streams", str(opts.streams),
            "--tokens", str(opts.tokens), stdout=subprocess.PIPE,
        )
        if profile is not None:
            profile.enable()
        out, _ = await child.communicate()
        if profile is not None:
            profile.disable()
        if child.returncode != 0:
            raise RuntimeError(f"the client exited {child.returncode}")
        seen = json.loads(out)
    finally:
        await service.stop()
        await drt.shutdown()
        if feed:
            engine.stop()
    if profile is not None:
        text = io.StringIO()
        pstats.Stats(profile, stream=text).sort_stats("tottime").print_stats(
            opts.top
        )
        print(text.getvalue(), file=sys.stderr)
    # The tokens and the usage chunk carry `choices`; the client counts
    # every event that opens a chunk.
    wanted = opts.streams * (opts.tokens + 1)
    if seen["events"] != wanted:
        raise RuntimeError(f"{seen['events']} events, {wanted} wanted")
    by_render = getattr(service.metrics, "stream_events", None)
    busy_s = getattr(service.metrics, "stream_busy_s", None)
    written = sum(by_render.values()) if by_render else None
    fed = {
        "feed": feed, "busy_ms": opts.busy_ms, "wait_ms": opts.wait_ms,
        "switch_ms": round(1e3 * sys.getswitchinterval(), 4),
        "steps": engine.steps, "handoff_wakeups": engine.wakeups,
        "handoff_items": engine.items,
        "handoff_wait_ms_per_step": 1e3 * engine.wait_for_loop_s / engine.steps,
        "step_ms": 1e3 * seen["seconds"] / engine.steps,
    } if feed else {}
    return {
        "streams": opts.streams, "tokens": opts.tokens,
        "profiled": bool(opts.profile), **fed,
        "events": seen["events"], "seconds": seen["seconds"],
        "events_per_s": seen["events"] / seen["seconds"],
        "us_per_event": 1e6 * seen["seconds"] / seen["events"],
        "gap_ms_p50": seen["gap_ms_p50"], "gap_ms_p95": seen["gap_ms_p95"],
        "gap_ms_p99": seen["gap_ms_p99"],
        "frontend_stream_events_total": by_render,
        "frontend_stream_busy_seconds_total": busy_s,
        "busy_us_per_event": 1e6 * busy_s / written if written else None,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python -m tools.frontend_stream_bench")
    ap.add_argument("--streams", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=400)
    ap.add_argument("--repeat", type=int, default=1,
                    help="runs, a line each (a server each)")
    ap.add_argument("--profile", action="store_true",
                    help="under cProfile: the top by own time on stderr")
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--feed", action="append",
                    choices=("token", "step", "paced"),
                    help="a second thread makes the steps and hands over a "
                    "token at a time, a step at a time, or a step when the "
                    "loop has written the one before; give several to run "
                    "each")
    ap.add_argument("--busy-ms", type=float, default=6.0,
                    help="(--feed) pure Python a step, the interpreter held")
    ap.add_argument("--wait-ms", type=float, default=0.0,
                    help="(--feed) asleep a step, the interpreter released")
    ap.add_argument("--switch-ms", type=float, action="append",
                    help="(--feed) the interpreter's switch interval; give "
                    "several to run each (default: as the process has it)")
    ap.add_argument("--client", type=int, default=None, metavar="PORT",
                    help="(the child) drive the server at PORT")
    opts = ap.parse_args(argv)
    if opts.client is not None:
        print(json.dumps(asyncio.run(
            _client(opts.client, opts.streams, opts.tokens)
        )))
        return
    for _ in range(opts.repeat):
        for switch_ms in opts.switch_ms or [1e3 * sys.getswitchinterval()]:
            sys.setswitchinterval(switch_ms / 1e3)
            for feed in opts.feed or [None]:
                print(
                    json.dumps(asyncio.run(_serve_and_drive(opts, feed))),
                    flush=True,
                )


if __name__ == "__main__":
    main()
