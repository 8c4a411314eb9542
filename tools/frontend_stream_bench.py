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
"""

from __future__ import annotations

import argparse
import asyncio
import cProfile
import io
import json
import pstats
import subprocess
import sys
import time

MODEL = "echo"


async def _client_stream(port: int, body: bytes) -> int:
    """One streaming request over a raw socket; the `data:` events with
    choices it carried (what ``chipbench/loadgen.py`` counts a token)."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(
        b"POST /v1/chat/completions HTTP/1.1\r\nHost: x\r\n"
        b"Content-Type: application/json\r\nContent-Length: "
        + str(len(body)).encode() + b"\r\n\r\n" + body
    )
    await writer.drain()
    events = 0
    tail = b""
    try:
        while True:
            chunk = await reader.read(1 << 16)
            if not chunk:
                raise RuntimeError("stream closed before [DONE]")
            # An event ends in a blank line and is never split by the
            # chunked framing: count in what is whole, keep the rest.
            buf = tail + chunk
            cut = buf.rfind(b"\n\n") + 2
            whole, tail = buf[:cut], buf[cut:]
            events += whole.count(b'data: {"id"')
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
    counts = await asyncio.gather(
        *[_client_stream(port, body) for _ in range(streams)]
    )
    return {"events": sum(counts), "seconds": time.monotonic() - t0}


async def _serve_and_drive(opts) -> dict:
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
    await endpoint.serve(EchoEngineCore(), offer_local=True)
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
    return {
        "streams": opts.streams, "tokens": opts.tokens,
        "profiled": bool(opts.profile),
        "events": seen["events"], "seconds": seen["seconds"],
        "events_per_s": seen["events"] / seen["seconds"],
        "us_per_event": 1e6 * seen["seconds"] / seen["events"],
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
    ap.add_argument("--client", type=int, default=None, metavar="PORT",
                    help="(the child) drive the server at PORT")
    opts = ap.parse_args(argv)
    if opts.client is not None:
        print(json.dumps(asyncio.run(
            _client(opts.client, opts.streams, opts.tokens)
        )))
        return
    for _ in range(opts.repeat):
        print(json.dumps(asyncio.run(_serve_and_drive(opts))), flush=True)


if __name__ == "__main__":
    main()
