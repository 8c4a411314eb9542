"""Prove on the chip that the main path starts, serves and computes right.

    python chip_smoke.py              # one TPU chip (what the driver runs)
    python chip_smoke.py --multichip  # one host with four chips; runs the
                                      # tensor-parallel path and what it is
                                      # compared with, and nothing else

One process: the server is started the way a user starts it
(``dynamo-tpu run --in http --out tpu --model-path preset:llama3.2-1b`` —
``cli.build_parser`` + ``cli._run``, the CLI's default engine sizes,
warmup on) and an ``httpx`` client on the same event loop talks to it
over 127.0.0.1. A chip belongs to one process at a time, so nothing here
spawns a child. Weights are random from the engine's seed, every input
is generated from ``SEED``, and nothing touches the network.

Phases of the one-chip run, each of which must pass (no phase's failure
is caught): (1) the ragged Pallas kernel against its XLA twin on a seeded
mixed batch at the served widths, whole and as one tp=4 shard sees them;
(2) the server: /health 200, requests
answered with exactly the tokens asked for, zero mid-traffic compiles,
the Pallas path compiled in, clean shutdown on SIGTERM; (3) the served
model's logits, Pallas path against the XLA twin, on the engine's params.

Every phase prints one JSON line; the LAST line of stdout is the result
``{"ok": true, "device": {...}}`` and nothing else. Without a TPU the
script exits non-zero and prints no result. One overall deadline
(``DEADLINE_S``) turns a hung kernel — a DMA semaphore that never fires
compiles fine and waits for ever — into a non-zero exit.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import re
import signal
import sys
import threading
import time
from unittest import mock

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 22
# The driver allows 1200 s including compilation; leave room to report.
DEADLINE_S = 1100
MULTICHIP_DEADLINE_S = 2400
# Kernel vs twin, bf16 in / bf16 out, f32 accumulation in both: the two
# round differently (the twin's f32 matmuls run as bf16 MXU passes, the
# output cast rounds once more), each ~2^-8 relative. A kernel that reads
# a wrong page or row is off by O(1).
KERNEL_ATOL = KERNEL_RTOL = 2e-2
# Model logits, one implementation against another (tp=4 vs one chip;
# Pallas path vs XLA twin), bf16. Under tp every row-parallel matmul (wo,
# down — 2 per layer) sums four partials and rounds once more than the
# one-chip program, through 16 layers; the attention paths differ as the
# kernel check above says, per layer. Stated as a fraction of the largest
# logit (measured on the chip, PR 22: 1.6 % for tp=4).
LOGIT_RTOL = 4e-2
# Per-device bytes in use under tp=4: weights and KV shard four ways,
# only small replicated state differs. max/min within this factor.
TP_BYTES_EVEN = 1.15


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def arm_deadline(seconds: float) -> threading.Timer:
    """Hard stop: a kernel that waits for ever blocks inside the runtime,
    where no exception can reach — a timer thread can still end the
    process (device waits release the GIL)."""

    def expire():
        print(
            f"chip_smoke: overall deadline of {seconds:.0f} s passed",
            file=sys.stderr, flush=True,
        )
        os._exit(124)

    t = threading.Timer(seconds, expire)
    t.daemon = True
    t.start()
    return t


def device_report() -> dict:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def require_tpu(count: int) -> dict:
    dev = device_report()
    if dev["platform"] != "tpu" or dev["count"] != count:
        raise SystemExit(
            f"chip_smoke needs {count} TPU chip(s); jax reports {dev}"
        )
    return dev


def memory_report() -> list[dict]:
    import jax

    out = []
    for d in jax.devices():
        ms = d.memory_stats() or {}
        out.append({
            "id": d.id,
            "bytes_in_use": ms.get("bytes_in_use"),
            "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
            "bytes_limit": ms.get("bytes_limit"),
        })
    return out


def assert_even(mem: list[dict], what: str) -> float:
    used = [m["bytes_in_use"] for m in mem]
    assert all(u for u in used), f"{what}: a device holds nothing: {used}"
    ratio = max(used) / min(used)
    assert ratio <= TP_BYTES_EVEN, (
        f"{what}: bytes in use uneven across devices "
        f"(max/min {ratio:.3f} > {TP_BYTES_EVEN}): {used}"
    )
    return ratio


# ---------------------------------------------------------------------------
# a seeded ragged batch (shared by the kernel check and the tp=4 check)
# ---------------------------------------------------------------------------


#: ``llama.unified``'s metadata operands after ``token_ids``, in order.
UNIFIED_META = (
    "token_pos", "slot_mapping", "token_seq", "block_tables",
    "q_start", "q_len", "kv_len", "row_start",
)


def ragged_batch(
    spans: list[tuple[int, int]], *, T: int, S: int, max_blocks: int,
    block_size: int, num_blocks: int = 0, rng=None, reserve: int = 0,
    block_tables: np.ndarray | None = None,
) -> dict[str, np.ndarray]:
    """Host metadata of one unified dispatch — the same arrays
    ``ModelRunner.unified_step`` builds — for ``spans`` = [(prefix_len,
    new_tokens), ...]; (0, 0) is an idle metadata row. Spans pack back to
    back from row 0, so each starts wherever the previous one ended.
    Without ``block_tables``, each span draws a disjoint seeded table
    (never trash block 0) with room for ``reserve`` more tokens."""
    assert len(spans) <= S
    m = {
        "token_pos": np.full(T, -1, np.int32),
        "slot_mapping": np.zeros(T, np.int32),
        "token_seq": np.zeros(T, np.int32),
        "block_tables": np.zeros((S, max_blocks), np.int32),
        "q_start": np.zeros(S, np.int32),
        "q_len": np.zeros(S, np.int32),
        "kv_len": np.zeros(S, np.int32),
        "row_start": np.zeros(S, np.int32),
    }
    if block_tables is not None:
        m["block_tables"] = block_tables
    else:
        ids = rng.permutation(np.arange(1, num_blocks))
        used = 0
        for s, (prefix, n) in enumerate(spans):
            nb = -(-(prefix + n + reserve) // block_size) if n else 0
            assert nb <= max_blocks and used + nb <= len(ids)
            m["block_tables"][s, :nb] = ids[used : used + nb]
            used += nb
    cursor = 0
    for s, (prefix, n) in enumerate(spans):
        if n == 0:
            continue
        pos = np.arange(prefix, prefix + n)
        m["q_start"][s], m["q_len"][s] = prefix, n
        m["kv_len"][s], m["row_start"][s] = prefix + n, cursor
        m["token_pos"][cursor : cursor + n] = pos
        m["token_seq"][cursor : cursor + n] = s
        m["slot_mapping"][cursor : cursor + n] = (
            m["block_tables"][s, pos // block_size] * block_size
            + pos % block_size
        )
        cursor += n
    assert cursor <= T, f"{cursor} tokens exceed the budget {T}"
    return m


# ---------------------------------------------------------------------------
# phase: kernel vs twin
# ---------------------------------------------------------------------------


def kernel_vs_twin(
    *, num_heads: int, num_kv_heads: int, head_dim: int, block_size: int,
    num_blocks: int, S: int, max_blocks: int, T: int,
    spans: list[tuple[int, int]], seed: int = SEED,
) -> dict:
    """``AttnDispatch(use_pallas=True).ragged`` against the XLA twin on
    one mixed batch, bf16, TRUE head dim on lane-padded caches — so the
    padding and the q pre-scale of ``_pad_q_for_cache`` are inside the
    comparison. Rows no span owns must come back zero from both."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops.attention import AttnDispatch
    from dynamo_tpu.ops.pallas.attention import cache_head_dim

    rng = np.random.default_rng(seed)
    m = ragged_batch(
        spans, T=T, S=S, max_blocks=max_blocks, block_size=block_size,
        num_blocks=num_blocks, rng=rng,
    )
    Dc = cache_head_dim(head_dim)
    shape = (num_blocks * block_size, num_kv_heads, Dc)

    def cache():
        # The engine writes K/V into lanes [:head_dim]; the pad stays 0.
        x = rng.standard_normal(shape, np.float32)
        x[..., head_dim:] = 0
        return jnp.asarray(x, jnp.bfloat16)

    k, v = cache(), cache()
    q = jnp.asarray(
        rng.standard_normal((T, num_heads, head_dim), np.float32),
        jnp.bfloat16,
    )
    args = (
        q, k, v, *(jnp.asarray(m[n]) for n in (
            "block_tables", "token_seq", "token_pos", "q_start", "q_len",
            "kv_len", "row_start",
        )), block_size,
    )
    t0 = time.monotonic()
    want = jax.block_until_ready(AttnDispatch(use_pallas=False).ragged(*args))
    got = jax.block_until_ready(AttnDispatch(use_pallas=True).ragged(*args))
    seconds = time.monotonic() - t0
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32)
    assert got.shape == want.shape == (T, num_heads, head_dim)
    assert np.isfinite(got).all(), "kernel output has non-finite values"
    owned = m["token_pos"] >= 0
    assert np.abs(want[owned]).max() > 0.1, "twin output is degenerate"
    assert not got[~owned].any() and not want[~owned].any(), (
        "rows no span owns must be zero"
    )
    err = np.abs(got - want)
    worst = float(err.max())
    bound = KERNEL_ATOL + KERNEL_RTOL * np.abs(want)
    bad = int((err > bound).sum())
    report = {
        "spans": spans, "tokens": int(owned.sum()), "budget": T,
        "max_abs_err": worst, "mean_abs_err": float(err[owned].mean()),
        "atol": KERNEL_ATOL, "rtol": KERNEL_RTOL,
        "elements_out_of_tolerance": bad, "seconds": round(seconds, 2),
    }
    assert bad == 0, f"kernel disagrees with the XLA twin: {report}"
    return report


# ---------------------------------------------------------------------------
# phase: serve through the CLI entry
# ---------------------------------------------------------------------------


def _prompts(lengths: list[int], seed: int) -> list[str]:
    """Seeded lowercase text of the given byte lengths (the preset's
    tokenizer is byte-level: bytes = prompt tokens, plus the template).
    The second prompt extends the first — a prefix-cache hit."""
    rng = np.random.default_rng(seed)
    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz ", np.uint8)
    out = []
    for n in lengths:
        out.append(bytes(rng.choice(letters, n)).decode())
    if len(out) > 1 and lengths[1] > lengths[0]:
        out[1] = out[0] + out[1][lengths[0]:]
    return out


async def _chat(client, base, model, prompt, max_tokens, stream) -> int:
    """One /v1/chat/completions request; returns completion tokens."""
    body = {
        "model": model,
        "messages": [{"role": "user", "content": prompt}],
        "max_tokens": max_tokens,
        "stream": stream,
        "temperature": 0.0,
        # Random weights may sample <eos> at any step.
        "nvext": {"ignore_eos": True},
    }
    r = await client.post(f"{base}/v1/chat/completions", json=body)
    assert r.status_code == 200, (r.status_code, r.text[:500])
    if not stream:
        return r.json()["usage"]["completion_tokens"]
    assert r.headers["content-type"].startswith("text/event-stream")
    events = [
        ln[len("data: "):] for ln in r.text.splitlines()
        if ln.startswith("data: ")
    ]
    assert events and events[-1] == "[DONE]", events[-3:]
    chunks = [json.loads(e) for e in events[:-1]]
    assert not any("error" in c for c in chunks), chunks[-1]
    usage = [c["usage"] for c in chunks if c.get("usage")]
    assert usage, "stream carried no usage chunk"
    return usage[-1]["completion_tokens"]


def _metric(text: str, name: str) -> float:
    hit = re.search(
        rf"^\S*{name}(?:\{{[^}}]*\}})?\s+(\S+)$", text, re.MULTILINE
    )
    assert hit, f"/metrics has no {name}"
    return float(hit.group(1))


def lowered_step_kernels(runner) -> int:
    """Mentions of Mosaic's custom call in the runner's own top-rung
    unified program, lowered and neither compiled nor run (identical
    per-layer calls share one lowered function, so this is "present or
    not", not a kernel count)."""
    return runner.lower_unified_top().as_text().count("tpu_custom_call")


class JaxEvents:
    """JAX's own account of compiling, summed by event name as
    ``[count, seconds]``: tracing, lowering and backend-compile
    durations, persistent-cache hits and retrieval time, over the whole
    start (``CompileStats`` counts the hits and misses of the warmup
    alone)."""

    def __init__(self) -> None:
        import jax.monitoring

        self.seen: dict[str, list] = {}
        self._mark: dict[str, tuple] = {}
        jax.monitoring.register_event_listener(self._count)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _count(self, event, **_kw):
        self.seen.setdefault(event, [0, 0.0])[0] += 1

    def _duration(self, event, seconds, **_kw):
        slot = self.seen.setdefault(event, [0, 0.0])
        slot[0] += 1
        slot[1] += seconds

    def since_mark(self) -> dict[str, list]:
        """Compile events since the last call, then mark again."""
        out = {}
        for name, (n, secs) in self.seen.items():
            n0, s0 = self._mark.get(name, (0, 0.0))
            if n > n0 and "compil" in name:
                out[name.removeprefix("/jax/")] = [n - n0, round(secs - s0, 2)]
        self._mark = {k: tuple(v) for k, v in self.seen.items()}
        return out

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_listener(self._count)
        jax.monitoring.unregister_event_duration_listener(self._duration)


def cache_report(runner) -> dict:
    cache_dir = runner.compile_cache_dir
    if cache_dir is None:
        return {"dir": None}
    entries = [e for e in os.scandir(cache_dir) if e.is_file()]
    return {
        "dir": cache_dir,
        "from_env": "JAX_COMPILATION_CACHE_DIR" in os.environ,
        "xla_entries": len(entries),
        "xla_bytes": sum(e.stat().st_size for e in entries),
    }


async def serve_and_query(
    model_path: str, *, cli_args: list[str], prompt_lens: list[int],
    max_tokens: int, expect_mosaic: bool = True, seed: int = SEED,
    startup_timeout_s: float = 900.0, request_timeout_s: float = 300.0,
) -> tuple[dict, object]:
    """Start ``dynamo-tpu run --in http --out tpu`` in this process, talk
    to it over HTTP, stop it with SIGTERM. ``prompt_lens``: the first two
    go one at a time (plain, then SSE); the rest are fired together,
    alternating SSE and plain, so prefill quanta and decode rows share
    dispatches. Prints the report as one JSON line, then raises on
    anything wrong in it; returns it with the stopped engine's runner. ``expect_mosaic=False`` is for the CPU
    rehearsal, where the kernel runs interpreted and there is no
    ``tpu_custom_call`` to find."""
    import httpx

    from dynamo_tpu import cli

    assert len(prompt_lens) >= 3
    args = cli.build_parser().parse_args([
        "run", "--in", "http", "--out", "tpu", "--model-path", model_path,
        "--http-host", "127.0.0.1", "--http-port", "0", *cli_args,
    ])
    assert not args.no_warmup, "the smoke serves with warmup on"
    loop = asyncio.get_running_loop()
    serving: asyncio.Future = loop.create_future()
    real_serve_http = cli._serve_http

    async def serve_http(a, stack, manager, engine=None):
        # The CLI prints the port; in-process, take it (and the engine,
        # to check what it compiled) from the call that made them.
        service = await real_serve_http(a, stack, manager, engine)
        serving.set_result((service, engine))
        return service

    events = JaxEvents()
    t0 = time.monotonic()
    with mock.patch.object(cli, "_serve_http", serve_http):
        run = asyncio.ensure_future(cli._run(args))
        done, _ = await asyncio.wait(
            {run, serving}, timeout=startup_timeout_s,
            return_when=asyncio.FIRST_COMPLETED,
        )
    if serving not in done:
        if run in done:
            run.result()  # raises what stopped the server
        run.cancel()
        raise TimeoutError(f"server not up in {startup_timeout_s:.0f} s")
    service, engine = serving.result()
    startup_s = time.monotonic() - t0
    runner = engine.runner
    base = f"http://127.0.0.1:{service.port}"
    prompts = _prompts(prompt_lens, seed)
    report: dict = {"model": model_path, "startup_s": round(startup_s, 1)}
    try:
        async with httpx.AsyncClient(timeout=request_timeout_s) as client:
            # /health answers 503 "warming" until the hot shape set is
            # compiled and 200 after. `dynamo-tpu run` opens the port
            # only once warmup is through, so through this entry a
            # client can see the second state alone; that a warmup came
            # before it is read from the engine's counters below.
            seen = []
            while True:
                r = await client.get(f"{base}/health")
                state = r.json()["engine"]["state"]
                if not seen or seen[-1] != [r.status_code, state]:
                    seen.append([r.status_code, state])
                if r.status_code == 200:
                    break
                assert state == "warming", r.text
                assert time.monotonic() - t0 < startup_timeout_s
                await asyncio.sleep(0.5)
            health = r.json()
            report["health"] = seen
            report["attention_path"] = health["engine"]["attention_path"]
            model = health["models"][0]
            cs = runner.compile_stats.snapshot()
            report["warmup_programs"] = cs["warmup_programs_total"]
            report["warmup_cache_hits"] = cs["warmup_cache_hits_total"]
            report["warmup_cache_misses"] = cs["warmup_cache_misses_total"]
            report["compile_cache"] = cache_report(runner)
            # Executables behind the runner's unified jit. CompileStats
            # counts first executions per (kind, budget); jit also keys
            # on input shardings, so a program can be compiled behind
            # its back (PR 22: one per rung under a mesh).
            programs_warm = runner.unified_executables()
            report["jax_events_startup"] = events.since_mark()

            t1 = time.monotonic()
            got = [
                await _chat(client, base, model, prompts[0], max_tokens, False),
                await _chat(client, base, model, prompts[1], max_tokens, True),
            ]
            got += await asyncio.gather(*(
                _chat(client, base, model, p, max_tokens, i % 2 == 0)
                for i, p in enumerate(prompts[2:])
            ))
            report["requests"] = len(got)
            report["tokens_returned"] = got
            report["traffic_s"] = round(time.monotonic() - t1, 2)
            report["jax_events_traffic"] = events.since_mark()

            metrics = (await client.get(f"{base}/metrics")).text
            report["mid_traffic_compiles_total"] = _metric(
                metrics, "mid_traffic_compiles_total"
            )
            report["unified_programs"] = [
                programs_warm, runner.unified_executables()
            ]
            steps = engine.debug_steps()
            report["dispatches"] = len(steps)
            report["mixed_dispatches"] = sum(
                1 for s in steps
                if s.get("decode_tokens") and s.get("prefill_tokens")
            )
            ready = engine.readiness()
            report["prefix_reused_blocks"] = ready[
                "kv_reused_device_blocks_total"
            ]
        report["unified_step_kernels"] = lowered_step_kernels(runner)
        report["memory"] = memory_report()
        emit("serve", **report)
        assert got == [max_tokens] * len(prompts), (
            f"asked {max_tokens} tokens of each request, got {got}"
        )
        assert report["health"][-1] == [200, "ready"]
        assert report["warmup_programs"] > 0, "ready without a warmup"
        assert report["mid_traffic_compiles_total"] == 0
        assert report["unified_programs"][0] == report["unified_programs"][1], (
            "traffic compiled unified programs that warmup had not: "
            f"{report['unified_programs']}"
        )
        assert report["mixed_dispatches"] > 0
        assert report["prefix_reused_blocks"] > 0
        assert runner.attn.use_pallas, "runner fell back to the XLA twin"
        assert report["attention_path"] == "pallas"
        if expect_mosaic:
            assert report["unified_step_kernels"] > 0, (
                "the lowered unified step holds no tpu_custom_call"
            )
    finally:
        events.close()
        # Stop the way a user does. cli._wait_for_signal installed the
        # loop's handler before the port was reported; without one the
        # default action would end this process.
        assert signal.getsignal(signal.SIGTERM) not in (
            signal.SIG_DFL, signal.SIG_IGN, None
        )
        os.kill(os.getpid(), signal.SIGTERM)
        await asyncio.wait_for(run, timeout=120)
    emit("shutdown", clean=True)
    return report, runner


# ---------------------------------------------------------------------------
# model logits, one implementation against a reference
# ---------------------------------------------------------------------------


def compare_model_logits(
    runner, sides: dict, *, prompt_lens: list[int], decode_steps: int = 3,
    seed: int = SEED,
) -> list[dict]:
    """Push one seeded ragged prefill batch and ``decode_steps`` greedy
    steps through ``llama.unified`` once per side and hold the second
    side to the first. ``sides`` = {name: (params, kv_caches, attn)},
    reference first; geometry (metadata rows, block size, budgets) is
    ``runner``'s. Logits must be finite and agree within ``LOGIT_RTOL``
    of the reference's largest logit; greedy tokens must agree, or
    differ only where the reference's logits of the two candidates tie
    within that tolerance (random weights give small logits and many
    near-ties). Decode steps feed the reference's tokens to both sides
    so they stay comparable. Caches are donated and keep their sharding,
    as in the runner's own program."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    cfg = runner.cfg
    model, bs = cfg.model, cfg.block_size
    geometry = dict(
        S=runner.unified_slots, max_blocks=cfg.max_blocks_per_seq,
        block_size=bs,
    )
    (ref, test) = sides
    rng = np.random.default_rng(seed)
    n = len(prompt_lens)

    def step_fn(kv, attn):
        def step(params, kv, token_ids, *meta):
            logits, kv = llama.unified(
                model, params, kv, token_ids, *meta, bs, attn=attn
            )
            return logits.astype(jnp.float32), kv

        kv_sh = jax.tree.map(lambda a: a.sharding, kv)
        return jax.jit(step, donate_argnums=(1,), out_shardings=(None, kv_sh))

    params = {name: p for name, (p, _, _) in sides.items()}
    kv = {name: k for name, (_, k, _) in sides.items()}
    steps = {name: step_fn(k, a) for name, (_, k, a) in sides.items()}

    def run_step(meta, token_ids, label):
        out = {}
        for name in sides:
            logits, kv[name] = steps[name](
                params[name], kv[name], jnp.asarray(token_ids),
                *(jnp.asarray(meta[m]) for m in UNIFIED_META),
            )
            out[name] = np.asarray(jax.block_until_ready(logits))[:n]
            bad = int((~np.isfinite(out[name])).sum())
            assert not bad, f"{label}: {name} logits hold {bad} non-finite values"
        want, got = out[ref], out[test]
        scale = float(np.abs(want).max())
        err = float(np.abs(got - want).max())
        tol = LOGIT_RTOL * scale
        assert err <= tol, (
            f"{label}: {test} logits off {ref} by {err:.4f} > {tol:.4f} "
            f"({LOGIT_RTOL} of the largest logit {scale:.3f})"
        )
        t_want, t_got = want.argmax(-1), got.argmax(-1)
        rows = np.arange(n)
        gap = want[rows, t_want] - want[rows, t_got]
        assert (gap <= tol).all(), (
            f"{label}: greedy tokens differ beyond a tie: {t_want} vs "
            f"{t_got}, {ref} logit gaps {gap}"
        )
        return {
            "step": label, "max_abs_logit_err": err, "largest_logit": scale,
            "tolerance": tol, f"tokens_{ref}": t_want.tolist(),
            f"tokens_{test}": t_got.tolist(),
            "tokens_equal": int((t_want == t_got).sum()),
        }

    T = cfg.unified_token_budget
    meta = ragged_batch(
        [(0, p) for p in prompt_lens], T=T, num_blocks=cfg.num_blocks,
        rng=rng, reserve=decode_steps, **geometry,
    )
    tables = meta["block_tables"]
    token_ids = np.zeros(T, np.int32)
    total = sum(prompt_lens)
    token_ids[:total] = rng.integers(1, model.vocab_size, total)
    results = [run_step(meta, token_ids, "prefill")]
    T_dec = 16  # the budget ladder's lowest rung
    for i in range(decode_steps):
        meta = ragged_batch(
            [(p + i, 1) for p in prompt_lens], T=T_dec,
            block_tables=tables, **geometry,
        )
        token_ids = np.zeros(T_dec, np.int32)
        token_ids[:n] = results[-1][f"tokens_{ref}"]
        results.append(run_step(meta, token_ids, f"decode{i + 1}"))
    return results


def pallas_vs_xla_model(runner, *, prompt_lens: list[int]) -> dict:
    """The served model itself, Pallas path against the XLA twin, on the
    (stopped) engine's own params: what the server samples from must be
    finite and must not depend on which attention implementation ran —
    the request phase counts tokens, it cannot see their values."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops.attention import AttnDispatch

    assert runner.attn.use_pallas and runner.attn.mesh is None
    sides = {
        "xla": (
            runner.params, jax.tree.map(jnp.zeros_like, runner.kv_caches),
            AttnDispatch(use_pallas=False),
        ),
        "pallas": (runner.params, runner.kv_caches, runner.attn),
    }
    return {
        "prompt_lens": prompt_lens,
        "steps": compare_model_logits(runner, sides, prompt_lens=prompt_lens),
    }


def tp_vs_one_chip(
    preset: str, *, mesh_shape: dict, num_blocks: int, max_num_seqs: int,
    max_model_len: int, token_budget: int, prompt_lens: list[int],
    check_memory: bool = True,
) -> dict:
    """(Four chips.) Build the runner twice in this process — sharded
    over the mesh, then on one device — from the same EngineConfig and
    seed, and hold the sharded model's logits to the one-chip model's
    (``compare_model_logits``), each with its own params, caches and
    attention dispatch. ``check_memory=False`` is for the CPU rehearsal,
    whose backend reports no memory statistics."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models.config import PRESETS

    def make_runner(mesh):
        ecfg = EngineConfig(
            model=PRESETS[preset](), num_blocks=num_blocks,
            max_num_seqs=max_num_seqs, max_model_len=max_model_len,
            unified_token_budget=token_budget,
            mesh_shape=mesh, compile_cache_dir=None,
        )
        return ModelRunner(ecfg, rng_seed=ecfg.seed)

    sharded = make_runner(mesh_shape)
    mem_sharded = memory_report()
    even = (
        assert_even(mem_sharded, f"{preset} {mesh_shape}")
        if check_memory else None
    )
    single = make_runner({})
    assert sharded.attn.use_pallas and single.attn.use_pallas
    assert sharded.attn.mesh is not None and single.attn.mesh is None
    sides = {
        "one_chip": (single.params, single.kv_caches, single.attn),
        "tp": (sharded.params, sharded.kv_caches, sharded.attn),
    }
    return {
        "model": preset, "mesh": mesh_shape, "prompt_lens": prompt_lens,
        "attention_path": sharded.attention_path,
        "bytes_even_max_over_min": even,
        "memory_sharded_runner": mem_sharded,
        "steps": compare_model_logits(
            sharded, sides, prompt_lens=prompt_lens
        ),
    }


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------

# Llama-3.2-1B / 3.1-8B attention widths at the CLI's default engine sizes
# (dynamo_tpu/cli.py: --num-blocks 2048 --max-num-seqs 32 --prefill-batch 4
# --max-model-len 2048 --unified-token-budget 256).
WIDTHS_1B = dict(
    num_heads=32, num_kv_heads=8, head_dim=64, block_size=16,
    num_blocks=2048, S=36, max_blocks=128, T=256,
)
# Decode spans at contexts 1, 17, 130, 2047 (one exactly a block + 1, one
# the full table); an idle row between spans; a prefill from 0 that starts
# at flat row 4 and runs 83 rows (neither end on the kernel's 16-row tile);
# a prefix hit (3 cached blocks, 37 new rows); a mid-prompt chunk that
# crosses fold boundaries; trailing idle rows and budget padding.
MIXED_SPANS = [
    (0, 1), (16, 1), (129, 1), (2046, 1), (0, 0), (0, 83), (48, 37),
    (300, 100),
]
# Bytes of user text per request (the chat template adds a few dozen
# tokens): two in sequence — the second extends the first, a prefix hit —
# then eight together, from a one-block prompt to one that needs four
# 256-token dispatches, so decode rows ride beside prefill quanta.
# What one chip of a tp=4 mesh runs for the same model: 8 of the 32 query
# heads against 2 of the 8 kv heads. Not a formality — a packed page
# buffer re-viewed per head was exact at 8 kv heads and wrong at 2 (PR 22).
WIDTHS_TP4_SHARD = dict(WIDTHS_1B, num_heads=8, num_kv_heads=2)
SERVE_PROMPT_LENS = [40, 200, 5, 33, 90, 150, 260, 420, 700, 900]
SERVE_MAX_TOKENS = 24
# Prompt tokens of the model-logit comparisons: one ragged prefill batch
# (243 of 256 rows), then three greedy decode steps.
MODEL_PROMPT_LENS = [7, 33, 83, 120]


def run_one_chip() -> None:
    for widths in (WIDTHS_1B, WIDTHS_TP4_SHARD):
        emit(
            "kernel_vs_twin", heads=[widths["num_heads"], widths["num_kv_heads"]],
            **kernel_vs_twin(spans=MIXED_SPANS, **widths),
        )
    _, runner = asyncio.run(serve_and_query(
        "preset:llama3.2-1b", cli_args=[],
        prompt_lens=SERVE_PROMPT_LENS, max_tokens=SERVE_MAX_TOKENS,
    ))
    emit("pallas_vs_xla_model", **pallas_vs_xla_model(
        runner, prompt_lens=MODEL_PROMPT_LENS
    ))


def run_multichip() -> None:
    emit("tp_vs_one_chip", **tp_vs_one_chip(
        "llama3.2-1b", mesh_shape={"tp": 4}, num_blocks=2048,
        max_num_seqs=32, max_model_len=2048, token_budget=256,
        prompt_lens=MODEL_PROMPT_LENS,
    ))
    # The configuration that NEEDS four chips: ~16 GB of bf16 weights.
    report, _ = asyncio.run(serve_and_query(
        "preset:llama3.1-8b", cli_args=["--mesh", "tp=4"],
        prompt_lens=SERVE_PROMPT_LENS, max_tokens=SERVE_MAX_TOKENS,
        startup_timeout_s=1500.0,
    ))
    emit("bytes_even", max_over_min=round(
        assert_even(report["memory"], "llama3.1-8b tp=4"), 4
    ))


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--multichip", action="store_true",
        help="run the four-chip tensor-parallel path (and only it)",
    )
    opts = ap.parse_args(argv)
    arm_deadline(MULTICHIP_DEADLINE_S if opts.multichip else DEADLINE_S)
    sys.path.insert(0, REPO)
    dev = require_tpu(4 if opts.multichip else 1)
    emit("device", **dev)
    if opts.multichip:
        run_multichip()
    else:
        run_one_chip()
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
