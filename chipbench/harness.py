"""One run of one cell: ``python3 -m chipbench --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

One run is one process. It registers the cell's configuration (weights
made on the device from the seed), starts the served path in THIS process
the way a user starts it (``dynamo-tpu run --in http --out tpu``:
``cli.build_parser`` + ``cli._run``, the port taken from ``_serve_http``,
``/health`` 200 after warmup — the pattern of ``chip_smoke.py``), drives it
over HTTP with streaming chat completions (``loadgen``), measures for
``--seconds`` after an unmeasured ramp, drains, stops the server with
SIGTERM, checks the outputs outside the window (``check``) and prints the
result line. Without the chips the cell asks for it exits 3 and prints no
result; ``--allow-cpu`` is the rehearsal's switch and reports
``device.platform = cpu``.

From the program it takes the system under test and its counters: the
flight recorder (``engine.debug_steps()``, polled — its ring holds 512
steps), ``engine.readiness()``, the tracer's finished requests, XLA's
compile events (``jax.monitoring``) and, with ``--trace 1``, the
profiler's device trace of the window's last few seconds.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from unittest import mock

from chipbench import manifest, registry, traffic
from chipbench.observe import Observations

T_PROCESS = time.monotonic()
TRACE_SECONDS = 3.0
POLL_S = 2.0
#: seconds from the plan being made to the first client's start: room for
#: the child process to come up
LEAD_S = 2.0


def say(what: str, **fields) -> None:
    print(json.dumps({"chipbench": what, **fields}), flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(prog="python3 -m chipbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal: run on whatever backend JAX has")
    ap.add_argument("--keep", default=None, metavar="DIR",
                    help="also write there what the run observed: the "
                    "requests' records, the flight recorder's steps and "
                    "the simplified trace (gzipped JSON)")
    return ap.parse_args(argv)


def require_devices(chips: int, allow_cpu: bool) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": chips}
    if allow_cpu:
        return dev
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(
            f"chipbench: the cell needs {chips} TPU chip(s); JAX reports "
            f"{len(devs)} x {devs[0].platform}", file=sys.stderr,
        )
        raise SystemExit(3)
    return dev


class CompileEvents:
    """XLA's own account of compiling: every ``jax.monitoring`` duration
    event whose name has ``compil`` in it, stamped on arrival."""

    def __init__(self) -> None:
        import jax.monitoring

        self.events: list[tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_kw):
        if "compil" in event:
            self.events.append((time.monotonic(), event))

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)


class DispatchLog:
    """Around the call into the runner: each dispatch's lanes as
    ``(prefix_len, new_tokens)``, for the kernel's operations and bytes.
    Recorded only while ``on`` (the traced part of the window)."""

    def __init__(self, runner) -> None:
        self.seen: list[tuple[float, list]] = []
        self.on = False
        real = runner.unified_step

        def unified_step(lanes, *a, **kw):
            if self.on:
                self.seen.append((
                    time.monotonic(),
                    [(int(p), len(t)) for t, _, p, _ in lanes],
                ))
            return real(lanes, *a, **kw)

        runner.unified_step = unified_step


async def start_server(cli_args: list[str], weights_seed: int, timeout_s: float):
    """``dynamo-tpu run --in http --out tpu ...`` in this process; returns
    ``(run task, service, engine)`` once the port is open."""
    from dynamo_tpu import cli

    args = cli.build_parser().parse_args([
        "run", "--in", "http", "--out", "tpu", "--http-host", "127.0.0.1",
        "--http-port", "0", *cli_args,
    ])
    loop = asyncio.get_running_loop()
    serving: asyncio.Future = loop.create_future()
    real_serve_http = cli._serve_http
    real_cfg = cli._tpu_local_and_cfg

    async def serve_http(a, stack, manager, engine=None):
        service = await real_serve_http(a, stack, manager, engine)
        serving.set_result((service, engine))
        return service

    def local_and_cfg(a):
        # The CLI has no option for the seed the weights are drawn from;
        # the benchmark's weights come from --seed.
        local, ecfg = real_cfg(a)
        ecfg.seed = weights_seed
        return local, ecfg

    with mock.patch.object(cli, "_serve_http", serve_http), \
            mock.patch.object(cli, "_tpu_local_and_cfg", local_and_cfg):
        run = asyncio.ensure_future(cli._run(args))
        done, _ = await asyncio.wait(
            {run, serving}, timeout=timeout_s,
            return_when=asyncio.FIRST_COMPLETED,
        )
    if serving not in done:
        if run in done:
            run.result()
        run.cancel()
        raise TimeoutError(f"server not up in {timeout_s:.0f} s")
    service, engine = serving.result()
    return run, service, engine


async def wait_healthy(base: str, deadline: float) -> dict:
    import aiohttp

    async with aiohttp.ClientSession() as s:
        while True:
            async with s.get(base + "/health") as r:
                body = await r.json()
                if r.status == 200:
                    return body
            if time.monotonic() > deadline:
                raise TimeoutError(f"/health not 200: {body}")
            await asyncio.sleep(0.25)


async def run_loadgen(plan: dict, workdir: str) -> dict:
    """The load generator as a JAX-free child process (this one holds the
    chip and the server's loop)."""
    plan_path = os.path.join(workdir, "plan.json")
    out_path = os.path.join(workdir, "out.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "chipbench.loadgen", plan_path, out_path],
        cwd=manifest.CHECKOUT, env=env,
    )
    try:
        rc = await asyncio.to_thread(proc.wait)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        raise RuntimeError(f"the load generator exited {rc}")
    with open(out_path) as f:
        return json.load(f)


async def observe_window(engine, window, trace_dir, dispatch_log):
    """Poll the flight recorder and ``readiness()`` through the window
    and, with a ``trace_dir``, trace its last ``TRACE_SECONDS``: stopping
    the profiler stalls the host for a while, and at the window's end that
    falls into the drain and not onto the window's own requests."""
    import jax

    w0, w1 = window
    flight: dict[int, dict] = {}
    readiness: list[dict] = []
    edges: list[dict] = []
    loop_lag: list[float] = []
    trace_host_window = None

    def poll():
        for rec in engine.debug_steps():
            flight[rec["seq"]] = rec
        readiness.append(dict(engine.readiness(), t=time.monotonic()))

    async def tracer():
        nonlocal trace_host_window
        await asyncio.sleep(max(0.0, w1 - TRACE_SECONDS - time.monotonic()))
        # Host TraceMe events name the idle gaps; the Python tracer would
        # only slow the server's loop and swell the file.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        await asyncio.to_thread(
            lambda: jax.profiler.start_trace(
                trace_dir, profiler_options=options
            )
        )
        t0 = time.monotonic()
        dispatch_log.on = True
        await asyncio.sleep(TRACE_SECONDS)
        dispatch_log.on = False
        t1 = time.monotonic()
        await asyncio.to_thread(jax.profiler.stop_trace)
        trace_host_window = (t0, t1)

    async def lag_probe():
        while time.monotonic() < w1:
            t = time.monotonic()
            await asyncio.sleep(0.01)
            if t >= w0:
                loop_lag.append(time.monotonic() - t - 0.01)

    task = asyncio.ensure_future(tracer()) if trace_dir else None
    probe = asyncio.ensure_future(lag_probe())
    await asyncio.sleep(max(0.0, w0 - time.monotonic()))
    poll()
    edges.append(readiness[-1])
    while time.monotonic() + POLL_S < w1:
        await asyncio.sleep(POLL_S)
        poll()
    await asyncio.sleep(max(0.0, w1 - time.monotonic()))
    poll()
    edges.append(readiness[-1])
    await probe
    if task is not None:
        await task
    return (list(flight.values()), readiness, tuple(edges),
            trace_host_window, sorted(loop_lag) or [0.0])


def memory_peak(chips: int):
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()[:chips]
    ]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def read_metrics(names: list[str], obs: Observations) -> dict:
    out = {}
    for name in names:
        m = manifest.metric(name)
        value = registry.load("readers", m["reader"]).read(
            obs, **m.get("params", {})
        )
        if value is not None:
            out[name] = {"value": value, "unit": m["unit"]}
    return out


async def run(opts) -> dict:
    cell = manifest.workload(opts.workload)
    data = manifest.config(cell["config"])
    spec = traffic.load(cell["traffic"])
    chips = int(cell["chips"])
    if data["chips"] != chips:
        raise SystemExit(f"{cell['name']}: the cell and its configuration "
                         "disagree on the chips")
    device = require_devices(chips, opts.allow_cpu)
    on_tpu = device["platform"] == "tpu"
    say("start", workload=cell["name"], seed=opts.seed, device=device)

    from chipbench import check, modelcfg

    model = modelcfg.register(data)
    # PRNGKey takes 32 bits; seeds reach a little over 2**31.
    weights_seed = int(opts.seed) % (2**31 - 1)
    events = CompileEvents()
    run_task, service, engine = await start_server(
        ["--model-path", f"preset:{data['name']}", *data["serve_args"]],
        weights_seed, timeout_s=1100.0,
    )
    runner = engine.runner
    base = f"http://127.0.0.1:{service.port}"
    failures: list[str] = []
    workdir = tempfile.mkdtemp(prefix="chipbench_")
    trace_dir = os.path.join(workdir, "trace") if opts.trace else None
    try:
        health = await wait_healthy(base, time.monotonic() + 600)
        t_ready = time.monotonic()
        dispatch_log = DispatchLog(runner)
        t_begin = t_ready + LEAD_S
        window = (t_begin + spec["ramp_s"],
                  t_begin + spec["ramp_s"] + opts.seconds)
        plan = {
            "base": base, "model": health["models"][0], "traffic": spec,
            "seed": opts.seed, "t_begin": t_begin, "window": list(window),
            "request_timeout_s": 240,
        }
        load, seen = await asyncio.gather(
            run_loadgen(plan, workdir),
            observe_window(engine, window, trace_dir, dispatch_log),
        )
        flight, readiness, edges, trace_host_window, loop_lag = seen
        unix_minus_mono = time.time() - time.monotonic()
        traces = engine_traces()
        peak = memory_peak(chips)
        attention_path = engine.readiness().get("attention_path")
    finally:
        events.close()
        os.kill(os.getpid(), signal.SIGTERM)
        await asyncio.wait_for(run_task, timeout=180)
    say("served", ready_s=round(t_ready - T_PROCESS, 3),
        requests=len(load["records"]), drained_s=round(
            load["finished"] - window[1], 3))

    late = sorted(load["generator_late_s"]) or [0.0]
    lag = sorted(load["loop_lag_s"]) or [0.0]
    say("generator", late_ms_p50=1e3 * late[len(late) // 2],
        late_ms_max=1e3 * late[-1],
        loop_lag_ms_p99=1e3 * lag[int(0.99 * (len(lag) - 1))],
        loop_lag_ms_max=1e3 * lag[-1])
    # This loop is the server's: how late IT wakes says whether the
    # frontend keeps up with the engine's tokens.
    say("server_loop", lag_ms_p50=1e3 * loop_lag[len(loop_lag) // 2],
        lag_ms_p99=1e3 * loop_lag[int(0.99 * (len(loop_lag) - 1))],
        lag_ms_max=1e3 * loop_lag[-1])

    w0 = window[0] + unix_minus_mono
    obs = Observations(
        window=window, chips=chips, setup_s=window[0] - T_PROCESS,
        records=load["records"], unix_minus_mono=unix_minus_mono,
        flight=[r for r in flight
                if w0 <= r["t_unix"] < w0 + opts.seconds],
        readiness=[r for r in readiness
                   if window[0] <= r["t"] <= window[1]],
        readiness_edges=edges, traces=traces,
        compile_events=events.events,
        dispatches=dispatch_log.seen,
        model=scalar_fields(model),
        engine=engine_facts(runner),
        device_kind=device["kind"],
    )

    keep(opts, "observed", {
        "window": window, "records": obs.records, "flight": obs.flight,
        "readiness": obs.readiness, "dispatches": obs.dispatches,
    })

    # (b) every request returned exactly the tokens it asked for
    failed = 0
    for r in load["records"]:
        n = len(r.get("token_times") or [])
        if (r.get("status") != 200 or "error" in r or "done" not in r
                or n != r["output_tokens"]
                or r.get("usage_completion") != r["output_tokens"]):
            failed += 1
            if len(failures) < 5:
                failures.append(
                    f"request {r['index']}: status {r.get('status')} "
                    f"{n}/{r['output_tokens']} tokens {r.get('error', '')}"
                )
    # (c) nothing compiled in the window, and the Pallas path served
    compiles = registry.load("readers", "compile_events").read(obs)
    say("compiles_in_window", value=compiles, limit=0)
    if compiles:
        failures.append(f"{compiles:.0f} compile events inside the window")
    say("attention_path", value=attention_path, wanted="pallas")
    if on_tpu and attention_path != "pallas":
        failures.append(f"attention path {attention_path}, not pallas")

    device_extra = {}
    breakdown = None
    if opts.trace:
        from chipbench import xprof

        simple = xprof.load(trace_dir)
        keep(opts, "trace", simple)
        for plane in xprof.describe(simple):
            say("trace_plane", **plane)
        if on_tpu:
            obs.trace = dict(
                xprof.reduce(simple, chips), host_window=trace_host_window
            )
            device_extra = {"busy_s": obs.trace["busy_s"],
                            "window_s": obs.trace["window_s"]}
            breakdown = {"device_ops": obs.trace["device_ops"],
                         "idle_gaps": obs.trace["idle_gaps"]}
        names = cell["per_layer"]
    else:
        names = cell["end_to_end"]
    metrics = read_metrics(names, obs)

    # (a) the served runner against the plain reference
    limits = data.get("check", {})
    if "limit" not in limits:
        raise SystemExit(f"{data['name']}: its file states no check limit")
    verdict = check.compare(
        data, opts.seed, runner, weights_seed=weights_seed,
        **check.compare_kwargs(data),
    )
    say("runner_vs_reference", **verdict, limits={
        "token_mismatch_limit": 0,
        **{k: limits[k] for k in check.LIMIT_KEYS if k in limits},
    })
    failures.extend(check.judge(verdict, limits))
    say("requests", attempted=len(load["records"]), failed=failed, limit=0)
    # Each number compared beside its limit, as the last lines on standard
    # error and as the result line's last key: what is kept of a run that
    # is not correct.
    compared = {
        **check.held(verdict, limits),
        "requests_failed": {"value": failed, "limit": 0},
        "compiles_in_window": {"value": compiles, "limit": 0},
    }
    for f in failures:
        say("not_correct", why=f)
        print(f"chipbench not_correct: {f}", file=sys.stderr)
    for name, c in compared.items():
        print(f"chipbench compared {name} {c['value']:.6g} limit "
              f"{c['limit']}", file=sys.stderr)
    sys.stderr.flush()

    result = {
        "correct": not failures and failed == 0,
        "attempted": len(load["records"]),
        "failed": failed,
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": peak, **device_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    shutil.rmtree(workdir, ignore_errors=True)
    return result


def keep(opts, name: str, doc) -> None:
    if opts.keep:
        import gzip

        os.makedirs(opts.keep, exist_ok=True)
        path = os.path.join(opts.keep, f"{name}.json.gz")
        with gzip.open(path, "wt") as f:
            json.dump(doc, f)


def engine_traces() -> list[dict]:
    from dynamo_tpu.utils.tracing import tracer

    return tracer().snapshot(n=1 << 20)["recent"]


def scalar_fields(model) -> dict:
    """Every scalar field of the served ``ModelConfig``, under the
    program's own names: what a kernel's cost function may read."""
    return {
        f.name: value for f in dataclasses.fields(model)
        if isinstance(value := getattr(model, f.name),
                      (bool, int, float, str))
    }


def engine_facts(runner) -> dict:
    import jax
    import jax.numpy as jnp

    cfg = runner.cfg
    # The first leaf, not [0][0]: a layer's cache need not be a (k, v) pair.
    leaves = jax.tree.leaves(runner.kv_caches)
    return {
        "block_size": cfg.block_size,
        "num_blocks": cfg.num_blocks,
        "token_budget": cfg.unified_token_budget,
        "tp": int((cfg.mesh_shape or {}).get("tp", 1)),
        "cache_head_dim": int(leaves[0].shape[-1]),
        "cache_arrays_per_layer": len(leaves) // cfg.model.num_layers,
        "dtype_bytes": jnp.dtype(cfg.dtype).itemsize,
        "kv_dtype_bytes": leaves[0].dtype.itemsize,
    }


def main(argv=None) -> None:
    opts = parse(argv)
    os.environ.pop("BENCH_RUN", None)
    result = asyncio.run(run(opts))
    print(json.dumps(result), flush=True)
