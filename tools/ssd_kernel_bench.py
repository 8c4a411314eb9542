"""The state-space kernels alone (``ops/pallas/ssd.py``) at the widths of
``nemotron-3-super-ep4-l11`` (128 heads of 64 x 128 in float32 in 8 groups,
4 MiB a lane a layer): the kernels against their XLA twin, microseconds a
lane beside the bytes bound, microseconds a prefill row beside the masked
form's FLOP bound, the kernel | its twin.

- ``check``: kernels against the XLA twin (the recurrence row by row) on
  one dispatch of ``--lanes``' last decode lanes beside ``--quanta`` (the
  first quantum fresh, the others continued): the worst relative error of
  the outputs and of the states;
- ``lanes``: ``ssd_recurrent`` over 8..128 decode lanes: microseconds a
  call and a lane, the bytes bound (``chipbench/costs/ssd_recurrent.py``)
  and the share of it reached; ``--twin`` times the XLA twin beside it;
- ``chunk``: ``ssd_chunk`` over one prefill quantum of 16..256 rows:
  microseconds a row beside the FLOP bound (``chipbench/costs/
  ssd_chunk.py``, at the bf16 peak);
- ``mixed``: one dispatch as the cell fills it (lanes beside quanta).

    chiprun -- python -m tools.ssd_kernel_bench --sweep check,lanes,chunk,mixed --twin

A line of JSON a measurement on stdout, all of them in
``chiprun_out/ssd_kernel_bench.jsonl``. Times are host clock around
``--layers`` chained calls inside one jitted scan, ended by
``block_until_ready``: device time, never a CPU number (without a TPU the
script stops, unless ``--allow-cpu`` rehearses it at a tiny size).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.costs import ssd_chunk, ssd_recurrent
from chipbench.peaks import peaks_for
from dynamo_tpu.ops import ssd

OUT = os.path.join("chiprun_out", "ssd_kernel_bench.jsonl")


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(line) + "\n")


def build(spans, T: int, slots: int, dims, rng):
    """Operands of ``ssd_ragged`` for ``spans`` [(prefix, rows)]: span
    ``s`` owns slot ``s + 1`` of a table of random finite values."""
    H, P, G, N = dims
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    S = max(len(spans), 4)
    x, B, C = f(T, H, P), f(T, G, N), f(T, G, N)
    dt = (0.05 * np.log1p(np.exp(f(T, H)))).astype(np.float32)
    la = (-np.exp(rng.uniform(0, 2.7, (T, H))) * dt).astype(np.float32)
    token_seq = np.zeros(T, np.int32)
    token_pos = -np.ones(T, np.int32)
    q_start, q_len, row_start, slot = (np.zeros(S, np.int32) for _ in range(4))
    r = 0
    for s, (prefix, n) in enumerate(spans):
        q_start[s], q_len[s], row_start[s], slot[s] = prefix, n, r, s + 1
        token_seq[r : r + n] = s
        token_pos[r : r + n] = np.arange(prefix, prefix + n)
        r += n
    assert r <= T
    row_start[len(spans):] = r
    state = 0.1 * f(slots, H, P, N)
    return tuple(jnp.asarray(a) for a in (x, dt, la, B, C)), jnp.asarray(
        state), tuple(jnp.asarray(a) for a in (
            token_seq, token_pos, q_start, q_len, row_start, slot))


def time_call(rows, state, meta, use_pallas: bool, layers: int, reps: int):
    """Median seconds of one call: ``layers`` chained calls in one jitted
    scan over the same rows, the state carried."""
    def chain(rows, state, meta):
        def body(state, _):
            y, state = ssd.ssd_ragged(
                *rows, state, *meta, use_pallas=use_pallas)
            return state, y[0, 0, 0]
        return jax.lax.scan(body, state, None, length=layers)

    fn = jax.jit(chain, donate_argnums=(1,))
    state, y = fn(rows, state, meta)
    jax.block_until_ready(y)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        state, y = fn(rows, state, meta)
        jax.block_until_ready(y)
        times.append((time.perf_counter() - t0) / layers)
    return statistics.median(times), state


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def bound_us(spans, model: dict, peaks: dict) -> dict:
    out = {}
    for name, mod in (("lanes", ssd_recurrent), ("chunk", ssd_chunk)):
        flops, nbytes = mod.cost(spans, model={**model, "num_layers": 1,
                                               "layer_pattern": "M"}, engine={})
        out[name] = 1e6 * max(flops / peaks["flops_bf16"],
                              nbytes / peaks["hbm_bytes_per_s"])
    return out


def ints(text: str) -> list[int]:
    return [int(t) for t in text.split(",") if t]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sweep", default="check,lanes,chunk,mixed")
    ap.add_argument("--lanes", type=ints, default=[8, 32, 128])
    ap.add_argument("--rows", type=ints, default=[16, 64, 128, 256])
    ap.add_argument("--quanta", type=ints, default=[128, 70])
    ap.add_argument("--layers", type=int, default=5)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dims", type=ints, default=[128, 64, 8, 128],
                    help="heads, head size, groups, state size")
    ap.add_argument("--twin", action="store_true",
                    help="time the XLA twin beside the kernels")
    ap.add_argument("--block-bytes", type=ints, default=[],
                    help="sweep ssd_recurrent's RECURRENT_BLOCK_BYTES (KiB)")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse at a tiny size (its numbers are no times)")
    args = ap.parse_args(argv)
    tpu = jax.default_backend() == "tpu"
    if not tpu and not args.allow_cpu:
        print("no TPU: a time from this machine is no device time",
              file=sys.stderr)
        return 2
    dims = tuple(args.dims)
    H, P, G, N = dims
    model = dict(mamba_num_heads=H, mamba_head_dim=P, mamba_n_groups=G,
                 ssm_state_size=N)
    kind = jax.devices()[0].device_kind
    peaks = peaks_for(kind) if tpu else peaks_for("TPU v5 lite")
    rng = np.random.default_rng(args.seed)
    dev = {"platform": jax.default_backend(), "kind": kind}
    timed = functools.partial(time_call, layers=args.layers, reps=args.reps)
    sweeps = args.sweep.split(",")
    mixed = [(40 + i, 1) for i in range(args.lanes[-1])] + [
        (0 if i == 0 else 300, n) for i, n in enumerate(args.quanta)]
    T = -(-(sum(n for _, n in mixed)) // 16) * 16
    if "check" in sweeps:
        rows, state, meta = build(mixed, T, len(mixed) + 1, dims, rng)
        y0, s0 = jax.jit(functools.partial(ssd.ssd_ragged, use_pallas=False))(
            *rows, state, *meta)
        y1, s1 = jax.jit(functools.partial(ssd.ssd_ragged, use_pallas=True))(
            *rows, state, *meta)
        emit({"sweep": "check", "device": dev, "spans": len(mixed),
              "worst_rel_y": rel(y1, y0), "worst_rel_state": rel(s1[1:], s0[1:])})
    from dynamo_tpu.ops.pallas import ssd as kernels

    served_block = kernels.RECURRENT_BLOCK_BYTES
    blocks = [k * 1024 for k in args.block_bytes] or [served_block]
    for n, block in [(n, b) for n in args.lanes for b in blocks] if (
            "lanes" in sweeps) else ():
        kernels.RECURRENT_BLOCK_BYTES = block
        spans = [(40 + i, 1) for i in range(n)]
        rows, state, meta = build(spans, max(16, n), n + 1, dims, rng)
        secs, state = timed(rows, state, meta, True)
        line = {"sweep": "lanes", "device": dev, "lanes": n,
                "block_bytes": block, "call_us": 1e6 * secs, "lane_us": 1e6 * secs / n,
                "bound_us": bound_us(spans, model, peaks)["lanes"]}
        line["roofline_pct"] = 100 * line["bound_us"] / line["call_us"]
        if args.twin:
            line["twin_call_us"] = 1e6 * timed(rows, state, meta, False)[0]
        emit(line)
    kernels.RECURRENT_BLOCK_BYTES = served_block
    for n in args.rows if "chunk" in sweeps else ():
        for prefix in (0, 512):
            spans = [(prefix, n)]
            rows, state, meta = build(spans, -(-n // 16) * 16, 2, dims, rng)
            secs, state = timed(rows, state, meta, True)
            line = {"sweep": "chunk", "device": dev, "rows": n,
                    "prefix": prefix, "call_us": 1e6 * secs,
                    "row_us": 1e6 * secs / n,
                    "bound_us": bound_us(spans, model, peaks)["chunk"]}
            line["roofline_pct"] = 100 * line["bound_us"] / line["call_us"]
            if args.twin and prefix == 0 and n <= 64:
                line["twin_call_us"] = 1e6 * timed(
                    rows, state, meta, False)[0]
            emit(line)
    if "mixed" in sweeps:
        rows, state, meta = build(mixed, T, len(mixed) + 1, dims, rng)
        secs, state = timed(rows, state, meta, True)
        b = bound_us(mixed, model, peaks)
        emit({"sweep": "mixed", "device": dev, "lanes": args.lanes[-1],
              "quanta": args.quanta, "call_us": 1e6 * secs,
              "lanes_bound_us": b["lanes"], "chunk_bound_us": b["chunk"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
