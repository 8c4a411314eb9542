"""The delta-rule kernels alone (``ops/pallas/kda.py``) at the widths of
``ling-3.0-flash-ep4-l8`` (32 heads of 128 x 128 in float32, 2 MiB a lane a
layer): microseconds a lane beside the bytes bound, the kernel | its XLA
twin, so a later optimisation starts from a split and not from a guess.

- ``check``: kernels against the XLA twin on one dispatch of decode lanes
  and a prefill quantum (a kernel can pass interpret mode and be wrong on
  the chip);
- ``lanes``: ``kda_recurrent`` over 8..128 decode lanes: microseconds a
  call and a lane, the bytes bound (``chipbench/costs/kda_recurrent.py``)
  and the share of it reached; ``--twin`` times the XLA twin beside it;
- ``chunk``: ``kda_chunk`` over one prefill quantum of 16..256 rows:
  microseconds a row;
- ``mixed``: one dispatch as the cell fills it (``--lanes``' last decode
  lanes beside two quanta of ``--rows``' second size): the pair of kernels
  the program runs | ONE ``kda_chunk`` call over every owned row, a decode
  lane a span of one row (``one_kernel`` below, not on the served path), so
  the split into two kernels is a measured choice.

    chiprun -- python -m tools.kda_kernel_bench --sweep check,lanes,chunk

A line of JSON a measurement on stdout, all of them in
``chiprun_out/kda_kernel_bench.jsonl``. Times are host clock around
``--layers`` chained calls inside one jitted scan, ended by
``block_until_ready``: device time, never a CPU number (without a TPU the
script stops, unless ``--allow-cpu`` rehearses it at a tiny size).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.costs.kda_recurrent import cost
from chipbench.peaks import peaks_for
from dynamo_tpu.ops import linear_attention as la

H, D = 32, 128


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def emit(line: dict) -> None:
    text = json.dumps(line)
    print(text, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_kernel_bench.jsonl", "a") as f:
        f.write(text + "\n")


def build(spans, T: int, slots: int, heads: int, d: int, rng):
    """One dispatch's operands: ``spans`` as (q_start, rows), span ``s`` in
    slot ``s + 1`` of a table of ``slots``."""
    S = len(spans) + 1
    token_seq = np.zeros(T, np.int32)
    token_pos = np.full(T, -1, np.int32)
    q_start, q_len, row_start, slot = (np.zeros(S, np.int32) for _ in range(4))
    at = 0
    for s, (start, n) in enumerate(spans):
        token_seq[at : at + n] = s
        token_pos[at : at + n] = start + np.arange(n)
        q_start[s], q_len[s], row_start[s], slot[s] = start, n, at, s + 1
        at += n
    assert at <= T and len(spans) < slots
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    kq, kk, kv, kg, kb, ks = jax.random.split(key, 6)
    shape = (T, heads, d)
    k = jax.random.normal(kk, shape, jnp.float32)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    rows = (
        jax.random.normal(kq, shape, jnp.float32) * d**-0.5, k,
        jax.random.normal(kv, shape, jnp.float32),
        -5.0 * jax.random.uniform(kg, shape, jnp.float32, 0.0, 0.2),
        jax.random.uniform(kb, (T, heads), jnp.float32, 0.1, 0.9),
    )
    state = jax.random.normal(ks, (slots, heads, d, d), jnp.float32)
    meta = tuple(map(jnp.asarray, (
        token_seq, token_pos, q_start, q_len, row_start, slot)))
    return rows, state, meta


def one_kernel(q, k, v, g, beta, state, token_seq, token_pos, q_start,
               q_len, row_start, state_slot):
    """``la.kda_ragged``'s contract through ONE ``kda_chunk`` call: every
    owned row in flat order, a decode lane a span of one row."""
    from dynamo_tpu.ops.pallas.kda import ACTIVE, FIRST, FRESH, kda_rows

    del row_start
    j, owned = la.span_rows(token_seq, token_pos, q_start, q_len)
    b = beta[:, :, None]
    x = jnp.concatenate([jnp.exp(g), k, b * k, q], axis=1)
    first = FIRST + FRESH * (q_start == 0)[token_seq]
    o, state = kda_rows(
        x, b * v, state, jnp.where(owned, state_slot[token_seq], 0),
        jnp.where(owned, ACTIVE + jnp.where(j == 0, first, 0), 0),
        chunked=True,
    )
    return jnp.where(owned[:, None, None], o, 0.0), state


def time_call(rows, state, meta, use_pallas, layers: int, reps: int):
    """Median microseconds of ONE layer's call, from ``layers`` chained
    calls that hand the state on. ``use_pallas``: the served pair of
    kernels, the XLA twin, or ``one_kernel``."""
    step = one_kernel if use_pallas == "one" else partial(
        la.kda_ragged, use_pallas=use_pallas)

    @jax.jit
    def chain(rows, state, meta):
        def body(carry, _):
            state, q = carry
            o, state = step(q, *rows[1:], state, *meta)
            return (state, rows[0] + 1e-3 * o), None

        (state, q), _ = jax.lax.scan(
            body, (state, rows[0]), None, length=layers)
        return state, q

    jax.block_until_ready(chain(rows, state, meta))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(rows, state, meta))
        times.append(time.perf_counter() - t0)
    return 1e6 * statistics.median(times) / layers


def bytes_bound_us(spans, heads: int, d: int) -> float:
    _flops, nbytes = cost(
        spans, model=dict(num_heads=heads, head_dim=d, num_layers=1,
                          layer_group_size=2), engine={})
    kind = jax.devices()[0].device_kind if on_tpu() else "TPU v5 lite"
    return 1e6 * nbytes / peaks_for(kind)["hbm_bytes_per_s"]


def sweep_check(args, rng, heads, d):
    lanes = args.lanes[-1]
    spans = [(int(c), 1) for c in rng.integers(1, 900, lanes)]
    spans += [(0, args.rows[0]), (128, args.rows[0])]
    T = sum(n for _, n in spans) + 5
    rows, state, meta = build(spans, T, lanes + 4, heads, d, rng)
    want_o, want_s = la.kda_ragged(*rows, state, *meta, use_pallas=False)
    got_o, got_s = la.kda_ragged(*rows, state, *meta, use_pallas=True)
    err_o = float(jnp.abs(got_o - want_o).max() / jnp.abs(want_o).max())
    err_s = float(jnp.abs(got_s[1:] - want_s[1:]).max()
                  / jnp.abs(want_s).max())
    emit(dict(sweep="check", spans=len(spans), out_rel=round(err_o, 7),
              state_rel=round(err_s, 7),
              ok=bool(np.isfinite(np.asarray(got_o)).all()
                      and err_o < 1e-4 and err_s < 1e-4)))


def sweep_lanes(args, rng, heads, d):
    for lanes in args.lanes:
        spans = [(int(c), 1) for c in rng.integers(1, 900, lanes)]
        rows, state, meta = build(spans, lanes, args.lanes[-1] + 1, heads, d, rng)
        bound = bytes_bound_us(spans, heads, d)
        line = dict(sweep="lanes", lanes=lanes, bytes_bound_us=round(bound, 2))
        for name, pallas in (("kernel", True),) + (
                (("twin", False),) if args.twin else ()):
            us = time_call(rows, state, meta, pallas, args.layers, args.reps)
            line[f"{name}_call_us"] = round(us, 2)
            line[f"{name}_us_per_lane"] = round(us / lanes, 3)
            line[f"{name}_roofline_pct"] = round(100 * bound / us, 2)
        emit(line)


def sweep_chunk(args, rng, heads, d):
    for n in args.rows:
        rows, state, meta = build([(64, n)], n, 4, heads, d, rng)
        us = time_call(rows, state, meta, True, args.layers, args.reps)
        emit(dict(sweep="chunk", rows=n, call_us=round(us, 2),
                  us_per_row=round(us / n, 3)))


def sweep_mixed(args, rng, heads, d):
    lanes, n = args.lanes[-1], args.rows[min(1, len(args.rows) - 1)]
    spans = [(int(c), 1) for c in rng.integers(1, 900, lanes)]
    spans += [(0, n), (128, n)]
    T = lanes + 2 * n
    rows, state, meta = build(spans, T, lanes + 4, heads, d, rng)
    want_o, want_s = la.kda_ragged(*rows, state, *meta, use_pallas=True)
    got_o, got_s = one_kernel(*rows, state, *meta)
    line = dict(
        sweep="mixed", lanes=lanes, quanta=[n, n],
        one_vs_pair_rel=round(float(max(
            jnp.abs(got_o - want_o).max() / jnp.abs(want_o).max(),
            jnp.abs(got_s[1:] - want_s[1:]).max() / jnp.abs(want_s).max(),
        )), 7),
    )
    for name, how in (("pair", True), ("one_kernel", "one")):
        line[f"{name}_call_us"] = round(
            time_call(rows, state, meta, how, args.layers, args.reps), 2)
    emit(line)


def ints(text: str) -> list[int]:
    return [int(x) for x in text.split(",")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sweep", default="check,lanes,chunk")
    ap.add_argument("--lanes", type=ints, default=[8, 16, 32, 64, 128])
    ap.add_argument("--rows", type=ints, default=[16, 64, 256])
    ap.add_argument("--layers", type=int, default=7)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--heads", type=int, default=H)
    ap.add_argument("--head-dim", type=int, default=D)
    ap.add_argument("--twin", action="store_true",
                    help="time the XLA twin beside the kernel")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU (interpret mode); no times")
    args = ap.parse_args(argv)
    if not on_tpu() and not args.allow_cpu:
        print("no TPU: a CPU run gives no device time (--allow-cpu "
              "rehearses)", file=sys.stderr)
        return 1
    rng = np.random.default_rng(args.seed)
    sweeps = {"check": sweep_check, "lanes": sweep_lanes,
              "chunk": sweep_chunk, "mixed": sweep_mixed}
    for what in args.sweep.split(","):
        sweeps[what](args, rng, args.heads, args.head_dim)
    return 0


if __name__ == "__main__":
    sys.exit(main())
