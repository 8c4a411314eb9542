"""The delta-rule kernels alone (``ops/pallas/kda.py``) at the widths of
``ling-3.0-flash-ep4-l8`` (32 heads of 128 x 128 in float32, 2 MiB a lane a
layer): microseconds a lane beside the bytes bound, microseconds a prefill
row beside the chunkwise form's FLOP bound, the kernel | its XLA twin, so a
later optimisation starts from a split and not from a guess.

- ``check``: kernels against the XLA twin (the recurrence row by row) on
  one dispatch of decode lanes beside ``--quanta``, the last of them with
  its decay AT the model's bound (-5 in every channel of every row): the
  worst relative error of the outputs and of the states;
- ``lanes``: ``kda_recurrent`` over 8..128 decode lanes: microseconds a
  call and a lane, the bytes bound (``chipbench/costs/kda_recurrent.py``)
  and the share of it reached; ``--twin`` times the XLA twin beside it;
- ``chunk``: ``kda_chunk`` over one prefill quantum of 16..256 rows:
  microseconds a row of the whole call (``this_call_us_per_row``: what
  the records before PR 49 and its issue quote) and of the ``kda_chunk``
  op alone from a trace (``this_kernel_us_per_row``: on the chip only;
  ``parent_*`` for ``--kernel-file``'s), each
  beside its share of the chunkwise form's FLOP bound (``chunk_flops``
  below, at the bf16 peak), and how full the tiles run;
- ``mixed``: one dispatch as the cell fills it (``--lanes``' last decode
  lanes beside ``--quanta`` prefill quanta): the pair of kernels the program
  runs; ``ops``: the same dispatch traced, device time an op a layer.

``--kernel-file PATH`` measures another checkout's ``ops/pallas/kda.py``
(one with ``kda_chunk``: PR 49's or later) beside this one's in the same
call (parent | change), under this checkout's glue.

    chiprun -- python -m tools.kda_kernel_bench --sweep check,lanes,chunk

A line of JSON a measurement on stdout, all of them in
``chiprun_out/kda_kernel_bench.jsonl``. Times are host clock around
``--layers`` chained calls inside one jitted scan, ended by
``block_until_ready``: device time, never a CPU number (without a TPU the
script stops, unless ``--allow-cpu`` rehearses it at a tiny size).
"""

from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.costs.kda_recurrent import cost
from chipbench.peaks import peaks_for
from dynamo_tpu.ops import linear_attention as la
from dynamo_tpu.ops.pallas import kda as kda_kernel

H, D = 32, 128
BOUND = -5.0            # ``kda_lower_bound`` of the published config

#: The kernels under measurement by name: this checkout's, and
#: ``--kernel-file``'s where given.
KERNELS = {"this": kda_kernel}


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def emit(line: dict) -> None:
    text = json.dumps(line)
    print(text, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_kernel_bench.jsonl", "a") as f:
        f.write(text + "\n")


def build(spans, T: int, slots: int, heads: int, d: int, rng, at_bound=()):
    """One dispatch's operands: ``spans`` as (q_start, rows), span ``s`` in
    slot ``s + 1`` of a table of ``slots``; the spans ``at_bound`` decay
    by the model's bound in every channel of every row."""
    S = len(spans) + 1
    token_seq = np.zeros(T, np.int32)
    token_pos = np.full(T, -1, np.int32)
    q_start, q_len, row_start, slot = (np.zeros(S, np.int32) for _ in range(4))
    bound = np.zeros((T, 1, 1), bool)
    at = 0
    for s, (start, n) in enumerate(spans):
        bound[at : at + n] = s in at_bound
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
        jnp.where(
            bound, BOUND,
            BOUND * jax.random.uniform(kg, shape, jnp.float32, 0.0, 0.2),
        ),
        jax.random.uniform(kb, (T, heads), jnp.float32, 0.1, 0.9),
    )
    state = jax.random.normal(ks, (slots, heads, d, d), jnp.float32)
    meta = tuple(map(jnp.asarray, (
        token_seq, token_pos, q_start, q_len, row_start, slot)))
    return rows, state, meta


def ragged_of(name: str):
    """The dispatch's call by a kernel's name (``KERNELS``), or the XLA
    twin."""
    if name == "twin":
        return la.kda_ragged_xla
    return functools.partial(
        la.kda_ragged_pallas, KERNELS[name], lower_bound=BOUND)


def time_call(rows, state, meta, name: str, layers: int, reps: int,
              trace_to: str | None = None):
    """Median microseconds of ONE layer's call, from ``layers`` chained
    calls that hand the state on; ``name`` as ``ragged_of`` takes it. With
    ``trace_to``, one more chain runs under the profiler."""
    step = ragged_of(name)

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
    if trace_to:
        with jax.profiler.trace(trace_to):
            jax.block_until_ready(chain(rows, state, meta))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(rows, state, meta))
        times.append(time.perf_counter() - t0)
    return 1e6 * statistics.median(times) / layers


def chunk_flops(rows: int, heads: int, d: int, tile: int) -> float:
    """What the chunkwise form needs for ``rows`` prefill rows of one
    layer: against the state ``Q S``, ``beta K S`` and ``K^T U`` (6 d^2 a
    row a head), and within a tile the lower triangles of the two score
    matrices, of the solve and of ``P U`` (4 x tile x d)."""
    return rows * heads * (6 * d * d + 4 * tile * d)


def bytes_bound_us(spans, heads: int, d: int) -> float:
    _flops, nbytes = cost(
        spans, model=dict(num_heads=heads, head_dim=d, num_layers=1,
                          layer_group_size=2), engine={})
    kind = jax.devices()[0].device_kind if on_tpu() else "TPU v5 lite"
    return 1e6 * nbytes / peaks_for(kind)["hbm_bytes_per_s"]


def rel(got, want) -> float:
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def sweep_check(args, rng, heads, d):
    # five rows no span owns behind the dispatch's
    rows, state, meta, line = mixed_dispatch(
        args, rng, heads, d, spare=5, last_at_bound=True)
    want_o, want_s = jax.jit(ragged_of("twin"))(*rows, state, *meta)
    line.update(sweep="check", decay_at_bound=len(args.quanta) - 1)
    for name in KERNELS:
        got_o, got_s = jax.jit(ragged_of(name))(*rows, state, *meta)
        err_o, err_s = rel(got_o, want_o), rel(got_s[1:], want_s[1:])
        line[f"{name}_out_rel"] = round(err_o, 8)
        line[f"{name}_state_rel"] = round(err_s, 8)
        line[f"{name}_ok"] = bool(
            np.isfinite(np.asarray(got_o)).all()
            and err_o < 1e-4 and err_s < 1e-4)
    emit(line)


def sweep_lanes(args, rng, heads, d):
    for lanes in args.lanes:
        spans = [(int(c), 1) for c in rng.integers(1, 900, lanes)]
        rows, state, meta = build(spans, lanes, args.lanes[-1] + 1, heads, d, rng)
        bound = bytes_bound_us(spans, heads, d)
        line = dict(sweep="lanes", lanes=lanes, bytes_bound_us=round(bound, 2))
        for name in tuple(KERNELS) + (("twin",) if args.twin else ()):
            us = time_call(rows, state, meta, name, args.layers, args.reps)
            line[f"{name}_call_us"] = round(us, 2)
            line[f"{name}_us_per_lane"] = round(us / lanes, 3)
            line[f"{name}_roofline_pct"] = round(100 * bound / us, 2)
        emit(line)


def traced(rows, state, meta, name: str, layers: int) -> dict:
    """One more chain under the profiler: device seconds an op, and the
    module's (``chipbench.xprof``, as the benchmark reduces a trace)."""
    import tempfile

    from chipbench import xprof

    with tempfile.TemporaryDirectory() as logdir:
        time_call(rows, state, meta, name, layers, 1, trace_to=logdir)
        return xprof.reduce(xprof.load(logdir))


def sweep_chunk(args, rng, heads, d):
    """``call_*`` is the whole call (the rows' operands, the lanes' kernel
    on no lane, the selects: ~150 us that a span of any length pays once);
    ``kernel_*`` the ``kda_chunk`` op alone, from a trace, on the chip."""
    kind = jax.devices()[0].device_kind if on_tpu() else "TPU v5 lite"
    tile = kda_kernel.TILE
    for n in args.rows:
        rows, state, meta = build([(64, n)], n, 4, heads, d, rng)
        bound = 1e6 * chunk_flops(n, heads, d, tile) / (
            peaks_for(kind)["flops_bf16"])
        line = dict(sweep="chunk", rows=n, flop_bound_us=round(bound, 3),
                    tile_fill_pct=round(100 * n / (-(-n // tile) * tile), 1))
        for name in KERNELS:
            took = {"call": time_call(
                rows, state, meta, name, args.layers, args.reps)}
            if on_tpu():
                ops = traced(rows, state, meta, name, args.layers)
                took["kernel"] = (
                    1e6 * ops["op_seconds"]["kda_chunk"] / args.layers)
            for what, us in took.items():
                line[f"{name}_{what}_us"] = round(us, 2)
                line[f"{name}_{what}_us_per_row"] = round(us / n, 3)
                line[f"{name}_{what}_flop_bound_pct"] = round(
                    100 * bound / us, 2)
        emit(line)


def mixed_dispatch(args, rng, heads, d, spare=0, last_at_bound=False):
    """One dispatch as the cell fills it: ``--lanes``' last decode lanes
    beside ``--quanta`` prefill quanta (the last of them decaying at the
    model's bound, if asked), ``spare`` rows no span owns behind them."""
    lanes = args.lanes[-1]
    spans = [(int(c), 1) for c in rng.integers(1, 900, lanes)]
    spans += [(128 * i, n) for i, n in enumerate(args.quanta)]
    rows, state, meta = build(
        spans, lanes + sum(args.quanta) + spare, len(spans) + 2, heads, d,
        rng, at_bound={len(spans) - 1} if last_at_bound else (),
    )
    return rows, state, meta, dict(lanes=lanes, quanta=args.quanta)


def sweep_mixed(args, rng, heads, d):
    rows, state, meta, line = mixed_dispatch(args, rng, heads, d)
    line["sweep"] = "mixed"
    for name in KERNELS:
        line[f"{name}_call_us"] = round(
            time_call(rows, state, meta, name, args.layers, args.reps), 2)
    emit(line)


def sweep_ops(args, rng, heads, d):
    """One traced chain on ``mixed``'s dispatch: device time an op a layer
    (the kernels as the benchmark's trace reads them, and what XLA runs
    around them: the rows' operands, the gathers, the selects)."""
    rows, state, meta, line = mixed_dispatch(args, rng, heads, d)
    for name in KERNELS:
        reduced = traced(rows, state, meta, name, args.layers)
        emit(dict(line, sweep="ops", kernel=name, us_per_layer={
            op: round(1e6 * sec / args.layers, 2)
            for op, sec in sorted(reduced["op_seconds"].items(),
                                  key=lambda kv: -kv[1])[:14]
        }, module_us_per_layer=round(
            1e6 * reduced["module_s"] / args.layers, 2)))


def ints(text: str) -> list[int]:
    return [int(x) for x in text.split(",")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sweep", default="check,lanes,chunk")
    ap.add_argument("--lanes", type=ints, default=[8, 16, 32, 64, 128])
    ap.add_argument("--rows", type=ints, default=[16, 64, 256])
    ap.add_argument("--quanta", type=ints, default=[64, 64],
                    help="the prefill quanta of `mixed`, `check` and `ops`")
    ap.add_argument("--layers", type=int, default=7)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--heads", type=int, default=H)
    ap.add_argument("--head-dim", type=int, default=D)
    ap.add_argument("--twin", action="store_true",
                    help="time the XLA twin beside the kernel")
    ap.add_argument("--kernel-file", default=None, metavar="PATH",
                    help="measure the kernels of this file too (another "
                    "checkout's ops/pallas/kda.py), as `parent`")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU (interpret mode); no times")
    args = ap.parse_args(argv)
    if not on_tpu() and not args.allow_cpu:
        print("no TPU: a CPU run gives no device time (--allow-cpu "
              "rehearses)", file=sys.stderr)
        return 1
    if args.kernel_file:
        spec = importlib.util.spec_from_file_location(
            "kda_under_test", args.kernel_file)
        KERNELS["parent"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(KERNELS["parent"])
    rng = np.random.default_rng(args.seed)
    sweeps = {"check": sweep_check, "lanes": sweep_lanes,
              "chunk": sweep_chunk, "mixed": sweep_mixed, "ops": sweep_ops}
    for what in args.sweep.split(","):
        sweeps[what](args, rng, args.heads, args.head_dim)
    return 0


if __name__ == "__main__":
    sys.exit(main())
