"""kv_sp per-shard attention cost: does the striped scan deliver
O(ctx/sp) per shard?

One real chip cannot host an sp>1 mesh, but it CAN run exactly the
workload ONE sp shard sees: the r05 striped decode kernel
(ops/pallas/attention.py page_stride) over a compacted stripe holding
1/sp of each lane's pages. Sweeping page_stride on the same per-lane
context measures the per-shard cost directly — the cross-shard merge
adds only an O(B*H) psum on top (measured separately by the virtual-mesh
tests; it is noise at these shapes).

Timing: kernel calls folded into jitted scans (q drawn cyclically from a
pool by traced index, so XLA cannot CSE the calls), at TWO rep counts —
the per-call figure is the SLOPE between them, which cancels the fixed
per-dispatch overhead.
"""

from __future__ import annotations

import time

import numpy as np


def run(
    B: int = 32,
    ctx: int = 4096,
    kvH: int = 8,
    H: int = 32,
    D: int = 128,
    bs: int = 16,
    strides: tuple[int, ...] = (1, 2, 4, 8),
    reps: tuple[int, int] = (64, 512),
    dtype="bfloat16",
) -> dict:
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops.pallas.attention import paged_decode_attention_pallas

    rng = np.random.default_rng(0)
    nb_lane = ctx // bs  # logical pages per lane
    POOL = 8
    out: dict[str, float] = {}
    for stride in strides:
        local_lane = -(-nb_lane // stride)  # this shard's pages per lane
        num_blocks = 1 + B * local_lane     # shard-local cache (+ trash)
        slots = num_blocks * bs
        k = jnp.asarray(
            rng.standard_normal((slots, kvH, D)), dtype=jnp.dtype(dtype)
        )
        v = jnp.asarray(
            rng.standard_normal((slots, kvH, D)), dtype=jnp.dtype(dtype)
        )
        tables = np.zeros((B, local_lane), np.int32)
        nxt = 1
        for b in range(B):
            tables[b] = range(nxt, nxt + local_lane)
            nxt += local_lane
        tables = jnp.asarray(tables)
        ctx_arr = jnp.full((B,), ctx, jnp.int32)
        off = jnp.zeros((1,), jnp.int32)
        qs = jnp.asarray(
            rng.standard_normal((POOL, B, H, D)), dtype=jnp.dtype(dtype)
        )

        def many(qs, k, v, tables, ctx_arr, off, R, _stride=stride):
            def step(acc, i):
                q = jax.lax.dynamic_index_in_dim(
                    qs, i % POOL, 0, keepdims=False
                )
                o, m, l = paged_decode_attention_pallas(
                    q, k, v, tables, ctx_arr, bs,
                    page_offset=off, page_stride=_stride, with_stats=True,
                )
                return acc + o.sum() + m.sum() + l.sum(), None

            acc, _ = jax.lax.scan(step, jnp.float32(0), jnp.arange(R))
            return acc

        def timed(R: int) -> float:
            fn = jax.jit(lambda *a: many(*a, R))
            # float() waits for the result (a scalar host transfer).
            float(fn(qs, k, v, tables, ctx_arr, off))
            t0 = time.monotonic()
            N = 3
            for _ in range(N):
                float(fn(qs, k, v, tables, ctx_arr, off))
            return (time.monotonic() - t0) / N

        t_lo, t_hi = timed(reps[0]), timed(reps[1])
        per_call_us = max(t_hi - t_lo, 1e-9) / (reps[1] - reps[0]) * 1e6
        out[f"shard_attn_us_sp{stride}"] = round(per_call_us, 1)
    base = out["shard_attn_us_sp1"]
    for stride in strides[1:]:
        out[f"speedup_sp{stride}"] = round(
            base / out[f"shard_attn_us_sp{stride}"], 2
        )
    out.update({"B": B, "ctx": ctx, "kvH": kvH, "D": D, "block_size": bs})
    return out


def main() -> dict:
    import os

    smoke = bool(os.environ.get("BENCH_SMOKE"))
    r = run(
        B=4 if smoke else 32,
        ctx=128 if smoke else 4096,
        strides=(1, 2) if smoke else (1, 2, 4, 8),
        reps=(4, 16) if smoke else (64, 512),
    )
    sp4 = r.get("speedup_sp2" if smoke else "speedup_sp4", 0.0)
    return {
        "metric": "kv_sp_shard_attention_speedup_sp4",
        "value": sp4,
        "unit": "x (vs full scan; ideal 4.0)",
        "vs_baseline": sp4,
        "extras": r,
    }


if __name__ == "__main__":
    import json

    print(json.dumps(main()))
