"""MoE expert-path microbenchmark: dense (all experts, gate-masked) vs
grouped (rows sorted by expert, one grouped product a projection, no
capacity and no drop), single-device and under an ep-sharded mesh.

Dense computes E/topk times the selected FLOPs and reads every expert;
grouped pays a sort and two gathers. This measures the crossover behind
models/moe.py GROUPED_MIN_EXPERTS and verifies that the two exact
formulations agree.

Run on the real chip: ``python benchmarks/moe_bench.py``
Virtual 8-device ep mesh: ``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 python benchmarks/moe_bench.py --mesh ep=8``
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def run(cfg_kw, T, mesh=None, iters=8):
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import moe
    from dynamo_tpu.models.moe import MoeConfig, init_moe_params, moe_mlp

    results = {}
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((T, cfg_kw["hidden_size"])),
        jnp.float32,
    )
    params = init_moe_params(
        jax.random.PRNGKey(0), MoeConfig(**cfg_kw), dtype=jnp.float32
    )
    if mesh is not None:
        from dynamo_tpu.models.moe import shard_moe_params

        params = shard_moe_params(params, mesh)
    outs = {}
    cfg = MoeConfig(**cfg_kw)
    line = moe.GROUPED_MIN_EXPERTS
    for mode in ("dense", "grouped"):
        # the path is chosen by the expert count alone: move the line
        moe.GROUPED_MIN_EXPERTS = 0 if mode == "grouped" else 10**9
        fn = jax.jit(lambda p, xx: moe_mlp(p, xx, cfg, mesh=mesh))
        out = fn(params, x)
        out.block_until_ready()
        t0 = time.monotonic()
        for _ in range(iters):
            out = fn(params, x)
        out.block_until_ready()
        results[mode] = (time.monotonic() - t0) / iters * 1000
        outs[mode] = np.asarray(out)
    moe.GROUPED_MIN_EXPERTS = line
    # Both are exact: same experts, same math, another order of sums.
    np.testing.assert_allclose(
        outs["dense"], outs["grouped"], rtol=2e-4, atol=2e-4
    )
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default=None, help="e.g. ep=8")
    ap.add_argument("--tokens", type=int, default=1024)
    args = ap.parse_args()

    mesh = None
    if args.mesh:
        from dynamo_tpu.parallel.mesh import build_mesh

        shape = {
            k: int(v)
            for k, v in (kv.split("=") for kv in args.mesh.split(","))
        }
        mesh = build_mesh(shape)

    print(f"tokens={args.tokens} mesh={args.mesh or 'single'}")
    print(f"{'E':>4} {'topk':>4} | {'dense ms':>9} {'grouped ms':>11} | winner")
    for E, topk in ((8, 2), (16, 4), (64, 8), (128, 8)):
        r = run(
            dict(
                hidden_size=1024,
                intermediate_size=512,
                num_experts=E,
                num_experts_per_tok=topk,
            ),
            args.tokens,
            mesh=mesh,
        )
        win = "grouped" if r["grouped"] < r["dense"] else "dense"
        print(
            f"{E:>4} {topk:>4} | {r['dense']:>9.2f} {r['grouped']:>11.2f}"
            f" | {win}"
        )


if __name__ == "__main__":
    main()
