"""8B device-efficiency bench: DEVICE-time decode
byte-rate and prefill MFU with per-fusion attribution.

r04 closed the 1B gap with profile-driven kernel work (86% of the HBM
floor); this points the same method at 8B. All times come from the XLA
Modules/Ops lanes of a captured profile (benchmarks/xprof.py): device
time, not host wall time, is what a byte-rate or an MFU divides by.

Run: ``BENCH_8B=1 python bench.py`` (env knobs below) — prints one JSON
line with decode_gbps / prefill_mfu + the top fusions for each.
"""

from __future__ import annotations

import os

import numpy as np

V5E_PEAK_FLOPS = 197e12   # bf16
V5E_PEAK_GBPS = 819.0


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _matmul_params(cfg) -> int:
    """Parameters participating in per-token matmuls (layers only —
    embedding lookups are gathers; the lm_head counts once per SAMPLED
    position, added separately)."""
    D, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    attn = D * H * hd + 2 * D * KV * hd + H * hd * D  # q,k,v,o
    mlp = 3 * D * I
    return L * (attn + mlp)


def run() -> dict:
    import jax.numpy as jnp

    from benchmarks.xprof import measure
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models.config import ModelConfig

    model_name = os.environ.get("BENCH_MODEL", "llama31_8b")
    quant = os.environ.get("DYNAMO_TPU_QUANT", "int8") or None
    m = getattr(ModelConfig, model_name)()
    bs = 16
    B = _env_int("BENCH_SEQS", 16)
    chunk = _env_int("BENCH_CHUNK", 16)
    lanes = _env_int("BENCH_PREFILL_BATCH", 4)
    pchunk = 512
    isl_long = _env_int("BENCH_ISL", 3000)
    cfg = EngineConfig(
        model=m, dtype="bfloat16", quant=quant, block_size=bs,
        num_blocks=_env_int("BENCH_BLOCKS", 1600), max_num_seqs=B,
        max_model_len=4096, decode_chunk=chunk, prefill_batch=lanes,
    )
    runner = ModelRunner(cfg)
    out: dict = {
        "model": model_name, "quant": quant or "none",
        "attention_path": "pallas" if runner.attn.use_pallas else "jnp",
    }

    import jax

    weight_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(runner.params)
    )
    kv_entry = 2 * m.num_layers * m.num_cache_heads * runner.cache_head_dim * 2

    def tables_for(nlanes: int, ctx: int, extra: int):
        per = (ctx + extra + bs - 1) // bs
        t = np.zeros((nlanes, cfg.max_blocks_per_seq), np.int32)
        nxt = 1
        for b in range(nlanes):
            t[b, :per] = range(nxt, nxt + per)
            nxt += per
        assert nxt <= cfg.num_blocks, "arena too small for the scenario"
        return t

    # ---- decode byte-rate at two contexts (the ISL-3000 droop probe) ----
    long_ctx = isl_long + 150
    long_lanes = _env_int("BENCH_LONG_LANES", 6)
    for label, ctx, nb in (
        ("short", 192, B), (f"isl{isl_long}", long_ctx, long_lanes),
    ):
        tables = tables_for(nb, ctx, chunk)
        if nb < B:
            tables = np.vstack([tables, np.zeros((B - nb, tables.shape[1]), np.int32)])
        ctx_arr = np.array([ctx] * nb + [0] * (B - nb), np.int32)
        zf, zi, of = (
            np.zeros(B, np.float32), np.zeros(B, np.int32),
            np.ones(B, np.float32),
        )
        toks = np.ones(B, np.int32)

        def one():
            r = runner.decode_multi(
                toks, np.maximum(ctx_arr - 1, 0), tables, ctx_arr,
                zf, zi, of, chunk,
            )
            np.asarray(r)

        one()  # compile outside the trace
        N = 3
        prof = measure(lambda: [one() for _ in range(N)])
        step_ms = prof["module_ms"] / (N * chunk)
        bytes_per_step = weight_bytes + nb * ctx * kv_entry
        out[f"decode_{label}"] = {
            "device_step_ms": round(step_ms, 3),
            "effective_gbps": round(bytes_per_step / (step_ms / 1e3) / 1e9, 1),
            "pct_of_peak": round(
                100 * bytes_per_step / (step_ms / 1e3) / 1e9 / V5E_PEAK_GBPS, 1
            ),
            "lanes": nb,
            "top_ops": prof["ops_ms"][:8],
        }

    # ---- prefill MFU at the harness shape (chunked, batched) -------------
    pchunk = min(pchunk, isl_long)
    tables = tables_for(lanes, isl_long, 0)
    prefix = max((isl_long - pchunk) // 2 // bs * bs, 0)  # mid-prompt chunk
    lanes_args = []
    for i in range(lanes):
        toks_l = [1] * pchunk
        lanes_args.append((toks_l, [int(x) for x in tables[i] if x], prefix,
                           (0.0, 0, 1.0)))

    def one_prefill():
        runner.prefill_batch(lanes_args)

    one_prefill()
    N = 3
    prof = measure(lambda: [one_prefill() for _ in range(N)])
    call_ms = prof["module_ms"] / N
    tokens = lanes * pchunk
    # Matmul flops + causal attention (QK^T and PV over the live prefix).
    mm_flops = 2 * _matmul_params(m) * tokens + 2 * m.hidden_size * m.vocab_size * lanes
    avg_ctx = prefix + pchunk / 2
    attn_flops = 4 * m.num_layers * m.num_heads * m.head_dim * tokens * avg_ctx
    flops = mm_flops + attn_flops
    out["prefill"] = {
        "device_call_ms": round(call_ms, 2),
        "lanes": lanes,
        "chunk": pchunk,
        "prefix": prefix,
        "mfu_pct": round(100 * flops / (call_ms / 1e3) / V5E_PEAK_FLOPS, 1),
        "tok_per_s_device": round(tokens / (call_ms / 1e3), 0),
        "top_ops": prof["ops_ms"][:8],
    }
    return out


def main() -> dict:
    r = run()
    return {
        # Default model is llama31_8b; BENCH_MODEL parameterizes the probe
        # (e.g. gemma3_1b).
        "metric": f"prefill_mfu_{r['model']}",
        "value": r["prefill"]["mfu_pct"],
        "unit": "% of v5e bf16 peak (device time)",
        "vs_baseline": r["prefill"]["mfu_pct"] / 100.0,
        "extras": r,
    }


if __name__ == "__main__":
    import json

    print(json.dumps(main()))
