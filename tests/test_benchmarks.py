"""Benchmark harness tests: synthesizer structure + sweep/agg-vs-disagg
drivers on the mocker (device-free)."""

import pytest

from benchmarks.synthesizer import WorkloadConfig, generate, prefix_stats

pytestmark = pytest.mark.anyio


def test_synthesizer_prefix_structure():
    cfg = WorkloadConfig(num_requests=64, isl_mean=100, reuse=0.6, seed=3)
    reqs = generate(cfg)
    assert len(reqs) == 64
    stats = prefix_stats(reqs)
    # Prefix sharing exists and is material (the radix structure the
    # reference synthesizer preserves, synthesizer.py:48-75).
    assert stats["shared_prefix_fraction"] > 0.2
    # Shared prefixes really are shared: at least two requests start with
    # the same depth-1 run.
    firsts = {}
    for r in reqs:
        key = tuple(r.token_ids[:10])
        firsts[key] = firsts.get(key, 0) + 1
    assert max(firsts.values()) >= 2
    # Determinism: same seed, same workload.
    again = generate(WorkloadConfig(num_requests=64, isl_mean=100, reuse=0.6, seed=3))
    assert [r.token_ids for r in again] == [r.token_ids for r in reqs]


def test_synthesizer_no_reuse_is_unique():
    reqs = generate(WorkloadConfig(num_requests=16, reuse=0.0, seed=1))
    assert len({tuple(r.token_ids) for r in reqs}) == 16


def test_synthesizer_poisson_arrivals():
    reqs = generate(WorkloadConfig(num_requests=32, arrival_rate=100.0, seed=2))
    times = [r.arrival_s for r in reqs]
    assert times == sorted(times)
    assert times[-1] > 0


async def test_sweep_and_agg_vs_disagg_on_mocker():
    from benchmarks.sweep import _agg_vs_disagg, _mock_engine, sweep

    engine = _mock_engine()
    await engine.start()
    levels = await sweep(
        engine,
        levels=(1, 8),
        requests_per_level=6,
        workload=WorkloadConfig(num_requests=6, isl_mean=64, osl_mean=8),
    )
    await engine.stop()
    assert [lv["concurrency"] for lv in levels] == [1, 8]
    for lv in levels:
        assert lv["tok_per_s"] > 0
        assert lv["p50_ttft_ms"] is not None
        assert lv["p50_itl_ms"] is not None

    reqs = generate(WorkloadConfig(num_requests=8, isl_mean=64, osl_mean=8))
    cmp = await _agg_vs_disagg(reqs)
    assert cmp["agg"]["tok_per_s"] > 0
    assert cmp["disagg"]["tok_per_s"] > 0
    assert cmp["remote_prefills"] > 0  # long prompts actually went remote


def test_mooncake_trace_replay_preserves_structure(tmp_path):
    """Mooncake-format traces drive the workload
    generator — shared hash_ids become shared token prefixes (the trace's
    radix structure), arrivals scale by speedup_ratio, and loading is
    deterministic."""
    import json

    from benchmarks.synthesizer import from_mooncake_trace

    trace = tmp_path / "mooncake.jsonl"
    recs = [
        # Requests 0 and 1 share their first two 512-token blocks (hash
        # ids 7, 8); request 2 is unique; request 3 shares only block 7.
        {"timestamp": 0, "input_length": 1100, "output_length": 12,
         "hash_ids": [7, 8, 9]},
        {"timestamp": 1000, "input_length": 1200, "output_length": 8,
         "hash_ids": [7, 8, 11]},
        {"timestamp": 2000, "input_length": 600, "output_length": 4,
         "hash_ids": [20, 21]},
        {"timestamp": 4000, "input_length": 800, "output_length": 6,
         "hash_ids": [7, 30]},
    ]
    trace.write_text("\n".join(json.dumps(r) for r in recs))

    reqs = from_mooncake_trace(trace, speedup_ratio=2.0)
    assert [len(r.token_ids) for r in reqs] == [1100, 1200, 600, 800]
    assert [r.max_tokens for r in reqs] == [12, 8, 4, 6]
    # speedup 2x: 0s, 0.5s, 1s, 2s
    assert [round(r.arrival_s, 3) for r in reqs] == [0.0, 0.5, 1.0, 2.0]
    # Shared hash ids -> IDENTICAL token prefixes (1024 = two full blocks).
    assert reqs[0].token_ids[:1024] == reqs[1].token_ids[:1024]
    assert reqs[0].token_ids[:512] == reqs[3].token_ids[:512]
    # ...and divergence after the shared part.
    assert reqs[0].token_ids[1024:1100] != reqs[1].token_ids[1024:1100]
    assert reqs[2].token_ids[:512] != reqs[0].token_ids[:512]
    # prefix_len marks the LEADING shared blocks only.
    assert [r.prefix_len for r in reqs] == [1024, 1024, 0, 512]
    # Deterministic reload.
    again = from_mooncake_trace(trace, speedup_ratio=2.0)
    assert [r.token_ids for r in again] == [r.token_ids for r in reqs]


def test_request_jsonl_roundtrip(tmp_path):
    from benchmarks.synthesizer import (
        WorkloadConfig,
        generate,
        load_request_jsonl,
        save_request_jsonl,
    )

    reqs = generate(WorkloadConfig(num_requests=8, isl_mean=32, seed=5))
    p = tmp_path / "capture.jsonl"
    save_request_jsonl(reqs, p)
    back = load_request_jsonl(p)
    assert [r.token_ids for r in back] == [r.token_ids for r in reqs]
    assert [r.max_tokens for r in back] == [r.max_tokens for r in reqs]
    assert [r.prefix_len for r in back] == [r.prefix_len for r in reqs]
    assert [r.request_id for r in back] == [r.request_id for r in reqs]
    # arrival_s is what makes a capture replayable with recorded timing.
    assert [r.arrival_s for r in back] == [r.arrival_s for r in reqs]


async def test_trace_replay_hits_prefix_cache_on_mocker(tmp_path):
    """Replaying a reuse-heavy trace through the engine exercises the
    prefix cache the way production traffic would: the trace's shared
    blocks turn into real G1 prefix hits."""
    import json

    from benchmarks.sweep import _mock_engine, run_level
    from benchmarks.synthesizer import from_mooncake_trace

    trace = tmp_path / "mooncake.jsonl"
    base = {"timestamp": 0, "input_length": 96, "output_length": 4}
    recs = [dict(base, hash_ids=[1], timestamp=i * 10) for i in range(6)]
    recs += [
        dict(base, hash_ids=[50 + i], timestamp=100 + i * 10)
        for i in range(2)
    ]
    trace.write_text("\n".join(json.dumps(r) for r in recs))
    reqs = from_mooncake_trace(trace, block_size=64, vocab_size=900)

    engine = _mock_engine()
    await engine.start()
    try:
        level = await run_level(engine, reqs, concurrency=1)
        assert level["tok_per_s"] > 0
        # 6 requests share their first 64-token block: after the first
        # computes it, the other 5 hit the prefix cache.
        assert engine.prefix_hit_rate > 0.5
    finally:
        await engine.stop()


def test_prefix_analyzer_over_capture_jsonl(tmp_path):
    """benchmarks/prefix_analyzer.py: prefix-sharing
    stats + the theoretical hit-rate-vs-cache-size curve over the repo's
    capture/replay JSONL, in the engine's own block-hash identity."""
    import json

    from benchmarks.prefix_analyzer import analyze, load_trace, main
    from benchmarks.synthesizer import save_request_jsonl

    reqs = generate(
        WorkloadConfig(num_requests=48, isl_mean=96, reuse=0.6, seed=7)
    )
    path = tmp_path / "capture.jsonl"
    save_request_jsonl(reqs, path)

    loaded = load_trace(path)  # auto-sniffs the request format
    assert len(loaded) == 48
    report = analyze(loaded, block_size=16)
    assert report["requests"] == 48
    assert report["total_prompt_blocks"] > report["unique_prompt_blocks"]
    # The synthesizer's radix structure must be visible as real sharing.
    assert report["ideal_hit_rate"] > 0.1
    assert report["shared_prefix_block_fraction"] > 0.1
    assert report["requests_with_shared_prefix"] >= 2
    # The LRU curve: monotone non-decreasing in capacity, and a cache big
    # enough for every unique block reaches the ideal ceiling exactly.
    curve = report["curve"]
    rates = [pt["hit_rate"] for pt in curve]
    assert rates == sorted(rates)
    assert curve[-1]["cache_blocks"] >= report["unique_prompt_blocks"]
    assert abs(rates[-1] - report["ideal_hit_rate"]) < 1e-6
    # A tiny cache does strictly worse than the full one (eviction bites).
    assert rates[0] < rates[-1]

    # Zero-reuse workload: ~no sharing, ideal hit rate ~0.
    unique = generate(WorkloadConfig(num_requests=16, reuse=0.0, seed=1))
    r2 = analyze(unique, block_size=16)
    assert r2["ideal_hit_rate"] < 0.05
    assert r2["shared_prefix_block_fraction"] < 0.05

    # CLI entry: prints one JSON report; explicit cache sizes respected.
    report_cli = main([str(path), "--block-size", "16",
                       "--cache-sizes", "32,64"])
    assert [pt["cache_blocks"] for pt in report_cli["curve"]] == [32, 64]
    assert json.dumps(report_cli)  # JSON-serializable end to end


def test_prefix_analyzer_mooncake_format(tmp_path):
    """The analyzer reads Mooncake-format traces through the same loader
    the replay path uses, preserving hash-id sharing structure."""
    import json

    from benchmarks.prefix_analyzer import analyze, load_trace

    path = tmp_path / "trace.jsonl"
    records = [
        {"timestamp": i * 100, "input_length": 1024,
         "output_length": 8, "hash_ids": [0, 1, i + 10]}
        for i in range(8)
    ]
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    reqs = load_trace(path)  # auto-sniffs mooncake
    assert len(reqs) == 8
    report = analyze(reqs, block_size=16)
    # Blocks 0/1 are shared by all 8 requests -> strong sharing signal.
    assert report["ideal_hit_rate"] > 0.3
    assert report["requests_with_shared_prefix"] == 7
