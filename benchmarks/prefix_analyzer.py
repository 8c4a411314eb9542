"""Trace prefix-sharing analyzer.

Role of the reference's ``benchmarks/data_generator/prefix_analyzer.py``:
before sizing a prefix cache or enabling KV-aware routing, an operator
wants to know — from a real trace — how much prefix sharing the workload
actually has and what hit rate a cache of N blocks could theoretically
reach. This tool answers both over the repo's capture/replay JSONL
formats (benchmarks/synthesizer.py):

- our request JSONL (``{"token_ids": [...], "max_tokens": N, ...}`` per
  line — ``save_request_jsonl`` writes it from any served workload), and
- Mooncake-format traces (``{"input_length", "output_length",
  "hash_ids", "timestamp"}`` — reconstructed via ``from_mooncake_trace``).

Block identity is the framework's own chained sequence hash
(llm/tokens.py TokenBlockSequence) — the exact identity the engine's
prefix cache and the KV router index by, so the predicted hit rates are
in the same currency as ``gpu_prefix_cache_hit_rate`` on /metrics.

Two curves come out:

- ``ideal`` hit rate: an infinite cache replaying requests in arrival
  order — the workload's intrinsic reuse ceiling;
- ``curve``: LRU caches of increasing block capacity — where the knee is
  tells you how many blocks (HBM, or G2 host tier) buy most of the
  ceiling.

Run: ``python -m benchmarks.prefix_analyzer TRACE.jsonl [--block-size N]
[--format auto|requests|mooncake] [--cache-sizes 256,1024,...]`` —
prints one JSON report.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import OrderedDict

from dynamo_tpu.llm.tokens import TokenBlockSequence


def _sniff_format(path) -> str:
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if "token_ids" in rec:
                return "requests"
            if "input_length" in rec or "hash_ids" in rec:
                return "mooncake"
            break
    raise ValueError(f"{path}: neither request JSONL nor a Mooncake trace")


def load_trace(path, fmt: str = "auto", block_size: int = 16):
    """Load either capture/replay format into synthesizer Requests."""
    from benchmarks.synthesizer import from_mooncake_trace, load_request_jsonl

    if fmt == "auto":
        fmt = _sniff_format(path)
    if fmt == "requests":
        return load_request_jsonl(path)
    if fmt == "mooncake":
        return from_mooncake_trace(path, block_size=max(block_size, 16) * 32)
    raise ValueError(f"unknown trace format {fmt!r}")


def _request_hashes(reqs, block_size: int) -> list[list[int]]:
    """Per request: the chained hashes of its FULL prompt blocks — the
    prefix-cache identity of each cacheable unit."""
    out = []
    for r in reqs:
        n_full = len(r.token_ids) // block_size
        if n_full == 0:
            out.append([])
            continue
        seq = TokenBlockSequence.from_tokens(
            list(r.token_ids[: n_full * block_size]), block_size=block_size
        )
        out.append(list(seq.sequence_hashes()[:n_full]))
    return out


def _lru_replay(hash_lists: list[list[int]], capacity: int) -> float:
    """Theoretical hit rate of an LRU block cache of `capacity` blocks
    over the trace in arrival order. Each request touches its prompt
    blocks front to back; a hit refreshes recency, a miss inserts (and
    evicts the coldest). Matches the engine's registration model: every
    computed block becomes cacheable."""
    lru: OrderedDict[int, None] = OrderedDict()
    hits = 0
    total = 0
    for hashes in hash_lists:
        for h in hashes:
            total += 1
            if h in lru:
                hits += 1
                lru.move_to_end(h)
            else:
                lru[h] = None
                if len(lru) > capacity:
                    lru.popitem(last=False)
    return hits / total if total else 0.0


def _shared_prefix_blocks(hash_lists: list[list[int]]) -> list[int]:
    """Per request: how many of its leading blocks were already produced
    by ANY earlier request (the streaming shared-prefix measure — what a
    warm, infinite cache would have hit)."""
    seen: set[int] = set()
    shared = []
    for hashes in hash_lists:
        n = 0
        for h in hashes:
            if h in seen:
                n += 1
            else:
                break  # chained hashes: a miss breaks the shared prefix
        shared.append(n)
        seen.update(hashes)
    return shared


def _default_cache_sizes(unique_blocks: int) -> list[int]:
    sizes = []
    n = 16
    while n < unique_blocks:
        sizes.append(n)
        n *= 4
    sizes.append(max(unique_blocks, 16))
    return sizes


def analyze(
    reqs,
    block_size: int = 16,
    cache_sizes: list[int] | None = None,
) -> dict:
    hash_lists = _request_hashes(reqs, block_size)
    total_blocks = sum(len(h) for h in hash_lists)
    unique_blocks = len({h for hl in hash_lists for h in hl})
    shared = _shared_prefix_blocks(hash_lists)
    total_tokens = sum(len(r.token_ids) for r in reqs)
    sizes = cache_sizes or _default_cache_sizes(unique_blocks)
    curve = [
        {
            "cache_blocks": c,
            "hit_rate": round(_lru_replay(hash_lists, c), 4),
        }
        for c in sorted(set(sizes))
    ]
    ideal = (
        (total_blocks - unique_blocks) / total_blocks if total_blocks else 0.0
    )
    return {
        "requests": len(reqs),
        "block_size": block_size,
        "total_tokens": total_tokens,
        "mean_isl": round(total_tokens / max(len(reqs), 1), 1),
        "mean_osl": round(
            sum(r.max_tokens for r in reqs) / max(len(reqs), 1), 1
        ),
        "total_prompt_blocks": total_blocks,
        "unique_prompt_blocks": unique_blocks,
        # Fraction of prompt blocks a warm infinite cache would hit — the
        # reuse ceiling no cache size can beat.
        "ideal_hit_rate": round(ideal, 4),
        # Streaming view: blocks already produced by an earlier request.
        "shared_prefix_block_fraction": round(
            sum(shared) / total_blocks, 4
        ) if total_blocks else 0.0,
        "requests_with_shared_prefix": sum(1 for s in shared if s > 0),
        # Hit rate vs LRU cache capacity — size the arena at the knee.
        "curve": curve,
    }


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(prog="prefix_analyzer")
    ap.add_argument("trace", help="capture/replay JSONL (see module doc)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument(
        "--format", default="auto", choices=["auto", "requests", "mooncake"]
    )
    ap.add_argument(
        "--cache-sizes", default=None,
        help="comma-separated block capacities for the LRU curve",
    )
    args = ap.parse_args(argv)
    sizes = (
        [int(s) for s in args.cache_sizes.split(",") if s.strip()]
        if args.cache_sizes
        else None
    )
    reqs = load_trace(args.trace, fmt=args.format, block_size=args.block_size)
    report = analyze(reqs, block_size=args.block_size, cache_sizes=sizes)
    print(json.dumps(report))
    return report


if __name__ == "__main__":
    main(sys.argv[1:])
