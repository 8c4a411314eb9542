"""Synthetic workload generator with prefix-tree structure.

Role of the reference's Mooncake-trace synthesizer (reference:
benchmarks/data_generator/synthesizer.py:48-75 — radix-structure-preserving
prompt generation with tunable length/speedup multipliers): produce
workloads whose prompts share realistic prefix structure, so prefix caching
and KV-aware routing have something to bite on.

Model: a random prefix tree. Each node carries a run of tokens; a request
samples a root→node path (its shared prefix) plus a unique suffix. Depth-1
nodes are "system prompts", deeper nodes are conversation turns. With
``reuse=0`` every prompt is unique; with high reuse most requests share
long prefixes.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class WorkloadConfig:
    num_requests: int = 64
    isl_mean: int = 128           # mean prompt length (tokens)
    osl_mean: int = 32            # mean generation length
    reuse: float = 0.5            # fraction of a prompt drawn from the tree
    branching: int = 3            # children per tree node
    depth: int = 3                # tree depth
    vocab_size: int = 32000
    arrival_rate: float = 0.0     # req/s Poisson arrivals; 0 = all at once
    seed: int = 0


@dataclass
class Request:
    token_ids: list[int]
    max_tokens: int
    arrival_s: float = 0.0
    prefix_len: int = 0           # tokens shared with at least one sibling
    request_id: str = ""


@dataclass
class _Node:
    tokens: list[int]
    children: list["_Node"] = field(default_factory=list)


def generate(cfg: WorkloadConfig) -> list[Request]:
    rng = np.random.default_rng(cfg.seed)
    prefix_budget = max(1, int(cfg.isl_mean * cfg.reuse))
    run_len = max(1, prefix_budget // max(cfg.depth, 1))

    def grow(depth: int) -> _Node:
        node = _Node(
            tokens=rng.integers(0, cfg.vocab_size, run_len).tolist()
        )
        if depth < cfg.depth:
            node.children = [grow(depth + 1) for _ in range(cfg.branching)]
        return node

    root = grow(1)

    def sample_path() -> list[int]:
        out: list[int] = []
        node = root
        while True:
            out += node.tokens
            if not node.children or rng.random() < 0.25:
                return out
            node = node.children[int(rng.integers(len(node.children)))]

    reqs: list[Request] = []
    t = 0.0
    for i in range(cfg.num_requests):
        prefix = sample_path() if cfg.reuse > 0 else []
        suffix_len = max(
            1, int(rng.normal(cfg.isl_mean - len(prefix), cfg.isl_mean * 0.1))
        )
        tokens = prefix + rng.integers(0, cfg.vocab_size, suffix_len).tolist()
        osl = max(1, int(rng.normal(cfg.osl_mean, cfg.osl_mean * 0.25)))
        if cfg.arrival_rate > 0:
            t += float(rng.exponential(1.0 / cfg.arrival_rate))
        reqs.append(
            Request(
                token_ids=tokens,
                max_tokens=osl,
                arrival_s=t,
                prefix_len=len(prefix),
                request_id=f"synth-{i}",
            )
        )
    return reqs


def prefix_stats(reqs: list[Request]) -> dict:
    """Prefix-analyzer-style summary (reference: prefix_analyzer.py)."""
    total = sum(len(r.token_ids) for r in reqs)
    shared = sum(r.prefix_len for r in reqs)
    return {
        "requests": len(reqs),
        "total_tokens": total,
        "mean_isl": round(total / max(len(reqs), 1), 1),
        "mean_osl": round(
            sum(r.max_tokens for r in reqs) / max(len(reqs), 1), 1
        ),
        "shared_prefix_fraction": round(shared / max(total, 1), 3),
    }


# ---------------------------------------------------------------------------
# Trace-driven replay. Two on-disk formats:
#
# - Mooncake-format JSONL (the reference synthesizer's input —
#   reference: benchmarks/data_generator/synthesizer.py:48-75): one record
#   per request, {"timestamp": ms, "input_length": N, "output_length": M,
#   "hash_ids": [...]}, where hash_ids name the request's 512-token prefix
#   blocks and SHARED ids across requests encode the real reuse structure.
#   Tokens are reconstructed deterministically per hash id, so two requests
#   sharing hash ids share the exact same token prefix — the radix
#   structure of the production trace is preserved while the actual text
#   (which the trace does not contain) is synthesized.
#
# - Our own request JSONL ({"token_ids": [...], "max_tokens": N,
#   "arrival_s": t} per line; save_request_jsonl writes it) — capture any
#   served workload and replay it bit-for-bit.
# ---------------------------------------------------------------------------


def from_mooncake_trace(
    path,
    vocab_size: int = 32000,
    block_size: int = 512,
    speedup_ratio: float = 1.0,
    max_requests: int | None = None,
    seed: int = 0,
) -> list[Request]:
    """Rebuild a replayable request list from a Mooncake-format trace,
    preserving its prefix-reuse structure and (speedup-scaled) arrival
    times."""
    import json

    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    if max_requests is not None:
        records = records[:max_requests]

    # Pass 1: hash-id occurrence counts (a block shared by 2+ requests is
    # "context" in the reference's terms — it is what routers/caches can
    # reuse).
    counts: dict[int, int] = {}
    for rec in records:
        for h in rec.get("hash_ids", []):
            counts[h] = counts.get(h, 0) + 1

    runs: dict[int, list[int]] = {}

    def run_for(h: int) -> list[int]:
        if h not in runs:
            rng = np.random.default_rng((seed + 1) * 1_000_003 + int(h))
            runs[h] = rng.integers(0, vocab_size, block_size).tolist()
        return runs[h]

    reqs: list[Request] = []
    t0 = None
    for i, rec in enumerate(records):
        ts = float(rec.get("timestamp", 0)) / 1000.0
        t0 = ts if t0 is None else t0
        hash_ids = list(rec.get("hash_ids", []))
        isl = int(rec.get("input_length", block_size * len(hash_ids)))
        tokens: list[int] = []
        shared = 0
        still_shared = True
        for j, h in enumerate(hash_ids):
            n = min(block_size, isl - j * block_size)
            if n <= 0:
                break
            tokens += run_for(h)[:n]
            if still_shared and counts.get(h, 0) > 1:
                shared += n
            else:
                still_shared = False
        if len(tokens) < isl:  # tail beyond hashed blocks = unique suffix
            rng = np.random.default_rng((seed + 1) * 7_000_003 + i)
            tokens += rng.integers(0, vocab_size, isl - len(tokens)).tolist()
        reqs.append(Request(
            token_ids=tokens,
            max_tokens=max(1, int(rec.get("output_length", 1))),
            arrival_s=max(0.0, (ts - t0) / max(speedup_ratio, 1e-9)),
            prefix_len=shared,
            request_id=f"trace-{i}",
        ))
    return reqs


def save_request_jsonl(reqs: list[Request], path) -> None:
    """Write requests in our replayable capture format."""
    import json

    # Streamed line-by-line; a torn capture fails replay loudly.
    # dynalint: allow[DT013] bench artifact regenerated per run
    with open(path, "w") as f:
        for r in reqs:
            f.write(json.dumps({
                "token_ids": r.token_ids,
                "max_tokens": r.max_tokens,
                "arrival_s": r.arrival_s,
                "prefix_len": r.prefix_len,
                "request_id": r.request_id,
            }) + "\n")


def load_request_jsonl(path) -> list[Request]:
    import json

    reqs = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            reqs.append(Request(
                token_ids=list(rec["token_ids"]),
                max_tokens=int(rec.get("max_tokens", 1)),
                arrival_s=float(rec.get("arrival_s", 0.0)),
                prefix_len=int(rec.get("prefix_len", 0)),
                request_id=rec.get("request_id") or f"replay-{i}",
            ))
    return reqs
