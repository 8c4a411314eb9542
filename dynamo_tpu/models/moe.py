"""Sparse Mixture-of-Experts MLP block with expert-parallel sharding.

The stage-5 prerequisite (BASELINE.md: DeepSeek-R1 671B on multi-host) the
reference never had to build — it delegated intra-model parallelism to
backend engines (SURVEY §2 "Parallelism strategies"). Here the MoE layer is
first-class JAX: a top-k softmax router and a dense einsum formulation of
the expert MLPs, with the expert dimension sharded over the mesh's ``ep``
axis and the per-expert intermediate dim over ``tp`` (specs in
``moe_param_specs``). GSPMD turns the expert-dim contractions into
psums over ep — no hand-written all-to-all at this stage; a capacity-based
dispatch kernel is the later optimization.

The dense formulation computes every expert on every token and masks by
the router's top-k gates. That is O(E/topk) extra FLOPs — acceptable for
correctness scaffolding and small expert counts; the Pallas blocked
dispatch replaces it when perf work reaches MoE.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class MoeConfig:
    hidden_size: int = 64
    intermediate_size: int = 128   # per expert
    num_experts: int = 8
    num_experts_per_tok: int = 2
    # Router scoring (DeepSeek-V3/R1 uses "sigmoid" with a per-expert
    # selection-bias correction; Mixtral/V2 use "softmax").
    gating: str = "softmax"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # Group-limited selection ("noaux_tc"): experts split into n_group
    # groups; each group scores as the sum of its top-2 biased scores and
    # only the topk_group best groups stay eligible.
    n_group: int = 1
    topk_group: int = 1
    # Expert execution: "dense" (all experts, gate-masked), "capacity"
    # (per-expert token buffers, only selected FLOPs — see moe_mlp), or
    # "auto" (capacity when num_experts >= AUTO_CAPACITY_MIN_EXPERTS).
    dispatch: str = "auto"
    capacity_factor: float = 2.0

    @property
    def resolved_dispatch(self) -> str:
        """Expert-count half of the "auto" rule; moe_mlp additionally
        requires enough tokens per call (see auto_capacity_ok) — at
        decode-size T the capacity C collapses toward 1 and collisions
        DROP routed contributions, so "auto" falls back to dense there
        (dense at tiny T is cheap anyway)."""
        if self.dispatch == "auto":
            return (
                "capacity"
                if self.num_experts >= AUTO_CAPACITY_MIN_EXPERTS
                else "dense"
            )
        return self.dispatch

    def auto_capacity_ok(self, num_tokens: int) -> bool:
        """Token-count guard for "auto": expect >= 2 tokens per expert
        so C = ceil(T*k/E * factor) stays comfortably above collision
        range. Explicit dispatch="capacity" bypasses this (caller's
        choice)."""
        return (
            num_tokens * self.num_experts_per_tok >= 2 * self.num_experts
        )


# Dense runs E/topk times the selected FLOPs; capacity pays scatter/gather
# overhead. E=16 is the measured crossover region.
AUTO_CAPACITY_MIN_EXPERTS = 16


def init_moe_params(key: jax.Array, cfg: MoeConfig, dtype=jnp.float32) -> dict:
    D, I, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts

    def dense(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, jnp.float32) / (fan_in**0.5)
        ).astype(dtype)

    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "w_router": dense(k1, (D, E), D),
        "w_gate": dense(k2, (E, D, I), D),
        "w_up": dense(k3, (E, D, I), D),
        "w_down": dense(k4, (E, I, D), I),
    }


def moe_param_specs() -> dict:
    """Experts over ep, per-expert intermediate over tp; the router is
    replicated (it is tiny and every token needs it)."""
    return {
        "w_router": P(),
        "w_gate": P("ep", None, "tp"),
        "w_up": P("ep", None, "tp"),
        "w_down": P("ep", "tp", None),
    }


def moe_router(params: dict, x: jnp.ndarray, cfg: MoeConfig) -> jnp.ndarray:
    """Top-k routing → dense gates [T, E] with mass only on each token's
    selected experts.

    softmax (Mixtral/DeepSeek-V2): probs = softmax over all experts, top-k
    by prob, optionally renormalized over the selection.
    sigmoid (DeepSeek-V3/R1): probs = sigmoid(logits); SELECTION ranks
    probs + per-expert bias (the load-balancing correction term,
    `router_bias`), but the WEIGHTS are the raw probs of the selected
    experts, renormalized, then scaled by routed_scaling_factor.
    """
    T = x.shape[0]
    logits = (x.astype(jnp.float32) @ params["w_router"].astype(jnp.float32))
    if cfg.gating == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        sel = probs + params.get("router_bias", jnp.zeros(()))
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        sel = probs
    if cfg.n_group > 1:
        # Group-limited eligibility: keep only the topk_group best groups
        # in selection (weights still come from the raw probs). Group
        # score follows the checkpoint family: V3/R1 sigmoid ("noaux_tc")
        # sums each group's top-2 biased scores; V2 softmax
        # ("group_limited_greedy") takes the group max.
        E = cfg.num_experts
        per = E // cfg.n_group
        grouped = sel.reshape(T, cfg.n_group, per)
        if cfg.gating == "sigmoid":
            top2, _ = jax.lax.top_k(grouped, min(2, per))        # [T, G, 2]
            group_scores = top2.sum(axis=-1)                     # [T, G]
        else:
            group_scores = grouped.max(axis=-1)                  # [T, G]
        _, keep = jax.lax.top_k(group_scores, cfg.topk_group)    # [T, kg]
        group_mask = jnp.zeros_like(group_scores).at[
            jnp.arange(T)[:, None], keep
        ].set(1.0)
        sel = jnp.where(
            jnp.repeat(group_mask, per, axis=-1) > 0, sel, -jnp.inf
        )
    _, topi = jax.lax.top_k(sel, cfg.num_experts_per_tok)        # [T, k]
    gates_k = jnp.take_along_axis(probs, topi, axis=-1)          # [T, k]
    if cfg.norm_topk_prob:
        gates_k = gates_k / jnp.maximum(
            gates_k.sum(axis=-1, keepdims=True), 1e-20
        )
    gates_k = gates_k * cfg.routed_scaling_factor
    return jnp.zeros_like(logits).at[
        jnp.arange(T)[:, None], topi
    ].set(gates_k)


def _expert_einsum(pattern: str, x: jnp.ndarray, w) -> jnp.ndarray:
    """Einsum against a stacked expert weight that may be int8-quantized
    (ops/quant.py dict {"q", "s"} with per-(expert, out-channel) scales —
    the scale multiplies the [T, E, out] result, broadcast over tokens)."""
    from dynamo_tpu.ops.quant import is_quantized

    if not is_quantized(w):
        return jnp.einsum(pattern, x, w.astype(jnp.float32))
    out = jnp.einsum(pattern, x, w["q"].astype(jnp.float32))
    return out * w["s"][None]  # s [E, out] → [1, E, out]


def moe_mlp(
    params: dict, x: jnp.ndarray, cfg: MoeConfig, mesh=None
) -> jnp.ndarray:
    """x [T, D] → [T, D] through top-k routed experts.

    dispatch="dense" computes every expert for every token and masks by
    the gates — exact, simple, O(E/topk) extra FLOPs; right for small
    expert counts and tiny tests. dispatch="capacity" gathers each
    expert's assigned tokens into fixed [E, C, D] buffers and runs only
    the selected experts' FLOPs (≈ topk/E of dense — at DeepSeek-R1
    scale, 256 experts top-8, that is 32× less MLP compute); tokens
    beyond an expert's capacity C = ceil(T·topk/E · factor) drop to zero
    contribution for that expert, the standard capacity-overflow rule.
    "auto" (default) picks by expert count. ``mesh`` (when ep > 1) pins
    the dispatch collectives explicitly — see _moe_mlp_capacity.
    """
    use_capacity = cfg.resolved_dispatch == "capacity" and (
        cfg.dispatch != "auto" or cfg.auto_capacity_ok(x.shape[0])
    )
    if use_capacity:
        return _moe_mlp_capacity(params, x, cfg, mesh)
    gates = moe_router(params, x, cfg)
    xf = x.astype(jnp.float32)
    up = _expert_einsum("td,edi->tei", xf, params["w_up"])
    gate = _expert_einsum("td,edi->tei", xf, params["w_gate"])
    h = jax.nn.silu(gate) * up                                    # [T, E, I]
    out = _expert_einsum("tei,eid->ted", h, params["w_down"])
    return jnp.einsum("ted,te->td", out, gates).astype(x.dtype)


def _moe_mlp_capacity(
    params: dict, x: jnp.ndarray, cfg: MoeConfig, mesh=None
) -> jnp.ndarray:
    """Capacity-dispatch formulation: scatter tokens to per-expert
    buffers, run per-expert SwiGLU as one [E, C, :] batched einsum (the
    expert dim stays sharded over ep), gather weighted results back.
    Static shapes throughout — C derives from T at trace time — so XLA
    compiles one program per prefill bucket exactly like the dense path.

    With a mesh carrying ep > 1, the token buffers are PINNED ep-sharded
    (with_sharding_constraint), so the communication pattern is explicit
    and stable: the scatter lands as a dispatch to each expert shard
    (serving activations are replicated across ep, so this is a local
    slice, not an all-to-all), each shard computes ONLY its local
    experts' [E/ep, C, :] einsums, and the token-side gather of expert
    outputs is the combine step. GSPMD left unpinned was free to
    replicate the buffers and waste the ep axis entirely."""
    T, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    gates = moe_router(params, x, cfg)                      # [T, E]
    gate_vals, expert_idx = jax.lax.top_k(gates, k)         # [T, k]
    C = max(1, int(math.ceil(T * k / E * cfg.capacity_factor)))

    flat_e = expert_idx.reshape(-1)                         # [T*k]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    # rank of each entry within its expert (arrival order)
    pos = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(-1)
    keep = pos < C
    idx_c = jnp.where(keep, pos, C)                         # C = drop slot

    ep_sharded = (
        mesh is not None and dict(mesh.shape).get("ep", 1) > 1
    )

    def pin(arr, spec):
        if not ep_sharded:
            return arr
        from jax.sharding import NamedSharding

        return jax.lax.with_sharding_constraint(
            arr, NamedSharding(mesh, spec)
        )

    xf = x.astype(jnp.float32)
    xx = jnp.repeat(xf, k, axis=0)                          # [T*k, D]
    buf = jnp.zeros((E, C, D), jnp.float32).at[flat_e, idx_c].set(
        xx, mode="drop"
    )
    buf = pin(buf, P("ep", None, None))
    gate = _expert_einsum3("ecd,edi->eci", buf, params["w_gate"])
    up = _expert_einsum3("ecd,edi->eci", buf, params["w_up"])
    h = jax.nn.silu(gate) * up                              # [E, C, I]
    h = pin(h, P("ep", None, "tp"))
    out_e = _expert_einsum3("eci,eid->ecd", h, params["w_down"])
    out_e = pin(out_e, P("ep", None, None))

    y = out_e[flat_e, jnp.minimum(pos, C - 1)]              # [T*k, D]
    y = jnp.where(keep[:, None], y, 0.0)
    out = (y.reshape(T, k, D) * gate_vals[:, :, None]).sum(axis=1)
    return pin(out.astype(x.dtype), P(None, None))


def _expert_einsum3(pattern: str, x: jnp.ndarray, w) -> jnp.ndarray:
    """Batched-over-experts einsum against a possibly-quantized stacked
    weight; the [E, out] scale broadcasts onto the [E, C, out] result."""
    from dynamo_tpu.ops.quant import is_quantized

    if not is_quantized(w):
        return jnp.einsum(pattern, x, w.astype(jnp.float32))
    out = jnp.einsum(pattern, x, w["q"].astype(jnp.float32))
    return out * w["s"][:, None, :]


def shard_moe_params(params: dict, mesh) -> dict:
    from jax.sharding import NamedSharding

    specs = moe_param_specs()
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params.items()
    }
