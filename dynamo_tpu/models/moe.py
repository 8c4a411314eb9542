"""Sparse Mixture-of-Experts MLP block with expert-parallel sharding.

The stage-5 prerequisite (BASELINE.md: DeepSeek-R1 671B on multi-host) the
reference never had to build — it delegated intra-model parallelism to
backend engines (SURVEY §2 "Parallelism strategies"). Here the MoE layer is
first-class JAX: a top-k softmax router and a dense einsum formulation of
the expert MLPs, with the expert dimension sharded over the mesh's ``ep``
axis and the per-expert intermediate dim over ``tp`` (specs in
``moe_param_specs``). GSPMD turns the expert-dim contractions into
psums over ep — no hand-written all-to-all at this stage.

Two exact expert paths, chosen by the expert count alone
(``GROUPED_MIN_EXPERTS``): below it the dense formulation computes every
expert on every token and masks by the router's top-k gates (O(E/topk)
extra FLOPs, fine for Mixtral's 8); from it up the grouped path sorts the
routed rows by expert and runs one grouped matrix product an expert
projection (``jax.lax.ragged_dot``) over the experts that have rows — no
capacity, no dropped contribution, whatever the routing.
"""

from __future__ import annotations

import contextlib
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
    # e > 0: the chosen experts' weights are s / (sum s + e), as LFM2's
    # modelling code normalises them (1e-6); 0 = s / sum s.
    norm_topk_eps: float = 0.0
    routed_scaling_factor: float = 1.0
    # Group-limited selection ("noaux_tc"): experts split into n_group
    # groups; each group scores as the sum of its top-2 biased scores and
    # only the topk_group best groups stay eligible.
    n_group: int = 1
    topk_group: int = 1
    # The share of the experts held here (docs/architecture/
    # expert_share.md): the router keeps its num_experts outputs and its
    # experts a token; the stacked weights are those of experts
    # [expert_held_offset, expert_held_offset + num_experts_held), and a
    # row's result is the weighted sum over those of its experts that are
    # held. 0 = every expert is here.
    num_experts_held: int = 0
    expert_held_offset: int = 0
    # Clamp before the activation: gate to at most L, up into [-L, L];
    # 0 = none.
    swiglu_limit: float = 0.0
    # An expert's form: "swiglu" (three matrices, silu(gate) * up) or
    # "relu2" (two, relu(x W1)^2 W2: no ``w_gate`` in the params).
    act: str = "swiglu"
    # The width the experts read and write where it is not the router's
    # (experts in a latent: ``moe_mlp``'s ``expert_x``); 0 = hidden_size.
    expert_input_size: int = 0

    @property
    def experts_here(self) -> int:
        return self.num_experts_held or self.num_experts

    @property
    def grouped(self) -> bool:
        """Which exact expert path ``moe_mlp`` runs: the grouped one from
        ``GROUPED_MIN_EXPERTS`` experts held here up, the dense one below."""
        return self.experts_here >= GROUPED_MIN_EXPERTS


# Dense runs E/topk times the selected FLOPs and reads every expert; the
# grouped path pays a sort and two gathers. Mixtral's 8 experts stay dense.
GROUPED_MIN_EXPERTS = 16


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


def moe_route(
    params: dict, x: jnp.ndarray, cfg: MoeConfig
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routing → (expert ids [T, k], their gate weights [T, k]).

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
        total = gates_k.sum(axis=-1, keepdims=True)
        gates_k = gates_k / (
            total + cfg.norm_topk_eps if cfg.norm_topk_eps
            else jnp.maximum(total, 1e-20)
        )
    return topi, gates_k * cfg.routed_scaling_factor


def moe_router(params: dict, x: jnp.ndarray, cfg: MoeConfig) -> jnp.ndarray:
    """``moe_route`` as dense gates [T, E_held] with mass only on those of
    each token's selected experts that are held here (all of them where
    every expert is)."""
    topi, gates_k = moe_route(params, x, cfg)
    gates = jnp.zeros((x.shape[0], cfg.num_experts), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], topi
    ].set(gates_k)
    lo = cfg.expert_held_offset
    return gates[:, lo : lo + cfg.experts_here]


def clamp_swiglu(gate, up, limit: float):
    """A swiglu limit ``L > 0`` before the activation: the gate to at most
    ``L``, the up projection into ``[-L, L]``; 0 = none."""
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return gate, up


def _act(gate, up, cfg: MoeConfig):
    if cfg.act == "relu2":                  # non-gated: ``gate`` is None
        return jnp.square(jax.nn.relu(up))
    gate, up = clamp_swiglu(gate, up, cfg.swiglu_limit)
    return jax.nn.silu(gate) * up


def expert_weights(params: dict, cfg: MoeConfig) -> tuple:
    """The stacked expert matrices in the order the products take them:
    (gate, up, down), or (up, down) for a non-gated expert."""
    names = ("w_up", "w_down") if cfg.act == "relu2" else (
        "w_gate", "w_up", "w_down")
    return tuple(params[n] for n in names)


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
    params: dict, x: jnp.ndarray, cfg: MoeConfig, mesh=None, valid=None,
    expert_x=None,
) -> jnp.ndarray:
    """x [T, D] → [T, D] through top-k routed experts, exactly. With
    ``expert_x`` [T, Z] (experts in a latent: ``cfg.expert_input_size``)
    the router reads ``x`` and the experts read and write ``expert_x``'s
    width: [T, Z] out.

    Below ``GROUPED_MIN_EXPERTS`` experts: every expert for every token,
    masked by the gates (O(E/topk) extra FLOPs; GSPMD shards it). From
    there up: ``_moe_mlp_grouped``. ``mesh`` places the grouped path's
    products per shard. ``valid`` [T] marks the rows that hold a token
    (budget padding does not): an expert share drops the others with the
    rows routed elsewhere."""
    width = cfg.expert_input_size or cfg.hidden_size
    if (x if expert_x is None else expert_x).shape[-1] != width:
        raise ValueError(
            f"the experts read rows {width} wide (MoeConfig."
            "expert_input_size): hand a latent in as expert_x"
        )
    if cfg.grouped:
        with jax.named_scope("moe_grouped_ffn"):
            return _moe_mlp_grouped(params, x, cfg, mesh, valid, expert_x)
    gates = moe_router(params, x, cfg)
    xf = (x if expert_x is None else expert_x).astype(jnp.float32)
    up = _expert_einsum("td,edi->tei", xf, params["w_up"])
    gate = None
    if cfg.act != "relu2":
        gate = _expert_einsum("td,edi->tei", xf, params["w_gate"])
    h = _act(gate, up, cfg)                                       # [T, E, I]
    out = _expert_einsum("tei,eid->ted", h, params["w_down"])
    return jnp.einsum("ted,te->td", out, gates).astype(x.dtype)


class _Collected(list):
    """One traced scalar a grouped expert layer: the experts held here that
    had a row; ``rows_held`` beside it, the routed (row, expert) pairs that
    landed on an expert held here."""

    def __init__(self):
        super().__init__()
        self.rows_held: list = []

    def results(self) -> tuple:
        """(experts hit, rows held) as tuples: what a jitted function that
        traced expert layers hands out for ``note_experts_hit``."""
        return tuple(self), tuple(self.rows_held)


#: While a step program is traced under ``collect_experts_hit``.
_EXPERTS_HIT: _Collected | None = None


@contextlib.contextmanager
def collect_experts_hit():
    """Collect, while the caller traces a model function, how many experts
    had a row in each grouped expert layer (what the layer's kernels had
    to read: routing decides it, so only the program can count it) and, as
    the list's ``rows_held``, how many routed rows landed here. Yields the
    list the layers append their traced scalars to."""
    global _EXPERTS_HIT
    before, _EXPERTS_HIT = _EXPERTS_HIT, _Collected()
    try:
        yield _EXPERTS_HIT
    finally:
        _EXPERTS_HIT = before


def note_experts_hit(hit, rows_held) -> None:
    """Hand the enclosing ``collect_experts_hit`` the counts that an inner
    jitted function collected in ITS trace and returned as results (models/
    llama.py ``_layer``): a traced scalar cannot cross a ``jax.jit``
    boundary by a side effect."""
    if _EXPERTS_HIT is not None:
        _EXPERTS_HIT.extend(hit)
        _EXPERTS_HIT.rows_held.extend(rows_held)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


#: rows a tile of the grouped matmul kernel: a group of fewer rows still
#: reads its expert's whole matrix once, which is what bounds decode.
GMM_ROWS = 128
#: the most one tile of an expert's matrix may hold (the kernel keeps two
#: in flight in VMEM): an expert's whole [K, N] where it is under this
#: (SDAR's 2048 x 768 and Ling's 2560 x 768 in bf16 are), else its
#: columns in tiles (Command A+'s 4096 x 4096: eight of 512; whole, the
#: chip's compiler refuses the kernel for 68 MB of VMEM).
GMM_TILE_BYTES = 4 * 1024 * 1024


def gmm_tile(K: int, N: int, itemsize: int) -> tuple[int, int]:
    """``(tk, tn)`` of the grouped matmul kernel's weight tile: all of K
    (one pass over a row tile, no accumulation across tiles) and as many
    columns as ``GMM_TILE_BYTES`` hold: the largest multiple of 128 that
    divides N (2,688 = 21 x 128 -> 896 under K 1,024; a power of two times
    128 halves as it always did), the smallest where none fits, all of N
    where N is no multiple of 128 (the kernel's gate refuses that shape)."""
    if N % 128 or K * N * itemsize <= GMM_TILE_BYTES:
        return K, N
    fits = [
        tn for tn in range(128, N, 128)
        if N % tn == 0 and K * tn * itemsize <= GMM_TILE_BYTES
    ]
    return K, max(fits, default=128)


def _grouped_dot(rows, w, sizes, row_expert):
    """``rows[r] @ w[expert of r]`` for rows sorted by expert, ``sizes``
    rows an expert: one grouped product, float32 out. On the Pallas path
    (ops/attention.py ``pallas_enabled``: a TPU, or interpret mode by
    ``DYNAMO_TPU_PALLAS=1``) that is the megablox grouped matmul kernel,
    each tile an expert's whole [K, N] matrix (its columns in tiles where
    that is over ``GMM_TILE_BYTES``: ``gmm_tile``) against up to
    ``GMM_ROWS`` of its rows (XLA's own ``ragged_dot`` read 26 % of the bytes bound at
    SDAR's widths, this 73 %: my chip run, PR 36); elsewhere, and for
    shapes the kernel's tiling does not take, ``jax.lax.ragged_dot``. A
    quantized stacked weight (ops/quant.py ``{"q", "s"}``, scales per
    (expert, out channel)) multiplies in its storage values and scales
    each row's result."""
    from dynamo_tpu.ops.attention import pallas_enabled
    from dynamo_tpu.ops.quant import is_quantized

    if is_quantized(w):
        q = w["q"].astype(rows.dtype)
    else:
        q, rows = w, rows.astype(w.dtype)
    (M, K), N = rows.shape, q.shape[-1]
    tm = min(GMM_ROWS, M)
    if pallas_enabled() and not (M % tm or tm % 8 or K % 128 or N % 128):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        out = gmm(
            rows, q, sizes, jnp.float32,
            (tm, *gmm_tile(K, N, q.dtype.itemsize)),
            interpret=_interpret(),
        )
    else:
        out = jax.lax.ragged_dot(
            rows, q, sizes, preferred_element_type=jnp.float32
        )
    if is_quantized(w):
        out = out * w["s"].astype(jnp.float32)[row_expert]
    return out


def _moe_mlp_grouped(
    params: dict, x: jnp.ndarray, cfg: MoeConfig, mesh=None, valid=None,
    expert_x=None,
) -> jnp.ndarray:
    """The dropless grouped path: the T*k routed (token, expert) rows
    sorted by expert, one ``ragged_dot`` an expert projection over the
    groups that have rows (an expert without rows is an empty group),
    each row's result weighted by its gate and summed back to its token.
    Exact for every routing; static shapes ([T*k, .] whatever the
    routing), so one program a budget rung like the dense path.

    Under a mesh the products run per shard (``shard_map``): each holds
    its ``tp`` slice of every expert's width and, over ``ep``, its own
    experts, whose rows lie together in the sorted order; the partial
    results meet in one all-reduce."""
    T = x.shape[0]
    E, k = cfg.experts_here, cfg.num_experts_per_tok
    topi, gates_k = moe_route(params, x, cfg)               # [T, k] each
    if expert_x is not None:       # the experts' own width (a latent)
        x = expert_x
    D = x.shape[1]
    flat_e = topi.reshape(-1) - cfg.expert_held_offset       # [T*k]
    held = None
    if cfg.num_experts_held:
        # Rows routed to an expert that is not held (and budget padding)
        # are dropped before the sort: they sort behind every held
        # expert's rows, belong to no group, and are zeroed behind the
        # products (the sentinel E falls outside `sizes`: dropped).
        held = (flat_e >= 0) & (flat_e < E)
        if valid is not None:
            held &= jnp.repeat(valid, k)
        flat_e = jnp.where(held, flat_e, E)
    order = jnp.argsort(flat_e, stable=True)                 # by expert
    row_expert = flat_e[order]
    if held is not None:
        row_expert = jnp.minimum(row_expert, E - 1)
    rows = x[order // k]                                     # [T*k, D]
    sizes = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    if _EXPERTS_HIT is not None:
        _EXPERTS_HIT.append((sizes > 0).sum().astype(jnp.int32))
        _EXPERTS_HIT.rows_held.append(sizes.sum())

    def ffn(rows, sizes, row_expert, *w):
        *w_gate, w_up, w_down = w          # no gate: a non-gated expert
        gate = (
            _grouped_dot(rows, w_gate[0], sizes, row_expert)
            if w_gate else None
        )
        up = _grouped_dot(rows, w_up, sizes, row_expert)
        h = _act(gate, up, cfg).astype(rows.dtype)
        return _grouped_dot(h, w_down, sizes, row_expert)    # [T*k, D] f32

    axes = {
        a: n for a, n in (dict(mesh.shape) if mesh is not None else {}).items()
        if a in ("ep", "tp") and n > 1
    }
    weights = expert_weights(params, cfg)
    if not axes:
        y = ffn(rows, sizes, row_expert, *weights)
    else:
        ep = axes.get("ep", 1)
        El = E // ep

        def shard(rows, sizes, row_expert, *w):
            if ep == 1:
                return jax.lax.psum(ffn(rows, sizes, row_expert, *w), "tp")
            # This shard's experts [r*El, (r+1)*El): their rows are one
            # run of the sorted order. Bring the run to the front, run the
            # local groups, zero what lies behind them, put it back.
            r = jax.lax.axis_index("ep")
            local = jax.lax.dynamic_slice_in_dim(sizes, r * El, El)
            first = jnp.where(jnp.arange(E) < r * El, sizes, 0).sum()
            shift = lambda a, n: jnp.roll(a, n, axis=0)
            y = ffn(
                shift(rows, -first), local,
                shift(row_expert, -first) - r * El, *w,
            )
            mine = jnp.arange(T * k) < local.sum()
            y = shift(jnp.where(mine[:, None], y, 0.0), first)
            return jax.lax.psum(y, tuple(axes))

        tp = "tp" if "tp" in axes else None
        e_ax = "ep" if ep > 1 else None
        specs = moe_weight_specs(weights, e_ax, tp)
        y = jax.shard_map(
            shard, mesh=mesh,
            in_specs=(P(), P(), P(), *specs), out_specs=P(),
            check_vma=False,
        )(rows, sizes, row_expert, *weights)
    y = y * gates_k.reshape(-1)[order][:, None]
    if held is not None:
        # (Rows behind the last group are whatever the kernel left there.)
        y = jnp.where(held[order][:, None], y, 0.0)
    back = jnp.argsort(order)                                # row of (t, j)
    return y[back].reshape(T, k, D).sum(axis=1).astype(x.dtype)


def moe_weight_specs(weights, e_ax, tp):
    """``shard_map`` specs of (w_gate, w_up, w_down) (or (w_up, w_down)),
    plain or quantized: experts over ``e_ax``, the experts' width over
    ``tp`` (the last matrix's rows, every other's columns)."""
    from dynamo_tpu.ops.quant import is_quantized

    def spec(w, wide_last: bool):
        full = P(e_ax, None, tp) if wide_last else P(e_ax, tp, None)
        if not is_quantized(w):
            return full
        return {"q": full, "s": P(e_ax, tp) if wide_last else P(e_ax, None)}

    return tuple(
        spec(w, i < len(weights) - 1) for i, w in enumerate(weights))


def shard_moe_params(params: dict, mesh) -> dict:
    from jax.sharding import NamedSharding

    specs = moe_param_specs()
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params.items()
    }
