"""Ask the TPU's compiler, without a TPU, whether the main path compiles.

The sandbox has libtpu installed and no chip attached: a v5e:2x2 topology
can be DESCRIBED and jit programs compiled for it (nothing runs). That
catches what interpret mode cannot — tile-misaligned slices, VMEM
overruns, unpartitionable kernels, programs that do not fit HBM — at the
real widths (Llama-3.2-1B / 3.1-8B attention: H=32, kvH=8, D=128
lane-padded, block 16) before any chip time is spent.

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture that SKIPS when it
cannot be — never at import, never in conftest, never autouse; every
compile runs in the test's own process (libtpu's lock belongs to the
worker that was handed this file); the persistent compile cache is off
around them (an entry compiled for a described chip cannot be read back
without one); and interpret mode is steered from here by monkeypatch,
not by an option of the program. A compile that passes is not a chip
run — `python chip_smoke.py` is.
"""

from __future__ import annotations

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.attention import AttnDispatch
from dynamo_tpu.ops.pallas import attention as phase_kernels
from dynamo_tpu.ops.pallas import ragged_attention as ragged_kernel

# The CLI's default engine sizes (dynamo_tpu/cli.py): 2048 blocks of 16
# tokens, 32 decode slots + 4 prefill lanes of metadata, 2048-token
# sequences (128 blocks each).
BS, NUM_BLOCKS, S, MAX_BLOCKS = 16, 2048, 36, 128
H, KVH, D = 32, 8, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp4(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("tp",))


@pytest.fixture
def mosaic(monkeypatch):
    """Compile the kernels for the chip (not the interpreter) with the
    persistent cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(ragged_kernel, "_interpret", lambda: False)
    monkeypatch.setattr(phase_kernels, "_interpret", lambda: False)
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_count(text: str) -> int:
    return text.count('custom_call_target="tpu_custom_call"')


_HLO_OP = re.compile(r" = \w+\[([\d,]+)\](?:\{[^}]*\})? ([\w\-]+)\(")


def _pool_copies(text: str, kv_heads: int, width: int = D) -> list[str]:
    """The compiled program's ``copy`` / ``transpose`` ops that produce a
    whole layer's pool of joined pages (in any view of it)."""
    pool = NUM_BLOCKS * 2 * BS * kv_heads * width
    out = []
    for line in text.splitlines():
        m = _HLO_OP.search(line)
        if m and m.group(2) in ("copy", "transpose") and (
            np.prod([int(x) for x in m.group(1).split(",")]) == pool
        ):
            out.append(line.strip()[:160])
    return out


def test_pool_copies_reads_an_hlo_line():
    pool = f"bf16[{NUM_BLOCKS},2,{BS},{KVH},{D}]"
    lay = "{4,3,2,1,0:T(8,128)(2,1)}"
    text = "\n".join([
        f"  %copy.1 = {pool}{lay} copy(%p.1), metadata={{}}",
        f"  %bitcast.2 = bf16[{NUM_BLOCKS},2,{BS * KVH},{D}]{lay} bitcast(%p.1)",
        f"  %t.3 = bf16[{NUM_BLOCKS},2,{BS * KVH},{D}] transpose(%p.1)",
        "  %copy.4 = bf16[256,32,128]{2,1,0} copy(%q)",
    ])
    assert [ln.split()[0] for ln in _pool_copies(text, KVH)] == [
        "%copy.1", "%t.3"]


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("T", [16, 256])
def test_ragged_kernel_compiles_at_served_widths(mosaic, one_chip, T, kv_dtype):
    i32 = partial(_sds, dtype=jnp.int32, sharding=one_chip)
    cache = _sds((NUM_BLOCKS * BS, KVH, D), jnp.dtype(kv_dtype), one_chip)
    scales = {}
    if kv_dtype == "int8":
        sc = _sds((NUM_BLOCKS, KVH), jnp.float32, one_chip)
        scales = {"k_scales": sc, "v_scales": sc}
    compiled = ragged_kernel.ragged_paged_attention_pallas.lower(
        _sds((T, H, D), jnp.bfloat16, one_chip), cache, cache,
        i32((S, MAX_BLOCKS)), i32((S,)), i32((S,)), i32((S,)), i32((S,)),
        block_size=BS, **scales,
    ).compile()
    assert _kernel_count(compiled.as_text()) == 1


@pytest.mark.parametrize(
    "heads,kv_heads,block,T,kv_dtype,window",
    [
        (8, 2, 1, 256, "bfloat16", 4096),  # mistral-7b-tp4: a chip's share
        (8, 2, 1, 256, "int8", 4096),
        (32, 4, 4, 512, "bfloat16", 0),    # sdar-30b-a3b: spans of a block of 4
        (32, 4, 4, 512, "int8", 0),
        # command-a-plus-ep8-l4: 16 queries a cached head, a window layer
        # and a full one at a budget of 1,024 under the kernel's stated
        # VMEM limit; both tiles fold by cached head and read K and V a
        # head pair at a stride from the slot's 32-bit words (PR 47); int8
        # pages take the same folds through a dequantised f32 slot
        (128, 8, 1, 1024, "bfloat16", 4096),
        (128, 8, 1, 1024, "bfloat16", 0),
        (128, 8, 1, 1024, "int8", 0),
        # no cell's: one, two and seven queries a cached head, where the
        # long tile's folded rows along the lanes are no multiple of 128
        (32, 32, 1, 256, "bfloat16", 0),   # a Llama-2-style model's 32 / 32
        (16, 8, 1, 256, "bfloat16", 4096),
        (56, 8, 1, 256, "bfloat16", 0),    # Qwen2's ratio at 8 KV heads
        (56, 8, 1, 256, "int8", 0),
    ],
)
def test_ragged_kernel_compiles_at_the_cells_chip_shapes(
    mosaic, one_chip, heads, kv_heads, block, T, kv_dtype, window
):
    """The benchmark's other per-chip shapes (PERF.md §4): two KV heads a
    chip under tp=4, the short tile of a diffusion block (by cached head,
    32 rows a head), and 128 query heads over 8 cached heads at a budget of
    1,024 (Mosaic's verdict on both folds' strided read and on VMEM at the
    long tile, ``VMEM_LIMIT``)."""
    i32 = partial(_sds, dtype=jnp.int32, sharding=one_chip)
    cache = _sds((NUM_BLOCKS * BS, kv_heads, D), jnp.dtype(kv_dtype), one_chip)
    scales = {}
    if kv_dtype == "int8":
        sc = _sds((NUM_BLOCKS, kv_heads), jnp.float32, one_chip)
        scales = {"k_scales": sc, "v_scales": sc}
    lanes = 129
    compiled = ragged_kernel.ragged_paged_attention_pallas.lower(
        _sds((T, heads, D), jnp.bfloat16, one_chip), cache, cache,
        i32((lanes, 256)), i32((lanes,)), i32((lanes,)), i32((lanes,)),
        i32((lanes,)), block_size=BS, window=window,
        diffusion_block=block, **scales,
    ).compile()
    assert _kernel_count(compiled.as_text()) == 1


@pytest.mark.parametrize(
    "heads,kv_heads,block,T,window",
    [
        (8, 2, 1, 256, 4096),     # mistral-7b-tp4: 16 KiB joined pages
        (32, 8, 1, 256, 4096),    # one chip's dense cells: 64 KiB
        (32, 4, 4, 512, 0),       # sdar-30b-a3b: 32 KiB, blocks of 4 rows
        (128, 8, 1, 1024, 4096),  # command-a-plus: a window layer
        (128, 8, 1, 1024, 0),     # and a full one
        (32, 32, 1, 256, 0),      # no GQA: 256 KiB pages, folds of 128 keys
        # lfm2-24b-a2b-l10: 64-wide heads stored a lane row (128) wide, at
        # the budget its cell serves
        (32, 8, 1, 512, 0),
    ],
)
def test_ragged_kernel_compiles_over_joined_pages(
    mosaic, one_chip, heads, kv_heads, block, T, window
):
    """A (k, v) layer's JOINED pages (PR 59) at the cells' per-chip shapes:
    ONE descriptor a page into one ring of ``2 * NBUF`` rows passes Mosaic
    within the kernel's VMEM, and the kernel's view of the array, ``[blocks,
    2, bs * kvH, D]``, costs no copy of the pool (a bitcast)."""
    i32 = partial(_sds, dtype=jnp.int32, sharding=one_chip)
    pages = _sds((NUM_BLOCKS, 2, BS, kv_heads, D), jnp.bfloat16, one_chip)
    lanes = 129
    compiled = ragged_kernel.ragged_paged_attention_pallas.lower(
        _sds((T, heads, D), jnp.bfloat16, one_chip), pages, None,
        i32((lanes, 256)), i32((lanes,)), i32((lanes,)), i32((lanes,)),
        i32((lanes,)), block_size=BS, window=window, diffusion_block=block,
    ).compile()
    text = compiled.as_text()
    assert _kernel_count(text) == 1
    assert _pool_copies(text, kv_heads) == []
    assert " copy(" not in text


@pytest.mark.parametrize("with_stats", [False, True])
def test_kv_sp_decode_kernel_compiles_at_served_widths(
    mosaic, one_chip, with_stats
):
    """The one phase-split kernel left, the paged decode kernel:
    ``--kv-sp`` runs the ragged batch through its strided with-stats
    form (AttnDispatch._kv_sp_decode); the plain form is what the
    oracle tests hold it to."""
    i32 = partial(_sds, dtype=jnp.int32, sharding=one_chip)
    cache = _sds((NUM_BLOCKS * BS, KVH, D), jnp.bfloat16, one_chip)
    kw = (
        {"with_stats": True, "page_stride": 4, "page_offset": i32((1,))}
        if with_stats
        else {}
    )
    decode = phase_kernels.paged_decode_attention_pallas.lower(
        _sds((32, H, D), jnp.bfloat16, one_chip), cache, cache,
        i32((32, MAX_BLOCKS)), i32((32,)), block_size=BS, **kw,
    ).compile()
    assert _kernel_count(decode.as_text()) == 1


def _unified_step_args(cfg: ModelConfig, T: int, sharding_of):
    """Shapes of one ``llama.unified`` dispatch at the CLI's default
    sizes; ``sharding_of(partition_spec)`` places each operand."""
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    from dynamo_tpu.parallel.sharding import kv_cache_spec, llama_param_specs

    specs = llama_param_specs(cfg)
    params = jax.tree.map(
        lambda a, s: _sds(a.shape, a.dtype, sharding_of(s)),
        params, specs,
    )
    # a (k, v) layer's pages as the engine serves a bfloat16 cache: joined
    # (EngineConfig.cache_form), heads over tp
    kv_sh = sharding_of(kv_cache_spec(False, form="joined"))
    kv = [
        (_sds((NUM_BLOCKS, 2, BS, cfg.num_kv_heads, D), jnp.bfloat16, kv_sh),)
        for _ in range(cfg.num_layers)
    ]
    i32 = partial(_sds, dtype=jnp.int32, sharding=sharding_of(P()))
    meta = (
        i32((T,)), i32((T,)), i32((T,)), i32((T,)), i32((S, MAX_BLOCKS)),
        i32((S,)), i32((S,)), i32((S,)), i32((S,)),
    )
    return params, kv, meta


def _compile_unified(cfg: ModelConfig, T: int, attn, sharding_of):
    params, kv, meta = _unified_step_args(cfg, T, sharding_of)

    def step(params, kv, *meta):
        logits, kv = llama.unified(cfg, params, kv, *meta, BS, attn=attn)
        return jnp.argmax(logits, axis=-1), kv

    return jax.jit(step, donate_argnums=(1,)).lower(
        params, kv, *meta
    ).compile()


def test_unified_step_1b_compiles_one_chip_and_tp4(mosaic, one_chip, tp4):
    """One whole Llama-3.2-1B unified step (16 layers, T=256, donated
    lane-padded caches) on one chip, and the same step head-sharded over
    the four chips of the host: a kernel per layer either way, about a
    quarter of the bytes per device, and the collectives tensor
    parallelism needs."""
    cfg = ModelConfig.llama32_1b()
    single = _compile_unified(
        cfg, 256, AttnDispatch(use_pallas=True), lambda _spec: one_chip
    )
    sharded = _compile_unified(
        cfg, 256, AttnDispatch(use_pallas=True, mesh=tp4),
        lambda spec: NamedSharding(tp4, spec),
    )
    one_text, tp_text = single.as_text(), sharded.as_text()
    assert _kernel_count(one_text) == cfg.num_layers
    assert _kernel_count(tp_text) == cfg.num_layers
    # the kernel's view of the joined pages is a bitcast, the write one
    # scatter in place: nothing copies a layer's pool
    assert _pool_copies(one_text, cfg.num_kv_heads) == []
    assert _pool_copies(tp_text, cfg.num_kv_heads // 4) == []
    assert "all-reduce" in tp_text and "all-reduce" not in one_text
    one_bytes = single.memory_analysis().argument_size_in_bytes
    tp_bytes = sharded.memory_analysis().argument_size_in_bytes
    # Weights + KV of the 1B at these sizes are ~4.6 GB on one chip.
    assert 4.0e9 < one_bytes < 5.5e9, one_bytes
    assert 0.2 < tp_bytes / one_bytes < 0.3, (tp_bytes, one_bytes)


@pytest.mark.parametrize("T", [1024, 512])
def test_joined_write_lays_no_pool_out_anew_at_the_wide_rungs(
    mosaic, one_chip, T
):
    """The joined pages' write at the budgets the long-context cells run
    (PR 59): indexed on the pages' own (block, row) axes, XLA's scatter at
    1,024 rows chose a layout of its own for the WHOLE pool and copied it
    in and out a layer (3.6 GB of temporaries in the Command A+ step);
    as rows of ``[blocks * 2 * bs, kvH, D]`` it is the two arrays' row
    scatter and writes in place."""
    cfg = ModelConfig.llama32_1b().scaled(num_layers=2)
    compiled = _compile_unified(
        cfg, T, AttnDispatch(use_pallas=True), lambda _spec: one_chip
    )
    text = compiled.as_text()
    assert _kernel_count(text) == cfg.num_layers
    assert _pool_copies(text, cfg.num_kv_heads) == []
    pool = NUM_BLOCKS * 2 * BS * cfg.num_kv_heads * D * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool


def test_sdar_block_step_compiles_at_published_widths(
    mosaic, one_chip, monkeypatch
):
    """Two whole SDAR-30B-A3B layers of one unified step at T=256 under
    the mask by block, at the published widths: the ragged kernel with
    ``diffusion_block=4`` and the grouped expert path's three grouped
    matmul kernels a layer (128 experts of [2048, 768], 2048 routed rows)
    pass the chip's compiler."""
    from dynamo_tpu.models import moe

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    cfg = ModelConfig.sdar_30b_a3b().scaled(num_layers=2)
    compiled = _compile_unified(
        cfg, 256, AttnDispatch(use_pallas=True), lambda _spec: one_chip
    )
    text = compiled.as_text()
    # a layer: the attention kernel and gate, up, down
    assert _kernel_count(text) == 4 * cfg.num_layers, _kernel_count(text)
    assert "all-reduce" not in text


@pytest.mark.slow  # 35 s of many-threaded compiling beside the suite's timing-gated tests
def test_ling_share_step_compiles_at_published_widths(
    mosaic, one_chip, monkeypatch
):
    """The whole of ``ling-3.0-flash-ep4-l8`` (two dense layers and one
    group of six over 128 of 512 experts, a quarter of the vocabulary) in
    one unified step at T=256 with the state table of 128 lanes: the
    delta-rule kernels (two a recurrent layer, the state aliased in
    place), the ragged kernel over one cached head of 640, the grouped
    expert path's kernels, within one chip's memory."""
    from dynamo_tpu.models import moe
    from dynamo_tpu.ops.pallas import kda

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    monkeypatch.setattr(kda, "_interpret", lambda: False)
    cfg = ModelConfig.ling_30_flash_ep4_l8()
    sds = partial(_sds, sharding=one_chip)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
    lanes, rows = 129, 132
    page = sds((20000 * BS, 1, 640), jnp.bfloat16)
    kv = [(page, page) if cfg.layer_kind(li) == "attn" else ()
          for li in range(cfg.num_layers)]
    rec = [
        (sds((lanes, 32, 128, 128), jnp.float32),
         sds((lanes, 3, 3 * 32 * 128), jnp.bfloat16))
        for _ in cfg.recurrent_layers
    ]
    i32 = partial(sds, dtype=jnp.int32)
    T = 256
    meta = (
        i32((T,)), i32((T,)), i32((T,)), i32((T,)), i32((rows, 256)),
        i32((rows,)), i32((rows,)), i32((rows,)), i32((rows,)),
    )

    def step(params, kv, rec, slot, *meta):
        logits, kv, rec = llama.unified(
            cfg, params, kv, *meta, BS, attn=AttnDispatch(use_pallas=True),
            rec_state=rec, state_slot=slot,
        )
        return jnp.argmax(logits, axis=-1), kv, rec

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, kv, rec, i32((rows,)), *meta
    ).compile()
    # 7 recurrent layers x (kda_recurrent, kda_chunk), the one ragged
    # kernel, 6 expert layers x (gate, up, down)
    assert _kernel_count(compiled.as_text()) == 14 + 1 + 18
    mem = compiled.memory_analysis()
    # weights 10.54 GB, state 1.96 GB, pages 0.82 GB: all arguments, and
    # the state and the pages alias their outputs
    assert 13.0e9 < mem.argument_size_in_bytes < 13.7e9
    assert mem.temp_size_in_bytes < 1.5e9, mem.temp_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 15.5e9


@pytest.mark.parametrize("T", [256, 16])
def test_kda_kernels_compile_at_published_widths(
    mosaic, one_chip, monkeypatch, T
):
    """The delta rule over one dispatch at Ling-3.0-flash's widths (32
    heads of 128 x 128 float32, a state table of 129 slots): the one-row
    lanes' kernel and the chunk kernel (a tile's strided reads of the flat
    rows, its batched products at float32 contract precision, the state
    and two tiles' rows within VMEM), at the top rung and at the lowest."""
    from dynamo_tpu.ops import linear_attention as la
    from dynamo_tpu.ops.pallas import kda

    monkeypatch.setattr(kda, "_interpret", lambda: False)
    sds = partial(_sds, sharding=one_chip)
    i32, f32 = partial(sds, dtype=jnp.int32), jnp.float32
    lanes, rows = 129, 132
    compiled = jax.jit(
        partial(la.kda_ragged, use_pallas=True, lower_bound=-5.0),
        donate_argnums=(5,),
    ).lower(
        *[sds((T, 32, 128), f32)] * 4, sds((T, 32), f32),
        sds((lanes, 32, 128, 128), f32), i32((T,)), i32((T,)),
        *[i32((rows,))] * 4,
    ).compile()
    assert _kernel_count(compiled.as_text()) == 2
    mem = compiled.memory_analysis()
    # the state is updated in place by both kernels: no second table
    assert mem.alias_size_in_bytes >= lanes * 32 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 0.2e9, mem.temp_size_in_bytes


@pytest.mark.slow  # a minute of many-threaded compiling beside the suite's timing-gated tests
def test_nemotron_share_step_compiles_at_published_widths(
    mosaic, one_chip, monkeypatch
):
    """The whole of ``nemotron-3-super-ep4-l11`` (five Mamba-2 mixers, five
    expert layers over 128 of 512 latent experts, one attention layer, a
    quarter of the vocabulary) in one unified step at T=256 with the state
    table of 128 lanes: the state-space kernels (two a mixer, the state
    aliased in place), the ragged kernel over two cached heads of 128, the
    grouped expert path's kernels (two a layer: non-gated), within one
    chip's memory."""
    from dynamo_tpu.models import moe
    from dynamo_tpu.ops.pallas import ssd as ssd_kernels

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    monkeypatch.setattr(ssd_kernels, "_interpret", lambda: False)
    cfg = ModelConfig.nemotron_3_super_ep4_l11()
    sds = partial(_sds, sharding=one_chip)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
    lanes, rows = 129, 132
    # the one attention layer's pages, joined (EngineConfig.cache_form)
    pages = sds((65536, 2, BS, 2, 128), jnp.bfloat16)
    kv = [(pages,) if cfg.layer_kind(li) == "attn" else ()
          for li in range(cfg.num_layers)]
    rec = [
        tuple(sds(shape, dt) for shape, dt in
              cfg.recurrent_state_arrays(li, lanes, "bfloat16"))
        for li in cfg.recurrent_layers
    ]
    i32 = partial(sds, dtype=jnp.int32)
    T = 256
    meta = (
        i32((T,)), i32((T,)), i32((T,)), i32((T,)), i32((rows, 512)),
        i32((rows,)), i32((rows,)), i32((rows,)), i32((rows,)),
    )

    def step(params, kv, rec, slot, *meta):
        logits, kv, rec = llama.unified(
            cfg, params, kv, *meta, BS, attn=AttnDispatch(use_pallas=True),
            rec_state=rec, state_slot=slot,
        )
        return jnp.argmax(logits, axis=-1), kv, rec

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, kv, rec, i32((rows,)), *meta
    ).compile()
    # 5 mixers x (ssd_recurrent, ssd_chunk), the one ragged kernel, 5
    # expert layers x (up, down)
    assert _kernel_count(compiled.as_text()) == 10 + 1 + 10
    mem = compiled.memory_analysis()
    # weights 9.30 GB, state 2.74 GB, pages 1.07 GB: all arguments, and
    # the state and the pages alias their outputs
    # (13.117 GB of arguments, 0.18 GB of temporaries, 3.82 GB aliased)
    assert 12.9e9 < mem.argument_size_in_bytes < 13.4e9, (
        mem.argument_size_in_bytes)
    assert mem.temp_size_in_bytes < 1.5e9, mem.temp_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 15.5e9


@pytest.mark.slow  # a minute of many-threaded compiling beside the suite's timing-gated tests
@pytest.mark.parametrize("T", [512, 1024])
def test_lfm2_l10_step_compiles_at_published_widths(
    mosaic, one_chip, monkeypatch, T
):
    """The whole of ``lfm2-24b-a2b-l10`` (eight gated short convolutions,
    two attention layers of 32 heads over 8 cached heads of 64 stored 128
    wide, two dense MLPs, eight expert layers of all 64 experts, the whole
    tied vocabulary) in one unified step with the state table of 128
    lanes: the ragged kernel over lane-padded joined pages, the grouped
    expert path's three kernels a layer, no kernel of the convolution's
    own, within one chip's memory."""
    from dynamo_tpu.models import moe

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    cfg = ModelConfig.lfm2_24b_a2b_l10()
    sds = partial(_sds, sharding=one_chip)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
    lanes, rows = 129, 132
    # an attention layer's pages, joined, a head stored 128 wide
    pages = sds((16385, 2, BS, 8, 128), jnp.bfloat16)
    kv = [(pages,) if cfg.layer_kind(li) == "attn" else ()
          for li in range(cfg.num_layers)]
    rec = [
        tuple(sds(shape, dt) for shape, dt in
              cfg.recurrent_state_arrays(li, lanes, "bfloat16"))
        for li in cfg.recurrent_layers
    ]
    assert [len(r) for r in rec] == [1] * 8
    i32 = partial(sds, dtype=jnp.int32)
    meta = (
        i32((T,)), i32((T,)), i32((T,)), i32((T,)), i32((rows, 128)),
        i32((rows,)), i32((rows,)), i32((rows,)), i32((rows,)),
    )

    def step(params, kv, rec, slot, *meta):
        logits, kv, rec = llama.unified(
            cfg, params, kv, *meta, BS, attn=AttnDispatch(use_pallas=True),
            rec_state=rec, state_slot=slot,
        )
        return jnp.argmax(logits, axis=-1), kv, rec

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, kv, rec, i32((rows,)), *meta
    ).compile()
    # 2 attention layers' ragged kernel, 8 expert layers x (gate, up, down)
    assert _kernel_count(compiled.as_text()) == 2 + 24
    mem = compiled.memory_analysis()
    # weights 10.53 GB, pages 2 x 1.07 GB, the tails 8 MB: all arguments,
    # and the tails and the pages alias their outputs
    assert 12.5e9 < mem.argument_size_in_bytes < 12.9e9, (
        mem.argument_size_in_bytes)
    assert mem.temp_size_in_bytes < 1.5e9, mem.temp_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 15.5e9


@pytest.mark.parametrize("T", [256, 16])
def test_ssd_kernels_compile_at_published_widths(
    mosaic, one_chip, monkeypatch, T
):
    """The state-space recurrence over one dispatch at Nemotron-3-Super's
    widths (128 heads of 64 x 128 float32 in 8 groups, a state table of 129
    slots): the one-row lanes' kernel (a group's 512 KiB block a grid step)
    and the chunk kernel (a tile's strided reads of the flat rows, its
    products at float32 contract precision, a group's state and two tiles'
    rows within VMEM), at the top rung and at the lowest."""
    from dynamo_tpu.ops import ssd
    from dynamo_tpu.ops.pallas import ssd as kernels

    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    sds = partial(_sds, sharding=one_chip)
    i32, f32 = partial(sds, dtype=jnp.int32), jnp.float32
    lanes, rows = 129, 132
    compiled = jax.jit(
        partial(ssd.ssd_ragged, use_pallas=True), donate_argnums=(5,),
    ).lower(
        sds((T, 128, 64), f32), sds((T, 128), f32), sds((T, 128), f32),
        sds((T, 8, 128), f32), sds((T, 8, 128), f32),
        sds((lanes, 128, 64, 128), f32), i32((T,)), i32((T,)),
        *[i32((rows,))] * 4,
    ).compile()
    assert _kernel_count(compiled.as_text()) == 2
    mem = compiled.memory_analysis()
    # the state is updated in place by both kernels: no second table
    assert mem.alias_size_in_bytes >= lanes * 128 * 64 * 128 * 4
    assert mem.temp_size_in_bytes < 0.2e9, mem.temp_size_in_bytes


@pytest.mark.slow  # a minute of many-threaded compiling beside the suite's timing-gated tests
def test_command_a_share_step_compiles_at_published_widths(
    mosaic, one_chip, monkeypatch
):
    """The whole of ``command-a-plus-ep8-l4`` (one period: three window
    layers and one full layer, 16 of 128 experts, an eighth of the
    vocabulary) in one unified step at T=1024 over TWO pools and two block
    tables of 1,280 entries for 52 rows: the ragged kernel at 128 query
    heads over 8 cached heads (16 a head: a 16-row long tile), with and
    without the window, the grouped expert path's kernels, within one
    chip's memory."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.models import moe

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    cfg = ModelConfig.command_a_plus_ep8_l4()
    ecfg = EngineConfig(
        model=cfg, max_num_seqs=48, max_model_len=20480, num_blocks=36000,
        unified_token_budget=1024, unified_prefill_quantum=1024)
    pools = ecfg.group_num_blocks
    assert pools == (36000, 48 * (256 + 64 + 2) + 1)
    sds = partial(_sds, sharding=one_chip)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
    assert ecfg.cache_form == "joined"
    # a layer's pages are its group's pool, K and V of a block one page
    kv = [
        (sds((pools[cfg.layer_cache_group(li)], 2, BS, 8, 128),
             jnp.bfloat16),)
        for li in range(cfg.num_layers)
    ]
    i32 = partial(sds, dtype=jnp.int32)
    T, rows, MB = 1024, 52, ecfg.max_blocks_per_seq
    meta = (
        i32((T,)), i32((T,)), (i32((T,)), i32((T,))), i32((T,)),
        (i32((rows, MB)), i32((rows, MB))),
        i32((rows,)), i32((rows,)), i32((rows,)), i32((rows,)),
    )

    def step(params, kv, *meta):
        logits, kv = llama.unified(
            cfg, params, kv, *meta, BS, attn=AttnDispatch(use_pallas=True))
        return jnp.argmax(logits, axis=-1), kv

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, kv, *meta).compile()
    # four layers x (the ragged kernel + gate, up, down)
    assert _kernel_count(compiled.as_text()) == 4 * 4
    # neither pool is laid out anew around its write
    assert [
        ln for ln in compiled.as_text().splitlines()
        if " copy(" in ln and ",2,16,8,128]" in ln
    ] == []
    mem = compiled.memory_analysis()
    # weights 9.47 GB, the window pool 3.04 GB, the full pool 2.36 GB
    assert 14.6e9 < mem.argument_size_in_bytes < 15.1e9
    assert mem.temp_size_in_bytes < 1.0e9, mem.temp_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 16.0e9


@pytest.mark.slow  # many-threaded compiling beside the suite's timing-gated tests
@pytest.mark.parametrize("L,NT,C", [(24, 32, 128), (24, 25, 16)])
def test_retention_kernels_compile_at_published_widths(
    mosaic, one_chip, monkeypatch, L, NT, C
):
    """``retention_recurrent`` over 24 lanes and ``retention_chunk`` over
    the tiles of a 1,024-row (and a 16-row) budget at Brumby-14B's widths:
    40 query heads over 8 cached heads of 128, a state of 65 x 128 x 128
    float32 a (slot, cached head) over 21 slots, within the kernel's VMEM."""
    from dynamo_tpu.ops import power_retention as pr
    from dynamo_tpu.ops.pallas import retention as rk

    monkeypatch.setattr(rk, "_interpret", lambda: False)
    sds = partial(_sds, sharding=one_chip)
    i32 = partial(sds, dtype=jnp.int32)
    H_r, KVH_r, bf16, f32 = 40, 8, jnp.bfloat16, jnp.float32
    S_shape, z_shape = pr.state_shapes(21, KVH_r, D)
    assert S_shape[2] * D == 8320 and z_shape[2] == 72
    state = (sds(S_shape, f32), sds(z_shape, f32))

    def lanes(q, k, v, lg, S, z, slots, flags):
        return rk.retention_recurrent(q, k, v, lg, (S, z), slots, flags)

    def tiles(q, k, v, g, S, z, slots, flags, n):
        return rk.retention_chunk(q, k, v, g, (S, z), slots, flags, n)

    rec = jax.jit(lanes, donate_argnums=(4, 5)).lower(
        sds((L, H_r, D), bf16), sds((L, KVH_r, D), bf16),
        sds((L, KVH_r, D), bf16), sds((L, KVH_r), f32), *state,
        i32((L,)), i32((L,)),
    ).compile()
    chunk = jax.jit(tiles, donate_argnums=(4, 5)).lower(
        sds((NT, C, H_r, D), bf16), sds((NT, C, KVH_r, D), bf16),
        sds((NT, C, KVH_r, D), bf16), sds((KVH_r, NT, C), f32), *state,
        i32((NT,)), i32((NT,)), i32((NT,)),
    ).compile()
    for compiled in (rec, chunk):
        assert _kernel_count(compiled.as_text()) == 1
        mem = compiled.memory_analysis()
        # the state is updated in place: no second table
        assert mem.alias_size_in_bytes >= 21 * 8 * 65 * 128 * 128 * 4
        assert mem.temp_size_in_bytes < 0.3e9, mem.temp_size_in_bytes


@pytest.mark.slow  # minutes of many-threaded compiling beside the suite's timing-gated tests
def test_brumby_l8_step_compiles_at_published_widths(
    mosaic, one_chip, monkeypatch
):
    """The whole of ``brumby-14b-l8`` (eight retention layers, the whole
    vocabulary) in one unified step at T=1024 with the state table of 20
    lanes and NO paged cache: two retention kernels a layer, no ragged
    kernel, within one chip's memory."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.ops.pallas import retention as rk

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    monkeypatch.setattr(rk, "_interpret", lambda: False)
    cfg = ModelConfig.brumby_14b().scaled(num_layers=8)
    ecfg = EngineConfig(
        model=cfg, max_num_seqs=20, max_model_len=32768,
        unified_token_budget=1024, unified_prefill_quantum=1024)
    ecfg.validate()
    assert ecfg.group_num_blocks == ()
    sds = partial(_sds, sharding=one_chip)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
    empty = sds((0, 8, 128), jnp.bfloat16)
    kv = [(empty, empty)] * cfg.num_layers
    rec = [
        tuple(sds(shape, jnp.dtype(dt)) for shape, dt in
              cfg.recurrent_state_arrays(li, 21, "bfloat16"))
        for li in cfg.recurrent_layers
    ]
    i32 = partial(sds, dtype=jnp.int32)
    T, rows = 1024, 24
    meta = (
        i32((T,)), i32((T,)), i32((T,)), i32((T,)), i32((rows, 1)),
        i32((rows,)), i32((rows,)), i32((rows,)), i32((rows,)),
    )

    def step(params, kv, rec, slot, *meta):
        logits, kv, rec = llama.unified(
            cfg, params, kv, *meta, BS, attn=AttnDispatch(use_pallas=True),
            rec_state=rec, state_slot=slot,
        )
        return jnp.argmax(logits, axis=-1), kv, rec

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, kv, rec, i32((rows,)), *meta
    ).compile()
    assert _kernel_count(compiled.as_text()) == 16
    mem = compiled.memory_analysis()
    # weights 8.40 GB and the state table 5.77 GB: all arguments, and the
    # state aliases its output
    assert 14.0e9 < mem.argument_size_in_bytes < 14.4e9
    assert mem.temp_size_in_bytes < 1.0e9, mem.temp_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 15.6e9


@pytest.mark.parametrize("kv_dtype,held_once,fits", [
    ("bfloat16", True, True), ("int8", True, True),
    ("bfloat16", False, False)])
def test_ragged_kernel_compiles_over_a_latent_held_once(
    mosaic, one_chip, kv_dtype, held_once, fits
):
    """128 query heads over ONE cached head of 640 (DeepSeek-V2's latent,
    lane-padded) at a budget of 1,024, with the values read from the key
    slot (one array, one ring, one scale array): Mosaic's verdict on both
    tiles at a 16-row long tile whose folded rows are 2,048 lanes wide,
    under ``VMEM_LIMIT``. The PAIR at these widths does not fit it (33.07
    MB of 32: the second ring's 1.3 MB is the difference), which is what a
    program that stored this model's latent twice would have met."""
    i32 = partial(_sds, dtype=jnp.int32, sharding=one_chip)
    cache = _sds((NUM_BLOCKS * BS, 1, 640), jnp.dtype(kv_dtype), one_chip)
    scales = {}
    if kv_dtype == "int8":
        sc = _sds((NUM_BLOCKS, 1), jnp.float32, one_chip)
        scales = {"k_scales": sc, "v_scales": None if held_once else sc}
    lanes = 52
    lowered = ragged_kernel.ragged_paged_attention_pallas.lower(
        _sds((1024, 128, 640), jnp.bfloat16, one_chip), cache,
        None if held_once else cache,
        i32((lanes, 1280)), i32((lanes,)), i32((lanes,)), i32((lanes,)),
        i32((lanes,)), block_size=BS, **scales,
    )
    if not fits:
        with pytest.raises(Exception, match="vmem"):
            lowered.compile()
        return
    assert _kernel_count(lowered.compile().as_text()) == 1


@pytest.mark.parametrize("T,heads", [
    (1024, 128),   # deepseek-v2-ep4-l5's top rung: one tile of 1,024 rows
    (512, 128),    # the lowest rung that carries the body
    (2048, 32),    # no cell's: a tp=4 chip's heads, spans of two tiles
])
def test_expanded_latent_body_compiles_at_published_widths(
    mosaic, one_chip, T, heads
):
    """The expanded form's body (ops/pallas/latent_expanded.py) at
    DeepSeek-V2's widths: un-absorbed queries of 128 + 64, ``w_uk`` /
    ``w_uv`` of 128 x 512 a head, ONE cached head of 640 with a table of
    1,280 entries a row (20,480 tokens of context), under the ragged
    kernel's ``VMEM_LIMIT``; one kernel, under a name the benchmark's
    readers sum with the ragged kernel's."""
    from dynamo_tpu.ops.pallas import latent_expanded

    i32 = partial(_sds, dtype=jnp.int32, sharding=one_chip)
    bf = partial(_sds, dtype=jnp.bfloat16, sharding=one_chip)
    lanes = 52
    k = latent_expanded.expanded_k(ModelConfig.deepseek_v2_ep4_l5(), T)
    assert k and k == latent_expanded.expanded_k(ModelConfig.deepseek_v2(), T)
    assert not latent_expanded.expanded_k(ModelConfig.deepseek_v2_ep4_l5(), 256)
    compiled = latent_expanded.ragged_paged_attention_pallas_expanded.lower(
        bf((T, heads, 192)), bf((NUM_BLOCKS * BS, 1, 640)),
        bf((heads, 128, 512)), bf((heads, 128, 512)),
        i32((lanes, 1280)), i32((lanes,)), i32((lanes,)), i32((lanes,)),
        block_size=BS, scale=0.1147,
    ).compile()
    text = compiled.as_text()
    assert _kernel_count(text) == 1
    assert "ragged_paged_attention_pallas_expanded" in text


@pytest.mark.slow  # a minute of many-threaded compiling beside the suite's timing-gated tests
def test_deepseek_v2_share_step_compiles_at_published_widths(
    mosaic, one_chip, monkeypatch
):
    """The whole of ``deepseek-v2-ep4-l5`` (the dense layer and four expert
    layers, 40 of 160 experts, a quarter of the vocabulary) in one unified
    step at T=1024 over ONE array a layer of 40,000 blocks and a table of
    1,280 entries for 52 rows: the ragged kernel at 128 query heads over
    one cached head of 640 read once, the grouped expert path's kernels,
    within one chip's memory."""
    from dynamo_tpu.models import moe

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    cfg = ModelConfig.deepseek_v2_ep4_l5()
    sds = partial(_sds, sharding=one_chip)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
    blocks = 40000
    kv = [(sds((blocks * BS, 1, 640), jnp.bfloat16),)
          for _ in range(cfg.num_layers)]
    assert all(cfg.layer_cache_arrays(li) == 1 for li in range(5))
    i32 = partial(sds, dtype=jnp.int32)
    T, rows, MB = 1024, 52, 1280
    meta = (
        i32((T,)), i32((T,)), i32((T,)), i32((T,)), i32((rows, MB)),
        i32((rows,)), i32((rows,)), i32((rows,)), i32((rows,)),
    )

    def step(params, kv, *meta):
        logits, kv = llama.unified(
            cfg, params, kv, *meta, BS, attn=AttnDispatch(use_pallas=True))
        return jnp.argmax(logits, axis=-1), kv

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, kv, *meta).compile()
    text = compiled.as_text()
    # five layers' ragged kernel and its expanded body (the top rung holds
    # long spans) + four expert layers x (gate, up, down)
    assert _kernel_count(text) == 5 * 2 + 4 * 3
    assert "latent_mixer/attn_latent" in text
    mem = compiled.memory_analysis()
    # weights 10.33 GB + 40,000 blocks x 100 KiB = 4.10 GB
    assert 14.2e9 < mem.argument_size_in_bytes < 14.7e9
    assert mem.temp_size_in_bytes < 1.2e9, mem.temp_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 16.0e9
