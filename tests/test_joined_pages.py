"""A (k, v) layer's JOINED pages (PR 59; docs/architecture/unified_step.md
"Three forms of a layer's pages"): ONE array ``[blocks, 2, bs, kvH, D]`` in
which a block's keys and then its values are one contiguous page, so the
ragged kernel streams a page with one descriptor. The kernel (interpret
mode) against its XLA twin AND against its own two-array path, the layer's
one-scatter write, the ring's shape, the host's count of page descriptors;
then the form's one decision (``EngineConfig.cache_form``) and everything
that reads it: block IO (a joined worker and one that keeps K and V apart
move each other's blocks), a mesh over heads."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import CACHE_FORMS, EngineConfig
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS, ModelConfig
from dynamo_tpu.ops.attention import (
    join_pages,
    page_form,
    ragged_paged_attention,
)
from dynamo_tpu.ops.pallas import ragged_attention as ragged_kernel
from dynamo_tpu.ops.pallas.ragged_attention import (
    ragged_paged_attention_pallas,
)
from stepdrive import greedy_tokens

BS = 16
_K = ragged_kernel.FOLD_KEYS  # keys a fold at these pages


def join(k, v, block_size=BS):
    return join_pages(k, v, block_size)


# -- the kernel over the joined layout, the write, the ring, the count -------

_TL = ragged_kernel.long_tile(8, 2)
KERNEL_CASES = {
    # a tp=4 chip's share of Mistral: 8 KiB of K a page, the slot as it lies
    "tp4_chip_h8_kv2": dict(
        H=8, kvH=2, spans=[(36, 1), (0, 1), (0, 20), (16, 13), (300, 1)]),
    # one chip's dense cells: 32 KiB of K a page
    "dense_h32_kv8": dict(
        H=32, kvH=8, spans=[(2 * _K + 5, 1), (40, 33), (7, 1)]),
    # SDAR: a lane is a block of 4 rows, the short tile by cached head
    "sdar_h32_kv4_block4": dict(
        H=32, kvH=4, diffusion_block=4,
        spans=[(2 * _K + 8, 4), (_K - 4, 4), (_K + 4, 2), (8, 40)]),
    # a window whose lower edge lies inside a fold
    "window": dict(
        H=8, kvH=2, window=_K + 60,
        spans=[(3 * _K + 17, 1), (2 * _K + 100, _TL + 1), (40, 1)]),
    # a long span across several tiles over several folds
    "long_span_several_tiles": dict(
        H=8, kvH=2, spans=[(19, 1), (2 * _K + 9, 3 * _TL + 5), (_K + 1, 1)]),
    # contexts that end one key past a fold: the tail's pages are clamped
    # to the last page the tile sees
    "clamped_tail": dict(
        H=8, kvH=2, spans=[(_K, 1), (2 * _K, 1), (BS, 1), (_K - 3, 5)]),
    # idle metadata rows between live spans, budget rows nobody owns
    "idle_row": dict(
        H=8, kvH=2, spans=[(0, 0), (33, 1), (0, 0), (5, 18), (0, 0)]),
    # float32 pages take the whole-slot read (the CPU rehearsal's dtype)
    "float32_pages": dict(
        H=8, kvH=2, dtype=jnp.float32, spans=[(33, 1), (5, 18)]),
}


def _kernel_case(case: dict) -> None:
    H, kvH, D = case["H"], case["kvH"], 128
    dtype = case.get("dtype", jnp.bfloat16)
    window = case.get("window", 0)
    B = case.get("diffusion_block", 1)
    spans = case["spans"]
    rng = np.random.default_rng(sum(n for _, n in spans))
    S = len(spans)
    max_blocks = max(-(-(p + n) // BS) for p, n in spans) + 1
    num_blocks = S * max_blocks + 1
    tables = jnp.asarray(
        rng.permutation(np.arange(1, num_blocks)).reshape(S, max_blocks),
        jnp.int32)
    T = sum(n for _, n in spans) + 3
    q_start, q_len, row_start = (np.zeros(S, np.int32) for _ in range(3))
    token_seq = np.zeros(T, np.int32)
    token_pos = np.full(T, -1, np.int32)
    cursor = 0
    for s, (p, n) in enumerate(spans):
        q_start[s], q_len[s], row_start[s] = p, n, cursor
        token_seq[cursor:cursor + n] = s
        token_pos[cursor:cursor + n] = np.arange(p, p + n)
        cursor += n
    q = jnp.asarray(rng.standard_normal((T, H, D)), dtype)
    k, v = (
        jnp.asarray(rng.standard_normal((num_blocks * BS, kvH, D)), dtype)
        for _ in range(2)
    )
    pages = join(k, v)
    assert page_form(pages) == "joined" and page_form(k, v) == "apart"
    with pytest.raises(ValueError):   # a 5-D array is not joined by its rank
        page_form(jnp.zeros((4, 3, BS, kvH, D), dtype))
    meta = tuple(
        jnp.asarray(a) for a in (q_start, q_len, q_start + q_len, row_start))
    kw = dict(window=window, diffusion_block=B)
    got = ragged_paged_attention_pallas(q, pages, None, tables, *meta, BS, **kw)
    apart = ragged_paged_attention_pallas(q, k, v, tables, *meta, BS, **kw)
    # the same products in the same order: not a digit differs
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(apart, np.float32))
    twin = partial(
        ragged_paged_attention, block_tables=tables,
        token_seq=jnp.asarray(token_seq), token_pos=jnp.asarray(token_pos),
        block_size=BS, window=window, diffusion_block=B, kv_len=meta[2])
    want = twin(q, pages, pages)
    # the twin gathers a joined block's page whole: the two-array gather's
    # numbers exactly
    np.testing.assert_array_equal(np.asarray(want), np.asarray(twin(q, k, v)))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)
    owned = token_pos >= 0
    assert not np.asarray(got)[~owned].any()
    assert np.abs(np.asarray(got, np.float32)[owned]).max() > 0


def _write_case() -> None:
    """``unified`` over joined pages: ONE scatter puts a slot's key and its
    value where the two scatters put them, the padded rows of the budget
    land in the trash block 0, and the logits are the pair's."""
    cfg = ModelConfig.tiny_test()
    params = llama.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    bs, blocks, T = 8, 6, 16
    shape = (blocks * bs, cfg.num_kv_heads, cfg.head_dim)
    apart = [
        (jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16))
        for _ in range(cfg.num_layers)
    ]
    joined = [(join(k, v, bs),) for k, v in apart]
    n = 11                      # rows of the prompt; 5 rows of padding
    table = [3, 5]
    ids = np.zeros(T, np.int32)
    ids[:n] = np.arange(7, 7 + n)
    pos = np.full(T, -1, np.int32)
    pos[:n] = np.arange(n)
    slots = np.arange(T, dtype=np.int32) % bs      # padding: block 0
    slots[:n] = [table[p // bs] * bs + p % bs for p in range(n)]
    args = [jnp.asarray(a) for a in (
        ids, pos, slots, np.zeros(T, np.int32), np.asarray([table], np.int32),
        np.int32([0]), np.int32([n]), np.int32([n]), np.int32([0]))]
    text = jax.jit(
        partial(llama.unified, cfg, block_size=bs)
    ).lower(params, joined, *args).as_text()
    logits_j, caches_j = llama.unified(cfg, params, joined, *args, bs)
    logits_a, caches_a = llama.unified(cfg, params, apart, *args, bs)
    np.testing.assert_array_equal(np.asarray(logits_j), np.asarray(logits_a))
    # one scatter a layer where two
    assert text.count("stablehlo.scatter") == cfg.num_layers
    for (pages,), (k, v) in zip(caches_j, caches_a):
        assert pages.shape == (blocks, 2, bs, *shape[1:])
        np.testing.assert_array_equal(
            np.asarray(pages), np.asarray(join(k, v, bs)))
        written = np.abs(np.asarray(pages, np.float32)).sum(axis=(1, 3, 4))
        assert (written[3] > 0).all() and (written[5, :n - bs] > 0).all()
        assert not written[5, n - bs:].any()
        # the padded rows went to the trash block, keys and values alike
        assert (written[0, n % bs:] > 0).all()
        assert not written[[1, 2, 4]].any()


def _ring_case() -> None:
    """``ring_shape`` gives every shape of the tool the fold it had before
    the joined form (its cap counts K's share of a slot, joined or apart),
    and the joined ring holds exactly the bytes the two rings held."""
    from tools.ragged_kernel_bench import SHAPES, width

    folds_before = {"mha": 8}           # 128 KiB pages cap the slot
    for name, shape in SHAPES.items():
        page = BS * shape["kvH"] * width(shape) * 2
        nbuf, pp = ragged_kernel.ring_shape(page, BS)
        assert (nbuf, pp) == (
            ragged_kernel.RAGGED_NBUF, folds_before.get(name, 16)), name
    # what the kernel allocates, read from its jaxpr: one ring of 2 * NBUF
    # rows where two of NBUF
    H, kvH, D, S = 8, 2, 128, 3
    q = jnp.zeros((4, H, D), jnp.bfloat16)
    k = jnp.zeros((4 * BS, kvH, D), jnp.bfloat16)
    meta = (jnp.zeros((S, 2), jnp.int32), *(jnp.zeros(S, jnp.int32),) * 4)
    rings = {}
    for form, (kc, vc) in (("apart", (k, k)), ("joined", (join(k, k), None))):
        jaxpr = jax.make_jaxpr(partial(
            ragged_paged_attention_pallas, block_size=BS))(q, kc, vc, *meta)
        (call,) = [
            e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
            if e.primitive.name == "pallas_call"
        ]
        shapes = [
            v.aval.shape for v in call.params["jaxpr"].invars
            if getattr(v.aval, "shape", ())[-2:] == (16 * BS * kvH, D)
        ]
        rings[form] = shapes
    nb = ragged_kernel.RAGGED_NBUF
    assert rings["apart"] == [(nb, 16 * BS * kvH, D)] * 2
    assert rings["joined"] == [(2 * nb, 16 * BS * kvH, D)]


def _page_dmas_case(monkeypatch) -> None:
    """The page descriptors a dispatch starts: its folds (the host's
    ``fold_counts``, on every step's record) x pages a fold x streams. The
    runner says the last two from the form, once, and ``readiness()``
    carries them: one stream where the pages are joined, two where they
    are apart, and a descriptor of twice the bytes."""
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    model = dataclasses.replace(ModelConfig.tiny_test(), head_dim=128)
    lanes = [
        ([5], (list(range(1, 11)),), 149, (0.0, 0, 1.0)),
        (list(range(40)), (list(range(30, 39)),), 100, (0.0, 0, 1.0)),
    ]
    seen = {}
    for dtype, kv_quant in (
        ("bfloat16", None), ("float32", None), ("bfloat16", "int8"),
    ):
        cfg = EngineConfig(
            model=model, dtype=dtype, kv_quant=kv_quant, num_blocks=64,
            max_num_seqs=4, max_model_len=256, block_size=16)
        runner = ModelRunner(cfg, rng_seed=0)
        assert runner.attn.use_pallas
        lanes_ = [(t, b[0], p, s) for t, b, p, s in lanes]
        runner._unified_operands(lanes_, None, 64)
        page = 16 * 2 * 128 * runner.kv_dtype.itemsize
        pp = ragged_kernel.ring_shape(page, 16)[1]
        streams = 1 if cfg.cache_form == "joined" else 2
        assert runner.page_dmas_per_fold == pp * streams
        assert runner.kv_page_dma_bytes == 2 * page // streams
        count = runner._fold_plan["count"]
        starts, rows = np.int32([149, 100]), np.int32([1, 40])
        folds = sum(count(starts, rows, starts + rows, window=0))
        assert sum(runner.attn_folds) == folds * model.num_layers > 0
        seen[dtype, cfg.cache_form] = (
            sum(runner.attn_folds) * runner.page_dmas_per_fold)
    # the same dispatch, the same 256-key fold at every one of these page
    # sizes: half the descriptors joined, whatever the dtype
    assert seen["bfloat16", "apart"] == 2 * seen["bfloat16", "joined"]
    assert seen["float32", "joined"] == seen["bfloat16", "joined"]

    # and where a reader finds them: the folds on a step's record, the
    # constants on readiness()
    import asyncio

    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    async def serve():
        cfg = EngineConfig(
            model=model, num_blocks=64, max_num_seqs=4, max_model_len=128,
            block_size=16)
        assert cfg.cache_form == "joined"
        engine = TpuEngine(cfg)
        await engine.start()
        pre = PreprocessedRequest(
            token_ids=list(range(1, 40)),
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=6, ignore_eos=True),
        )
        try:
            async for _ in engine.generate(Context(pre.to_wire())):
                pass
            steps = [r for r in engine.debug_steps() if "dispatch_ms" in r]
            ready = engine.readiness()
        finally:
            await engine.stop()
        assert "attn_page_dmas" not in steps[0]
        assert any(r["attn_short_folds"] + r["attn_long_folds"] for r in steps)
        assert ready["kv_cache_arrays_per_layer"] == 1
        assert ready["kv_page_dmas_per_fold"] == 16  # a 256-key fold, joined
        assert ready["kv_page_dma_bytes"] == 2 * 16 * 2 * 128 * 2

    asyncio.run(serve())


@pytest.mark.parametrize(
    "name", [*KERNEL_CASES, "write", "ring_shape", "page_dmas"])
def test_joined_layout(name, monkeypatch):
    if name in KERNEL_CASES:
        _kernel_case(KERNEL_CASES[name])
    elif name == "write":
        _write_case()
    elif name == "ring_shape":
        _ring_case()
    else:
        _page_dmas_case(monkeypatch)


# -- the form's one decision, and who reads it --------------------------------

TINY = ModelConfig.tiny_test()


def _cfg(model=TINY, **kw):
    base = dict(num_blocks=32, max_num_seqs=4, max_model_len=64, block_size=8)
    return EngineConfig(model=model, **{**base, **kw})


@pytest.mark.parametrize("name,cfg,form,arrays", [
    ("bf16_kv_pair", _cfg(), "joined", 1),
    ("int8_kv", _cfg(kv_quant="int8"), "apart", 2),
    # the dtype decides nothing: the CPU rehearsal's float32 cells run the
    # path the chip's bfloat16 cells serve
    ("f32_cache", _cfg(dtype="float32"), "joined", 1),
    ("kv_sp", _cfg(kv_sp=True, mesh_shape={"sp": 2}, num_blocks=32),
     "apart", 2),
    ("latent_held_once", _cfg(PRESETS["tiny-mla-test"]()), "once", 1),
    # a latent layer that still stores its latent twice goes to "once",
    # not to joined pages
    ("latent_pair", _cfg(PRESETS["tiny-ling-test"]()), "apart", 2),
    ("window_and_full_groups", _cfg(PRESETS["tiny-command-a-test"]()),
     "joined", 1),
])
def test_cache_form_is_decided_once_from_what_the_configuration_shows(
    name, cfg, form, arrays
):
    assert cfg.cache_form == form and form in CACHE_FORMS
    if name == "kv_sp":
        return  # its runner wants a mesh; the decision is the config's
    runner = ModelRunner(cfg, rng_seed=0)
    paged = [c for c in runner.kv_caches if c]
    assert {len(c) for c in paged} == {arrays}
    assert runner.kv_arrays_per_layer == arrays
    m, bs = cfg.model, cfg.block_size
    for li, layer in enumerate(runner.kv_caches):
        for a in layer:
            assert (page_form(*layer) == "joined") == (form == "joined")
            assert a.shape[-1] == runner.cache_head_dim
            blocks = runner.group_blocks[m.layer_cache_group(li)]
            if form == "joined":
                assert a.shape[:3] == (blocks, 2, bs)
            else:
                assert a.shape[0] == blocks * bs
    # the bytes a cached TOKEN costs do not depend on the form
    entry = m.num_cache_heads * runner.cache_head_dim * runner.kv_dtype.itemsize
    paged_layers = sum(1 for c in runner.kv_caches if c)
    assert runner.kv_bytes_per_token == paged_layers * m.cache_arrays * entry


def test_block_io_round_trips_a_joined_block():
    """A block of a joined layer IS the host block's ``[2, bs, H, D]``:
    gather / scatter, one block and many, host and device, move what the
    pair moved."""
    runner = ModelRunner(_cfg(), rng_seed=0)
    assert runner.cfg.cache_form == "joined"
    greedy_tokens(runner, list(range(3, 23)), [1, 2, 3], 2)
    L, bs = TINY.num_layers, runner.cfg.block_size
    shape = (L, 2, bs, TINY.num_kv_heads, runner.cache_head_dim)
    held = runner.gather_many([1, 2, 3])
    assert held.shape == (3, *shape) and np.abs(held[:2]).max() > 0
    # what the block holds is the layer's own page, keys then values
    np.testing.assert_array_equal(
        held[1, 0], np.asarray(runner.kv_caches[0][0][2]))
    np.testing.assert_array_equal(runner.gather_block(2), held[1])
    runner.scatter_many([1, 2, 3], list(np.zeros_like(held)))
    assert not np.asarray(runner.gather_many([1, 2, 3])).any()
    runner.scatter_many([1, 2, 3], list(held))
    np.testing.assert_array_equal(runner.gather_many([1, 2, 3]), held)
    runner.scatter_many_device([4], runner.gather_many_device([2]))
    np.testing.assert_array_equal(runner.gather_block(4), held[1])
    runner.scatter_block(5, held[0])
    np.testing.assert_array_equal(runner.gather_block(5), held[0])
    runner.scatter_block(6, runner.gather_block_device(1))
    np.testing.assert_array_equal(runner.gather_block(6), held[0])
    # the host's geometry is the pair's: two entries a token a layer
    from dynamo_tpu.block_manager.config import KvLayoutConfig

    lay = KvLayoutConfig.for_engine(runner.cfg, runner.cache_head_dim, None)
    assert lay.outer_dim == 2 and lay.block_bytes == held[0].nbytes


@pytest.mark.parametrize("sender,receiver", [
    ("joined", "apart"), ("apart", "joined"),
])
def test_two_forms_move_each_others_blocks(sender, receiver):
    """The block that travels (``[L, 2, bs, H, D]``: ops/kv_copy.py) is
    the same whichever form holds it on the device, so a worker whose pages
    are joined and one that keeps K and V apart (``kv_sp``; a worker of the
    version before) are a pair as they were: the advertised layout does not
    name the form, and a block gathered from either lands in the other."""
    from dynamo_tpu.disagg import worker
    from dynamo_tpu.ops import kv_copy

    runner = ModelRunner(_cfg(), rng_seed=0)
    assert runner.cfg.cache_form == "joined"
    greedy_tokens(runner, list(range(3, 23)), [1, 2, 3], 2)
    bs = runner.cfg.block_size
    caches = {
        "joined": runner.kv_caches,
        "apart": [
            tuple(p[:, j].reshape(-1, *p.shape[3:]) for j in range(2))
            for (p,) in runner.kv_caches
        ],
    }
    held = kv_copy.gather_blocks(caches[sender], [1, 2], bs)
    assert np.abs(held).max() > 0
    np.testing.assert_array_equal(
        held, kv_copy.gather_blocks(caches[receiver], [1, 2], bs))
    empty = jax.tree.map(jnp.zeros_like, caches[receiver])
    got = kv_copy.scatter_blocks(empty, [4, 5], bs, held)
    assert {page_form(*layer) for layer in got} == {receiver}
    np.testing.assert_array_equal(kv_copy.gather_blocks(got, [4, 5], bs), held)
    np.testing.assert_array_equal(
        kv_copy.gather_block(
            kv_copy.scatter_block(got, 6, bs, held[0]), 6, bs), held[0])
    # and the layout a worker advertises is the pair's, form unsaid
    engine = type("Engine", (), {"cfg": runner.cfg, "runner": runner})()
    op = type("Op", (), {"engine": engine})()
    layout = worker.DecodeOperator._layout(op)
    assert layout["cache_arrays"] == 2 and "cache_form" not in layout
    assert worker.PrefillWorker._check_layout(op, {"layout": layout})


def test_a_mesh_of_four_shards_the_joined_array_by_heads(monkeypatch):
    """``tp=4`` over four cached heads: every chip holds ONE head of every
    block's page, the kernel runs under ``shard_map`` on its share, and the
    tokens are one device's."""
    from jax.sharding import PartitionSpec as P

    from dynamo_tpu.parallel.mesh import build_mesh

    model = dataclasses.replace(TINY, num_heads=8, num_kv_heads=4)
    cfg = _cfg(model, block_size=16)  # a chip's page fills a bf16 tile
    assert cfg.cache_form == "joined"
    prompt = [5, 9, 2, 7, 11, 3, 8, 1, 13]

    def run(mesh, pallas):
        monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1" if pallas else "0")
        runner = ModelRunner(cfg, mesh=mesh, rng_seed=0)
        assert runner.attn.use_pallas is pallas
        return runner, greedy_tokens(runner, prompt, [1, 2], 4)

    _, single = run(None, False)
    runner, sharded = run(build_mesh({"dp": 2, "tp": 4}), True)
    (pages,) = runner.kv_caches[0]
    assert pages.sharding.spec == P(None, None, None, "tp", None)
    assert pages.shape[3] == 4
    assert {s.data.shape[3] for s in pages.addressable_shards} == {1}
    assert sharded == single
