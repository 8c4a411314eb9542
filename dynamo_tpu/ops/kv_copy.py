"""Device-side KV block gather/scatter — the G1 edge of the offload path.

The TPU analogue of the reference's CUDA block-copy machinery (reference:
lib/llm/src/block_manager/block/transfer/cuda.rs + src/kernels/
block_copy.cu): move one block's KV for all layers between the paged HBM
cache and a host buffer. Jitted slice/update (XLA fuses the per-layer
slices into one D2H/H2D transfer program); called only from the engine
thread, serialized with steps, so the non-donated gather never races a
donated step buffer.

Layout contract: host block = [num_layers, A, block_size, kv_heads,
head_dim], matching KvLayoutConfig; A is the entries a layer's cache holds
of a token, 2 (k, v) or 1 (a latent held once: ModelConfig.
layer_cache_arrays), the same for every layer of a model that moves blocks.
A (k, v) layer whose pages are JOINED on the device (one array
[num_blocks, 2, block_size, kv_heads, head_dim]: ops/attention.py
``page_form``) moves the same host block: a block of that array IS its
[2, block_size, kv_heads, head_dim].
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.ops.attention import page_form


def _joined(arrays) -> bool:
    return bool(arrays) and page_form(*arrays) == "joined"


@partial(jax.jit, static_argnames=("block_size",), donate_argnums=())
def _gather(kv_caches, blk: jnp.ndarray, *, block_size: int):
    outs = []
    for arrays in kv_caches:
        if _joined(arrays):
            outs.append(jax.lax.dynamic_index_in_dim(
                arrays[0], blk, 0, keepdims=False))
            continue
        outs.append(
            jnp.stack(
                [
                    jax.lax.dynamic_slice_in_dim(
                        a, blk * block_size, block_size, 0)
                    for a in arrays
                ]
            )
        )
    return jnp.stack(outs)  # [L, A, bs, H, D]


@partial(jax.jit, donate_argnums=(0,))
def _scatter(kv_caches, blk: jnp.ndarray, data: jnp.ndarray):
    new = []
    for i, arrays in enumerate(kv_caches):
        if _joined(arrays):
            (a,) = arrays
            new.append((jax.lax.dynamic_update_index_in_dim(
                a, data[i].astype(a.dtype), blk, 0),))
            continue
        new.append(
            tuple(
                jax.lax.dynamic_update_slice_in_dim(
                    a, data[i, j].astype(a.dtype), blk * data.shape[2], 0
                )
                for j, a in enumerate(arrays)
            )
        )
    return new


def gather_block(kv_caches, block_idx: int, block_size: int) -> np.ndarray:
    """Read one block's KV to host: [L, A, bs, H, D] numpy (bf16 via
    ml_dtypes)."""
    return np.asarray(gather_block_device(kv_caches, block_idx, block_size))


def gather_block_device(kv_caches, block_idx: int, block_size: int) -> jax.Array:
    """Read one block's KV as a DEVICE-resident array [L, A, bs, H, D] —
    the HBM→HBM transfer path's snapshot (no host sync; scatter_block
    consumes it directly, so a same-process prefill→decode block move
    never touches host memory)."""
    return _gather(kv_caches, jnp.int32(block_idx), block_size=block_size)


def scatter_block(kv_caches, block_idx: int, block_size: int, data: np.ndarray):
    """Write one block's KV from host; returns the new cache list (donated
    update — caller must replace its reference)."""
    return _scatter(kv_caches, jnp.int32(block_idx), jnp.asarray(data))


# -- batched block IO ---------------------------------------------------------
# One device program moves N blocks at once: every dispatch has a fixed
# host-side cost, so onboarding a 128-block prefix with per-block calls
# pays it 128 times. The
# batched forms pad N up to a power-of-two bucket (bounded compile count)
# and aim padding at block 0, the engine's trash block (kv_cache.py:13).


@partial(jax.jit, static_argnames=("block_size",), donate_argnums=())
def _gather_many(kv_caches, blks, *, block_size: int):
    idx = blks[:, None] * block_size + jnp.arange(block_size)[None, :]  # [N, bs]
    outs = []
    for arrays in kv_caches:
        if _joined(arrays):
            outs.append(arrays[0][blks])
            continue
        outs.append(jnp.stack([a[idx] for a in arrays], axis=1))  # [N, A, bs, H, D]
    return jnp.stack(outs, axis=1)  # [N, L, A, bs, H, D]


@partial(jax.jit, donate_argnums=(0,))
def _scatter_many(kv_caches, blks, data):
    bs = data.shape[3]
    idx = (blks[:, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)  # [N*bs]
    new = []
    for i, arrays in enumerate(kv_caches):
        if _joined(arrays):
            (a,) = arrays
            new.append((a.at[blks].set(data[:, i].astype(a.dtype)),))
            continue
        new.append(tuple(
            a.at[idx].set(
                data[:, i, j].astype(a.dtype).reshape(-1, *a.shape[1:])
            )
            for j, a in enumerate(arrays)
        ))
    return new


def _bucket(n: int) -> int:
    return 1 << (n - 1).bit_length()


def gather_blocks(kv_caches, block_idxs, block_size: int) -> np.ndarray:
    """Read N blocks' KV to host in ONE device call: [N, L, A, bs, H, D].
    Padding reads the trash block and is dropped before return."""
    return np.asarray(gather_blocks_device(kv_caches, block_idxs, block_size))


def gather_blocks_device(kv_caches, block_idxs, block_size: int) -> jax.Array:
    """Device-resident batched snapshot [N, L, A, bs, H, D] — one dispatch,
    NO host sync. The copy is ordered before any later cache rewrite, so
    the caller may materialize it lazily (e.g. on the KVBM pump thread)."""
    n = len(block_idxs)
    blks = np.zeros(_bucket(n), np.int32)
    blks[:n] = np.asarray(block_idxs, np.int32)
    out = _gather_many(kv_caches, jnp.asarray(blks), block_size=block_size)
    return out[:n] if _bucket(n) != n else out


# -- per-block KV scale sidecars (kv_quant int8; kv_quant.md) ---------------
# The scale state is [L, A, num_blocks, kvH] float32 on device; block IO
# moves [N, L, A, kvH] rows with the same power-of-two bucketing (padding
# aims at trash block 0, whose scale is never read as real KV).


@jax.jit
def _gather_scales(kv_scales, idxs):
    return jnp.transpose(kv_scales[:, :, idxs], (2, 0, 1, 3))  # [N, L, A, H]


@partial(jax.jit, donate_argnums=(0,))
def _scatter_scales(kv_scales, idxs, rows):
    return kv_scales.at[:, :, idxs].set(jnp.transpose(rows, (1, 2, 0, 3)))


def gather_scales_device(kv_scales, block_idxs) -> jax.Array:
    """Device-resident [N, L, A, kvH] scale rows for N blocks (one
    dispatch, no host sync — pairs with gather_blocks_device)."""
    n = len(block_idxs)
    idxs = np.zeros(_bucket(n), np.int32)
    idxs[:n] = np.asarray(block_idxs, np.int32)
    out = _gather_scales(kv_scales, jnp.asarray(idxs))
    return out[:n] if _bucket(n) != n else out


def gather_scales(kv_scales, block_idxs) -> np.ndarray:
    return np.asarray(gather_scales_device(kv_scales, block_idxs))


def scatter_scales(kv_scales, block_idxs, rows):
    """Write N blocks' scale rows ([N, L, A, kvH], host or device) in one
    donated program; returns the new scale array."""
    n = len(block_idxs)
    b = _bucket(n)
    idxs = np.zeros(b, np.int32)
    idxs[:n] = np.asarray(block_idxs, np.int32)
    if isinstance(rows, jax.Array):
        arr = rows
        if b != n:
            arr = jnp.concatenate(
                [arr, jnp.zeros((b - n, *arr.shape[1:]), arr.dtype)], axis=0
            )
    else:
        arr = np.asarray(rows, np.float32)
        if b != n:
            arr = np.concatenate(
                [arr, np.zeros((b - n, *arr.shape[1:]), arr.dtype)], axis=0
            )
    return _scatter_scales(
        kv_scales, jnp.asarray(idxs), jnp.asarray(arr, jnp.float32)
    )


def scatter_blocks(kv_caches, block_idxs, block_size: int, data):
    """Write N blocks' KV from host in ONE device call (donated update —
    caller must replace its cache reference). `data` is [N, L, A, bs, H, D]
    (any same-width dtype view; cast happens on device). Padding writes
    zeros into trash block 0, which is never read as real KV."""
    n = len(block_idxs)
    b = _bucket(n)
    blks = np.zeros(b, np.int32)
    blks[:n] = np.asarray(block_idxs, np.int32)
    if isinstance(data, jax.Array):
        arr = data  # device-resident: pad on device, never touch host
        if b != n:
            arr = jnp.concatenate(
                [arr, jnp.zeros((b - n, *arr.shape[1:]), arr.dtype)], axis=0
            )
    else:
        arr = np.asarray(data)
        if b != n:
            pad = np.zeros((b - n, *arr.shape[1:]), arr.dtype)
            arr = np.concatenate([arr, pad], axis=0)
    return _scatter_many(kv_caches, jnp.asarray(blks), jnp.asarray(arr))
