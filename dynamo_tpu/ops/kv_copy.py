"""Device-side KV block gather/scatter — the G1 edge of the offload path.

The TPU analogue of the reference's CUDA block-copy machinery (reference:
lib/llm/src/block_manager/block/transfer/cuda.rs + src/kernels/
block_copy.cu): move one block's KV for all layers between the paged HBM
cache and a host buffer. Jitted slice/update (XLA fuses the per-layer
slices into one D2H/H2D transfer program); called only from the engine
thread, serialized with steps, so the non-donated gather never races a
donated step buffer.

Layout contract: host block = [num_layers, 2(k/v), block_size, kv_heads,
head_dim], matching KvLayoutConfig.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("block_size",), donate_argnums=())
def _gather(kv_caches, start: jnp.ndarray, *, block_size: int):
    outs = []
    for k, v in kv_caches:
        outs.append(
            jnp.stack(
                [
                    jax.lax.dynamic_slice_in_dim(k, start, block_size, 0),
                    jax.lax.dynamic_slice_in_dim(v, start, block_size, 0),
                ]
            )
        )
    return jnp.stack(outs)  # [L, 2, bs, H, D]


@partial(jax.jit, donate_argnums=(0,))
def _scatter(kv_caches, start: jnp.ndarray, data: jnp.ndarray):
    new = []
    for i, (k, v) in enumerate(kv_caches):
        new.append(
            (
                jax.lax.dynamic_update_slice_in_dim(
                    k, data[i, 0].astype(k.dtype), start, 0
                ),
                jax.lax.dynamic_update_slice_in_dim(
                    v, data[i, 1].astype(v.dtype), start, 0
                ),
            )
        )
    return new


def gather_block(kv_caches, block_idx: int, block_size: int) -> np.ndarray:
    """Read one block's KV to host: [L, 2, bs, H, D] numpy (bf16 via
    ml_dtypes)."""
    return np.asarray(gather_block_device(kv_caches, block_idx, block_size))


def gather_block_device(kv_caches, block_idx: int, block_size: int) -> jax.Array:
    """Read one block's KV as a DEVICE-resident array [L, 2, bs, H, D] —
    the HBM→HBM transfer path's snapshot (no host sync; scatter_block
    consumes it directly, so a same-process prefill→decode block move
    never touches host memory)."""
    return _gather(
        kv_caches, jnp.int32(block_idx * block_size), block_size=block_size
    )


def scatter_block(kv_caches, block_idx: int, block_size: int, data: np.ndarray):
    """Write one block's KV from host; returns the new cache list (donated
    update — caller must replace its reference)."""
    return _scatter(kv_caches, jnp.int32(block_idx * block_size), jnp.asarray(data))


# -- batched block IO ---------------------------------------------------------
# One device program moves N blocks at once: every dispatch has a fixed
# host-side cost, so onboarding a 128-block prefix with per-block calls
# pays it 128 times. The
# batched forms pad N up to a power-of-two bucket (bounded compile count)
# and aim padding at block 0, the engine's trash block (kv_cache.py:13).


@partial(jax.jit, static_argnames=("block_size",), donate_argnums=())
def _gather_many(kv_caches, starts, *, block_size: int):
    idx = starts[:, None] + jnp.arange(block_size)[None, :]  # [N, bs]
    outs = []
    for k, v in kv_caches:
        outs.append(jnp.stack([k[idx], v[idx]], axis=1))  # [N, 2, bs, H, D]
    return jnp.stack(outs, axis=1)  # [N, L, 2, bs, H, D]


@partial(jax.jit, donate_argnums=(0,))
def _scatter_many(kv_caches, starts, data):
    bs = data.shape[3]
    idx = (starts[:, None] + jnp.arange(bs)[None, :]).reshape(-1)  # [N*bs]
    new = []
    for i, (k, v) in enumerate(kv_caches):
        kd = data[:, i, 0].astype(k.dtype).reshape(-1, *k.shape[1:])
        vd = data[:, i, 1].astype(v.dtype).reshape(-1, *v.shape[1:])
        new.append((k.at[idx].set(kd), v.at[idx].set(vd)))
    return new


def _bucket(n: int) -> int:
    return 1 << (n - 1).bit_length()


def gather_blocks(kv_caches, block_idxs, block_size: int) -> np.ndarray:
    """Read N blocks' KV to host in ONE device call: [N, L, 2, bs, H, D].
    Padding reads the trash block and is dropped before return."""
    return np.asarray(gather_blocks_device(kv_caches, block_idxs, block_size))


def gather_blocks_device(kv_caches, block_idxs, block_size: int) -> jax.Array:
    """Device-resident batched snapshot [N, L, 2, bs, H, D] — one dispatch,
    NO host sync. The copy is ordered before any later cache rewrite, so
    the caller may materialize it lazily (e.g. on the KVBM pump thread)."""
    n = len(block_idxs)
    starts = np.zeros(_bucket(n), np.int32)
    starts[:n] = np.asarray(block_idxs, np.int32) * block_size
    out = _gather_many(kv_caches, jnp.asarray(starts), block_size=block_size)
    return out[:n] if _bucket(n) != n else out


# -- per-block KV scale sidecars (kv_quant int8; kv_quant.md) ---------------
# The scale state is [L, 2, num_blocks, kvH] float32 on device; block IO
# moves [N, L, 2, kvH] rows with the same power-of-two bucketing (padding
# aims at trash block 0, whose scale is never read as real KV).


@jax.jit
def _gather_scales(kv_scales, idxs):
    return jnp.transpose(kv_scales[:, :, idxs], (2, 0, 1, 3))  # [N, L, 2, H]


@partial(jax.jit, donate_argnums=(0,))
def _scatter_scales(kv_scales, idxs, rows):
    return kv_scales.at[:, :, idxs].set(jnp.transpose(rows, (1, 2, 0, 3)))


def gather_scales_device(kv_scales, block_idxs) -> jax.Array:
    """Device-resident [N, L, 2, kvH] scale rows for N blocks (one
    dispatch, no host sync — pairs with gather_blocks_device)."""
    n = len(block_idxs)
    idxs = np.zeros(_bucket(n), np.int32)
    idxs[:n] = np.asarray(block_idxs, np.int32)
    out = _gather_scales(kv_scales, jnp.asarray(idxs))
    return out[:n] if _bucket(n) != n else out


def gather_scales(kv_scales, block_idxs) -> np.ndarray:
    return np.asarray(gather_scales_device(kv_scales, block_idxs))


def scatter_scales(kv_scales, block_idxs, rows):
    """Write N blocks' scale rows ([N, L, 2, kvH], host or device) in one
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
    caller must replace its cache reference). `data` is [N, L, 2, bs, H, D]
    (any same-width dtype view; cast happens on device). Padding writes
    zeros into trash block 0, which is never read as real KV."""
    n = len(block_idxs)
    b = _bucket(n)
    starts = np.zeros(b, np.int32)
    starts[:n] = np.asarray(block_idxs, np.int32) * block_size
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
    return _scatter_many(kv_caches, jnp.asarray(starts), jnp.asarray(arr))
