"""Pallas TPU kernels for the hot ops.

The jnp implementations in ops/attention.py are the semantics contract and
test oracle; these kernels keep the same math but stream KV pages
HBM→VMEM explicitly with double-buffered DMA, which is what gets decode
attention to HBM-bandwidth-bound instead of gather-bound.
"""

from dynamo_tpu.ops.pallas.attention import paged_decode_attention_pallas
from dynamo_tpu.ops.pallas.ragged_attention import (
    ragged_paged_attention_pallas,
)

__all__ = [
    "paged_decode_attention_pallas",
    "ragged_paged_attention_pallas",
]
