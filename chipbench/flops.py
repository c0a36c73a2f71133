"""Operations and bytes the work needs, counted from shapes.

Model FLOPs follow the usual training count: 6 per matmul parameter per
token (forward and backward) plus causal attention's two products, three
times over; recomputation under remat is not counted. Kernel counts are
per call of the kernel, from the sizes it works on: the head dimension
is the model's (64), not the 128 the kernels pad it to, so padding shows
as a lower share of the roofline. Bytes of the window-average kernels are
``docs/ARCHITECTURE.md``'s: the fused sync reads (K+2)·N and writes 3·N
float32 elements, the window update reads 3·N and writes 3·N.
"""
from __future__ import annotations

F32 = 4


def layer_matmul_params(dims) -> int:
    """Matmul parameters of one decoder layer: q, k, v, o and the gated
    MLP's three matrices."""
    D, H, K, P, F = (dims.d_model, dims.n_heads, dims.n_kv_heads,
                     dims.head_dim, dims.d_ff)
    return D * H * P + 2 * D * K * P + H * P * D + 3 * D * F


def matmul_params(dims) -> int:
    """All matmul parameters: the layers and the output head (the
    embedding is a lookup)."""
    return dims.n_layers * layer_matmul_params(dims) \
        + dims.d_model * dims.vocab


def causal_matmul_flops(dims, seq_len: int) -> int:
    """FLOPs of one causal (S, S) attention product over all query heads
    of one sequence: 2·H·P per (query, key) pair with key <= query."""
    return dims.n_heads * dims.head_dim * seq_len * (seq_len + 1)


def model_flops_per_token(dims, seq_len: int) -> float:
    """Training FLOPs per token, recomputation not counted."""
    attn = 2 * causal_matmul_flops(dims, seq_len) / seq_len   # fwd, 1 layer
    return 6 * matmul_params(dims) + 3 * dims.n_layers * attn


#: causal products each flash kernel computes per sequence and layer:
#: forward s = q·kᵀ and o = p·v; dq recomputes s and takes dp = do·vᵀ,
#: dq = ds·k; dkv recomputes s and dp and takes dv = pᵀ·do, dk = dsᵀ·q
FLASH_PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_call_flops(dims, seq_len: int, kernel: str,
                     sequences: float) -> float:
    """FLOPs of one call of a flash kernel over ``sequences`` sequences
    of one layer."""
    return FLASH_PRODUCTS[kernel] * causal_matmul_flops(dims, seq_len) \
        * sequences


def fused_sync_bytes(n_replicas: int, padded: int) -> int:
    """Bytes the fused sync kernel must move over a packed buffer of
    ``padded`` float32 elements: K replicas, the evicted ring slot and
    the total read; the slot, the total and W̿ written."""
    return F32 * ((n_replicas + 2) * padded + 3 * padded)


def packed_size(params_abs, align: int = 8 * 1024) -> int:
    """Elements of the packed float32 buffer that holds every parameter
    back to back, padded to a whole number of (8, 1024) tiles."""
    import jax
    import numpy as np
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params_abs))
    return max(align, -(-n // align) * align)
