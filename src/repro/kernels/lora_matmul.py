"""Pallas TPU kernel: fused LoRA matmul  y = x·W + s·(x·A)·B.

D2FT-LoRA keeps the frozen QKV weight and its low-rank adapter co-located on
the subnet's device (paper §II-D); this kernel fuses the adapter branch into
the frozen matmul so the [M, r] intermediate never round-trips HBM.

Tiling: grid over (M/bm, N/bn); each step loads a full-K stripe of x
[bm, K] and W [K, bn] into VMEM plus the whole adapter (A [K, r], B [r,bn]),
computes base and low-rank contribution on the MXU and writes one output
tile. K stripes are fine for fine-tuning-scale d_model (K·(bm+bn)·2 bytes
must fit VMEM — checked in the wrapper).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, a_ref, b_ref, o_ref, *, scale: float):
    x = x_ref[...]
    base = jax.lax.dot_general(x, w_ref[...], (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    u = jax.lax.dot_general(x, a_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    delta = jax.lax.dot_general(u.astype(x.dtype), b_ref[...],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[...] = (base + scale * delta).astype(o_ref.dtype)


def lora_matmul(x, w, a, b, scale: float = 1.0, *, block_m: int = 256,
                block_n: int = 256, interpret: bool = False):
    """x: [M, K]; w: [K, N]; a: [K, r]; b: [r, N]. Returns [M, N]."""
    M, K = x.shape
    _, N = w.shape
    r = a.shape[1]
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    assert M % block_m == 0 and N % block_n == 0, (M, N, block_m, block_n)
    # VMEM budget check (bf16/f32): x stripe + w stripe + A + B + out tile
    vmem = (block_m * K + K * block_n + K * r + r * block_n +
            block_m * block_n) * x.dtype.itemsize
    assert vmem < 100 * 2 ** 20, f"tile working set {vmem} too large"

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=(M // block_m, N // block_n),
        in_specs=[
            pl.BlockSpec((block_m, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((K, r), lambda i, j: (0, 0)),
            pl.BlockSpec((r, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, w, a, b)
