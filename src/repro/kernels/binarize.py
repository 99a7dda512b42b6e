"""Pallas TPU kernel: float32 -> packed 2-bit Sign-Magnitude signatures.

Stage-0 bulk pre-installation (QuIVer §4.1) as a single fused pass:
per-row threshold tau = mean|x|, sign/magnitude bit planes, and bit
packing into uint32 words, one (block_n, D) VMEM tile at a time.  The
float vector is read exactly once from HBM; only D/4 bytes per vector are
written back (12:1 compression happens on-chip).

Packing runs on the MXU and in int32: each bit plane (0/1, exact in
bf16) is multiplied by four constant (D, W) matrices that place bit d
at weight 2^(d % 8) in byte (d % 32) // 8 of word d // 32, so every
product column is one byte of a word (an exact f32 sum of at most 8
powers of two, <= 255).  The four bytes are shifted and or-ed into an
int32 word and bitcast to uint32 once, at the end.  Mosaic has no
unsigned-integer reduction and no (D,) -> (W, 32) lane reshape, which
is what a plain shift-and-sum pack needs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.bq import WORD_BITS


def _byte_weights(d_pad: int) -> jnp.ndarray:
    """(4, D_pad, W) bf16: [k, d, d // 32] = 2^(d % 8) where byte
    (d % 32) // 8 == k, zero elsewhere."""
    w = d_pad // WORD_BITS
    d = np.arange(d_pad)
    out = np.zeros((4, d_pad, w), dtype=np.float32)
    out[(d % WORD_BITS) // 8, d, d // WORD_BITS] = 2.0 ** (d % 8)
    return jnp.asarray(out, dtype=jnp.bfloat16)


def _binarize_kernel(x_ref, p_ref, out_ref, *, true_dim: int, w: int):
    """x_ref: (block_n, D_pad) float32; p_ref: (4, D_pad, W) bf16 byte
    weights; out_ref: (block_n, 2W) uint32."""
    x = x_ref[...]
    absx = jnp.abs(x)
    # Padding columns are zero, so sum is over the true dims only.
    tau = absx.sum(axis=-1, keepdims=True) / jnp.float32(true_dim)

    def pack(bits):
        b = bits.astype(jnp.bfloat16)
        word = jnp.zeros((x.shape[0], w), dtype=jnp.int32)
        for k in range(4):
            byte = jnp.dot(b, p_ref[k], preferred_element_type=jnp.float32)
            word = word | (byte.astype(jnp.int32) << (8 * k))
        return jax.lax.bitcast_convert_type(word, jnp.uint32)

    out_ref[:, :w] = pack(x > 0)
    out_ref[:, w:] = pack(absx > tau)


@functools.partial(
    jax.jit, static_argnames=("true_dim", "block_n", "interpret")
)
def binarize_pallas(
    x_padded: jnp.ndarray,
    *,
    true_dim: int,
    block_n: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(N, D_pad) float32 (D_pad % 32 == 0, zero-padded) -> (N, 2W) uint32.

    ``interpret=None`` resolves by platform: compiled Mosaic on TPU,
    interpreter elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, d_pad = x_padded.shape
    assert d_pad % WORD_BITS == 0 and n % block_n == 0, (n, d_pad)
    w = d_pad // WORD_BITS

    return pl.pallas_call(
        functools.partial(_binarize_kernel, true_dim=true_dim, w=w),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((4, d_pad, w), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 2 * w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 2 * w), jnp.uint32),
        interpret=interpret,
    )(x_padded, _byte_weights(d_pad))
