"""Pallas TPU kernels: symmetric 2-bit Sign-Magnitude BQ distance.

TPU adaptation of QuIVer's AVX-512 VPOPCNTDQ hot loop (§3.1): the six
Table-1 category terms are evaluated with bitwise ops +
``lax.population_count`` on the VPU and accumulated into an int32
distance tile, one word at a time (the word loop is unrolled
statically; W <= 48 for D <= 1536).  Two kernels share the per-word
arithmetic (:func:`_table1_word`):

* :func:`bq_distance_pallas` — a (Q, 2W) query block against an
  (N, 2W) base block, tiled HBM->VMEM in (block_q x 2W) /
  (block_n x 2W) tiles; every query meets every base row (the build's
  all-pairs prune, the IVF list scan).
* :func:`bq_distance_rows_pallas` — each of B queries against its *own*
  K rows, (B, 2W) x (B, K, 2W) -> (B, K): the beam's gathered
  neighbours, one call per hop for the whole batch.  Its operands
  arrive word-major, so word i of every pair in a block is one dense
  (8, 128)-tiled tile and no word needs a column-to-row relayout.

Both keep every intermediate a 2-D uint32/int32 tile on Mosaic's native
(8, 128) register tiling, and both are HBM-bandwidth bound by design,
mirroring the memory-bound character of the paper's CPU loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import bq


def _pc(v):
    return jax.lax.population_count(v).astype(jnp.int32)


def _table1_word(qp, qs, bp, bs, m):
    """One word's Table-1 similarity: the six category terms of a query
    word pair (qp, qs) against a base word pair (bp, bs), broadcast
    against each other.  ``m`` is the word's valid-bit mask."""
    diff = qp ^ bp                      # pad bits are 0 in both planes
    same = (~diff) & m
    both_strong = qs & bs
    one_strong = qs ^ bs
    both_weak = (~(qs | bs)) & m
    return (
        4 * _pc(same & both_strong)
        + 2 * _pc(same & one_strong)
        + _pc(same & both_weak)
        - 4 * _pc(diff & both_strong)
        - 2 * _pc(diff & one_strong)
        - _pc(diff & both_weak)
    )


def _bq_distance_kernel(mask_ref, q_ref, base_ref, out_ref, *, w: int):
    """One (block_q, block_n) output tile.

    q_ref:    (block_q, 2W) uint32 — [pos | strong] words
    base_ref: (block_n, 2W) uint32
    mask_ref: (1, W)        uint32 valid-bit mask
    out_ref:  (block_q, block_n) int32
    """
    sim = jnp.zeros(out_ref.shape, dtype=jnp.int32)
    for i in range(w):
        sim += _table1_word(
            q_ref[:, i][:, None], q_ref[:, w + i][:, None],      # (bq, 1)
            base_ref[:, i][None, :], base_ref[:, w + i][None, :],  # (1, bn)
            mask_ref[0, i],
        )
    out_ref[...] = -sim


@functools.partial(
    jax.jit, static_argnames=("dim", "block_q", "block_n", "interpret")
)
def bq_distance_pallas(
    q_words: jnp.ndarray,
    base_words: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    dim: int,
    block_q: int = 8,
    block_n: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(Q, 2W) x (N, 2W) -> (Q, N) int32. Q % block_q == N % block_n == 0.

    ``interpret=None`` resolves by platform: compiled Mosaic on TPU,
    interpreter elsewhere (correctness-only fallback).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    q, ww2 = q_words.shape
    n = base_words.shape[0]
    w = ww2 // 2
    assert q % block_q == 0 and n % block_n == 0, (q, n, block_q, block_n)

    grid = (q // block_q, n // block_n)
    return pl.pallas_call(
        functools.partial(_bq_distance_kernel, w=w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, w), lambda i, j: (0, 0)),
            pl.BlockSpec((block_q, ww2), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, ww2), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.int32),
        interpret=interpret,
    )(mask.reshape(1, -1), q_words, base_words)


# Output elements per grid step of the rows kernel — four (8, 128) int32
# vregs, so a word's intermediates stay in registers — and the most
# sublanes a block takes (K = 32 neighbours fill them).
_ROWS_TILE = 4096
_ROWS_SUBLANES = 32


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _bq_distance_rows_kernel(mask_ref, q_ref, rows_ref, out_ref, *, w: int):
    """One (bs, bt) output tile of the word-major rows kernel.

    q_ref, rows_ref: (2W, bs | 1, bt | 1) uint32, word-major — word i of
                     every pair in the block is one dense (bs, bt) tile,
                     and an operand of extent 1 on an axis broadcasts
                     along it
    mask_ref:        (1, W) uint32 valid-bit mask
    out_ref:         (bs, bt) int32
    """
    sim = jnp.zeros(out_ref.shape, dtype=jnp.int32)
    for i in range(w):
        sim += _table1_word(
            q_ref[i], q_ref[w + i], rows_ref[i], rows_ref[w + i],
            mask_ref[0, i],
        )
    out_ref[...] = -sim


@functools.partial(jax.jit, static_argnames=("dim", "interpret"))
def bq_distance_rows_pallas(
    q_words: jnp.ndarray,
    rows: jnp.ndarray,
    *,
    dim: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(B, 2W) queries x (B, K, 2W) rows -> (B, K) int32 distance.

    Query b is scored against its own K rows only; rows of shape
    (1, K, 2W) are shared by every query.  One call covers the batch:
    the beam scores all B queries' gathered neighbours per hop with it.
    The valid-bit mask follows from ``dim``.

    XLA lays both operands out word-major first, so the kernel reads
    word i of a block as one dense tile.  Of B and K, the axis that pads
    less to 128 goes on lanes and the other on sublanes; the query
    broadcasts along the row axis.  ``interpret=None`` resolves by
    platform, as in :func:`bq_distance_pallas`.
    """
    # lowering counter (repro.plan.trace): this body only runs when jax
    # traces it, so the count moves when a program lowers the batched
    # kernel and never on a call.  Imported lazily, as in
    # repro.core.beam.
    from repro.plan.trace import note_trace
    note_trace("bq_distance_rows")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, ww2 = q_words.shape
    k = rows.shape[1]
    w = ww2 // 2

    q_t = q_words.T                                  # (2W, B)
    r_t = jnp.transpose(rows, (2, 1, 0))             # (2W, K, B | 1)
    q_on_lanes = (_round_up(k, 8) * _round_up(b, 128)
                  <= _round_up(b, 8) * _round_up(k, 128))
    if q_on_lanes:                                   # out (K, B)
        s, t = k, b
        q_t = q_t[:, None, :]
    else:                                            # out (B, K)
        s, t = b, k
        q_t = q_t[:, :, None]
        r_t = jnp.swapaxes(r_t, 1, 2)                # (2W, B | 1, K)

    bs = s if s <= _ROWS_SUBLANES else _ROWS_SUBLANES
    lanes = max(128, _ROWS_TILE // _round_up(bs, 8) // 128 * 128)
    bt = t if t <= 128 else min(_round_up(t, 128), lanes)
    s_pad, t_pad = _round_up(s, bs), _round_up(t, bt)

    def block(x):
        """Pad ``x``'s non-broadcast axes to the grid; its BlockSpec."""
        _, a, c = x.shape
        x = jnp.pad(x, ((0, 0), (0, s_pad - s if a > 1 else 0),
                        (0, t_pad - t if c > 1 else 0)))
        spec = pl.BlockSpec(
            (ww2, bs if a > 1 else 1, bt if c > 1 else 1),
            lambda i, j: (0, i if a > 1 else 0, j if c > 1 else 0),
        )
        return x, spec

    q_t, q_spec = block(q_t)
    r_t, r_spec = block(r_t)
    out = pl.pallas_call(
        functools.partial(_bq_distance_rows_kernel, w=w),
        grid=(s_pad // bs, t_pad // bt),
        in_specs=[pl.BlockSpec((1, w), lambda i, j: (0, 0)), q_spec, r_spec],
        out_specs=pl.BlockSpec((bs, bt), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((s_pad, t_pad), jnp.int32),
        interpret=interpret,
    )(bq.valid_mask(dim).reshape(1, -1), q_t, r_t)[:s, :t]
    return out.T if q_on_lanes else out
