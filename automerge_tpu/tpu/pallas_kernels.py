"""Pallas TPU kernels for the sync protocol's Bloom filter hot path.

The sync protocol probes every candidate change hash against every peer's
`have` filter (reference backend/sync.js: getProbes:88, containsHash:116,
addHash:107). At replica-farm scale that is B filters x C candidates x 7
probes of bit tests — a bandwidth-bound bitwise workload that XLA executes
as a chain of gathers. These kernels fuse the whole probe sequence in VMEM:

- probe positions follow the reference's triple-hashing recurrence
  (x += y; y += z, all mod filter size) unrolled NUM_PROBES times. The
  wrapper reduces x, y, z mod the filter size once in XLA, so the kernel
  needs only add/compare/subtract;
- the word gather `words[probe >> 5]` and the build's OR-scatter ride the
  MXU as one-hot contractions. Every operand is a 0/1 one-hot or a byte
  (uint32 words travel as four byte planes), so each product is exact in
  bf16 with f32 accumulation, whatever precision the MXU runs at;
- hashes and words are lane-major rows (``[3, N]``, ``[1, W]``) and the
  per-filter scalars ride scalar prefetch, so every block is (8, 128)-
  aligned or full-dim, as Mosaic requires;
- the grid tiles the entry/query axis and the word axis, OR-accumulating
  into revisited output blocks, so every VMEM block stays small no matter
  how large the filter or candidate set grows.

Index maps and in-kernel fill values are ``jnp.int32`` literals: under the
package-wide ``jax_enable_x64`` a bare Python int lowers to i64, and Mosaic
refuses an index map that returns (i32, i64) and recurses converting a
weak i64 fill.

On CPU the kernels run in the Pallas interpreter (tests); on TPU they are
compiled. Results are bit-identical to the XLA reference implementations in
sync_batch.py, which remain the default host API.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..sync import NUM_PROBES
from .jitprof import profiled_jit

WORD_BITS = 32
_LANES = 128
# VMEM budgets: the largest intermediate is one probe's bf16 one-hot,
# [WORD_TILE, ENTRY/QUERY_TILE] = 512 * 256 * 2 B = 256 KB.
_ENTRY_TILE = 256
_QUERY_TILE = 256
_WORD_TILE = 512
_NT = (((1,), (1,)), ((), ()))  # dot_general: contract both operands' lanes


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m or m


def _zero():
    """An i32 index-map literal (see module docstring)."""
    return jnp.int32(0)


def _reduced_rows(xyz, modulo, n_pad):
    """[B, N, 3] uint32 -> [B, 3, n_pad] uint32 holding xyz % modulo, one
    lane-major row per hash word (pad columns are zero)."""
    m = jnp.maximum(modulo, 1).astype(jnp.uint32)[:, None, None]
    rows = jnp.transpose(xyz % m, (0, 2, 1))
    return jnp.pad(rows, ((0, 0), (0, 0), (0, n_pad - xyz.shape[1])))


def _probe_rows(xyz, modulo):
    """Triple-hash probe positions. xyz: [3, N] uint32 already reduced mod
    `modulo` (uint32 scalar). Returns NUM_PROBES rows of [1, N] uint32.
    x + y < 2 * modulo < 2^32, so the conditional subtract is the exact
    `% modulo` of the reference."""
    x, y, z = xyz[0:1, :], xyz[1:2, :], xyz[2:3, :]
    rows = [x]
    for _ in range(NUM_PROBES - 1):
        x = x + y
        x = jnp.where(x >= modulo, x - modulo, x)
        y = y + z
        y = jnp.where(y >= modulo, y - modulo, y)
        rows.append(x)
    return rows


def _join_bytes(planes):
    """[4, N] int32 byte rows -> [1, N] int32 word (uint32 bit pattern)."""
    return (planes[0:1] | (planes[1:2] << 8) | (planes[2:3] << 16)
            | (planes[3:4] << 24))


def _bloom_query_kernel(modulo_ref, words_ref, xyz_ref, out_ref, *, num_words):
    """One (filter, query-tile, word-tile) cell. Blocks: words [1, W_T]
    int32, xyz [3, C_T] uint32, out [P, C_T] int32 holding the probed bit
    per (probe, query), OR-accumulated across word tiles (each probe's
    word lives in exactly one tile, so the OR is exact). word_idx is
    clamped to num_words - 1 exactly like sync_batch.query_filters' gather,
    keeping the two implementations bit-identical even for over-sized moduli
    (possible only when a caller undersizes num_words for the filter count)."""
    w_idx = pl.program_id(2)
    w_t = words_ref.shape[1]
    c_t = xyz_ref.shape[1]
    modulo = jnp.maximum(modulo_ref[pl.program_id(0)], 1).astype(jnp.uint32)
    words = words_ref[...]
    planes = jnp.concatenate(
        [(words >> (8 * k)) & 0xFF for k in range(4)], axis=0
    ).astype(jnp.bfloat16)  # [4, W_T]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (w_t, c_t), 0)
    rows = _probe_rows(xyz_ref[...], modulo)
    bits = []
    for p in range(NUM_PROBES):
        probe = rows[p]
        word_idx = jnp.minimum(
            (probe >> 5).astype(jnp.int32), num_words - 1
        )
        local = word_idx - w_idx * w_t  # [1, C_T]; off-tile never matches
        onehot = (lanes == local).astype(jnp.bfloat16)  # [W_T, C_T]
        gathered = _join_bytes(jnp.dot(
            planes, onehot, preferred_element_type=jnp.float32
        ).astype(jnp.int32))
        bit_idx = (probe & 31).astype(jnp.int32)
        bits.append(jax.lax.shift_right_logical(gathered, bit_idx) & 1)
    bits = jnp.concatenate(bits, axis=0)  # [P, C_T]

    @pl.when(w_idx == 0)
    def _init():
        out_ref[...] = bits

    @pl.when(w_idx > 0)
    def _accumulate():
        out_ref[...] = out_ref[...] | bits


@profiled_jit("pallas.bloom_query", static_argnames=("interpret",))
def bloom_query(words, modulo, counts, query_xyz, *, interpret=False):
    """Pallas analogue of sync_batch.query_filters.

    words: [B, W] uint32, modulo: [B] int32, counts: [B] int32,
    query_xyz: [B, C, 3] uint32. Returns [B, C] bool."""
    batch, num_words = words.shape
    _, c, _ = query_xyz.shape
    w_t = min(_pad_to(num_words, _LANES), _WORD_TILE)
    c_t = min(_pad_to(c, _LANES), _QUERY_TILE)
    w_pad = _pad_to(num_words, w_t)
    c_pad = _pad_to(c, c_t)
    words = jnp.pad(
        jax.lax.bitcast_convert_type(words, jnp.int32),
        ((0, 0), (0, w_pad - num_words)),
    ).reshape(batch, 1, w_pad)
    rows = _reduced_rows(query_xyz, modulo, c_pad)

    bits = pl.pallas_call(
        partial(_bloom_query_kernel, num_words=num_words),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, c_pad // c_t, w_pad // w_t),
            in_specs=[
                pl.BlockSpec((None, 1, w_t),
                             lambda b, q, w, m: (b, _zero(), w)),
                pl.BlockSpec((None, 3, c_t),
                             lambda b, q, w, m: (b, _zero(), q)),
            ],
            out_specs=pl.BlockSpec((None, NUM_PROBES, c_t),
                                   lambda b, q, w, m: (b, _zero(), q)),
        ),
        out_shape=jax.ShapeDtypeStruct((batch, NUM_PROBES, c_pad), jnp.int32),
        interpret=interpret,
    )(modulo.astype(jnp.int32), words, rows)
    all_set = jnp.min(bits[:, :, :c], axis=1)
    return jnp.where(counts[:, None] > 0, all_set, 0).astype(jnp.bool_)


def _bloom_build_kernel(modulo_ref, count_ref, xyz_ref, out_ref):
    """One (filter, word-tile, entry-tile) cell. Blocks: xyz [3, E_T]
    uint32, out words [1, W_T] int32, OR-accumulated across entry tiles
    (the innermost grid axis, so the block is revisited consecutively).
    Per probe, ``hit[w, e] . bit[k, e]^T`` counts the entries that set bit
    k of word w; a second contraction folds the set bits into byte rows."""
    b = pl.program_id(0)
    w_idx = pl.program_id(1)
    e_idx = pl.program_id(2)
    e_t = xyz_ref.shape[1]
    w_t = out_ref.shape[1]
    modulo = jnp.maximum(modulo_ref[b], 1).astype(jnp.uint32)
    global_e = e_idx * e_t + jax.lax.broadcasted_iota(jnp.int32, (1, e_t), 1)
    entry_ok = global_e < count_ref[b]
    word_lanes = jax.lax.broadcasted_iota(jnp.int32, (w_t, e_t), 0)
    bit_lanes = jax.lax.broadcasted_iota(jnp.int32, (WORD_BITS, e_t), 0)
    rows = _probe_rows(xyz_ref[...], modulo)
    counts = jnp.zeros((w_t, WORD_BITS), jnp.float32)
    for p in range(NUM_PROBES):
        probe = rows[p]
        local = jnp.where(
            entry_ok, (probe >> 5).astype(jnp.int32) - w_idx * w_t,
            jnp.int32(-1),
        )
        hit = (word_lanes == local).astype(jnp.bfloat16)  # [W_T, E_T]
        bit = (bit_lanes == (probe & 31).astype(jnp.int32)).astype(
            jnp.bfloat16
        )  # [32, E_T]
        counts = counts + jax.lax.dot_general(
            hit, bit, _NT, preferred_element_type=jnp.float32
        )
    present = (counts > 0).astype(jnp.bfloat16)  # [W_T, 32]
    k = jax.lax.broadcasted_iota(jnp.int32, (4, WORD_BITS), 1)
    byte = jax.lax.broadcasted_iota(jnp.int32, (4, WORD_BITS), 0)
    weights = jnp.where(k >> 3 == byte, jnp.ones_like(k) << (k & 7),
                        jnp.zeros_like(k)).astype(jnp.bfloat16)
    words = _join_bytes(jax.lax.dot_general(
        weights, present, _NT, preferred_element_type=jnp.float32
    ).astype(jnp.int32))  # [1, W_T]

    @pl.when(e_idx == 0)
    def _init():
        out_ref[...] = words

    @pl.when(e_idx > 0)
    def _accumulate():
        out_ref[...] = out_ref[...] | words


@profiled_jit("pallas.bloom_build", static_argnames=("num_words", "interpret"))
def bloom_build(xyz, counts, num_words: int, *, interpret=False):
    """Pallas analogue of sync_batch.build_filters.

    xyz: [B, E, 3] uint32, counts: [B] int32. Returns (words [B, num_words]
    uint32, modulo [B] int32) exactly like sync_batch.build_filters."""
    from .sync_batch import filter_modulo

    batch, e, _ = xyz.shape
    modulo = filter_modulo(counts)
    e_t = min(_pad_to(e, _LANES), _ENTRY_TILE)
    w_t = min(_pad_to(num_words, _LANES), _WORD_TILE)
    e_pad = _pad_to(e, e_t)
    w_pad = _pad_to(num_words, w_t)
    rows = _reduced_rows(xyz, modulo, e_pad)

    words = pl.pallas_call(
        _bloom_build_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, w_pad // w_t, e_pad // e_t),
            in_specs=[
                pl.BlockSpec((None, 3, e_t),
                             lambda b, w, ei, m, n: (b, _zero(), ei)),
            ],
            out_specs=pl.BlockSpec((None, 1, w_t),
                                   lambda b, w, ei, m, n: (b, _zero(), w)),
        ),
        out_shape=jax.ShapeDtypeStruct((batch, 1, w_pad), jnp.int32),
        interpret=interpret,
    )(modulo.astype(jnp.int32), counts.astype(jnp.int32), rows)
    words = jax.lax.bitcast_convert_type(
        words[:, 0, :num_words], jnp.uint32
    )
    return words, modulo


_SEG_TILE = 128
_BYTE_TILE = 512


def _leb_segsum_kernel(planes_ref, seg_ref, out_ref):
    """One (varint-tile, byte-tile) cell of the LEB128 segmented sum.

    Blocks: planes [B_T, P] f32 (7-bit payload planes per byte), seg
    [B_T, 1] int32 (varint id per byte, -1 for padding), out [V_T, P] f32.
    Each byte belongs to exactly one varint, so accumulating partial
    one-hot matmuls over byte tiles reconstructs the exact per-varint
    plane sums (every operand is below 2^8, exact in bf16)."""
    v_idx = pl.program_id(1)
    b_idx = pl.program_id(2)
    v_t = out_ref.shape[0]
    seg = seg_ref[:, 0]  # [B_T]
    local = seg - v_idx * v_t
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (v_t, seg.shape[0]), 0) == local[None, :]
    ).astype(jnp.bfloat16)  # [V_T, B_T]
    partial_sums = jnp.dot(
        onehot, planes_ref[...].astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )

    @pl.when(b_idx == 0)
    def _init():
        out_ref[...] = partial_sums

    @pl.when(b_idx > 0)
    def _accumulate():
        out_ref[...] = out_ref[...] + partial_sums


@profiled_jit("pallas.leb128_segment_sum",
              static_argnames=("num_segments", "interpret"))
def leb128_segment_sum(planes, seg_ids, num_segments: int, *, interpret=False):
    """Per-varint payload-plane sums for the vectorized LEB128 decode
    (tpu/decode.leb128_scan_device): ``out[v, p] = sum(planes[i, p] for i
    with seg_ids[i] == v)``.

    planes: [N, P] f32 with integer entries below 2^8, seg_ids: [N] int32
    in [0, num_segments). XLA lowers this reduction to serialised scatters
    on TPU; here it rides the MXU as a tiled one-hot contraction, the same
    pattern as the Bloom word gather above."""
    n, p = planes.shape
    b_t = min(_pad_to(n, 8), _BYTE_TILE)
    v_t = min(_pad_to(num_segments, 8), _SEG_TILE)
    n_pad = _pad_to(n, b_t)
    v_pad = _pad_to(num_segments, v_t)
    planes = jnp.pad(planes, ((0, n_pad - n), (0, 0)))
    seg_ids = jnp.pad(
        seg_ids.astype(jnp.int32), (0, n_pad - n), constant_values=-1
    )

    out = pl.pallas_call(
        _leb_segsum_kernel,
        grid=(1, v_pad // v_t, n_pad // b_t),
        in_specs=[
            pl.BlockSpec((b_t, p), lambda g, v, b: (b, _zero()),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((b_t, 1), lambda g, v, b: (b, _zero()),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (v_t, p), lambda g, v, b: (v, _zero()), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((v_pad, p), jnp.float32),
        interpret=interpret,
    )(planes, seg_ids.reshape(n_pad, 1))
    return out[:num_segments]
