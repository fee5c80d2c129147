"""BFP matmul Pallas kernel — paper C2 adapted to TPU (DESIGN.md §2).

One launch runs a whole 1x1 BFP conv: the activation quantization
(Algorithm 1, the paper's Fig. 6 normalization module), the MXU
contraction with a wide f32 accumulator (§IV.C), and the conv's bias
and ReLU epilogue (Fig. 5's per-layer datapath flags).

Operands, as the data pool holds them:

- ``a`` (M, K): the activation, f16 storage (or f32), read straight from
  HBM.  Each (bm, K) tile is quantized in VMEM along K in blocks of
  ``block_size``: the block's shared exponent is the largest frexp
  exponent of its nonzero values, each mantissa is truncated to
  ``mantissa_bits`` fractional bits and arithmetically shifted down to
  the shared exponent — bit for bit what ``core.bfp.quantize`` does.
- ``w`` (K, N) f32: weights already BFP-valued along K (the load-time
  normalization of ``FCNEngine.normalize_weights``), used as they are.

Blocks never straddle a 128-lane vreg: K is padded with zeros to a
multiple of 128 (lanes the tiled layout keeps anyway; zeros never win a
block max), and the tile is quantized in slabs of 128 lanes.  The block
max takes five lane rolls (1, 2, 4, 8, 16 for blocks of 32): each lane
ends with the maximum of the 32 lanes up to it, so each block's last
lane holds the block's; one MXU pass with a 0/1 matrix spreads it over
the block.  Measured on a TPU v5e, this beat a butterfly of ten rolls,
and slabs of 256 rows beat slabs of 16-128 (more independent vregs
between the rolls' latencies).

The 16-bit storage type is not a Mosaic vector type on this chip
generation, so f16 reaches the kernel as its int16 bit pattern and is
widened to f32 bits in VMEM (subnormals included, exactly).

Grid: (M/bm, N/bn).  Every tile holds whole rows of A (bk = K, no K
sweep); bn is N on every served shape, so the weight matrix's block
index never changes and it is fetched once per call.  At a row tile's
first column tile the quantization walks it into an f32 VMEM scratch;
then one MXU contraction at ``Precision.HIGHEST`` (exact on the 11-bit
signed mantissas) feeds the flush: + bias, ReLU, one f32 store.

``bfp_roundtrip_rows`` runs the same quantization alone, for the convs
the kernels above do not take (3x3, strided, depthwise): one pass over
(M, C) rows, f16 bits in, the dequantized f32 values out, each 128-lane
slab of a row tile quantized as in the matmul's prologue.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import repro.kernels

#: rows of A per grid step at most, and the VMEM the step's blocks may take
_ROW_CAP = 2048
_BLOCK_BUDGET = 24 << 20
#: rows of one quantization slab (x 128 lanes)
_CHUNK_ROWS = 256
#: rows per grid step at most of the round-trip pass
_ROWS_CAP = 4096


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _f32_bits(h: jax.Array) -> jax.Array:
    """int16 bits of f16 values -> int32 bits of the same values as f32
    (exact: f16 normals rebias their exponent, f16 subnormals become f32
    normals through an exact int->float conversion).  int32 input is
    taken as f32 bits already."""
    if h.dtype != jnp.int16:
        return h
    h = h.astype(jnp.int32)                   # sign-extended
    mag = h & 0x7FFF
    normal = (mag << 13) + (112 << 23)        # f16 bias 15 -> f32 bias 127
    tiny = pltpu.bitcast(mag.astype(jnp.float32) * (2.0 ** -24), jnp.int32)
    bits = jnp.where(mag < 0x400, tiny, normal)
    return bits | (h & jnp.int32(-(2 ** 31)))


def _spread(block_size: int) -> jax.Array:
    """(128, 128) 0/1: row ``i`` copies lane ``i`` to every lane of its
    block when ``i`` is the block's last lane, else nothing."""
    src = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
    return ((src // block_size == dst // block_size)
            & (src % block_size == block_size - 1)).astype(jnp.float32)


def _block_max(e: jax.Array, spread: jax.Array, block_size: int
               ) -> jax.Array:
    """Each lane's maximum of ``e`` (int32, 0..255) over its block of
    ``block_size`` lanes (a power of two dividing 128).  Lane rolls by
    1, 2, 4, ... leave each lane the maximum of the ``block_size`` lanes
    up to it, so a block's last lane holds the block's maximum; one MXU
    pass spreads it over the block (exact: small integers, one nonzero
    term per sum)."""
    s = 1
    while s < block_size:
        e = jnp.maximum(e, pltpu.roll(e, s, 1))        # from lane i - s
        s *= 2
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    ends = jnp.where(lane % block_size == block_size - 1, e, 0)
    return jnp.dot(ends.astype(jnp.float32), spread,
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def _quantize_slab(bits: jax.Array, spread: jax.Array, *, block_size: int,
                   mantissa_bits: int, rounding: str) -> jax.Array:
    """Algorithm 1 on f32 bit patterns (rows, 128), blocks along the
    lanes: the dequantized values ``mantissa * 2**(exponent -
    mantissa_bits)``.

    Integer form of ``core.bfp.quantize``: the block exponent is the
    largest exponent field among nonzero values (zeros carry field 0 and
    never win; an all-zero block quantizes to zeros); ``mi`` is the
    24-bit significand cut to ``mantissa_bits`` fractional bits of the
    frexp mantissa, signed; the shift by the exponent distance (capped
    at 31) floors negative values.  f32 subnormals are outside the
    contract (f16 storage never produces one)."""
    e = (bits >> 23) & 0xFF
    top = _block_max(e, spread, block_size)
    d = jnp.minimum(top - e, 31)
    # implicit bit only for nonzero values
    sig = (bits & 0x7FFFFF) | (jnp.minimum(e, 1) << 23)
    mi = sig >> (24 - mantissa_bits)
    neg = bits >> 31                                   # 0 or -1
    mi = (mi ^ neg) - neg
    if rounding == "nearest":
        half = jnp.where(d > 0, 1 << jnp.maximum(d - 1, 0), 0)
        mi = mi + jnp.where(mi > 0, half, jnp.where(mi < 0, -half, 0))
    q = mi >> d
    # 2**(xi - mantissa_bits), xi = field - 126, clamped as exp2i
    scale = jnp.clip(top + 1 - mantissa_bits, 1, 254) << 23
    return q.astype(jnp.float32) * pltpu.bitcast(scale, jnp.float32)


def _check(block_size: int, mantissa_bits: int, rounding: str) -> None:
    if rounding not in ("trunc", "nearest"):
        raise ValueError(rounding)
    if block_size & (block_size - 1) or not 0 < block_size <= 128 \
            or not 0 < mantissa_bits < 24:
        raise ValueError(f"block_size {block_size}, mantissa_bits "
                         f"{mantissa_bits}: need a power-of-two block up to "
                         f"128 and 1-23 bits")


def _as_bits(a: jax.Array) -> jax.Array:
    """The kernels' view of an activation: f16 as its int16 bits (no
    Mosaic vector type on this chip generation), anything else as the
    int32 bits of its f32 value."""
    if a.dtype == jnp.float16:
        return jax.lax.bitcast_convert_type(a, jnp.int16)
    return jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.int32)


def _kernel(a_ref, w_ref, b_ref, o_ref, q_ref, *, block_size: int,
            mantissa_bits: int, rounding: str, relu: bool, rows: int):
    """a: (bm, Kp) int16/int32 bits, Kp a multiple of 128; w: (K, bn)
    f32, K <= Kp; b: (1, bn) f32; o: (bm, bn) f32; q: (bm, Kp) f32
    scratch of the quantized tile, filled at the row tile's first
    column tile."""
    kp, k = a_ref.shape[1], w_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _quantize():
        spread = _spread(block_size)

        def chunk(c, carry):
            r = pl.multiple_of(c * rows, rows)
            # 128-lane slabs: blocks never straddle one
            for j in range(0, kp, 128):
                q_ref[pl.ds(r, rows), pl.ds(j, 128)] = _quantize_slab(
                    _f32_bits(a_ref[pl.ds(r, rows), pl.ds(j, 128)]),
                    spread, block_size=block_size,
                    mantissa_bits=mantissa_bits, rounding=rounding)
            return carry

        jax.lax.fori_loop(0, a_ref.shape[0] // rows, chunk, 0)

    q = q_ref[...] if k == kp else q_ref[:, :k]
    acc = jnp.dot(q, w_ref[...], preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)
    acc = acc + b_ref[...]
    if relu:
        acc = jnp.maximum(acc, 0.0)
    o_ref[...] = acc


def _row_block(m: int, sub: int, cap: int, fits):
    """(rows as called, bm): ``bm`` the largest power-of-two row tile up
    to ``cap`` that divides ``m`` and ``fits``.  An ``m`` with no tile of
    a whole sublane group (``sub`` rows) is one block up to 256 rows,
    else padded to a multiple of 256."""
    bm = 1
    while (m % (2 * bm) == 0 and 2 * bm <= cap
           and (2 * bm <= sub or fits(2 * bm))):
        bm *= 2
    if bm >= sub:
        return m, bm
    if m > 256:
        return _row_block(-(-m // 256) * 256, sub, cap, fits)
    return m, m


def _vmem_limit(vmem: int) -> int:
    return int(min(max(vmem + (16 << 20), 32 << 20), 100 << 20))


def _tiles(m: int, k: int, n: int, itemsize: int):
    """(rows of A as called, bm, bn, chunk rows, vmem bytes).  ``bn`` is
    N unless the weight matrix overflows half the budget (then the
    largest 128-lane multiple dividing N that fits); ``bm`` the row tile
    of ``_row_block`` that fits the rest."""
    kp, k8 = _lanes(k), -(-k // 8) * 8 + 8
    w_bytes = lambda bn: 2 * k8 * _lanes(bn) * 4    # double-buffered
    bn = n
    if w_bytes(n) > _BLOCK_BUDGET // 2 and n % 128 == 0:
        bn = 128
        while (n % (2 * bn) == 0
               and w_bytes(2 * bn) <= _BLOCK_BUDGET // 2):
            bn *= 2
    # A (double-buffered), the quantized scratch, out (double-buffered)
    # and the dot's result, per row
    per_row = kp * (2 * itemsize + 4) + 3 * _lanes(bn) * 4
    sub = 32 // itemsize                      # 16 rows for 16-bit input
    m, bm = _row_block(
        m, sub, _ROW_CAP,
        lambda b: b * per_row + w_bytes(bn) <= _BLOCK_BUDGET)
    rows = bm if bm % sub else min(bm, _CHUNK_ROWS)
    return m, bm, bn, rows, bm * per_row + w_bytes(bn)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "mantissa_bits", "rounding", "relu",
                     "interpret"),
)
def bfp_matmul_quantized(
    a: jax.Array,                 # (M, K) f16 or f32 activation
    w: jax.Array,                 # (K, N) BFP-valued f32 weights
    b: jax.Array | None = None,   # (N,) bias, applied in the flush
    *,
    block_size: int,
    mantissa_bits: int,
    rounding: str = "trunc",
    relu: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """(M, N) f32 = [relu](quantize(a) @ w + b), ``a`` quantized along K
    in the kernel, ``block_size`` a power of two up to 128.  Tiles adapt
    to M, K and N.  A K of no 128-lane multiple is padded with zeros
    (they fill lanes the tiled layout keeps anyway), an ``M`` with no
    power-of-two tile of a sublane group with zero rows."""
    if interpret is None:
        interpret = repro.kernels.default_interpret()
    _check(block_size, mantissa_bits, rounding)
    m, k = a.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(f"K={k}, weights {k2}: need equal K")
    w = w.astype(jnp.float32)
    bits = _as_bits(a)
    if k % 128:
        # the lanes a narrow K leaves empty in the tiled layout, as
        # zeros: they never win a block max and meet no weight row
        bits = jnp.pad(bits, ((0, 0), (0, (-k) % 128)))
    mp, bm, bn, rows, vmem = _tiles(m, k, n, bits.dtype.itemsize)
    if mp != m:
        bits = jnp.pad(bits, ((0, mp - m), (0, 0)))
    bias = (jnp.zeros((1, n), jnp.float32) if b is None
            else b.astype(jnp.float32).reshape(1, n))
    out = pl.pallas_call(
        functools.partial(_kernel, block_size=block_size,
                          mantissa_bits=mantissa_bits, rounding=rounding,
                          relu=relu, rows=rows),
        grid=(mp // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, _lanes(k)), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, _lanes(k)), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(vmem)),
        interpret=interpret,
    )(bits, w, bias)
    return out if mp == m else out[:m]


def _rows_kernel(a_ref, o_ref, *, block_size: int, mantissa_bits: int,
                 rounding: str, rows: int, groups: int):
    """a: (bm, C) int16/int32 bits; o: (bm, C) f32, the dequantized
    values.  A C dividing 128 packs ``groups`` = 128 / C runs of
    ``rows`` rows side by side into one 128-lane slab (lane rolls in and
    out; blocks of at most C lanes never straddle two rows).  Any other
    slab narrower than 128 lanes (C's last) is widened with zero lanes,
    which never win a block max."""
    c = a_ref.shape[1]
    spread = _spread(block_size)
    quantize = functools.partial(_quantize_slab, spread=spread,
                                 block_size=block_size,
                                 mantissa_bits=mantissa_bits,
                                 rounding=rounding)

    def widen(bits):
        w = bits.shape[1]
        if w == 128:
            return bits
        return jnp.concatenate(
            [bits, jnp.zeros((bits.shape[0], 128 - w), jnp.int32)], axis=1)

    def chunk(i, carry):
        r = pl.multiple_of(i * rows * groups, rows * groups)
        if groups > 1:
            slab = None
            for k in range(groups):
                part = widen(_f32_bits(a_ref[pl.ds(r + k * rows, rows), :]))
                part = pltpu.roll(part, k * c, 1) if k else part
                slab = part if slab is None else slab | part
            q = quantize(slab)
            for k in range(groups):
                qk = pltpu.roll(q, 128 - k * c, 1) if k else q
                o_ref[pl.ds(r + k * rows, rows), :] = qk[:, :c]
            return carry
        for j in range(0, c, 128):
            w = min(128, c - j)
            q = quantize(widen(_f32_bits(a_ref[pl.ds(r, rows),
                                               pl.ds(j, w)])))
            o_ref[pl.ds(r, rows), pl.ds(j, w)] = q if w == 128 else q[:, :w]
        return carry

    jax.lax.fori_loop(0, a_ref.shape[0] // (rows * groups), chunk, 0)


def _row_tiles(m: int, c: int, itemsize: int):
    """(rows of A as called, bm, chunk rows, groups, vmem bytes): ``bm``
    the row tile of ``_row_block`` whose double-buffered input and
    output tiles fit the budget; ``groups`` runs of ``chunk rows`` share
    a slab where C divides 128 and the tile holds whole sublane groups
    of each."""
    per_row = 2 * _lanes(c) * (itemsize + 4)
    sub = 32 // itemsize
    m, bm = _row_block(m, sub, _ROWS_CAP,
                       lambda b: b * per_row <= _BLOCK_BUDGET)
    groups = 128 // c if c < 128 and 128 % c == 0 else 1
    if groups > 1 and bm % (groups * sub) == 0:
        rows = min(bm // groups, _CHUNK_ROWS)
    else:
        groups = 1
        rows = bm if bm % sub else min(bm, _CHUNK_ROWS)
    return m, bm, rows, groups, bm * per_row


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "mantissa_bits", "rounding",
                     "interpret"),
)
def bfp_roundtrip_rows(
    a: jax.Array,                 # (M, C) f16 or f32 activation
    *,
    block_size: int,
    mantissa_bits: int,
    rounding: str = "trunc",
    interpret: bool | None = None,
) -> jax.Array:
    """(M, C) f32: Algorithm 1 along C in blocks of ``block_size`` (a
    power of two up to 128; a C below it is one block), quantized and
    dequantized in one pass: ``core.bfp.roundtrip(a, axis=-1)`` bit for
    bit, f32 subnormal inputs aside (f16 storage never holds one)."""
    if interpret is None:
        interpret = repro.kernels.default_interpret()
    _check(block_size, mantissa_bits, rounding)
    m, c = a.shape
    bits = _as_bits(a)
    mp, bm, rows, groups, vmem = _row_tiles(m, c, bits.dtype.itemsize)
    if mp != m:
        bits = jnp.pad(bits, ((0, mp - m), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rows_kernel, mantissa_bits=mantissa_bits,
                          block_size=min(block_size, c) if groups > 1
                          else block_size,
                          rounding=rounding, rows=rows, groups=groups),
        grid=(mp // bm,),
        in_specs=[pl.BlockSpec((bm, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(vmem)),
        interpret=interpret,
    )(bits)
    return out if mp == m else out[:m]
