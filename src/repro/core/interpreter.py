"""Microcode interpreter — the paper's FCN module + microcode interpreter
(Fig. 5), as a trace-time executor emitting one XLA program.

The hardware parses one microcode per layer and drives fixed datapath
units (conv / pool / upsample / post-process) against a DDR4 data pool.
Here the data pool is a trace-time *arena* keyed by the microcode address
fields; the datapath units are jnp/Pallas implementations chosen by
``mode``:

    mode="reference"  pure lax/jnp ops (the oracle)
    mode="optimized"  Winograd F(4x4,3x3) for stride-1 3x3 convs, fused
                      phase-decomposed upsample, Pallas kernels where
                      available

BFP numerics (paper §III.E): when a :class:`BFPConfig` is given, conv
weights are run through Algorithm 1 quantization once, at load time
(:meth:`FCNEngine.normalize_weights`), conv inputs in every call before the
MAC, and the accumulator stays wide (f32 >= the paper's 15-bit mantissa) —
the §IV.C accuracy-maintenance discipline.  Storage between layers is FP16
(``storage_dtype``), exactly the paper's data-pool format.

The same interpreter executes LM architectures: :func:`build_stream_fn`
turns a microcode segment into a layer function by dispatching extended
opcodes against a module registry (the "datapath modules" for
transformers), with ``res_op`` cache/add providing residual connections —
the transformer residual is *literally* the paper's Fig. 3 mechanism.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import bfp as bfp_lib
from . import fuse, winograd
from .assembler import Program, STORAGE_BYTES
from .memplan import plan_program
from .microcode import ExtOp, LayerType, Microcode, ResOp


@dataclasses.dataclass(frozen=True)
class BFPConfig:
    block_size: int = bfp_lib.DEFAULT_BLOCK
    mantissa_bits: int = bfp_lib.DEFAULT_MANTISSA
    rounding: str = "trunc"
    wide_accum: bool = True      # False reproduces the pre-Fig.7 failure


def word_kind(mc: Microcode, spec) -> str:
    """The datapath a microcode word drives, as its named scope says:
    ``conv1x1``/``conv3x3`` (``conv<k>x<k>`` at stride 1),
    ``conv_strided``, ``depthwise``, ``pool``, ``upsample``, or the
    extended op (``add``, ``sigmoid``, ``identity``)."""
    lt = LayerType(mc.layer_type)
    if lt == LayerType.CONV:
        if spec.table and spec.table.get("depthwise"):
            return "depthwise"
        if mc.stride_n > 1:
            return "conv_strided"
        return f"conv{mc.kernel_size}x{mc.kernel_size}"
    if lt == LayerType.POOL:
        return "pool"
    if lt == LayerType.UPSAMPLE:
        return "upsample"
    return ExtOp(mc.ext_opcode).name.lower()


#: parameter key of a 1x1 conv's load-time weights: the BFP-normalized
#: (Cin, Cout) matrix (``FCNEngine.normalize_weights``)
MATRIX = "w_kn"
#: parameter key of any other conv's load-time weights: the BFP-normalized
#: HWIO kernel, used as it is
KERNEL = "w_bfp"
#: the least input channels for which a conv's activation round trip runs
#: as one Pallas pass.  On a TPU v5e XLA's fused round trip won at 3 and 16
#: channels (rows that fill a few of a tile's 128 lanes), the two tied at
#: 32, and from 64 up the pass won by a wide margin
ROWS_MIN_CIN = 32


def weight_key(mc: Microcode, spec) -> str:
    """The key a conv word's load-time weights sit under on a BFP engine:
    ``MATRIX`` for a ``conv1x1`` word (a matmul), ``KERNEL`` for every
    other conv."""
    return MATRIX if word_kind(mc, spec) == "conv1x1" else KERNEL


def _he_init(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * np.sqrt(2.0 / fan_in)


class FCNEngine:
    """Executes an assembled FCN :class:`Program` (paper Figs. 2 & 5)."""

    def __init__(
        self,
        program: Program,
        mode: str = "reference",
        bfp: Optional[BFPConfig] = None,
        storage_dtype=jnp.float32,
        use_pallas: bool = False,
    ):
        if mode not in ("reference", "optimized"):
            raise ValueError(mode)
        self.program = program
        self.mode = mode
        self.bfp = bfp
        self.storage_dtype = storage_dtype
        self.use_pallas = use_pallas
        # the static memory plan (core.memplan, a pure function of the
        # program): the live words, fusion facts, dead stores and
        # per-word free-after sets, so the trace drops a buffer reference
        # at its last use instead of pinning every intermediate.
        # memplan.identity_plan runs every word and keeps everything.
        self.memplan = plan_program(
            program, dtype_bytes=jnp.dtype(storage_dtype).itemsize)

    # -- parameters ----------------------------------------------------------
    def init_params(self, key: jax.Array) -> Dict[str, Dict[str, jax.Array]]:
        """He-normal convs; the last conv of each residual branch (a
        ``res add`` word) is scaled by 1/sqrt(#branches), as Fixup/SkipInit
        do, so the residual stream's variance stays within 2x of its input
        instead of doubling per block.  Unscaled, a random ResNet-50's
        logits reach std ~20 and any rounding (BFP, bf16) is amplified
        through its 16 blocks."""
        params: Dict[str, Dict[str, jax.Array]] = {}
        n_branches = sum(1 for idx in self.program.weight_bindings
                         if self.program.words[idx].res_op == ResOp.ADD)
        for idx, name in self.program.weight_bindings.items():
            mc = self.program.words[idx]
            spec = self.program.layer_specs[idx]
            key, k1 = jax.random.split(key)
            if spec.op == "conv":
                k = mc.kernel_size
                cin, cout = mc.in_ch, mc.out_ch
                if spec.table and spec.table.get("depthwise"):
                    p = {"w": _he_init(k1, (k, k, 1, cout), k * k)}
                else:
                    p = {"w": _he_init(k1, (k, k, cin, cout), k * k * cin)}
                if mc.res_op == ResOp.ADD:
                    p["w"] = p["w"] / np.sqrt(n_branches)
                if spec.bias:
                    p["b"] = jnp.zeros((cout,), jnp.float32)
                if spec.bn:
                    p.update(
                        gamma=jnp.ones((cout,), jnp.float32),
                        beta=jnp.zeros((cout,), jnp.float32),
                        mean=jnp.zeros((cout,), jnp.float32),
                        var=jnp.ones((cout,), jnp.float32),
                    )
                params[name] = p
            elif spec.op == "upsample" and spec.upsample_mode == "fused":
                cin = mc.in_ch
                cout = mc.out_ch or cin
                params[name] = {"w": _he_init(k1, (3, 3, cin, cout), 9 * cin)}
        return params

    def normalize_weights(self, params):
        """Paper Fig. 4 right branch: fold BN, then BFP-normalize weights.
        A BFP engine's conv words read their weights only in this form,
        under :func:`weight_key`; f32 engines and upsample words keep
        ``w``."""
        out = {}
        for idx, name in self.program.weight_bindings.items():
            mc, spec = self.program.words[idx], self.program.layer_specs[idx]
            p = dict(params[name])
            if spec.op == "conv" and spec.bn:
                w, b = fuse.fold_batchnorm(
                    p["w"], p.get("b"), p["gamma"], p["beta"], p["mean"],
                    p["var"],
                )
                p = {"w": w, "b": b}
            if self.bfp is not None and "w" in p:
                # Algorithm 1 on the kernel, blocked along Cin (the K dim)
                w = bfp_lib.roundtrip(
                    p.pop("w").astype(jnp.float32),
                    block_size=self.bfp.block_size,
                    mantissa_bits=self.bfp.mantissa_bits,
                    axis=-2,
                    rounding=self.bfp.rounding,
                )
                key = weight_key(mc, spec) if spec.op == "conv" else "w"
                # a 1x1 conv is a matmul: its (Cin, Cout) matrix is what
                # the BFP matmul kernel takes as it is
                p[key] = w.reshape(w.shape[-2:]) if key == MATRIX else w
            out[name] = p
        return out

    def kernel_words(self) -> Dict[str, int]:
        """How the conv words of a BFP engine run: the 1x1 words on the
        BFP matmul kernel (``bfp1x1_fused_words``), and the other conv
        words whose activation round trip runs as one Pallas pass
        (``bfp_rows_words``)."""
        fused = rows = 0
        for idx in self.program.weight_bindings:
            mc, spec = self.program.words[idx], self.program.layer_specs[idx]
            if spec.op != "conv":
                continue
            fused += self._bfp_matmul(mc, spec)
            rows += self._bfp_rows(mc, spec)
        return {"bfp1x1_fused_words": fused, "bfp_rows_words": rows}

    def _pallas_bfp(self) -> bool:
        return (self.bfp is not None and self.use_pallas
                and self.mode == "optimized")

    def _bfp_matmul(self, mc: Microcode, spec) -> bool:
        """Whether a word runs on the BFP matmul kernel: a conv whose
        load-time weights are a ``MATRIX`` (1x1, stride 1), on an
        optimized BFP engine with the Pallas kernels on."""
        return self._pallas_bfp() and weight_key(mc, spec) == MATRIX

    def _bfp_rows(self, mc: Microcode, spec) -> bool:
        """Whether a conv word off the BFP matmul kernel round-trips its
        activation in one Pallas pass (``bfp_roundtrip_rows``): an
        optimized BFP engine with the Pallas kernels on, blocks of a
        power of two up to 128 lanes, and at least ``ROWS_MIN_CIN``
        input channels."""
        bs = self.bfp.block_size if self.bfp is not None else 0
        return (self._pallas_bfp() and not self._bfp_matmul(mc, spec)
                and 0 < bs <= 128 and not bs & (bs - 1)
                and mc.in_ch >= ROWS_MIN_CIN)

    # -- datapath units -------------------------------------------------------
    def _conv(self, x, p, mc: Microcode, spec, *, transposed: bool = False,
              relu: bool = False):
        """One conv microcode word.  ``transposed`` rides in as an
        explicit argument — never instance state, so concurrent traces
        of one cached engine (transposed vs not, PR 4's async dispatch)
        each bake their own kernel orientation.  ``relu=True`` fuses the
        word's activation into this launch (the memory plan decides
        eligibility).  A BFP engine reads the weights only in their
        load-time form, under :func:`weight_key`."""
        b = p.get("b")
        if self.bfp is None:
            w = p["w"]
        else:
            key = weight_key(mc, spec)
            if key not in p:
                raise ValueError(
                    f"a BFP engine reads {word_kind(mc, spec)} weights only "
                    f"in their load-time form {key!r}; run the parameters "
                    f"through normalize_weights first")
            w = p[key]
        if self._bfp_matmul(mc, spec):
            # a 1x1 conv IS a matmul: one BFP kernel launch quantizes the
            # f16 activation along Cin in VMEM, multiplies by the
            # load-time matrix and applies bias and ReLU in its flush
            from repro.kernels.bfp_matmul.kernel import bfp_matmul_quantized

            n, hh, ww, cin = x.shape
            # the views in and out of the kernel: scope bfp_matmul_io
            with jax.named_scope("bfp_matmul_io"):
                xm = x.reshape(-1, cin)
            y = bfp_matmul_quantized(
                xm, w, b,
                block_size=self.bfp.block_size,
                mantissa_bits=self.bfp.mantissa_bits,
                rounding=self.bfp.rounding,
                relu=relu,
            )
            with jax.named_scope("bfp_matmul_io"):
                return y.reshape(n, hh, ww, -1)
        if w.ndim == 2:
            # a MATRIX off the matmul kernel: the 1x1 kernel it was
            w = w[None, None]
        if transposed:
            # transposed-image mode: transpose the weight kernels (paper:
            # "transposing the corresponding weight kernels and modifying
            # the convolution mode")
            w = jnp.swapaxes(w, 0, 1)
        depthwise = bool(spec.table and spec.table.get("depthwise"))
        if self.bfp is not None:
            # Algorithm 1 on the activation: scope bfp_roundtrip (what the
            # benchmark's quantize_share reads)
            with jax.named_scope("bfp_roundtrip"):
                if self._bfp_rows(mc, spec):
                    from repro.kernels.bfp_matmul.kernel import (
                        bfp_roundtrip_rows)

                    x = bfp_roundtrip_rows(
                        x.reshape(-1, x.shape[-1]),
                        block_size=self.bfp.block_size,
                        mantissa_bits=self.bfp.mantissa_bits,
                        rounding=self.bfp.rounding,
                    ).reshape(x.shape)
                else:
                    x = bfp_lib.roundtrip(
                        x.astype(jnp.float32),
                        block_size=self.bfp.block_size,
                        mantissa_bits=self.bfp.mantissa_bits,
                        axis=-1,
                        rounding=self.bfp.rounding,
                    )
        x = x.astype(jnp.float32)
        w = w.astype(jnp.float32)
        if depthwise:
            y = lax.conv_general_dilated(
                x, w, (mc.stride_n, mc.stride_n), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=mc.in_ch,
                preferred_element_type=jnp.float32,
            )
            return fuse.conv_epilogue(y, b, relu)
        if (
            self.mode == "optimized"
            and mc.kernel_size == 3
            and mc.stride_n == 1
        ):
            if self.use_pallas:
                from repro.kernels.winograd_conv import ops as wops

                # bias + ReLU fused into the kernel's output-transform
                # flush: one launch for the whole microcode sequence
                return wops.winograd_conv2d(x, w, b, relu=relu)
            y = winograd.winograd_conv2d(x, w, padding="SAME")
        else:
            y = lax.conv_general_dilated(
                x, w, (mc.stride_n, mc.stride_n), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32,
            )
        return fuse.conv_epilogue(y, b, relu)

    @staticmethod
    def _pool(x, mc: Microcode, spec):
        k = 2 if mc.kernel == 0 else 3
        s = mc.stride_n
        if spec.pool_kind == "max":
            init, op = -jnp.inf, lax.max
        else:
            init, op = 0.0, lax.add
        y = lax.reduce_window(
            x, init, op, (1, k, k, 1), (1, s, s, 1), "SAME"
        )
        if spec.pool_kind == "avg":
            y = y / (k * k)
        return y

    def _upsample(self, x, p, *, decomposed: bool):
        # ``decomposed`` is the plan fact "this upsample carries a 3x3
        # conv eligible for phase decomposition"
        if not decomposed:
            return fuse.upsample_nearest_2x(x)
        w = p["w"].astype(jnp.float32)
        if self.mode == "optimized":
            return fuse.upsample2x_conv3x3_fused(x.astype(jnp.float32), w)
        return fuse.upsample2x_conv3x3_naive(x.astype(jnp.float32), w)

    # -- row-banded spatial execution (paper §IV.B across devices) ------------
    @staticmethod
    def _spatial_banded(band_ctx, x, k, s, op, out_scale: int = 1):
        """Run one spatial layer on a row-band shard: exchange enough
        neighbor rows (``band_ctx.exchange`` — see
        runtime.collectives.halo_exchange), apply the op with its normal
        SAME padding on the extended band, slice this band's own output
        rows back out.  The halo is rounded up to a multiple of 4 so
        stride phase is always preserved and the Winograd F(4x4) tile
        grid stays aligned with the full plane wherever the band offset
        is itself a tile multiple."""
        if band_ctx is None or k <= s:
            # k <= s: windows never cross a band boundary (rows % s == 0)
            return op(x)
        halo = s * (-(-(k - 1) // s))            # context + stride phase
        halo = -(-halo // 4) * 4                 # winograd tile alignment
        bh = x.shape[1]
        y = op(band_ctx.exchange(x, halo))
        j0 = halo * out_scale // s
        return lax.slice_in_dim(y, j0, j0 + bh * out_scale // s, axis=1)

    # -- the interpreter loop ---------------------------------------------------
    def __call__(
        self, params, x: jax.Array, *, transposed: bool = False,
        band_ctx=None,
    ) -> Dict[str, jax.Array]:
        """x: (N, H, W, C) matching the program's input plane.

        ``band_ctx`` enables row-banded execution (paper §IV.B spread
        over a device mesh): ``x`` is one horizontal band of a larger
        plane and every spatial layer halo-exchanges its boundary rows
        through ``band_ctx.exchange(x, halo)`` so each band computes the
        full plane's rows (the multi-device generalization of
        core.rowband.conv2d_banded — see runtime/executor.py; exact up
        to Winograd tile-regrouping float noise in "optimized" mode).

        ``transposed=True`` is the paper's §IV.B over-wide-image mode: the
        SAME microcode program runs on the transposed plane with
        transposed kernels (square kernels, symmetric strides — so the
        datapath is reused unchanged); outputs come back transposed and
        the caller inverse-transposes.  Region extents are invariant
        (H*W*C bytes), so the address plan still holds.
        """
        prog = self.program
        c0, h0, w0 = prog.input_shape_chw
        if transposed:
            if x.shape[1:] != (w0, h0, c0):
                raise ValueError(
                    f"transposed input {x.shape} != plane {(w0, h0, c0)}"
                )
        elif x.shape[1:] != (h0, w0, c0):
            raise ValueError(
                f"input {x.shape} != program plane {(h0, w0, c0)}"
            )
        arena: Dict[int, jax.Array] = {prog.input_addr: x}
        extents: Dict[int, int] = {
            prog.input_addr: h0 * w0 * c0 * STORAGE_BYTES
        }
        cache: Optional[jax.Array] = None

        def read(addr: int, want_ch: int) -> jax.Array:
            if addr in arena and arena[addr].shape[-1] == want_ch:
                return arena[addr]
            # concat read: collect memory-contiguous buffers from addr
            parts, cur, got = [], addr, 0
            while got < want_ch:
                if cur not in arena:
                    raise KeyError(
                        f"read at {cur:#x}: no buffer (concat walk from "
                        f"{addr:#x}, have {got}/{want_ch} channels)"
                    )
                buf = arena[cur]
                parts.append(buf)
                got += buf.shape[-1]
                cur += extents[cur]
            if got != want_ch:
                raise ValueError(f"concat channel mismatch {got}!={want_ch}")
            return jnp.concatenate(parts, axis=-1)

        plan = self.memplan
        for idx in plan.schedule:
            mc = prog.words[idx]
            spec = prog.layer_specs[idx]
            with jax.named_scope(f"w{idx:03d}.{word_kind(mc, spec)}"):
                wp = plan.word(idx)
                xin = read(mc.in_addr, mc.in_ch)
                name = prog.weight_bindings.get(idx)
                p = params.get(name, {}) if name else {}
                lt = LayerType(mc.layer_type)
                fused_relu = False
                if lt == LayerType.CONV:
                    # conv+bias+ReLU fuse into one launch (optimized
                    # mode, where the plan says so — the residual
                    # register reads the pre-activation value, so res
                    # words keep a separate ReLU)
                    fused_relu = self.mode == "optimized" and wp.fuse_relu
                    y = self._spatial_banded(
                        band_ctx, xin, mc.kernel_size, mc.stride_n,
                        lambda xb: self._conv(xb, p, mc, spec,
                                              transposed=transposed,
                                              relu=fused_relu),
                    )
                elif lt == LayerType.POOL:
                    y = self._spatial_banded(
                        band_ctx, xin, 2 if mc.kernel == 0 else 3,
                        mc.stride_n,
                        lambda xb: self._pool(xb, mc, spec),
                    )
                elif lt == LayerType.UPSAMPLE:
                    up_conv = wp.fuse_upsample
                    y = self._spatial_banded(
                        band_ctx, xin,
                        3 if up_conv else 1, 1,
                        lambda xb: self._upsample(xb, p, decomposed=up_conv),
                        out_scale=2,
                    )
                else:
                    op = ExtOp(mc.ext_opcode)
                    if op == ExtOp.SIGMOID:
                        y = jax.nn.sigmoid(xin)
                    elif op == ExtOp.ADD:
                        y = xin + read(mc.ext_addr2, mc.in_ch)
                    elif op == ExtOp.IDENTITY:
                        y = xin
                    else:
                        raise NotImplementedError(
                            f"FCN engine does not implement {op!r}; LM "
                            f"opcodes run through build_stream_fn"
                        )
                if mc.res_op == ResOp.CACHE:
                    cache = y
                elif mc.res_op == ResOp.ADD:
                    assert cache is not None, \
                        "res add with empty cache register"
                    y = y + cache
                if mc.relu and not fused_relu:
                    y = jax.nn.relu(y)
                # write back to the data pool in storage precision (FP16
                # in the paper; f32 for the reference numerics)
                y = y.astype(self.storage_dtype)
                if wp.store:
                    arena[mc.out_addr] = y
                    h, w, c = prog.addr_shapes[mc.out_addr]
                    extents[mc.out_addr] = h * w * c * STORAGE_BYTES
                # drop buffers at their last use so the trace holds no
                # reference past the plan's liveness range
                for a in wp.free_after:
                    arena.pop(a, None)
                    extents.pop(a, None)
                if wp.drop_cache:
                    cache = None

        return {k: arena[a] for k, a in prog.outputs.items()}


# ---------------------------------------------------------------------------
# LM stream execution — same ISA, transformer datapath modules.
# ---------------------------------------------------------------------------

# module signature: fn(params, x, *, mc, table, ctx) -> y
ModuleFn = Callable[..., jax.Array]


def build_stream_fn(
    words: Sequence[Microcode],
    tables: Sequence[Dict[str, Any]],
    registry: Dict[ExtOp, ModuleFn],
    weight_bindings: Dict[int, str],
):
    """Compile a microcode segment into ``fn(params, x, ctx) -> (y, ctx)``.

    ``params`` is a dict keyed by binding name.  The residual cache/add
    register is interpreted exactly as in :class:`FCNEngine`; transformer
    pre-norm residuals are expressed as IDENTITY(cache) ... ATTN(add).
    The returned function is pure and scan-friendly: a transformer stack
    scans it over stacked per-layer params (see models/lm/transformer.py).
    """

    words = list(words)

    def _deq(p, ctx):
        """BFP-stored weights (serving mode): int8 mantissas stream from
        HBM; the widening to compute dtype is the VMEM dequant unit."""
        is_bfp = lambda x: isinstance(x, bfp_lib.BFPTensor)
        if not any(is_bfp(l) for l in
                   jax.tree_util.tree_leaves(p, is_leaf=is_bfp)):
            return p
        dt = ctx.get("compute_dtype", jnp.bfloat16)
        return jax.tree_util.tree_map(
            lambda x: bfp_lib.dequantize(x).astype(dt) if is_bfp(x) else x,
            p, is_leaf=is_bfp,
        )

    def fn(params, x, ctx=None):
        ctx = {} if ctx is None else ctx
        cache = None
        cur = x
        for idx, mc in enumerate(words):
            op = ExtOp(mc.ext_opcode)
            name = weight_bindings.get(idx)
            p = params.get(name) if name else None
            if p is not None:
                p = _deq(p, ctx)
            table = tables[mc.ext_table_idx - 1] if mc.ext_table_idx else {}
            if op == ExtOp.IDENTITY:
                y = cur
            elif op == ExtOp.ADD:
                y = cur + (cache if cache is not None else 0)
            elif op in registry:
                y = registry[op](p, cur, mc=mc, table=table, ctx=ctx)
            else:
                raise NotImplementedError(f"no module registered for {op!r}")
            if mc.res_op == ResOp.CACHE:
                cache = y
            elif mc.res_op == ResOp.ADD and op != ExtOp.ADD:
                assert cache is not None
                y = y + cache
            if mc.relu:
                y = jax.nn.relu(y)
            cur = y
        return cur, ctx

    return fn
