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
inputs and weights are run through Algorithm 1 quantization before the MAC
and the accumulator stays wide (f32 >= the paper's 15-bit mantissa) — the
§IV.C accuracy-maintenance discipline.  Storage between layers is FP16
(``storage_dtype``), exactly the paper's data-pool format.

The same interpreter executes LM architectures: :func:`build_stream_fn`
turns a microcode segment into a layer function by dispatching extended
opcodes against a module registry (the "datapath modules" for
transformers), with ``res_op`` cache/add providing residual connections —
the transformer residual is *literally* the paper's Fig. 3 mechanism.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import bfp as bfp_lib
from . import fuse, winograd
from .assembler import Program, STORAGE_BYTES
from .microcode import ExtOp, LayerType, Microcode, ResOp


@dataclasses.dataclass(frozen=True)
class BFPConfig:
    block_size: int = bfp_lib.DEFAULT_BLOCK
    mantissa_bits: int = bfp_lib.DEFAULT_MANTISSA
    rounding: str = "trunc"
    wide_accum: bool = True      # False reproduces the pre-Fig.7 failure


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
        memplan=None,
    ):
        if mode not in ("reference", "optimized"):
            raise ValueError(mode)
        self.program = program
        self.mode = mode
        self.bfp = bfp
        self.storage_dtype = storage_dtype
        self.use_pallas = use_pallas
        # memplan: None/False -> legacy keep-everything loop; True ->
        # compute the static plan here (once per engine, pure function of
        # the program); a MemPlan instance is used as-is.  The plan
        # supplies fusion facts, dead-word/dead-store elimination, and
        # per-word free-after sets so the trace drops a buffer reference
        # at its last use instead of pinning every intermediate.
        if memplan is True:
            from . import memplan as memplan_lib

            memplan = memplan_lib.plan_program(
                program, dtype_bytes=jnp.dtype(storage_dtype).itemsize
            )
        self.memplan = memplan or None

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
        """Paper Fig. 4 right branch: fold BN, then BFP-normalize weights."""
        out = {}
        for idx, name in self.program.weight_bindings.items():
            spec = self.program.layer_specs[idx]
            p = dict(params[name])
            if spec.op == "conv" and spec.bn:
                w, b = fuse.fold_batchnorm(
                    p["w"], p.get("b"), p["gamma"], p["beta"], p["mean"],
                    p["var"],
                )
                p = {"w": w, "b": b}
            if self.bfp is not None and "w" in p:
                p["w"] = bfp_lib.roundtrip(
                    p["w"],
                    block_size=self.bfp.block_size,
                    mantissa_bits=self.bfp.mantissa_bits,
                    axis=-2,                       # block along Cin (K dim)
                    rounding=self.bfp.rounding,
                )
            out[name] = p
        return out

    # -- datapath units -------------------------------------------------------
    def _conv(self, x, p, mc: Microcode, spec, *, transposed: bool = False,
              relu: bool = False):
        """One conv microcode word.  ``transposed`` rides in as an
        explicit argument — never instance state, so concurrent traces
        of one cached engine (transposed vs not, PR 4's async dispatch)
        each bake their own kernel orientation.  ``relu=True`` fuses the
        word's activation into this launch (fuse.can_fuse_conv_epilogue
        decides eligibility at the call site)."""
        w = p["w"]
        b = p.get("b")
        if transposed:
            # transposed-image mode: transpose the weight kernels (paper:
            # "transposing the corresponding weight kernels and modifying
            # the convolution mode")
            w = jnp.swapaxes(w, 0, 1)
        depthwise = bool(spec.table and spec.table.get("depthwise"))
        if (
            self.bfp is not None
            and self.use_pallas
            and self.mode == "optimized"
            and not depthwise
            and mc.kernel_size == 1
            and mc.stride_n == 1
        ):
            # a 1x1 conv IS a matmul: run the BFP Pallas kernel, which
            # quantizes both operands along the contraction dim itself
            # (activations axis=-1, weights axis=Cin — the same blocking
            # as the roundtrip below, so numerics match)
            from repro.kernels.bfp_matmul import ops as bops

            n, hh, ww, cin = x.shape
            y = bops.bfp_matmul(
                x.astype(jnp.float32).reshape(-1, cin),
                w.astype(jnp.float32).reshape(cin, -1),
                block_size=self.bfp.block_size,
                mantissa_bits=self.bfp.mantissa_bits,
                rounding=self.bfp.rounding,
            ).reshape(n, hh, ww, -1)
            return fuse.conv_epilogue(y, b, relu)
        if self.bfp is not None:
            x = bfp_lib.roundtrip(
                x.astype(jnp.float32),
                block_size=self.bfp.block_size,
                mantissa_bits=self.bfp.mantissa_bits,
                axis=-1,
                rounding=self.bfp.rounding,
            )
            # weights quantize in-call too (paper Fig. 4's normalization
            # branch must hold whether or not the caller ran
            # normalize_weights() offline — trunc rounding is idempotent,
            # so pre-normalized weights pass through unchanged)
            w = bfp_lib.roundtrip(
                w.astype(jnp.float32),
                block_size=self.bfp.block_size,
                mantissa_bits=self.bfp.mantissa_bits,
                axis=-2,                       # block along Cin (K dim)
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

    def _upsample(self, x, p, mc, spec, *, decomposed: Optional[bool] = None):
        # ``decomposed`` is the plan fact "this upsample carries a 3x3
        # conv eligible for phase decomposition"; None derives it from
        # the spec (legacy no-plan path).
        if decomposed is None:
            decomposed = spec.upsample_mode != "nearest"
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
        indices = plan.schedule if plan is not None else range(len(prog.words))
        for idx in indices:
            mc = prog.words[idx]
            wp = plan.word(idx) if plan is not None else None
            spec = prog.layer_specs[idx]
            xin = read(mc.in_addr, mc.in_ch)
            name = prog.weight_bindings.get(idx)
            p = params.get(name, {}) if name else {}
            lt = LayerType(mc.layer_type)
            fused_relu = False
            if lt == LayerType.CONV:
                # conv+bias+ReLU fuse into one launch (optimized mode;
                # eligibility is a plan fact when a memplan is bound, the
                # per-call fuse.py check otherwise — the residual register
                # reads the pre-activation value, so res words keep a
                # separate ReLU either way)
                eligible = (wp.fuse_relu if wp is not None
                            else fuse.can_fuse_conv_epilogue(mc))
                fused_relu = self.mode == "optimized" and eligible
                y = self._spatial_banded(
                    band_ctx, xin, mc.kernel_size, mc.stride_n,
                    lambda xb: self._conv(xb, p, mc, spec,
                                          transposed=transposed,
                                          relu=fused_relu),
                )
            elif lt == LayerType.POOL:
                y = self._spatial_banded(
                    band_ctx, xin, 2 if mc.kernel == 0 else 3, mc.stride_n,
                    lambda xb: self._pool(xb, mc, spec),
                )
            elif lt == LayerType.UPSAMPLE:
                up_conv = (wp.fuse_upsample if wp is not None
                           else spec.upsample_mode != "nearest")
                y = self._spatial_banded(
                    band_ctx, xin,
                    3 if up_conv else 1, 1,
                    lambda xb: self._upsample(xb, p, mc, spec,
                                              decomposed=up_conv),
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
                        f"FCN engine does not implement {op!r}; LM opcodes "
                        f"run through build_stream_fn"
                    )
            if mc.res_op == ResOp.CACHE:
                cache = y
            elif mc.res_op == ResOp.ADD:
                assert cache is not None, "res add with empty cache register"
                y = y + cache
            if mc.relu and not fused_relu:
                y = jax.nn.relu(y)
            # write back to the data pool in storage precision (FP16 in the
            # paper; f32 for the reference numerics)
            y = y.astype(self.storage_dtype)
            if wp is None or wp.store:
                arena[mc.out_addr] = y
                h, w, c = prog.addr_shapes[mc.out_addr]
                extents[mc.out_addr] = h * w * c * STORAGE_BYTES
            if wp is not None:
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
