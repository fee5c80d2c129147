"""FCN engine tests: assembler address/concat semantics, residual cache
ops, backbone assembly, engine modes (reference/optimized/BFP), STD model
end-to-end, CC postprocess vs union-find."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:            # bare interpreter: seeded fallback shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import Assembler, BFPConfig, FCNEngine, LayerSpec
from repro.core.microcode import unpack_program, pack_program


def tiny_program():
    specs = [
        LayerSpec("c1", "conv", ["input"], out_ch=8, kernel=3, relu=True,
                  bn=True),
        LayerSpec("p1", "pool", ["c1"], kernel=2, stride=2),
        LayerSpec("c2", "conv", ["p1"], out_ch=8, kernel=3, relu=True,
                  res="cache"),
        LayerSpec("c3", "conv", ["c2"], out_ch=8, kernel=3, res="add",
                  relu=True),
        LayerSpec("u1", "upsample", ["c3"], upsample_mode="nearest"),
        LayerSpec("cc", "conv", ["u1", "c1"], out_ch=4, kernel=1),
        LayerSpec("sg", "sigmoid", ["cc"]),
    ]
    return Assembler((16, 16, 3)).assemble(specs, outputs=["sg"])


class TestAssembler:
    def test_concat_producers_adjacent(self):
        """Concat = adjacent addresses (paper §III.B), no copy op."""
        prog = tiny_program()
        by_name = {prog.layer_specs[i].name: w
                   for i, w in enumerate(prog.words)}
        u1, c1 = by_name["u1"], by_name["c1"]
        u1_bytes = 16 * 16 * 8 * 2
        assert c1.out_addr == u1.out_addr + u1_bytes
        cc = by_name["cc"]
        assert cc.in_addr == u1.out_addr
        assert cc.in_ch == 16                      # combined extent

    def test_shape_fields_propagate(self):
        prog = tiny_program()
        w = prog.words[2]                          # c2: after 2x2/2 pool
        assert (w.height, w.width) == (8, 8)
        assert (w.in_ch, w.out_ch) == (8, 8)

    def test_program_packs_to_config_ram_format(self):
        prog = tiny_program()
        raw = pack_program(prog.words)
        assert raw.shape == (len(prog.words), 32)
        assert unpack_program(raw) == prog.words

    def test_double_concat_feeding_rejected(self):
        specs = [
            LayerSpec("a", "conv", ["input"], out_ch=4, kernel=1),
            LayerSpec("b", "conv", ["input"], out_ch=4, kernel=1),
            LayerSpec("c", "conv", ["a", "b"], out_ch=4, kernel=1),
            LayerSpec("d", "conv", ["b", "a"], out_ch=4, kernel=1),
        ]
        with pytest.raises(ValueError, match="concat"):
            Assembler((8, 8, 3)).assemble(specs, outputs=["d"])


class TestEngine:
    def setup_method(self, _):
        self.prog = tiny_program()
        self.x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 3))

    def test_reference_forward(self):
        eng = FCNEngine(self.prog)
        params = eng.init_params(jax.random.PRNGKey(1))
        out = eng(params, self.x)
        assert out["sg"].shape == (2, 16, 16, 4)
        assert bool(jnp.all((out["sg"] >= 0) & (out["sg"] <= 1)))

    def test_optimized_matches_reference(self):
        eng_r = FCNEngine(self.prog, mode="reference")
        eng_o = FCNEngine(self.prog, mode="optimized")
        params = eng_r.init_params(jax.random.PRNGKey(1))
        a = eng_r(params, self.x)["sg"]
        b = eng_o(params, self.x)["sg"]
        np.testing.assert_allclose(a, b, atol=1e-4)

    def test_pallas_winograd_path_matches(self):
        eng_r = FCNEngine(self.prog, mode="reference")
        eng_p = FCNEngine(self.prog, mode="optimized", use_pallas=True)
        params = eng_r.init_params(jax.random.PRNGKey(1))
        a = eng_r(params, self.x)["sg"]
        b = eng_p(params, self.x)["sg"]
        np.testing.assert_allclose(a, b, atol=1e-3)

    def test_bfp_mode_close_and_storage_fp16(self):
        eng_r = FCNEngine(self.prog)
        eng_b = FCNEngine(self.prog, bfp=BFPConfig(mantissa_bits=10),
                          storage_dtype=jnp.float16)
        params = eng_r.init_params(jax.random.PRNGKey(1))
        a = eng_r(params, self.x)["sg"]
        b = eng_b(eng_b.normalize_weights(params), self.x)["sg"]
        assert b.dtype == jnp.float16
        assert float(jnp.mean(jnp.abs(a - b.astype(jnp.float32)))) < 0.05

    def test_residual_cache_semantics(self):
        """res=cache then res=add must equal manual residual."""
        specs = [
            LayerSpec("id", "identity", ["input"], res="cache"),
            LayerSpec("c", "conv", ["input"], out_ch=3, kernel=1,
                      res="add"),
        ]
        prog = Assembler((4, 4, 3)).assemble(specs, outputs=["c"])
        eng = FCNEngine(prog)
        params = eng.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 4, 4, 3))
        got = eng(params, x)["c"]
        w, b = params["c"]["w"], params["c"]["b"]
        want = x + (jnp.einsum("nhwc,co->nhwo", x, w[0, 0]) + b)
        np.testing.assert_allclose(got, want, atol=1e-5)


class TestBackbones:
    @pytest.mark.parametrize("backbone", ["resnet50", "vgg16", "mobilenet"])
    def test_backbone_feature_pyramid(self, backbone):
        from repro.models.fcn import backbones as bb

        specs, taps = bb.BACKBONES[backbone](0.25)
        prog = Assembler((64, 64, 3)).assemble(specs, outputs=taps)
        eng = FCNEngine(prog)
        params = eng.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64, 3))
        out = eng(params, x)
        sizes = [out[t].shape[1] for t in taps]
        assert sizes == [16, 8, 4, 2]              # 1/4, 1/8, 1/16, 1/32

    def test_resnet50_random_init_logits_stay_bounded(self):
        """The residual-branch init scale (1/sqrt(#branches)) keeps a
        random ResNet-50's logits O(1): std ~0.28 at width 0.125, ~14
        without it.  The bfp-vs-f32 parity gate is held on these weights,
        so a change here changes what that gate measures."""
        from repro.configs.pixellink_std import RESNET50
        from repro.models.fcn import PixelLinkModel

        cfg = dataclasses.replace(RESNET50, width=0.125,
                                  image_size=(64, 64), bfp=None,
                                  storage_fp16=False)
        m = PixelLinkModel(cfg)
        params = m.init_params(jax.random.PRNGKey(0))
        x = jax.random.uniform(jax.random.PRNGKey(1), (2, 64, 64, 3))
        logits = m.apply(params, x)["logits"]
        assert 0.01 < float(jnp.std(logits)) < 3.0

    @pytest.mark.parametrize("backbone", ["vgg16", "resnet50"])
    def test_std_model_end_to_end(self, backbone):
        from repro.models.fcn import PixelLinkModel, STDLoss
        from repro.models.fcn.pixellink import STDConfig

        cfg = STDConfig(backbone=backbone, width=0.125,
                        image_size=(64, 64), merge_ch=(16, 16, 8),
                        mode="reference", storage_fp16=False)
        m = PixelLinkModel(cfg)
        params = m.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64, 3))
        out = m.apply(params, x)
        assert out["score"].shape == (1, 16, 16)
        assert out["links"].shape == (1, 16, 16, 8)
        sg = (jax.random.uniform(jax.random.PRNGKey(2), (1, 16, 16)) > 0.7
              ).astype(jnp.float32)
        lg = jnp.zeros((1, 16, 16, 8))
        losses = STDLoss()(out, sg, lg)
        grads = jax.grad(
            lambda p: STDLoss()(m.apply(p, x), sg, lg)["loss"]
        )(params)
        assert all(bool(jnp.all(jnp.isfinite(g)))
                   for g in jax.tree_util.tree_leaves(grads))
        assert float(losses["loss"]) > 0


class TestPostprocess:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 100), st.integers(6, 20))
    def test_cc_matches_union_find(self, seed, size):
        from repro.models.fcn import postprocess as pp

        rng = np.random.default_rng(seed)
        score = rng.random((size, size)).astype(np.float32)
        links = rng.random((size, size, 8)).astype(np.float32)
        lj = np.asarray(pp.cc_label(jnp.asarray(score), jnp.asarray(links)))
        ln = pp.cc_label_numpy(score, links)

        def canon(lab):
            mapping, out = {}, np.zeros_like(lab)
            for i, v in enumerate(lab.flat):
                if v:
                    out.flat[i] = mapping.setdefault(v, len(mapping) + 1)
            return out

        np.testing.assert_array_equal(canon(lj), canon(ln))

    def test_boxes_and_f_measure(self):
        from repro.models.fcn import postprocess as pp

        labels = np.zeros((16, 16), np.int32)
        labels[2:5, 3:9] = 7
        labels[10:12, 1:4] = 9
        boxes = pp.boxes_from_labels(labels)
        assert len(boxes) == 2
        gt = [b["box"] for b in boxes]
        fm = pp.f_measure(boxes, gt)
        assert fm["f_measure"] == 1.0
        fm2 = pp.f_measure(boxes, [(0, 0, 1, 1)])
        assert fm2["f_measure"] < 0.5


class TestAssemblerAddOp:
    """Regressions for the add-op channel-summing bug: a binary ``add``
    reads two SAME-shape operands (second via ext_addr2), so its word's
    in_ch is one operand's channel count — the concat path used to sum
    them, corrupting the word and every downstream reader."""

    def _residual_program(self, outputs=("c3",)):
        specs = [
            LayerSpec("c1", "conv", ["input"], out_ch=8, kernel=3,
                      relu=True),
            LayerSpec("c2", "conv", ["c1"], out_ch=8, kernel=1),
            LayerSpec("a", "add", ["c2", "c1"], relu=True),
            LayerSpec("c3", "conv", ["a"], out_ch=4, kernel=1),
        ]
        return Assembler((16, 16, 3)).assemble(specs,
                                               outputs=list(outputs))

    def test_add_word_channels_not_summed(self):
        prog = self._residual_program()
        by = {prog.layer_specs[i].name: w
              for i, w in enumerate(prog.words)}
        add, c1, c3 = by["a"], by["c1"], by["c3"]
        assert add.in_ch == 8                 # bug summed this to 16
        assert add.out_ch == 8
        assert prog.addr_shapes[add.out_addr] == (16, 16, 8)
        # second operand rides in the ext page by address, not channels
        assert add.ext_addr2 == c1.out_addr
        assert c3.in_ch == 8                  # downstream consumer too

    def test_add_channel_mismatch_rejected(self):
        specs = [
            LayerSpec("c1", "conv", ["input"], out_ch=8, kernel=1),
            LayerSpec("c2", "conv", ["input"], out_ch=4, kernel=1),
            LayerSpec("a", "add", ["c1", "c2"]),
        ]
        with pytest.raises(ValueError, match="channel mismatch"):
            Assembler((8, 8, 3)).assemble(specs, outputs=["a"])

    def test_add_numerics_through_engine(self):
        """Interpreter check: the add ext op must compute relu(x + y)
        of its two operands, which only holds once the word carries the
        un-summed channel count."""
        prog = self._residual_program(outputs=("c1", "c2", "a"))
        eng = FCNEngine(prog)
        params = eng.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 3))
        out = eng(params, x)
        assert out["a"].shape == (1, 16, 16, 8)
        np.testing.assert_allclose(
            np.asarray(out["a"]),
            np.maximum(np.asarray(out["c1"]) + np.asarray(out["c2"]), 0),
            atol=1e-5,
        )


class TestKernelEncodingValidation:
    """Regressions for the silent kernel-snapping bug: unencodable
    kernels must raise at assembly, not quietly become a different
    hardware op."""

    def _pool(self, k):
        specs = [LayerSpec("p", "pool", ["input"], kernel=k, stride=2)]
        return Assembler((8, 8, 3)).assemble(specs, outputs=["p"])

    def test_pool_kernel_codes(self):
        # Table II pool convention: code 0 -> 2x2, code 1 -> 3x3
        assert self._pool(2).words[0].kernel == 0
        assert self._pool(3).words[0].kernel == 1

    def test_pool_kernel_unencodable_raises(self):
        with pytest.raises(ValueError, match="pool kernel 5"):
            self._pool(5)

    def test_conv_kernel_unencodable_raises(self):
        specs = [LayerSpec("c", "conv", ["input"], out_ch=4, kernel=5)]
        with pytest.raises(ValueError, match="conv kernel 5"):
            Assembler((8, 8, 3)).assemble(specs, outputs=["c"])


class TestSTDLossNormalization:
    def test_link_loss_matches_masked_mean_oracle(self):
        """Regression for the link-loss denominator bug: the masked
        BCE sum covers n_links channels of every positive pixel, so the
        mean divides by sum(mask) * n_links — dividing by sum(mask)
        alone inflated the link term 8-fold."""
        from repro.models.fcn import STDLoss

        rng = np.random.default_rng(7)
        logits = rng.normal(size=(2, 8, 8, 9)).astype(np.float32)
        score_gt = (rng.random((2, 8, 8)) > 0.6).astype(np.float32)
        link_gt = (rng.random((2, 8, 8, 8)) > 0.5).astype(np.float32)
        assert score_gt.sum() > 0
        losses = STDLoss()({"logits": jnp.asarray(logits)},
                           jnp.asarray(score_gt), jnp.asarray(link_gt))

        lg = logits[..., 1:]
        bce = (np.maximum(lg, 0) - lg * link_gt
               + np.log1p(np.exp(-np.abs(lg))))
        mask = (score_gt > 0.5).astype(np.float32)[..., None]
        want = (bce * mask).sum() / (mask.sum() * lg.shape[-1])
        assert float(losses["link_loss"]) == pytest.approx(want,
                                                           rel=1e-5)
