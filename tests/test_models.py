"""Model-family tests: ViT (image encoder) and MoE-GPT (expert-parallel LM).

ViT and MoE extend the model zoo beyond ResNet/GPT; the MoE tests exercise
the ep-axis all_to_all dispatch (parallel/ep.py) end to end through a real
GSPMD train step — the strategy the reference only provides primitives for
(SURVEY §2.6)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models.moe import (MoEGPT, MoEGPTConfig, moe_aux_loss,
                                    moe_partition_rules)
from horovod_tpu.models.vit import ViT_Tiny, ViTConfig, ViT, \
    vit_partition_rules
from horovod_tpu.parallel.mesh_utils import make_mesh
from horovod_tpu.parallel.tp import shard_params


class TestViT:
    def _tiny(self, **kw):
        kw.setdefault("attention_impl", "reference")
        return ViT_Tiny(num_classes=10, dtype=jnp.float32, **kw)

    def test_forward_shape_finite(self):
        model = self._tiny()
        imgs = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3),
                           jnp.float32)
        v = model.init(jax.random.PRNGKey(0), imgs)
        out = model.apply(v, imgs)
        assert out.shape == (2, 10)
        assert np.isfinite(np.asarray(out)).all()

    def test_cls_pool_matches_shape(self):
        cfg = ViTConfig(image_size=32, patch_size=8, num_classes=5,
                        num_layers=1, num_heads=2, head_dim=8, pool="cls",
                        dtype=jnp.float32, attention_impl="reference")
        model = ViT(cfg)
        imgs = jnp.zeros((3, 32, 32, 3))
        v = model.init(jax.random.PRNGKey(0), imgs)
        assert model.apply(v, imgs).shape == (3, 5)

    def test_dp_train_step_learns(self, hvd):
        from horovod_tpu.training import (init_replicated, make_train_step,
                                          shard_batch)
        mesh = hvd.core.basics.get_mesh()
        model = self._tiny()
        r = np.random.RandomState(0)
        imgs = r.rand(16, 32, 32, 3).astype(np.float32)
        lbls = r.randint(0, 10, (16,)).astype(np.int32)
        v = model.init(jax.random.PRNGKey(0), jnp.asarray(imgs[:1]))
        params = init_replicated(v["params"], mesh)
        tx = optax.adam(1e-3)
        step = make_train_step(model.apply, tx, mesh)
        opt = init_replicated(step.init_opt_state(params), mesh)
        xi, yi = shard_batch(imgs, mesh), shard_batch(lbls, mesh)
        params, opt, _, l1 = step(params, opt, {}, xi, yi)
        for _ in range(3):
            params, opt, _, l2 = step(params, opt, {}, xi, yi)
        assert float(l2) < float(l1)

    def test_tp_partition_rules_forward(self, hvd):
        mesh = make_mesh(dp=4, tp=2)
        model = self._tiny()
        imgs = jnp.zeros((4, 32, 32, 3))
        v = model.init(jax.random.PRNGKey(0), imgs)
        sharded = shard_params(v["params"], mesh, vit_partition_rules())
        qkv = sharded["layers_0"]["attn"]["qkv"]["kernel"]
        assert qkv.sharding.spec == P(None, "tp")
        out = jax.jit(lambda p, x: model.apply({"params": p}, x))(
            sharded, imgs)
        assert np.isfinite(np.asarray(out)).all()


class TestMoEGPT:
    def _cfg(self, **kw):
        kw.setdefault("vocab_size", 64)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 2)
        kw.setdefault("head_dim", 8)
        kw.setdefault("max_seq_len", 32)
        kw.setdefault("num_experts", 4)
        kw.setdefault("dtype", jnp.float32)
        kw.setdefault("attention_impl", "reference")
        return MoEGPTConfig(**kw)

    def test_single_device_forward(self):
        model = MoEGPT(self._cfg())
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, 64, (2, 16)), jnp.int32)
        v = model.init(jax.random.PRNGKey(0), toks)
        out = model.apply(v, toks)
        assert out.shape == (2, 16, 64)
        assert np.isfinite(np.asarray(out)).all()

    def test_aux_loss_sowed(self):
        model = MoEGPT(self._cfg())
        toks = jnp.zeros((2, 8), jnp.int32)
        v = model.init(jax.random.PRNGKey(0), toks)
        _, mut = model.apply(v, toks, mutable=["intermediates"])
        aux = moe_aux_loss(mut["intermediates"])
        # balanced-routing lower bound is 1.0 (Switch eq. 4)
        assert float(aux) >= 2.0 * 0.99  # 2 layers x >= ~1.0 each

    def test_ep_mesh_train_step_learns(self, hvd):
        """dp=2 x ep=4: experts sharded over ep, tokens all_to_all'd."""
        mesh = make_mesh(dp=2, ep=4)
        cfg = self._cfg(mesh=mesh)
        model = MoEGPT(cfg)
        r = np.random.RandomState(0)
        toks = jnp.asarray(r.randint(0, 64, (4, 16)), jnp.int32)
        tgts = jnp.roll(toks, -1, axis=1)
        v = model.init(jax.random.PRNGKey(0), toks)
        rules = moe_partition_rules()
        params = shard_params(v["params"], mesh, rules)
        up = params["layers_0"]["moe"]["up_kernel"]
        assert up.sharding.spec == P("ep")
        from horovod_tpu.training import make_gspmd_train_step
        tx = optax.adam(1e-2)
        opt = tx.init(params)
        step = make_gspmd_train_step(
            model.apply, tx, mesh, rules,
            batch_spec=P("dp", None),
            aux_loss_fn=moe_aux_loss)
        params, opt, l1 = step(params, opt, toks, tgts)
        for _ in range(3):
            params, opt, l2 = step(params, opt, toks, tgts)
        assert np.isfinite(float(l2))
        assert float(l2) < float(l1)

    def test_ep_matches_local_when_capacity_ample(self, hvd):
        """With generous capacity and identical per-shard routing inputs,
        the distributed dispatch must agree with the all-local oracle on
        token outputs that were not dropped by either."""
        mesh = make_mesh(dp=2, ep=4)
        # capacity_factor == num_experts => capacity == all local tokens,
        # so neither path can drop and outputs must agree exactly
        cfg_d = self._cfg(mesh=mesh, num_layers=1, capacity_factor=4.0)
        cfg_l = self._cfg(num_layers=1, capacity_factor=4.0)
        model_d, model_l = MoEGPT(cfg_d), MoEGPT(cfg_l)
        toks = jnp.asarray(
            np.random.RandomState(1).randint(0, 64, (4, 8)), jnp.int32)
        v = model_l.init(jax.random.PRNGKey(0), toks)
        out_l = model_l.apply(v, toks)
        out_d = model_d.apply(v, toks)
        np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_l),
                                   rtol=2e-3, atol=2e-3)


class TestSpaceToDepthStem:
    def test_stem_equivalent_to_conv7(self):
        """stem='space_to_depth' computes exactly the conv7 stem's map
        when its kernel is the stem_kernel_to_s2d rearrangement."""
        import jax
        from horovod_tpu.models.resnet import (ResNet50, space_to_depth,
                                               stem_kernel_to_s2d)
        rng = np.random.RandomState(0)
        imgs = jnp.asarray(rng.rand(2, 64, 64, 3), jnp.float32)
        m7 = ResNet50(num_classes=10, dtype=jnp.float32)
        ms = ResNet50(num_classes=10, dtype=jnp.float32,
                      stem="space_to_depth")
        v7 = m7.init(jax.random.PRNGKey(0), imgs, train=False)
        vs = jax.tree.map(lambda x: x, v7)
        k7 = v7["params"]["conv_init"]["kernel"]
        vs["params"] = {**vs["params"],
                        "conv_init": {"kernel": stem_kernel_to_s2d(k7)}}
        o7 = np.asarray(m7.apply(v7, imgs, train=False))
        os_ = np.asarray(ms.apply(vs, imgs, train=False))
        np.testing.assert_allclose(os_, o7, atol=1e-4)

    def test_space_to_depth_layout(self):
        from horovod_tpu.models.resnet import space_to_depth
        x = jnp.arange(2 * 4 * 4 * 3).reshape(2, 4, 4, 3).astype(jnp.float32)
        y = space_to_depth(x)
        assert y.shape == (2, 2, 2, 12)
        # channel order (dh, dw, c): y[b,i,j, dh*6+dw*3+c] = x[b,2i+dh,2j+dw,c]
        np.testing.assert_array_equal(
            np.asarray(y[0, 1, 0]),
            np.asarray(x[0, 2:4, 0:2].reshape(-1)))


class TestTopKRouting:
    def test_top2_ample_capacity_weighted_sum(self):
        """With ample capacity, top-2 output = normalized-gate-weighted
        sum of the two chosen experts' outputs."""
        from horovod_tpu.parallel.ep import topk_route
        rng = np.random.RandomState(0)
        T, E, C = 8, 4, 16
        logits = jnp.asarray(rng.randn(T, E), jnp.float32)
        dispatch, combine = topk_route(logits, E, C, k=2)
        probs = np.asarray(jax.nn.softmax(logits, -1))
        top2 = np.argsort(-probs, axis=-1)[:, :2]
        d = np.asarray(dispatch)
        c = np.asarray(combine)
        for t in range(T):
            chosen = np.where(d[t].sum(-1) > 0)[0]
            assert set(chosen) == set(top2[t])
            g = probs[t, top2[t]]
            g = g / g.sum()
            np.testing.assert_allclose(
                sorted(c[t].sum(-1)[top2[t]]), sorted(g), rtol=1e-5)

    def test_top2_capacity_drops_second_choice_first(self):
        """Under pressure, 1st choices keep their slots (GShard order)."""
        from horovod_tpu.parallel.ep import topk_route
        # all tokens prefer expert 0 then expert 1
        logits = jnp.asarray(np.tile([[2.0, 1.0, -5, -5]], (6, 1)),
                             jnp.float32)
        dispatch, _ = topk_route(logits, 4, capacity=6, k=2)
        d = np.asarray(dispatch)
        # expert 0 holds exactly its capacity of first choices
        assert d[:, 0].sum() == 6
        assert d[:, 1].sum() == 6  # second choices fill expert 1
        # a smaller capacity drops second choices, not first
        dispatch2, _ = topk_route(logits, 4, capacity=3, k=2)
        d2 = np.asarray(dispatch2)
        assert d2[:3, 0].sum() == 3 and d2[3:, 0].sum() == 0
        assert d2[:3, 1].sum() == 3

    def test_top1_backcompat(self):
        from horovod_tpu.parallel.ep import top1_route, topk_route
        rng = np.random.RandomState(1)
        logits = jnp.asarray(rng.randn(16, 4), jnp.float32)
        d1, c1 = top1_route(logits, 4, 4)
        dk, ck = topk_route(logits, 4, 4, k=1, normalize=False)
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(dk))
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(ck))

    def test_top2_moe_gpt_trains_on_ep_mesh(self, hvd):
        import optax
        from jax.sharding import PartitionSpec as P
        from horovod_tpu.models.moe import (MoEGPT, MoEGPTConfig,
                                            moe_aux_loss,
                                            moe_partition_rules)
        from horovod_tpu.parallel.mesh_utils import make_mesh
        from horovod_tpu.parallel.tp import shard_params
        from horovod_tpu.training import make_gspmd_train_step
        mesh = make_mesh(dp=2, ep=4)
        cfg = MoEGPTConfig(vocab_size=64, num_layers=1, num_heads=2,
                           head_dim=8, max_seq_len=32, num_experts=4,
                           router_top_k=2, mesh=mesh, dtype=jnp.float32,
                           attention_impl="reference")
        model = MoEGPT(cfg)
        toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (4, 16)),
                           jnp.int32)
        v = model.init(jax.random.PRNGKey(0), toks)
        params = shard_params(v["params"], mesh, moe_partition_rules())
        tx = optax.adam(1e-2)
        opt = tx.init(params)
        step = make_gspmd_train_step(model.apply, tx, mesh,
                                     moe_partition_rules(),
                                     batch_spec=P("dp", None),
                                     aux_loss_fn=moe_aux_loss)
        losses = []
        p, o = params, opt
        tg = jnp.asarray(np.roll(np.asarray(toks), -1, 1))
        for _ in range(4):
            p, o, loss = step(p, o, toks, tg)
            losses.append(float(loss))
        assert losses[-1] < losses[0]


class TestVGGAndInception:
    """The rest of the reference's headline scaling-benchmark trio
    (docs/benchmarks.rst:8-13: Inception V3 / ResNet-101 / VGG-16)."""

    def test_vgg16_forward_and_train_step(self, hvd):
        import optax
        from horovod_tpu.models.vgg import VGG16
        from horovod_tpu.training import (init_replicated, make_train_step,
                                          shard_batch)
        mesh = hvd.core.basics.get_mesh()
        # avg-pool head so the size-reduced test input works; flatten is
        # the canonical 224x224 benchmark head
        model = VGG16(num_classes=10, classifier="avg", dtype=jnp.float32)
        variables = model.init(
            {"params": jax.random.PRNGKey(0)},
            jnp.zeros((1, 32, 32, 3), jnp.float32), train=False)
        out = model.apply(variables, jnp.ones((2, 32, 32, 3)), train=False)
        assert out.shape == (2, 10)
        assert np.isfinite(np.asarray(out)).all()
        params = init_replicated(variables["params"], mesh)
        step = make_train_step(
            lambda v, x: model.apply(v, x, train=False), optax.sgd(0.01),
            mesh)
        opt = init_replicated(step.init_opt_state(params), mesh)
        rng = np.random.RandomState(0)
        imgs = shard_batch(rng.rand(8, 32, 32, 3).astype(np.float32), mesh)
        lbls = shard_batch(rng.randint(0, 10, (8,)).astype(np.int32), mesh)
        _, _, _, loss = step(params, opt, {}, imgs, lbls)
        assert np.isfinite(float(loss))

    def test_vgg16_flatten_head_param_shapes(self):
        # classic head: first FC is 7*7*512 x 4096 at 224 input
        from horovod_tpu.models.vgg import VGG16
        model = VGG16(num_classes=1000, dtype=jnp.float32)
        variables = jax.eval_shape(
            lambda: model.init({"params": jax.random.PRNGKey(0)},
                               jnp.zeros((1, 224, 224, 3), jnp.float32),
                               train=False))
        dense0 = variables["params"]["Dense_0"]["kernel"]
        assert dense0.shape == (7 * 7 * 512, 4096), dense0.shape

    def test_inception_v3_forward(self):
        from horovod_tpu.models.inception import InceptionV3
        model = InceptionV3(num_classes=13, dtype=jnp.float32)
        variables = model.init({"params": jax.random.PRNGKey(0)},
                               jnp.zeros((1, 96, 96, 3), jnp.float32),
                               train=False)
        out = model.apply(variables, jnp.ones((2, 96, 96, 3)), train=False)
        assert out.shape == (2, 13)
        assert np.isfinite(np.asarray(out)).all()

    def test_inception_v3_grid_sizes(self):
        # 299 input must reach the canonical 8x8 grid before pooling
        # (three stem reductions + two grid reductions); check via shape
        # inference only — no FLOPs
        from horovod_tpu.models.inception import InceptionV3
        model = InceptionV3(num_classes=5, dtype=jnp.float32)
        var_shapes = jax.eval_shape(
            lambda: model.init({"params": jax.random.PRNGKey(0)},
                               jnp.zeros((1, 299, 299, 3), jnp.float32),
                               train=False))
        # final 1x1 projection in the last InceptionE sees the 2048-ch mix
        last_e = var_shapes["params"]["InceptionE_1"]
        assert last_e["ConvBN_0"]["Conv_0"]["kernel"].shape[-2] == 2048


def test_bench_rejects_unknown_model_before_device_work(monkeypatch, capsys):
    """bench.py validates HVD_BENCH_MODEL against bench_zoo.BENCH_MODELS
    (the one registry) before it touches a device."""
    import importlib.util
    import json
    import os
    spec = importlib.util.spec_from_file_location(
        "bench_main", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    bench_main = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_main)
    monkeypatch.setenv("HVD_BENCH_MODEL", "resnet5000")
    assert bench_main.main() == 1
    line = json.loads(capsys.readouterr().out.strip())
    assert line["value"] is None and "resnet5000" in line["error"]
