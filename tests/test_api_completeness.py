"""Audit-driven API completeness: every name in the reference's public
__all__ lists must exist, and the non-trivial new ops must be correct
(torch/scipy as oracles)."""

import ast

import numpy as np
import pytest
import scipy.spatial.distance as sd
import torch

import paddle_tpu as paddle


def _ref_all(path):
    tree = ast.parse(open(path).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if getattr(t, "id", "") == "__all__":
                    return [ast.literal_eval(e) for e in node.value.elts]
    return []


REF = "/root/reference/python/paddle"


@pytest.mark.parametrize("ref_path,module_attr", [
    ("__init__.py", None),
    ("nn/__init__.py", "nn"),
    ("nn/functional/__init__.py", "nn.functional"),
    ("nn/initializer/__init__.py", "nn.initializer"),
    ("optimizer/__init__.py", "optimizer"),
    ("metric/__init__.py", "metric"),
    ("io/__init__.py", "io"),
    ("distributed/__init__.py", "distributed"),
    ("amp/__init__.py", "amp"),
    ("jit/__init__.py", "jit"),
    ("vision/__init__.py", "vision"),
    ("static/__init__.py", "static"),
    ("device/__init__.py", "device"),
    ("utils/__init__.py", "utils"),
    ("audio/__init__.py", "audio"),
    ("autograd/__init__.py", "autograd"),
    ("sparse/__init__.py", "sparse"),
    ("incubate/__init__.py", "incubate"),
    ("incubate/nn/functional/__init__.py", "incubate.nn.functional"),
    ("distribution/__init__.py", "distribution"),
    ("geometric/__init__.py", "geometric"),
    ("quantization/__init__.py", "quantization"),
    ("profiler/__init__.py", "profiler"),
    ("vision/datasets/__init__.py", "vision.datasets"),
    ("text/__init__.py", "text"),
    ("linalg.py", "linalg"),
    ("signal.py", "signal"),
    ("onnx/__init__.py", "onnx"),
])
def test_public_surface_complete(ref_path, module_attr):
    import os
    if not os.path.isdir(REF):
        pytest.skip(f"the reference tree {REF} (outside the checkout) is "
                    f"not on this machine")
    names = _ref_all(f"{REF}/{ref_path}")
    mod = paddle
    if module_attr:
        for part in module_attr.split("."):
            mod = getattr(mod, part)
    missing = [n for n in names if not hasattr(mod, n)]
    assert not missing, f"{module_attr or 'paddle'}: missing {missing}"


def _t(a):
    return paddle.to_tensor(np.asarray(a))


class TestNewTensorOps:
    rs = np.random.RandomState(0)

    def test_block_diag(self):
        a = self.rs.randn(2, 3).astype("float32")
        b = self.rs.randn(2, 2).astype("float32")
        got = paddle.block_diag([_t(a), _t(b)]).numpy()
        ref = torch.block_diag(torch.tensor(a), torch.tensor(b)).numpy()
        np.testing.assert_allclose(got, ref)

    def test_logcumsumexp(self):
        x = self.rs.randn(3, 5).astype("float32")
        got = paddle.logcumsumexp(_t(x), axis=1).numpy()
        ref = torch.logcumsumexp(torch.tensor(x), dim=1).numpy()
        np.testing.assert_allclose(got, ref, rtol=1e-5)

    def test_cdist_pdist(self):
        x = self.rs.randn(5, 4).astype("float64")
        got = paddle.cdist(_t(x), _t(x)).numpy()
        np.testing.assert_allclose(got, sd.cdist(x, x), atol=1e-6)
        np.testing.assert_allclose(paddle.pdist(_t(x)).numpy(),
                                   sd.pdist(x), atol=1e-6)

    def test_take_unfold_diagonal_scatter(self):
        x = np.arange(12, dtype=np.float32).reshape(3, 4)
        np.testing.assert_allclose(
            paddle.take(_t(x), _t(np.array([0, 5, -1]))).numpy(),
            torch.take(torch.tensor(x), torch.tensor([0, 5, -1])).numpy())
        np.testing.assert_allclose(
            paddle.unfold(_t(x), 1, 2, 1).numpy(),
            torch.tensor(x).unfold(1, 2, 1).numpy())
        np.testing.assert_allclose(
            paddle.diagonal_scatter(_t(np.zeros((3, 4), np.float32)),
                                    _t(np.ones(3, np.float32))).numpy(),
            torch.diagonal_scatter(torch.zeros(3, 4), torch.ones(3)).numpy())

    def test_renorm(self):
        x = self.rs.randn(3, 4, 5).astype("float32")
        got = paddle.renorm(_t(x), 2.0, 0, 1.0).numpy()
        ref = torch.renorm(torch.tensor(x), 2.0, 0, 1.0).numpy()
        np.testing.assert_allclose(got, ref, rtol=1e-4)

    def test_combinations(self):
        x = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
        got = paddle.combinations(_t(x), 2).numpy()
        ref = torch.combinations(torch.tensor(x), 2).numpy()
        np.testing.assert_allclose(got, ref)

    def test_trapezoid(self):
        y = self.rs.randn(8).astype("float32")
        np.testing.assert_allclose(
            paddle.trapezoid(_t(y), dx=0.5).numpy(),
            torch.trapezoid(torch.tensor(y), dx=0.5).numpy(), rtol=1e-5)
        got = paddle.cumulative_trapezoid(_t(y), dx=0.5).numpy()
        ref = torch.cumulative_trapezoid(torch.tensor(y), dx=0.5).numpy()
        np.testing.assert_allclose(got, ref, rtol=1e-5)

    def test_type_predicates_and_misc(self):
        assert paddle.is_floating_point(_t(np.zeros(2, np.float32)))
        assert paddle.is_integer(_t(np.zeros(2, np.int32)))
        assert paddle.is_complex(_t(np.zeros(2, np.complex64)))
        assert paddle.signbit(_t(np.array([-1.0, 2.0]))).numpy().tolist() \
            == [True, False]
        np.testing.assert_allclose(
            paddle.shape(_t(np.zeros((2, 5)))).numpy(), [2, 5])
        m, e = paddle.frexp(_t(np.array([8.0, 0.5])))
        np.testing.assert_allclose(m.numpy() * 2.0 ** e.numpy(), [8.0, 0.5])

    def test_isin_reduce_as(self):
        got = paddle.isin(_t(np.array([1, 2, 3, 4])),
                          _t(np.array([2, 4]))).numpy()
        np.testing.assert_allclose(got, [False, True, False, True])
        x = np.ones((2, 3, 4), np.float32)
        tgt = np.zeros((3, 1), np.float32)
        np.testing.assert_allclose(
            paddle.reduce_as(_t(x), _t(tgt)).numpy(), np.full((3, 1), 8.0))


class TestInplaceVariants:
    def test_elementwise_inplace(self):
        x0 = np.random.RandomState(1).rand(3, 4).astype("float32") + 0.5
        x = _t(x0.copy())
        paddle.log_(x)
        np.testing.assert_allclose(x.numpy(), np.log(x0), rtol=1e-6)

    def test_binary_inplace(self):
        a = _t(np.array([6, 4], np.int64))
        paddle.gcd_(a, _t(np.array([9, 6], np.int64)))
        np.testing.assert_allclose(a.numpy(), [3, 2])

    def test_inplace_requires_tensor(self):
        with pytest.raises(TypeError):
            paddle.tan_(np.zeros(3))

    def test_masked_fill_(self):
        x = _t(np.zeros((2, 2), np.float32))
        paddle.masked_fill_(x, _t(np.array([[True, False],
                                            [False, True]])), 5.0)
        np.testing.assert_allclose(x.numpy(), [[5, 0], [0, 5]])

    def test_sampling_inplace(self):
        z = _t(np.zeros((64,), np.float32))
        paddle.geometric_(z, 0.3)
        vals = z.numpy()
        assert (vals >= 1).all() and vals.std() > 0


class TestDistributedSurface:
    def test_aliases_and_enums(self):
        import paddle_tpu.distributed as dist
        assert dist.alltoall is not None
        assert dist.ReduceType.kRedSum == 0
        assert dist.ShardingStage2.stage == 2
        assert dist.get_backend().startswith("XLA:")

    def test_ps_datasets(self, tmp_path):
        import paddle_tpu.distributed as dist
        f = tmp_path / "data.txt"
        f.write_text("1 2 3\n4 5 6\n7 8 9\n10 11 12\n")
        ds = dist.InMemoryDataset()
        ds.init(batch_size=2, parse_fn=lambda s: np.array(s.split(),
                                                         np.float32))
        ds.set_filelist([str(f)])
        ds.load_into_memory()
        assert ds.get_memory_data_size() == 4
        ds.global_shuffle(seed=3)
        batches = list(ds)
        assert len(batches) == 2 and len(batches[0]) == 2
        ds.release_memory()

        qs = dist.QueueDataset()
        qs.init(batch_size=3, parse_fn=lambda s: np.array(s.split(),
                                                          np.float32))
        qs.set_filelist([str(f)])
        got = list(qs)
        assert len(got) == 2 and len(got[0]) == 3 and len(got[1]) == 1

    def test_entry_attrs(self):
        import paddle_tpu.distributed as dist
        assert dist.ProbabilityEntry(0.5)._to_attr() == \
            "probability_entry:0.5"
        assert dist.CountFilterEntry(10)._to_attr() == \
            "count_filter_entry:10"
        assert dist.ShowClickEntry("s", "c")._to_attr() == \
            "show_click_entry:s:c"
        with pytest.raises(ValueError):
            dist.ProbabilityEntry(2.0)

    def test_dist_io_round_trip(self, tmp_path):
        import paddle_tpu.distributed as dist
        import paddle_tpu.nn as nn
        net = nn.Linear(3, 2)
        ref = net.weight.numpy().copy()
        dist.io.save_persistables(None, str(tmp_path), main_program=net)
        net2 = nn.Linear(3, 2)
        dist.io.load_persistables(None, str(tmp_path), main_program=net2)
        np.testing.assert_allclose(net2.weight.numpy(), ref)


class TestVisionAmpJitTail:
    def test_image_load_ppm(self, tmp_path):
        img = (np.random.RandomState(0).rand(4, 5, 3) * 255).astype(
            np.uint8)
        p = tmp_path / "img.ppm"
        with open(p, "wb") as f:
            f.write(b"P6\n5 4\n255\n")
            f.write(img.tobytes())
        back = paddle.vision.image_load(str(p))
        np.testing.assert_allclose(back, img)

    def test_amp_support_queries(self):
        assert paddle.amp.is_bfloat16_supported() is True
        assert isinstance(paddle.amp.is_float16_supported(), bool)

    def test_jit_verbosity(self):
        paddle.jit.set_verbosity(3)
        from paddle_tpu.flags import flags
        assert flags.FLAGS_log_level == 3
        paddle.jit.set_verbosity(0)


def test_flops_counts_linear_and_conv():
    import paddle_tpu.nn as nn
    net = nn.Sequential(nn.Conv2D(1, 2, 3, padding=1), nn.Flatten(),
                        nn.Linear(2 * 8 * 8, 4))
    total = paddle.flops(net, [1, 1, 8, 8])
    # conv: 64 out-pixels*2ch*1in*9k*2 = 2304; linear: 2*128*4 = 1024
    assert total == 2 * 64 * 2 * 9 + 2 * 128 * 4



class TestLongTailBehaviors:
    def test_sparse_long_tail(self):
        import paddle_tpu.sparse as sp
        d = np.array([[0., 2., 0.], [3., 0., 4.]], np.float32)
        x = sp.to_sparse_coo(paddle.to_tensor(d), 2)
        assert float(sp.sum(x).numpy()) == 9.0
        np.testing.assert_allclose(sp.transpose(x, [1, 0]).to_dense().numpy(),
                                   d.T)
        np.testing.assert_allclose(sp.reshape(x, [3, 2]).to_dense().numpy(),
                                   d.reshape(3, 2))
        assert not sp.isnan(x).to_dense().numpy().any()
        m = sp.mask_as(paddle.to_tensor(np.ones((2, 3), np.float32)), x)
        np.testing.assert_allclose(m.to_dense().numpy(),
                                   (d != 0).astype(np.float32))

    def test_lookahead_and_model_average(self):
        import paddle_tpu.incubate as inc
        import paddle_tpu.nn as nn
        net = nn.Linear(2, 1)
        inner = paddle.optimizer.SGD(learning_rate=0.1,
                                     parameters=net.parameters())
        opt = inc.LookAhead(inner, alpha=0.5, k=2)
        x = paddle.to_tensor(np.ones((4, 2), np.float32))
        y = paddle.to_tensor(np.ones((4, 1), np.float32))
        ma = inc.ModelAverage(parameters=list(net.parameters()))
        losses = []
        for _ in range(4):
            loss = ((net(x) - y) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            ma.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        before = net.weight.numpy().copy()
        with ma.apply():
            inside = net.weight.numpy().copy()
        after = net.weight.numpy()
        np.testing.assert_allclose(before, after)
        assert not np.allclose(inside, before)

    def test_audio_io_round_trip(self, tmp_path):
        sr = 8000
        t = np.linspace(0, 0.1, sr // 10, dtype=np.float32)
        sig = 0.5 * np.sin(2 * np.pi * 440 * t)
        p = str(tmp_path / "tone.wav")
        paddle.audio.save(p, _t(sig[None]), sr)
        meta = paddle.audio.info(p)
        assert meta.sample_rate == sr and meta.num_channels == 1
        wav, sr2 = paddle.audio.load(p)
        assert sr2 == sr
        np.testing.assert_allclose(wav.numpy()[0], sig, atol=1e-3)

    def test_saved_tensors_hooks(self):
        packed, unpacked = [], []

        class Sq(paddle.autograd.PyLayer):
            @staticmethod
            def forward(ctx, x):
                ctx.save_for_backward(x)
                return x * x

            @staticmethod
            def backward(ctx, gy):
                (x,) = ctx.saved_tensor
                return 2.0 * x * gy

        x = _t(np.array([3.0], np.float32))
        x.stop_gradient = False
        with paddle.autograd.saved_tensors_hooks(
                lambda t: (packed.append(1), t.numpy())[1],
                lambda a: (unpacked.append(1), paddle.to_tensor(a))[1]):
            y = Sq.apply(x)
        y.backward()
        assert packed and unpacked
        np.testing.assert_allclose(x.grad.numpy(), [6.0])

    def test_static_ema_and_program_state(self, tmp_path):
        import paddle_tpu.static as st
        import paddle_tpu.nn as nn
        net = nn.Linear(2, 2)
        ema = st.ExponentialMovingAverage(decay=0.5)
        ema.update(parameters=list(net.parameters()))
        before = net.weight.numpy().copy()
        with ema.apply():
            pass
        np.testing.assert_allclose(net.weight.numpy(), before)
        path = str(tmp_path / "model")
        st.save(net, path)
        state = st.load_program_state(path)
        assert any("weight" in k for k in state)
        net2 = nn.Linear(2, 2)
        st.set_program_state(net2, {k: paddle.to_tensor(v)
                                    for k, v in state.items()})
        np.testing.assert_allclose(net2.weight.numpy(), before)

    def test_static_py_func(self):
        import paddle_tpu.static as st
        x = _t(np.array([1.0, 2.0], np.float32))
        out_spec = _t(np.zeros(2, np.float32))
        res = st.py_func(lambda a: a * 3.0, x, out_spec)
        np.testing.assert_allclose(res.numpy(), [3.0, 6.0])

    def test_device_events_and_streams(self):
        e1, e2 = paddle.device.Event(), paddle.device.Event()
        e1.record()
        e2.record()
        assert e1.elapsed_time(e2) >= 0
        with paddle.device.stream_guard(paddle.device.Stream()) as s:
            assert paddle.device.current_stream() is s

    def test_utils_deprecated_and_version(self):
        import warnings

        @paddle.utils.deprecated(update_to="new_fn", since="2.0")
        def old_fn():
            return 42

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert old_fn() == 42
            assert any(issubclass(x.category, DeprecationWarning)
                       for x in w)
        assert paddle.utils.require_version("0.0.0")

    def test_fused_serving_ops(self):
        import paddle_tpu.incubate.nn.functional as IF
        rs = np.random.RandomState(0)
        x = _t(rs.randn(2, 4, 8).astype("float32"))
        res = _t(rs.randn(2, 4, 8).astype("float32"))
        out = IF.fused_bias_dropout_residual_layer_norm(
            x, res, dropout_rate=0.0)
        assert tuple(out.shape) == (2, 4, 8)
        sl = _t(np.array([4, 2]))
        q = _t(rs.randn(2, 2, 4, 8).astype("float32"))
        att = IF.variable_length_memory_efficient_attention(q, q, q, sl, sl)
        # rows beyond kv_len contribute nothing for batch 1
        assert tuple(att.shape) == (2, 2, 4, 8)
        me, md = IF.blha_get_max_len(sl, sl, 2)
        assert int(me.numpy()) == 4


class TestReviewRegressions2:
    def test_ema_debias_exact_for_constant_weights(self):
        import paddle_tpu.static as st
        import paddle_tpu.nn as nn
        net = nn.Linear(2, 2)
        w = net.weight.numpy().copy()
        ema = st.ExponentialMovingAverage(decay=0.9)
        for i in range(3):
            ema.update(parameters=list(net.parameters()) if i == 0 else None)
        with ema.apply():
            # constant weights => debiased EMA equals the weights exactly
            np.testing.assert_allclose(net.weight.numpy(), w, rtol=1e-5)

    def test_model_average_windowing(self):
        import paddle_tpu.incubate as inc
        import paddle_tpu.nn as nn
        net = nn.Linear(1, 1)
        ma = inc.ModelAverage(parameters=list(net.parameters()),
                              max_average_window=4)
        for v in range(1, 11):           # weights 1..10
            net.weight._data = net.weight._data * 0 + float(v)
            ma.step()
        with ma.apply():
            avg = float(net.weight.numpy().reshape(-1)[0])
        # window restarts bound the average to recent steps (here 5..10),
        # not the lifetime mean inflated by count/max_window
        assert 5.0 <= avg <= 10.0, avg

    def test_varlen_attention_causal_cross_length(self):
        import paddle_tpu.incubate.nn.functional as IF
        rs = np.random.RandomState(0)
        q = _t(rs.randn(1, 1, 2, 4).astype("float32"))   # S=2
        k = _t(rs.randn(1, 1, 5, 4).astype("float32"))   # K=5
        v = _t(np.eye(5, 4, dtype=np.float32)[None, None])
        sl = _t(np.array([2]))
        kvl = _t(np.array([5]))
        out = IF.variable_length_memory_efficient_attention(
            q, k, v, sl, kvl, causal=True)
        # query 0 (end-aligned pos 3) must give zero weight to key 4
        s = np.einsum("bhqd,bhkd->bhqk", q.numpy(), k.numpy()) / 2.0
        mask = (np.arange(2)[:, None] + 3) >= np.arange(5)[None, :]
        s = np.where(mask[None, None], s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("bhqk,bhkd->bhqd", p, v.numpy())
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-4, atol=1e-5)

    def test_sparse_transpose_dense_dims(self):
        import paddle_tpu.sparse as sp
        import jax.numpy as jnp
        from paddle_tpu.tensor.tensor import wrap_array
        # hybrid COO: 1 sparse dim, values [nnz, 2, 3]
        idx = wrap_array(jnp.asarray([[0, 2]]))
        vals = wrap_array(jnp.arange(12, dtype=jnp.float32).reshape(2, 2, 3))
        x = sp.SparseCooTensor(idx, vals, [4, 2, 3])
        t = sp.transpose(x, [0, 2, 1])
        dense = x.to_dense().numpy()
        np.testing.assert_allclose(t.to_dense().numpy(),
                                   dense.transpose(0, 2, 1))

    def test_cdist_donot_use_mm(self):
        x = np.random.RandomState(0).randn(4, 3).astype("float32")
        exact = paddle.cdist(_t(x), _t(x),
                             compute_mode="donot_use_mm_for_euclid_dist")
        # exact mode: self-distances are exactly zero
        np.testing.assert_allclose(np.diag(exact.numpy()), np.zeros(4))

    def test_take_clip_clamps_negatives(self):
        x = _t(np.arange(12, dtype=np.float32))
        got = paddle.take(x, _t(np.array([-5, 20])), mode="clip").numpy()
        np.testing.assert_allclose(got, [0.0, 11.0])

    def test_pipe_dataset_early_break_no_error(self, tmp_path):
        import paddle_tpu.distributed as dist
        f = tmp_path / "d.txt"
        f.write_text("\n".join(str(i) for i in range(1000)) + "\n")
        ds = dist.QueueDataset()
        ds.init(batch_size=1, pipe_command="cat",
                parse_fn=lambda s: np.array([float(s)]))
        ds.set_filelist([str(f)])
        for batch in ds:
            break  # must not raise from the SIGPIPE'd cat
