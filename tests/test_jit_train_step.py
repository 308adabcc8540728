"""incubate.jit_train_step: whole-program compiled training matches the
eager loop for several optimizers (the lever that takes ResNet50 from
9 to 1159 img/s on the chip — PERF.md)."""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.incubate import jit_train_step


def _net():
    paddle.seed(7)
    return nn.Sequential(nn.Linear(6, 16), nn.Tanh(), nn.Linear(16, 3))


def _sync(src, dst):
    dst.set_state_dict({k: paddle.to_tensor(v.numpy())
                        for k, v in src.state_dict().items()})


@pytest.mark.parametrize("opt_name,kw", [
    ("SGD", {}),
    ("Momentum", {"momentum": 0.9}),
    ("AdamW", {}),
])
def test_jit_train_step_matches_eager(opt_name, kw):
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(16, 6).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 3, (16,)).astype(np.int64))
    loss_fn = paddle.nn.CrossEntropyLoss()

    net_j = _net()
    net_e = _net()
    _sync(net_j, net_e)
    opt_j = getattr(paddle.optimizer, opt_name)(
        learning_rate=0.05, parameters=net_j.parameters(), **kw)
    opt_e = getattr(paddle.optimizer, opt_name)(
        learning_rate=0.05, parameters=net_e.parameters(), **kw)

    step = jit_train_step(net_j, loss_fn, opt_j)
    for i in range(5):
        lj = float(step(x, y))
        le_t = loss_fn(net_e(x), y)
        le = float(le_t)
        le_t.backward()
        opt_e.step()
        opt_e.clear_grad()
        np.testing.assert_allclose(lj, le, atol=1e-5,
                                   err_msg=f"step {i}: {lj} vs {le}")
    # final weights agree
    for (n, pj), (_, pe) in zip(net_j.named_parameters(),
                                net_e.named_parameters()):
        np.testing.assert_allclose(pj.numpy(), pe.numpy(), atol=1e-5,
                                   err_msg=n)


def test_jit_train_step_global_norm_clip():
    rng = np.random.RandomState(1)
    x = paddle.to_tensor(rng.randn(8, 6).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 3, (8,)).astype(np.int64))
    loss_fn = paddle.nn.CrossEntropyLoss()
    net_j = _net()
    net_e = _net()
    _sync(net_j, net_e)
    opt_j = paddle.optimizer.SGD(
        learning_rate=0.5, parameters=net_j.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(0.1))
    opt_e = paddle.optimizer.SGD(
        learning_rate=0.5, parameters=net_e.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(0.1))
    step = jit_train_step(net_j, loss_fn, opt_j)
    for _ in range(4):
        step(x, y)
        le = loss_fn(net_e(x), y)
        le.backward()
        opt_e.step()
        opt_e.clear_grad()
    for (n, pj), (_, pe) in zip(net_j.named_parameters(),
                                net_e.named_parameters()):
        np.testing.assert_allclose(pj.numpy(), pe.numpy(), atol=1e-4,
                                   err_msg=n)


def test_jit_train_step_rejects_other_clips():
    net = _net()
    opt = paddle.optimizer.SGD(
        learning_rate=0.1, parameters=net.parameters(),
        grad_clip=paddle.nn.ClipGradByNorm(0.1))
    with pytest.raises(NotImplementedError):
        jit_train_step(net, paddle.nn.CrossEntropyLoss(), opt)


def test_jit_train_step_syncs_optimizer_state_dict():
    """Jitted moments land in optimizer.state_dict() so checkpoints
    carry them (round-3 review finding)."""
    net = _net()
    opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                 parameters=net.parameters())
    step = jit_train_step(net, paddle.nn.CrossEntropyLoss(), opt)
    rng = np.random.RandomState(2)
    x = paddle.to_tensor(rng.randn(8, 6).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 3, (8,)).astype(np.int64))
    for _ in range(3):
        step(x, y)
    sd = opt.state_dict()
    moment_keys = [k for k in sd if "moment" in k]
    assert moment_keys, sd.keys()
    # moments are non-trivial (all-zeros would mean the jitted state
    # never reached the optimizer store)
    total = sum(
        float(np.abs(np.asarray(v.numpy() if hasattr(v, "numpy")
                                else v)).sum())
        for k, v in sd.items() if k in moment_keys)
    assert total > 0.0
    assert sd["@step"] == 3


def test_jit_train_step_amp_o1_trains():
    """amp_level='O1' runs the traced program through the eager AMP
    hook (bf16 matmuls, fp32 master params) and still converges close
    to the fp32 step."""
    rng = np.random.RandomState(3)
    x = paddle.to_tensor(rng.randn(16, 6).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 3, (16,)).astype(np.int64))
    loss_fn = paddle.nn.CrossEntropyLoss()
    net_a = _net()
    net_f = _net()
    _sync(net_a, net_f)
    opt_a = paddle.optimizer.SGD(learning_rate=0.05,
                                 parameters=net_a.parameters())
    opt_f = paddle.optimizer.SGD(learning_rate=0.05,
                                 parameters=net_f.parameters())
    step_a = jit_train_step(net_a, loss_fn, opt_a, amp_level="O1")
    step_f = jit_train_step(net_f, loss_fn, opt_f)
    la = lf = None
    for _ in range(10):
        la = float(step_a(x, y))
        lf = float(step_f(x, y))
    # bf16 matmuls: close but not bit-equal
    assert abs(la - lf) < 0.05, (la, lf)
    assert la < 1.2   # converging from ~1.55


def test_jit_train_step_amp_rejects_o2():
    net = _net()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    with pytest.raises(NotImplementedError):
        jit_train_step(net, paddle.nn.CrossEntropyLoss(), opt,
                       amp_level="O2")


def test_jit_train_step_respects_optimizer_param_list():
    """Fine-tune semantics: only the optimizer's own parameters move;
    a trainable backbone excluded from the optimizer stays untouched
    (round-3 review finding)."""
    paddle.seed(11)
    backbone = nn.Linear(6, 16)
    head = nn.Linear(16, 3)
    net = nn.Sequential(backbone, nn.Tanh(), head)
    opt = paddle.optimizer.AdamW(learning_rate=0.05,
                                 parameters=head.parameters())
    step = jit_train_step(net, paddle.nn.CrossEntropyLoss(), opt)
    rng = np.random.RandomState(4)
    x = paddle.to_tensor(rng.randn(8, 6).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 3, (8,)).astype(np.int64))
    w_backbone = backbone.weight.numpy().copy()
    w_head = head.weight.numpy().copy()
    for _ in range(3):
        step(x, y)
    np.testing.assert_array_equal(backbone.weight.numpy(), w_backbone)
    assert not np.allclose(head.weight.numpy(), w_head)


def test_jit_train_step_dropout_resamples_per_step():
    """Train-mode Dropout inside the compiled step draws a FRESH mask
    every step (PRNG key threaded as a per-step argument, fold_in per
    call site — framework.random.traced_key_guard), instead of baking
    one mask at trace time.  Reference threads seed+offset into the
    cuRAND dropout kernel the same way
    (/root/reference/python/paddle/nn/functional/common.py:989)."""
    paddle.seed(21)
    net = nn.Sequential(nn.Linear(6, 64), nn.Dropout(0.5), nn.Linear(64, 3))
    net.train()
    # lr=0 freezes weights: any loss variation across steps is the mask
    opt = paddle.optimizer.SGD(learning_rate=0.0,
                               parameters=net.parameters())
    step = jit_train_step(net, paddle.nn.CrossEntropyLoss(), opt)
    rng = np.random.RandomState(5)
    x = paddle.to_tensor(rng.randn(16, 6).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 3, (16,)).astype(np.int64))
    losses = [float(step(x, y)) for _ in range(4)]
    assert len({round(v, 8) for v in losses}) > 1, \
        f"identical losses every step — dropout mask was baked: {losses}"


def test_jit_train_step_dropout_seed_deterministic():
    rng = np.random.RandomState(6)
    x = paddle.to_tensor(rng.randn(8, 6).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 3, (8,)).astype(np.int64))

    def run():
        paddle.seed(99)
        net = nn.Sequential(nn.Linear(6, 32), nn.Dropout(0.5),
                            nn.Linear(32, 3))
        net.train()
        opt = paddle.optimizer.SGD(learning_rate=0.0,
                                   parameters=net.parameters())
        step = jit_train_step(net, paddle.nn.CrossEntropyLoss(), opt)
        return [float(step(x, y)) for _ in range(3)]

    assert run() == run()


def test_jit_train_step_tuple_inputs_and_labels():
    """Multi-input models: step((ids, mask), (y1, y2)) runs model(*x)
    and hands loss_fn the label tuple."""
    paddle.seed(31)

    class TwoIn(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(6, 4)

        def forward(self, a, b):
            return self.fc(a) + self.fc(b)

    net = TwoIn()
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=net.parameters())

    def loss_fn(out, ys):
        y1, y2 = ys
        return ((out - y1) ** 2).mean() + ((out - y2) ** 2).mean()

    step = jit_train_step(net, loss_fn, opt)
    rng = np.random.RandomState(7)
    a = paddle.to_tensor(rng.randn(8, 6).astype(np.float32))
    b = paddle.to_tensor(rng.randn(8, 6).astype(np.float32))
    y1 = paddle.to_tensor(rng.randn(8, 4).astype(np.float32))
    y2 = paddle.to_tensor(rng.randn(8, 4).astype(np.float32))
    l0 = float(step((a, b), (y1, y2)))
    for _ in range(10):
        l1 = float(step((a, b), (y1, y2)))
    assert l1 < l0


@pytest.mark.slow
def test_jit_train_step_bert_qa_finetune_compiled():
    """BASELINE config 3 lane: BERT (tiny dims, real dropout) SQuAD-style
    QA fine-tune runs entirely through the compiled step with AMP O1 and
    the loss trajectory tracks the eager loop (dropout-off lane compared
    exactly; dropout-on lane must keep training)."""
    from paddle_tpu.models.bert import BertConfig, BertForQuestionAnswering

    cfg = BertConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=2, intermediate_size=64,
                     max_position_embeddings=64, dropout_prob=0.1)
    rng = np.random.RandomState(8)
    ids = paddle.to_tensor(rng.randint(0, 128, (4, 16)).astype(np.int64))
    tt = paddle.to_tensor(np.zeros((4, 16), np.int64))
    mask = paddle.to_tensor(np.ones((4, 16), np.float32))
    start = paddle.to_tensor(rng.randint(0, 16, (4,)).astype(np.int64))
    end = paddle.to_tensor(rng.randint(0, 16, (4,)).astype(np.int64))
    ce = paddle.nn.CrossEntropyLoss()

    def qa_loss(out, ys):
        s_logits, e_logits = out
        s_y, e_y = ys
        return (ce(s_logits, s_y) + ce(e_logits, e_y)) * 0.5

    paddle.seed(55)
    net = BertForQuestionAnswering(cfg)
    net.train()
    opt = paddle.optimizer.AdamW(learning_rate=5e-3,
                                 parameters=net.parameters())
    step = jit_train_step(net, qa_loss, opt, amp_level="O1")
    losses = [float(step((ids, tt, mask), (start, end))) for _ in range(8)]
    assert losses[-1] < losses[0], losses

    # dropout-off: compiled matches the eager loop closely (fp32 lane)
    cfg0 = BertConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                      num_attention_heads=2, intermediate_size=64,
                      max_position_embeddings=64, dropout_prob=0.0)
    paddle.seed(56)
    net_c = BertForQuestionAnswering(cfg0)
    paddle.seed(56)
    net_e = BertForQuestionAnswering(cfg0)
    _sync(net_c, net_e)
    opt_c = paddle.optimizer.SGD(learning_rate=0.05,
                                 parameters=net_c.parameters())
    opt_e = paddle.optimizer.SGD(learning_rate=0.05,
                                 parameters=net_e.parameters())
    step_c = jit_train_step(net_c, qa_loss, opt_c)
    for i in range(3):
        lc = float(step_c((ids, tt, mask), (start, end)))
        s_log, e_log = net_e(ids, tt, mask)
        le_t = qa_loss((s_log, e_log), (start, end))
        le = float(le_t)
        le_t.backward()
        opt_e.step()
        opt_e.clear_grad()
        assert abs(lc - le) < 5e-4, (i, lc, le)
