"""Model zoo tests (BASELINE ladder configs)."""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM, LeNet


def tiny_gpt(**kw):
    kw.setdefault("vocab_size", 128)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("use_flash_attention", False)
    return GPTConfig(**kw)


def test_gpt_forward_loss_and_grad():
    m = GPTForCausalLM(tiny_gpt())
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 128, (2, 16)).astype(np.int64))
    assert m(ids).shape == [2, 16, 128]
    # with labels the head's product is inside the loss: no logits exist
    none, loss = m(ids, labels=ids)
    assert none is None
    # initial loss ~ ln(vocab)
    assert 3.0 < float(loss) < 7.0
    loss.backward()
    assert m.gpt.wte.weight.grad is not None
    assert m.gpt.blocks[0].mlp.fc1.weight.grad is not None


def test_gpt_trains():
    import paddle_tpu.optimizer as opt

    m = GPTForCausalLM(tiny_gpt())
    optim = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    ids = paddle.to_tensor(
        np.random.RandomState(1).randint(0, 128, (2, 16)).astype(np.int64))
    losses = []
    for _ in range(8):
        _, loss = m(ids, labels=ids)
        loss.backward()
        optim.step()
        optim.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5, losses


def test_lenet_shapes():
    m = LeNet()
    x = paddle.to_tensor(np.zeros((3, 1, 28, 28), np.float32))
    y = m(x)
    assert y.shape == [3, 10]
