"""CNN model family: shape checks + convergence smoke (the reference's
book-test pattern, reference: tests/book/test_image_classification).

Uses tiny inputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer
from paddle_tpu.models import resnet, se_resnext, vgg


def test_resnet50_forward_shape():
    pt.seed(0)
    model = resnet.resnet50(num_classes=10).eval()
    x = jnp.zeros((2, 3, 64, 64), jnp.float32)
    out = model(x)
    assert out.shape == (2, 10)
    # 3+4+6+3 bottlenecks
    assert len(model.blocks) == 16


def test_resnet_cifar_trains():
    pt.seed(1)
    model = resnet.resnet20_cifar(num_classes=10)
    params, buffers = model.named_parameters(), model.named_buffers()
    opt = optimizer.Momentum(0.05, 0.9)
    state = opt.init(params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 3, 16, 16)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, 8))

    @jax.jit
    def step(params, buffers, state):
        def loss(p):
            logits, new_buf = model.functional_call(
                p, x, buffers=buffers, training=True)
            return resnet.loss_fn(logits, y), new_buf

        (l, new_buf), g = jax.value_and_grad(loss, has_aux=True)(params)
        params, state = opt.apply(params, g, state)
        return params, new_buf, state, l

    losses = []
    for _ in range(12):
        params, buffers, state, l = step(params, buffers, state)
        losses.append(float(l))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses[-1])


def test_vgg16_forward_shape():
    pt.seed(2)
    model = vgg.VGG(11, num_classes=7, image_size=32).eval()
    out = model(jnp.zeros((2, 3, 32, 32), jnp.float32))
    assert out.shape == (2, 7)


def test_se_resnext_forward_shape():
    pt.seed(3)
    model = se_resnext.SEResNeXt(depths=(1, 1, 1, 1), num_classes=5).eval()
    out = model(jnp.zeros((2, 3, 64, 64), jnp.float32))
    assert out.shape == (2, 5)


def test_resnet_batchnorm_buffers_update():
    pt.seed(4)
    model = resnet.resnet20_cifar()
    params, buffers = model.named_parameters(), model.named_buffers()
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(4, 3, 16, 16)).astype(np.float32))
    _, new_buf = model.functional_call(params, x, buffers=buffers,
                                       training=True)
    changed = [k for k in buffers
               if not np.allclose(np.asarray(buffers[k]),
                                  np.asarray(new_buf[k]))]
    assert changed, "BN running stats should update in training mode"


def test_alexnet_forward_and_train_step():
    from paddle_tpu.models import alexnet as A

    pt.seed(0)
    m = A.alexnet(num_classes=7)
    x = jnp.asarray(np.random.default_rng(0)
                    .normal(size=(2, 3, 224, 224)).astype(np.float32))
    params = m.named_parameters()
    out, _ = m.functional_call(params, x, training=False)
    assert out.shape == (2, 7)
    labels = jnp.asarray([1, 3])
    g = jax.grad(lambda p: A.loss_fn(
        m.functional_call(p, x, training=False)[0], labels))(params)
    assert all(bool(jnp.isfinite(v).all()) for v in g.values())


def test_googlenet_aux_heads_train_vs_eval():
    from paddle_tpu.models import googlenet as G

    pt.seed(0)
    m = G.googlenet(num_classes=5)
    x = jnp.asarray(np.random.default_rng(1)
                    .normal(size=(2, 3, 224, 224)).astype(np.float32))
    params = m.named_parameters()
    out_t, _ = m.functional_call(params, x, training=True)
    assert isinstance(out_t, tuple) and len(out_t) == 3  # main + 2 aux
    out_e, _ = m.functional_call(params, x, training=False)
    assert out_e.shape == (2, 5)  # aux heads vanish at inference
    labels = jnp.asarray([0, 4])
    loss = G.loss_fn(out_t, labels)
    assert bool(jnp.isfinite(loss))
    assert float(G.loss_fn(out_e, labels)) > 0  # eval form also scores
