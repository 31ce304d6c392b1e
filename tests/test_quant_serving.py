"""int8 serving end-to-end OFF-chip (VERDICT r4 #8): a PTQ-quantized
artifact exported through tools/export_serving.py --quantize runs
through the same serving paths as the fp32 one — the Python predictor
executes it with a bounded accuracy delta vs fp32, and the C++ native
reader parses it — so quantized serving is in the test loop before any
chip run (on-chip ptserve p50/p99 has not been measured). Reference role:
paddle/fluid/inference/api/mkldnn_quantizer.cc (PTQ for serving) +
inference/tests/api (per-model serving tests)."""

import numpy as np
import pytest

from conftest import load_tool


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory):
    es = load_tool("export_serving")
    d_fp32 = str(tmp_path_factory.mktemp("mnist_fp32"))
    d_int8 = str(tmp_path_factory.mktemp("mnist_int8"))
    es.export("mnist_mlp", d_fp32)
    es.export("mnist_mlp", d_int8, quantize=True)
    return d_fp32, d_int8


def test_int8_artifact_accuracy_vs_fp32(artifacts):
    """Both artifacts serve the same inputs through the Python predictor
    (jax.export path); int8 logits stay within 10% relative error of
    fp32 and agree on argmax for the vast majority of rows."""
    from paddle_tpu import static

    d_fp32, d_int8 = artifacts
    rng = np.random.default_rng(7)
    x = rng.normal(size=(64, 784)).astype(np.float32)
    ref = static.load_inference_model(d_fp32).run({"x": x})[0]
    got = static.load_inference_model(d_int8).run({"x": x})[0]
    assert got.shape == ref.shape == (64, 10)
    rel = float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6))
    assert rel < 0.1, rel
    agree = float(np.mean(got.argmax(1) == ref.argmax(1)))
    assert agree > 0.9, agree


def test_int8_artifact_parses_natively(artifacts):
    """The C++ reader loads the quantized artifact through the real
    C ABI: manifest + weights parse, feeds match the fp32 artifact's."""
    from paddle_tpu.native import NativePredictor

    d_fp32, d_int8 = artifacts
    p8 = NativePredictor(d_int8)
    p32 = NativePredictor(d_fp32)
    try:
        assert p8.feed_names == p32.feed_names == ["x"]
        assert len(p8.fetch_names) == len(p32.fetch_names)
    finally:
        p8.close()
        p32.close()


def test_int8_artifact_batch_polymorphic(artifacts):
    """The quantized export keeps the polymorphic batch dim — one
    artifact serves any batch size, same as fp32."""
    from paddle_tpu import static

    _, d_int8 = artifacts
    pred = static.load_inference_model(d_int8)
    for b in (1, 5):
        out = pred.run({"x": np.zeros((b, 784), np.float32)})[0]
        assert out.shape == (b, 10)


def test_quantize_refuses_unquantizable_model():
    """An 'int8' export that quantized nothing must fail loudly, not
    ship a float artifact under an int8 label."""
    es = load_tool("export_serving")

    import jax.numpy as jnp
    import paddle_tpu.nn as nn

    model = nn.LayerNorm(8)  # nothing quantizable inside
    swapped = es.ptq_int8(model, [jnp.zeros((1, 8), jnp.float32)])
    assert swapped == 0
