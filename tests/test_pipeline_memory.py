"""Pipeline peak-activation accounting (VERDICT r4 #5): pin the
per-schedule compiled memory behavior via XLA buffer-assignment stats
(utils.memory.memory_usage — the reference's runtime
get_mem_usage/print_mem_usage role, reference: pybind.cc:181; memory
estimation lineage: python/paddle/fluid/contrib/memory_usage_calc.py).

Measured facts these tests pin (8-device CPU mesh, fwd+bwd compiled):

1. At FIXED global batch, temp bytes are ~FLAT in the microbatch count
   for BOTH schedules: the tick scan saves O(ticks) states of size
   O(B/m) each, so the product stays ~B x hidden. Raising m does NOT
   blow activation memory in this design — the classical "GPipe banks
   O(m) microbatches" reading (O(m) states of FIXED size) doesn't apply
   when the global batch is what's fixed. This is why no depth-first
   (1F1B-memory) burst reorder was added: the conditional in VERDICT r4
   #5 ("if interleaved shows the same O(m) banking") measures false.

2. The interleaved schedule pays ~v x GPipe's temp bytes: ~v x as many
   ring ticks, each saving a same-size carry for backward. Lower bubble
   costs v x activation banking — the schedule-choice tradeoff (use
   interleaved when bubble-bound, i.e. m/n small; prefer GPipe when
   HBM-bound and m/n is already large).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.parallel import pipeline_apply
from paddle_tpu.utils.memory import memory_usage

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 devices")

L, D, B = 8, 256, 32


@pytest.fixture(scope="module")
def pp_mesh():
    return pt.build_mesh(dp=2, pp=4, devices=jax.devices()[:8])


def _temp_bytes(mesh, m, schedule="gpipe", v=1):
    rng = np.random.default_rng(0)
    p = {"w": jnp.asarray(
        rng.normal(scale=0.1, size=(L, D, D)).astype(np.float32))}
    x = jnp.asarray(rng.normal(size=(B, D)).astype(np.float32))

    def block(pl, h):
        return jnp.tanh(h @ pl["w"])

    def loss(p, x):
        out = pipeline_apply(block, p, x, num_microbatches=m, mesh=mesh,
                             schedule=schedule, virtual_stages=v)
        return jnp.mean(out ** 2)

    c = jax.jit(jax.value_and_grad(loss)).lower(p, x).compile()
    mu = memory_usage(c)
    if "temp_size_in_bytes" not in mu:
        pytest.skip("backend does not report buffer-assignment temp size")
    return mu["temp_size_in_bytes"]


def test_gpipe_temp_flat_in_microbatch_count(pp_mesh):
    """Fixed global batch: more microbatches -> smaller states x more
    ticks, net ~flat. A regression to O(m) banking (states of fixed
    size) would show ~8x growth here."""
    t2 = _temp_bytes(pp_mesh, 2)
    t16 = _temp_bytes(pp_mesh, 16)
    assert t16 < 1.5 * t2, (t2, t16)


def test_interleaved_temp_flat_in_microbatch_count(pp_mesh):
    t2 = _temp_bytes(pp_mesh, 2, "interleaved", 2)
    t16 = _temp_bytes(pp_mesh, 16, "interleaved", 2)
    assert t16 < 1.5 * t2, (t2, t16)


def test_interleaved_pays_about_v_times_gpipe(pp_mesh):
    """The bubble-vs-memory tradeoff is real and bounded: v=2
    interleaving costs between ~1.3x and ~3.5x GPipe's temp bytes (the
    v x tick-state banking), not more."""
    tg = _temp_bytes(pp_mesh, 8)
    ti = _temp_bytes(pp_mesh, 8, "interleaved", 2)
    assert 1.3 * tg < ti < 3.5 * tg, (tg, ti)
