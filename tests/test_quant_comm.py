"""Quantized execution plane, collective side (quant/collectives.py +
the Trainer grad_compression opt-in): the shared abs-max wire format,
the hand-written int8 ring psum on the 8-device sim, degenerate-scale
fallbacks, trajectory parity gates for pure-DP and fsdp runs, byte
accounting counter-verified, and the zero-cost-when-disabled pin."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import optimizer, parallel, telemetry
from paddle_tpu.parallel.plan import Plan
from paddle_tpu.quant import collectives as QC
from paddle_tpu.quant.ops import absmax_decode, absmax_encode

RNG = np.random.default_rng(23)


# ---------------------------------------------------------------------------
# the ONE shared abs-max helper (quant/ops.py) — round-trip bounds
# ---------------------------------------------------------------------------


class TestSharedAbsMax:
    def test_round_trip_error_bound_nearest(self):
        """Nearest rounding: |x - decode(encode(x))| <= scale/2, with
        scale = absmax/127 — the bound every consumer (activations, KV
        pages, collective payloads) inherits from the one helper."""
        x = jnp.asarray(RNG.normal(size=(64, 128)).astype(np.float32))
        q, scale = absmax_encode(x, axis=1)
        assert q.dtype == jnp.int8 and scale.shape == (64, 1)
        np.testing.assert_allclose(
            np.asarray(scale[:, 0]),
            np.abs(np.asarray(x)).max(1) / 127.0, rtol=1e-6)
        err = np.abs(np.asarray(absmax_decode(q, scale)) - np.asarray(x))
        assert (err <= np.asarray(scale) / 2 * (1 + 1e-5)).all(), err.max()

    def test_round_trip_error_bound_stochastic(self):
        """Stochastic rounding: error bounded by ONE step (floor+u can
        round either way) and unbiased in the mean."""
        x = jnp.asarray(RNG.normal(size=(256, 256)).astype(np.float32))
        q, scale = absmax_encode(x, axis=1, key=jax.random.key(0))
        err = np.asarray(absmax_decode(q, scale)) - np.asarray(x)
        assert (np.abs(err) <= np.asarray(scale) * (1 + 1e-5)).all()
        # unbiasedness: mean error across 64k draws ~ 0 (CLT bound)
        assert abs(err.mean()) < float(np.asarray(scale).mean()) * 0.02

    def test_whole_tensor_and_recorded_absmax(self):
        x = jnp.asarray(RNG.normal(size=(33,)).astype(np.float32))
        q, scale = absmax_encode(x)               # axis=None: scalar
        assert scale.shape == ()
        np.testing.assert_allclose(
            np.asarray(absmax_decode(q, scale)), np.asarray(x),
            atol=float(scale) / 2 * (1 + 1e-5))
        # recorded-absmax form (the int8 activation path): same grid
        q2, s2 = absmax_encode(x, absmax=jnp.abs(x).max())
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))

    def test_zero_input_is_exact(self):
        q, scale = absmax_encode(jnp.zeros((16,), jnp.float32))
        assert np.asarray(q).sum() == 0
        np.testing.assert_array_equal(
            np.asarray(absmax_decode(q, scale)), np.zeros(16))

    def test_quantize_acts_rides_the_shared_helper(self):
        """int8 activation execution and the shared helper must never
        drift apart (the three-conventions parity hazard)."""
        from paddle_tpu.quant.int8 import _quantize_acts

        x = jnp.asarray(RNG.normal(size=(8, 32)).astype(np.float32))
        am = jnp.abs(x).max()
        q_a, s_a = _quantize_acts(x, am)
        q_h, s_h = absmax_encode(x, absmax=am)
        np.testing.assert_array_equal(np.asarray(q_a), np.asarray(q_h))
        np.testing.assert_allclose(np.asarray(s_a), np.asarray(s_h))


# ---------------------------------------------------------------------------
# the hand-written int8 ring psum (shard_map, 8-device sim)
# ---------------------------------------------------------------------------


def _dp_mesh(devs):
    return Mesh(np.asarray(devs), ("dp",))


def _ring_psum(x_rows, devs, **kw):
    """Run quantized_psum over the dp axis; x_rows (n, ...) one row per
    device. Returns the (replicated) result."""
    n = len(devs)
    f = shard_map(lambda v: QC.quantized_psum(v[0], "dp", n, **kw),
                  mesh=_dp_mesh(devs), in_specs=P("dp"), out_specs=P(),
                  check_vma=False)
    return np.asarray(jax.jit(f)(x_rows))


class TestQuantizedPsum:
    def test_matches_fp32_psum_within_tolerance(self, eight_devices):
        n = 8
        x = RNG.normal(size=(n, 3000)).astype(np.float32)
        got = _ring_psum(jnp.asarray(x), eight_devices, group=256)
        want = x.sum(0)
        # per-hop requantization: worst case ~n quantization steps
        atol = np.abs(x).max() / 127 * n * 1.5
        np.testing.assert_allclose(got, want, atol=atol)
        # and it is meaningfully accurate, not just bounded
        assert np.abs(got - want).max() / np.abs(want).max() < 0.05

    def test_every_device_decodes_identical_bytes(self, eight_devices):
        """The replicated-update invariant: the all-gather forwards one
        encoding, so all 8 shards see bit-identical sums."""
        n = 8
        x = jnp.asarray(RNG.normal(size=(n, 1024)).astype(np.float32))
        f = shard_map(
            lambda v: QC.quantized_psum(v[0], "dp", n)[None],
            mesh=_dp_mesh(eight_devices), in_specs=P("dp"),
            out_specs=P("dp"), check_vma=False)
        rows = np.asarray(jax.jit(f)(x))
        for d in range(1, n):
            np.testing.assert_array_equal(rows[0], rows[d])

    def test_zero_input_sums_exactly_zero(self, eight_devices):
        got = _ring_psum(jnp.zeros((8, 512), jnp.float32),
                         eight_devices)
        np.testing.assert_array_equal(got, np.zeros(512))

    def test_nonfinite_poisons_output(self, eight_devices):
        """Scale-degenerate (inf/nan) leaves must POISON the sum — a
        quantizer that launders inf into finite int8 would blind the
        nan-guard."""
        x = RNG.normal(size=(8, 512)).astype(np.float32)
        for bad in (np.nan, np.inf):
            x2 = x.copy()
            x2[3, 7] = bad
            got = _ring_psum(jnp.asarray(x2), eight_devices)
            assert np.isnan(got).all()

    def test_stochastic_rounding_stays_bounded(self, eight_devices):
        n = 8
        x = RNG.normal(size=(n, 2048)).astype(np.float32)
        got = _ring_psum(jnp.asarray(x), eight_devices,
                         key=jax.random.key(3))
        atol = np.abs(x).max() / 127 * n * 2.0   # one step per hop
        np.testing.assert_allclose(got, x.sum(0), atol=atol)

    def test_tree_reduce_leaves_small_leaves_exact(self, eight_devices):
        """quantized_pmean_tree: tiny / integer leaves ride the exact
        fp32 pmean (the tiny-leaf fallback)."""
        n = 8
        big = RNG.normal(size=(n, 4096)).astype(np.float32)
        small = RNG.normal(size=(n, 4)).astype(np.float32)
        cnt = np.arange(n, dtype=np.int32).reshape(n, 1)

        def body(b, s, c):
            return QC.quantized_pmean_tree(
                {"w": b[0], "b": s[0], "step": c[0]}, "dp", n)

        f = shard_map(body, mesh=_dp_mesh(eight_devices),
                      in_specs=(P("dp"), P("dp"), P("dp")),
                      out_specs=P(), check_vma=False)
        out = jax.jit(f)(jnp.asarray(big), jnp.asarray(small),
                         jnp.asarray(cnt))
        # tiny float leaf: EXACT pmean
        np.testing.assert_allclose(np.asarray(out["b"]), small.mean(0),
                                   rtol=1e-6)
        # int leaf untouched by quantization
        np.testing.assert_allclose(np.asarray(out["step"]),
                                   cnt.mean(0), rtol=1e-6)
        # big leaf: compressed but accurate
        np.testing.assert_allclose(np.asarray(out["w"]), big.mean(0),
                                   atol=np.abs(big).max() / 127 * 2)

    def test_mode_validation(self):
        from paddle_tpu.core.enforce import EnforceError

        with pytest.raises(EnforceError, match="grad_compression"):
            QC.check_mode("int4")


# ---------------------------------------------------------------------------
# the custom-partitioned form (ISSUE 15: the ring INSIDE the
# partitioned computation — pjit-level callers, no shard_map body)
# ---------------------------------------------------------------------------


class TestPartitionedPsum:
    def _cp(self, x_rows, devs, **kw):
        from jax.sharding import NamedSharding

        mesh = _dp_mesh(devs)
        xx = jax.device_put(jnp.asarray(x_rows),
                            NamedSharding(mesh, P("dp")))
        return np.asarray(jax.jit(
            lambda v: QC.quantized_psum_partitioned(v, "dp", **kw))(xx))

    def test_bit_identical_to_shard_map_ring(self, eight_devices):
        """THE parity gate: the custom_partitioning form lowers to the
        SAME per-shard ring over the same mesh, so outputs are
        bit-identical to the shard_map spelling — not merely close."""
        x = RNG.normal(size=(8, 3000)).astype(np.float32)
        want = _ring_psum(jnp.asarray(x), eight_devices, group=256)
        got = self._cp(x, eight_devices, group=256)
        np.testing.assert_array_equal(got, want)

    def test_ring_runs_inside_partitioned_computation(
            self, eight_devices, monkeypatch):
        """The byte-count gate, structurally: the lowered computation
        calls quantized_psum with the SAME (axis, size, group) as the
        shard_map form — identical ring, identical per-hop payload
        (leaf_payload_bytes applies unchanged)."""
        seen = []
        real = QC.quantized_psum

        def counting(x, axis_name, axis_size, **kw):
            seen.append((axis_name, int(axis_size),
                         kw.get("group")))
            return real(x, axis_name, axis_size, **kw)

        monkeypatch.setattr(QC, "quantized_psum", counting)
        x = RNG.normal(size=(8, 2048)).astype(np.float32)
        got = self._cp(x, eight_devices, group=512)
        assert ("dp", 8, 512) in seen
        atol = np.abs(x).max() / 127 * 8 * 1.5
        np.testing.assert_allclose(got, x.sum(0), atol=atol)

    def test_stochastic_mode_preserved(self, eight_devices):
        x = RNG.normal(size=(8, 2048)).astype(np.float32)
        got = self._cp(x, eight_devices, key=jax.random.key(3))
        atol = np.abs(x).max() / 127 * 8 * 2.0
        np.testing.assert_allclose(got, x.sum(0), atol=atol)

    def test_nonfinite_poisons_output(self, eight_devices):
        x = RNG.normal(size=(8, 512)).astype(np.float32)
        x[3, 7] = np.inf
        assert np.isnan(self._cp(x, eight_devices)).all()

    def test_eager_fallback_is_exact(self):
        """Outside jit/mesh there is nothing to compress across — the
        reference body (exact fp32 sum) runs."""
        x = RNG.normal(size=(4, 300)).astype(np.float32)
        got = np.asarray(QC.quantized_psum_partitioned(
            jnp.asarray(x), "dp"))
        np.testing.assert_allclose(got, x.sum(0), atol=1e-5)

    def test_native_allreduce_probe_seam(self, eight_devices,
                                         monkeypatch):
        """utils.compat.native_int8_allreduce is the runtime-native
        int8 AllReduce seam: when it resolves, BOTH psum spellings
        bypass the hand-written ring through it."""
        from jax import lax

        from paddle_tpu.utils import compat

        def fake_native():
            return (lambda x, *, axis_name, axis_size, group, key:
                    lax.psum(x, axis_name) + 1000.0)

        monkeypatch.setattr(compat, "native_int8_allreduce",
                            fake_native)
        x = RNG.normal(size=(8, 512)).astype(np.float32)
        got = _ring_psum(jnp.asarray(x), eight_devices)
        np.testing.assert_allclose(got, x.sum(0) + 1000.0, rtol=1e-5)
        got_cp = self._cp(x, eight_devices)
        np.testing.assert_allclose(got_cp, x.sum(0) + 1000.0,
                                   rtol=1e-5)

    def test_partial_contract_native_refused_for_sr(
            self, eight_devices, monkeypatch):
        """An upstream-attr adapter can't forward the stochastic key
        (partial_contract=True): key= (int8_sr) calls must keep the
        ring — silently degrading SR to nearest rounding would let
        bias accumulate — while nearest-rounding calls adopt it."""
        from jax import lax

        from paddle_tpu.utils import compat

        def fake_native():
            def f(x, *, axis_name, axis_size, group, key):
                return lax.psum(x, axis_name) + 1000.0

            f.partial_contract = True
            return f

        monkeypatch.setattr(compat, "native_int8_allreduce",
                            fake_native)
        x = RNG.normal(size=(8, 2048)).astype(np.float32)
        # SR call: the ring runs (result near the true sum, NOT +1000)
        got = _ring_psum(jnp.asarray(x), eight_devices,
                         key=jax.random.key(0))
        np.testing.assert_allclose(got, x.sum(0),
                                   atol=np.abs(x).max() / 127 * 8 * 2)
        # nearest-rounding call: the native adapter is adopted
        got2 = _ring_psum(jnp.asarray(x), eight_devices)
        np.testing.assert_allclose(got2, x.sum(0) + 1000.0, rtol=1e-5)

    def test_native_probe_env_resolution(self, monkeypatch):
        """The PT_NATIVE_INT8_ALLREDUCE=module:fn override resolves;
        unset (this toolchain) the probe is None and the ring runs."""
        from paddle_tpu.utils import compat

        monkeypatch.delenv("PT_NATIVE_INT8_ALLREDUCE", raising=False)
        assert compat.native_int8_allreduce() is None
        monkeypatch.setenv("PT_NATIVE_INT8_ALLREDUCE",
                           "operator:add")
        assert compat.native_int8_allreduce() is not None

    def test_native_probe_env_malformed_is_typed(self, monkeypatch):
        """A spec missing the ':fn' half fails TYPED at the probe,
        naming the env var and expected form — not a bare getattr
        AttributeError from inside a traced collective."""
        from paddle_tpu.core.enforce import EnforceError
        from paddle_tpu.utils import compat

        for bad in ("operator", "operator:", ":add"):
            monkeypatch.setenv("PT_NATIVE_INT8_ALLREDUCE", bad)
            with pytest.raises(EnforceError,
                               match="PT_NATIVE_INT8_ALLREDUCE"):
                compat.native_int8_allreduce()


# ---------------------------------------------------------------------------
# byte accounting
# ---------------------------------------------------------------------------


class TestPayloadBytes:
    def test_int8_moves_at_least_3p5x_fewer_bytes(self):
        """The acceptance-gate arithmetic on a realistic gradient tree:
        compressed payload >= 3.5x smaller than fp32 (group-scale
        overhead included)."""
        tree = {"w1": np.zeros((784, 1024), np.float32),
                "w2": np.zeros((1024, 1024), np.float32),
                "b1": np.zeros((1024,), np.float32)}
        i8, f32_resid = QC.tree_payload_bytes(tree, 8, compression="int8")
        f32_i, f32_full = QC.tree_payload_bytes(tree, 8, compression=None)
        assert f32_i == 0
        ratio = f32_full / (i8 + f32_resid)
        assert ratio >= 3.5, ratio

    def test_single_device_moves_nothing(self):
        assert QC.leaf_payload_bytes(4096, 1, compressed=True) == 0


# ---------------------------------------------------------------------------
# Trainer integration: trajectory parity gates + counters + zero-cost
# ---------------------------------------------------------------------------


_BATCH_RNG = np.random.default_rng(5)
_B = {"x": jnp.asarray(_BATCH_RNG.normal(size=(16, 784))
                       .astype(np.float32)),
      "label": jnp.asarray(_BATCH_RNG.integers(0, 10, 16))}
_SINGLE = {}


def _batch(bs=16):
    return _B


def _single_device_trajectory(steps=4):
    """Memoized single-device reference (both parity tests compare
    against the SAME baseline — one compile instead of two)."""
    if steps not in _SINGLE:
        t0 = _trainer(mesh=pt.build_mesh(dp=1,
                                         devices=jax.devices()[:1]))
        for _ in range(steps):
            l0, _ = t0.train_step(_B)
        _SINGLE[steps] = (float(l0),
                          {k: np.asarray(v) for k, v in t0.params.items()})
    return _SINGLE[steps]


def _trainer(plan=None, mesh=None, seed=7, **kw):
    from paddle_tpu.models import mnist as M

    pt.seed(seed)
    model = M.MnistMLP(hidden1=16, hidden2=8)
    return parallel.Trainer.supervised(
        model, optimizer.Adam(1e-3), M.loss_fn, mesh=mesh, plan=plan,
        **kw)


class TestTrainerCompression:
    def test_pure_dp_trajectory_parity(self, eight_devices):
        """THE parity gate: an int8-compressed pure-DP run tracks the
        single-device trajectory within tolerance (the shard_map step
        compiles the ring psum in)."""
        l0, p0 = _single_device_trajectory()
        tq = _trainer(plan=Plan(dp=8, grad_compression="int8"))
        assert tq._jit_step.compiled_via == "shard_map"
        for _ in range(4):
            lq, _ = tq.train_step(_batch())
        assert abs(l0 - float(lq)) < 5e-3, (l0, float(lq))
        for k in p0:
            np.testing.assert_allclose(p0[k], np.asarray(tq.params[k]),
                                       atol=2e-2)

    def test_fsdp_trajectory_parity(self, eight_devices):
        """Explicit plans ride the wire-format round-trip at the GSPMD
        reduce boundary — same parity contract, pjit compile path."""
        l0, _ = _single_device_trajectory()
        tq = _trainer(plan=Plan(dp=2, fsdp=4, min_shard_size=64,
                                grad_compression="int8"))
        assert tq._jit_step.compiled_via == "pjit"
        for _ in range(4):
            lq, _ = tq.train_step(_batch())
        assert abs(l0 - float(lq)) < 5e-3, (l0, float(lq))

    def test_trainer_knob_beats_plan_default(self, eight_devices):
        tq = _trainer(plan=Plan(dp=8), grad_compression="int8_sr")
        assert tq.grad_compression == "int8_sr"
        l, _ = tq.train_step(_batch())
        assert np.isfinite(float(l))

    def test_compression_needs_multi_device_plan(self):
        from paddle_tpu.core.enforce import EnforceError

        with pytest.raises(EnforceError, match="multi-device"):
            _trainer(mesh=pt.build_mesh(dp=1,
                                        devices=jax.devices()[:1]),
                     grad_compression="int8")

    def test_byte_counters_advance_per_step(self, eight_devices):
        """pt_collective_bytes_total{compressed=} advances by exactly
        the static per-step payload — the counter-verification the
        quant_comm bench leans on."""
        tq = _trainer(plan=Plan(dp=8, grad_compression="int8"))
        assert tq._comm_bytes[0] > 0   # something compresses
        telemetry.enable()
        try:
            m = QC._comm_metrics()
            v0 = m["bytes_int8"].value, m["bytes_fp32"].value
            b = _batch()
            tq.train_step(b)
            tq.train_step(b)
            assert m["bytes_int8"].value - v0[0] == 2 * tq._comm_bytes[0]
            assert m["bytes_fp32"].value - v0[1] == 2 * tq._comm_bytes[1]
        finally:
            telemetry.disable()

    def test_zero_cost_when_disabled(self, eight_devices, monkeypatch):
        """grad_compression=None compiles NO quant code — pin by making
        every compression entry point explode."""
        def boom(*a, **k):
            raise AssertionError("compression code reached while off")

        monkeypatch.setattr(QC, "quantized_pmean_tree", boom)
        monkeypatch.setattr(QC, "quantized_psum", boom)
        monkeypatch.setattr(QC, "compress_grads", boom)
        t = _trainer(plan=Plan(dp=8))
        l, _ = t.train_step(_batch())
        assert np.isfinite(float(l))

    def test_plan_describe_reports_compression(self, eight_devices):
        d = Plan(dp=8, grad_compression="int8").describe()
        assert d["grad_compression"] == "int8"
