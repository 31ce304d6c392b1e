"""int8 quantized matmul — tiled Pallas TPU kernel with fused dequant.

Capability role: the reference's int8 inference stack (operators/
{quantize,dequantize,requantize}_op.cc + mkldnn int8 kernels + contrib/
int8_inference) runs quantized GEMMs on the CPU backend. The TPU-native
form: int8 A (activations, per-tensor scale) x int8 B (weights, per-tensor
or per-channel scale) accumulate in int32 on the MXU, dequantize to the
output dtype INSIDE the kernel epilogue — weights stay int8 in HBM (4x
smaller than fp32, half of bf16), and the dequant never materializes an
fp32 copy of B.

``quant_matmul`` picks the Pallas kernel on TPU and an XLA
preferred_element_type=int32 path elsewhere (same numerics — the tests
assert exact agreement, int8 math is exact in int32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ...core.enforce import enforce


def _spec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _kernel(a_ref, b_ref, scale_ref, o_ref, acc_ref, *, k_tiles):
    """One (TM, TN) output tile: loop over K tiles accumulating int32 on
    the MXU; dequant epilogue on the last K step."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]  # (TM, TK) int8
    b = b_ref[...]  # (TK, TN) int8
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == k_tiles - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * scale_ref[...]).astype(o_ref.dtype)   # (1, TN) row


def _pallas_quant_matmul(a_i8, b_i8, a_scale, b_scale, *, out_dtype,
                         tile_m: int, tile_n: int, tile_k: int,
                         interpret: bool):
    m, k = a_i8.shape
    k2, n = b_i8.shape
    grid = (m // tile_m, n // tile_n, k // tile_k)
    # ONE (1, N) f32 row of combined scales (a_scale is (1,), b_scale
    # (N,)): a 1-D operand tiles T(1024) in XLA's layout but T(128) in
    # Mosaic's, which the TPU compiler refuses; a 2-D row with a
    # (1, tile_n) block has one layout in both
    scale_row = (a_scale * b_scale).reshape(1, n)
    kernel = functools.partial(_kernel, k_tiles=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _spec((tile_m, tile_k), lambda i, j, kk: (i, kk)),
            _spec((tile_k, tile_n), lambda i, j, kk: (kk, j)),
            _spec((1, tile_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=_spec((tile_m, tile_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.int32)],
        interpret=interpret,
    )(a_i8, b_i8, scale_row)


def _qm_impl(a_i8, b_i8, a_scale_arr, b_scale_vec, *, out_dtype, tile_m,
             tile_n, tile_k, interpret):
    """Unpadded (global or per-shard) kernel invocation: pad to the tile
    grid (exact in integer math), run, slice back. Runs per shard under
    the partitioned call, so local shapes pad independently."""
    m, ka = a_i8.shape
    n = b_i8.shape[1]

    def _pad_to(arr, mult, axis):
        r = (-arr.shape[axis]) % mult
        if r == 0:
            return arr
        widths = [(0, 0)] * arr.ndim
        widths[axis] = (0, r)
        return jnp.pad(arr, widths)

    tm, tn, tk = min(tile_m, m), min(tile_n, n), min(tile_k, ka)
    a_p = _pad_to(_pad_to(a_i8, tm, 0), tk, 1)
    b_p = _pad_to(_pad_to(b_i8, tk, 0), tn, 1)
    bs_p = _pad_to(b_scale_vec, tn, 0)
    out = _pallas_quant_matmul(
        a_p, b_p, a_scale_arr, bs_p, out_dtype=out_dtype,
        tile_m=tm, tile_n=tn, tile_k=tk, interpret=interpret)
    return out[:m, :n]


@functools.lru_cache(maxsize=None)
def _partitioned_qm(out_dtype, tile_m, tile_n, tile_k, interpret):
    """custom_partitioning wrapper: the SPMD partitioners have no rule
    for a Pallas custom call and would all-gather the operands under
    pjit (same gap the flash kernel closed — see
    flash_attention.py). int8 GEMM shards over M (dp batch) and N
    (column-parallel weights, per-channel scales riding along); K and
    the scalar scale stay replicated."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    impl = functools.partial(_qm_impl, out_dtype=jnp.dtype(out_dtype),
                             tile_m=tile_m, tile_n=tile_n, tile_k=tile_k,
                             interpret=interpret)
    wrapped = custom_partitioning(lambda *args: impl(*args))

    def _axes_set(x):
        if x is None:
            return set()
        return set(x) if isinstance(x, tuple) else {x}

    def _shardings(mesh, a_sh, b_sh):
        msh = getattr(a_sh, "mesh", None) or mesh
        a_spec = tuple(a_sh.spec) + (None,) * (2 - len(tuple(a_sh.spec)))
        b_spec = tuple(b_sh.spec) + (None,) * (2 - len(tuple(b_sh.spec)))
        mx, nx = a_spec[0], b_spec[1]
        if _axes_set(mx) & _axes_set(nx):
            # e.g. FSDP-style weights sharded over the same axis as the
            # batch: one mesh axis cannot shard two output dims — keep
            # the batch sharding, re-replicate the weights' columns
            nx = None
        args = (NamedSharding(msh, P(mx, None)),
                NamedSharding(msh, P(None, nx)),
                NamedSharding(msh, P(None)),
                NamedSharding(msh, P(nx)))
        return msh, args, NamedSharding(msh, P(mx, nx))

    def partition(mesh, arg_shapes, result_shape):
        a_sh, b_sh = arg_shapes[0].sharding, arg_shapes[1].sharding
        if hasattr(a_sh, "spec") and hasattr(b_sh, "spec"):
            msh, arg_sh, res_sh = _shardings(mesh, a_sh, b_sh)
        else:  # opaque shardings inside a manual region: echo (see flash)
            msh = mesh
            arg_sh = tuple(s.sharding for s in arg_shapes)
            res_sh = result_shape.sharding

        def lower_fn(*args):
            return impl(*args)

        return msh, lower_fn, res_sh, arg_sh

    def infer_sharding_from_operands(mesh, arg_shapes, shape):
        a_sh, b_sh = arg_shapes[0].sharding, arg_shapes[1].sharding
        if not (hasattr(a_sh, "spec") and hasattr(b_sh, "spec")):
            return NamedSharding(mesh, P())
        return _shardings(mesh, a_sh, b_sh)[2]

    wrapped.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer_sharding_from_operands,
        sharding_rule="m k, k n, s, n -> m n",
        need_replication_factors=("k", "s"))
    return wrapped


def quant_matmul(a_i8, b_i8, a_scale, b_scale, *, out_dtype=jnp.float32,
                 tile_m: int = None, tile_n: int = None, tile_k: int = None,
                 use_pallas: bool = None, interpret: bool = False):
    """``dequant(a_i8 @ b_i8)``: int32 MXU accumulation, fused epilogue.

    a_i8 (M, K) int8 with scalar ``a_scale``; b_i8 (K, N) int8 with scalar
    or per-channel (N,) ``b_scale``. Returns (M, N) ``out_dtype``.
    Any shapes: when the kernel path runs, operands pad internally to the
    tile grid (exact in integer math) and the result slices back. Tile
    sizes default to the autotuned table (tuning.py) then 128^3.
    """
    m, ka = a_i8.shape
    kb, n = b_i8.shape
    enforce(ka == kb, "inner dims differ: %s vs %s", ka, kb)
    enforce(a_i8.dtype == jnp.int8 and b_i8.dtype == jnp.int8,
            "quant_matmul takes int8 operands, got %s/%s", a_i8.dtype,
            b_i8.dtype)
    # symbolic dims (jax.export batch-polymorphic serving artifacts)
    # can't bucket into the tuned table or feed a pallas grid — those
    # traces take the XLA dot_general path unconditionally (the Pallas
    # kernel is a runtime dispatch choice, not an artifact property)
    static_shape = all(isinstance(d, int) for d in (m, n, ka))
    if not static_shape:
        use_pallas = False
        interpret = False
    tuned = {}
    if static_shape and (tile_m is None or tile_n is None
                         or tile_k is None):
        from .tuning import get_tuned, matmul_key

        tuned = get_tuned(matmul_key(m, n, ka)) or {}
    tile_m = tile_m or tuned.get("tile_m", 128)
    tile_n = tile_n or tuned.get("tile_n", 128)
    tile_k = tile_k or tuned.get("tile_k", 128)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if (use_pallas or interpret) and min(m, n, ka) > 0:
        # padding/tiling happens per shard inside the partitioned call
        # (callers never manage the tiling contract themselves)
        fn = _partitioned_qm(jnp.dtype(out_dtype).name, int(tile_m),
                             int(tile_n), int(tile_k), bool(interpret))
        return fn(a_i8, b_i8,
                  jnp.asarray(a_scale, jnp.float32).reshape(1),
                  jnp.broadcast_to(jnp.asarray(b_scale, jnp.float32),
                                   (n,)))
    acc = jax.lax.dot_general(a_i8, b_i8, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    scale = jnp.asarray(a_scale, jnp.float32) * \
        jnp.broadcast_to(jnp.asarray(b_scale, jnp.float32), (n,))
    return (acc.astype(jnp.float32) * scale[None, :]).astype(out_dtype)


def quantize_tensor(x, *, per_channel_axis=None):
    """Symmetric int8 quantization: returns (x_i8, scale). Per-channel
    along ``per_channel_axis`` (weights), per-tensor otherwise
    (activations) — reference quantize_op.cc abs-max convention."""
    if per_channel_axis is None:
        scale = jnp.max(jnp.abs(x)) / 127.0
        scale = jnp.maximum(scale, 1e-10)
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        return q, scale
    axes = tuple(i for i in range(x.ndim) if i != per_channel_axis)
    scale = jnp.max(jnp.abs(x), axis=axes) / 127.0
    scale = jnp.maximum(scale, 1e-10)
    shape = [1] * x.ndim
    shape[per_channel_axis] = -1
    q = jnp.clip(jnp.round(x / scale.reshape(shape)), -127,
                 127).astype(jnp.int8)
    return q, scale
