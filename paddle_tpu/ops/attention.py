"""Attention ops.

The reference has no attention op — it composes matmul+softmax in python
(reference: python/paddle/fluid/nets.py:343 scaled_dot_product_attention).
Here attention is first-class: an XLA path (compiler-fused) and a Pallas
flash-attention path for long sequences (paddle_tpu.ops.pallas.flash_attention)
selected automatically on TPU.

Layout convention: (batch, seq, heads, head_dim) — "BTHD".
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.enforce import enforce


def scaled_dot_product_attention(q, k, v, mask=None, causal: bool = False,
                                 dropout_p: float = 0.0, dropout_key=None,
                                 scale: Optional[float] = None,
                                 use_flash: bool = True,
                                 segment_ids=None,
                                 window: Optional[int] = None):
    """q: (B, Tq, H, D), k/v: (B, Tk, H, D) → (B, Tq, H, D).

    mask: broadcastable to (B, H, Tq, Tk); True/1 = keep, False/0 = mask out.
    segment_ids: (B, T) int ids for packed batches (self-attention only);
    positions attend within their own segment. Composes with causal/mask.
    window: sliding-window/local attention — attend only keys within
    ``window - 1`` positions (lookback-only when causal, symmetric band
    otherwise); the flash kernel SKIPS out-of-band blocks (O(T*window)
    compute, the long-context local-attention pattern).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    enforce(segment_ids is None or q.shape[1] == k.shape[1],
            "segment_ids requires self-attention shapes (tq=%s != tk=%s)",
            q.shape[1], k.shape[1])
    enforce(window is None or window >= 1,
            "window must be >= 1, got %s", window)
    if use_flash and (dropout_p == 0.0 or dropout_key is not None):
        # key-padding masks (the broadcast (B, 1, 1, Tk) form every
        # ragged-batch model emits) ride the flash kernel; anything else
        # falls back to XLA — including 2D masks, whose historical
        # broadcast semantics are per-QUERY (Tq, Tk), right-aligned
        # against the (B, H, Tq, Tk) logits; promoting a (B, Tk)-shaped
        # one to key-padding would silently change meaning when B == Tq.
        # Attention-probability dropout runs INSIDE the kernel (in-kernel
        # counter-based mask) — the training configs with dropout keep
        # the no-HBM-scores property instead of falling back.
        kv_mask = _as_kv_mask(mask, q.shape[0], k.shape[1])
        if mask is None or kv_mask is not None:
            if _flash_ok(q, k, causal, window=window):
                return _get_flash()(
                    q, k, v, causal=causal, scale=scale, kv_mask=kv_mask,
                    segment_ids=segment_ids, dropout_p=dropout_p,
                    dropout_key=dropout_key, window=window)
    return xla_attention(q, k, v, mask=mask, causal=causal,
                         dropout_p=dropout_p, dropout_key=dropout_key,
                         scale=scale, segment_ids=segment_ids,
                         window=window)


def _as_kv_mask(mask, b: int, tk: int):
    """Normalize a keep-mask to the (B, Tk) key-padding form, or None if
    it constrains per-head/per-query and must stay on the XLA path.
    Only the explicit (B, 1, 1, Tk) broadcast form qualifies — a bare 2D
    mask means per-query (Tq, Tk) under the documented right-aligned
    broadcast, never key padding."""
    if mask is None:
        return None
    if mask.ndim == 4 and mask.shape[0] in (1, b) and mask.shape[1] == 1 \
            and mask.shape[2] == 1 and mask.shape[3] == tk:
        import jax.numpy as _jnp

        return _jnp.broadcast_to(mask[:, 0, 0, :], (b, tk))
    return None


def xla_attention(q, k, v, mask=None, causal: bool = False,
                  dropout_p: float = 0.0, dropout_key=None,
                  scale: Optional[float] = None, segment_ids=None,
                  window: Optional[int] = None):
    """Reference XLA implementation — materializes (B, H, Tq, Tk) scores."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if k.shape[2] != q.shape[2]:
        # GQA/MQA: expand the shared K/V heads (kv-major, matching the
        # flash kernel's head -> head // group mapping)
        group = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    if window is not None:
        enforce(window >= 1, "window must be >= 1, got %s", window)
        tq, tk = q.shape[1], k.shape[1]
        rows = jnp.arange(tq)[:, None] + (tk - tq)  # offset-aligned rows
        cols = jnp.arange(tk)[None, :]
        band = rows - cols < window
        if not causal:
            band = band & (cols - rows < window)
        mask = band if mask is None else (mask.astype(jnp.bool_) & band)
    if segment_ids is not None:
        ids = segment_ids
        seg = (ids[:, None, :, None] == ids[:, None, None, :])
        mask = seg if mask is None else (mask.astype(jnp.bool_) & seg)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    neg = jnp.finfo(logits.dtype).min
    keep = None
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        keep = jnp.tril(jnp.ones((tq, tk), jnp.bool_), tk - tq)
        logits = jnp.where(keep, logits, neg)
    if mask is not None:
        mask = mask.astype(jnp.bool_)
        keep = mask if keep is None else (keep & mask)
        logits = jnp.where(mask, logits, neg)
    probs = jax.nn.softmax(logits, axis=-1)
    if keep is not None:
        # rows with no valid key output zeros (flash-kernel convention),
        # not a uniform average of V
        any_valid = jnp.any(jnp.broadcast_to(keep, logits.shape), -1,
                            keepdims=True)
        probs = jnp.where(any_valid, probs, 0.0)
    if dropout_p > 0.0:
        enforce(dropout_key is not None, "attention dropout requires a key")
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(probs.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _get_flash():
    from .pallas.flash_attention import flash_attention

    return flash_attention


_FORCE_FLASH = False

# head dims both Pallas kernels support — ONE list so the decode and
# training dispatch gates never desynchronize
_FLASH_HEAD_DIMS = (64, 128, 256)


class force_flash:
    """Context manager: route eligible shapes to the flash kernel even
    off-TPU (interpret mode). For tests that must exercise the Pallas
    dispatch + partitioning path on the virtual CPU mesh — production
    dispatch stays backend-gated.

    CAVEAT (trace-time flag, jit cache): the flag is read when a
    function is TRACED, not when it is called — a function first jitted
    inside this context keeps the flash path via jax's jit cache after
    the context exits (and one jitted outside keeps the XLA path inside
    it). Tests that flip the flag must trace fresh functions (or call
    ``.clear_cache()`` on the jitted fn) on each side of the toggle.
    The flag is also process-global, not thread-local — don't toggle it
    concurrently from multiple threads."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled

    def __enter__(self):
        global _FORCE_FLASH
        self._prev = _FORCE_FLASH
        _FORCE_FLASH = self.enabled
        return self

    def __exit__(self, *exc):
        global _FORCE_FLASH
        _FORCE_FLASH = self._prev
        return False


def yarn_frequencies(half: int, theta: float, factor: float,
                     original_max_position: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0):
    """YaRN's rotary frequencies (Peng et al., arXiv:2309.00071, "NTK by
    parts"), (half,) float32: pair ``i`` turns at ``theta^(-i / half)``
    where it makes more than ``beta_fast`` turns over the
    ``original_max_position`` positions the model was trained on, at
    that over ``factor`` (positions interpolated) where it makes fewer
    than ``beta_slow``, and at a linear blend between the two pairs
    where those counts fall. The attention's own temperature
    (``mscale``) is the caller's: it scales scores, not angles."""
    import math

    def pair_of(turns):     # the pair that makes ``turns`` turns
        return half * math.log(original_max_position
                               / (turns * 2 * math.pi)) / math.log(theta)

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), half - 1)
    plain = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotary_embedding(x, positions, theta: float = 10000.0, yarn=None,
                     rotary_dim: Optional[int] = None,
                     attention_factor: float = 1.0):
    """Rotary position embedding (RoPE) over (B, T, H, D) with even D.

    ``positions``: (T,) or (B, T) integer absolute positions — decode
    passes the cache index, sequence-parallel callers pass GLOBAL
    positions (rotation happens on the pre-shard arrays, so sharded
    attention sees position-correct q/k). Rotate-half convention
    (GPT-NeoX/Llama): pairs are (x[..., i], x[..., i + D/2]).
    ``yarn``: None, or the keyword arguments of
    :func:`yarn_frequencies` (``factor``, ``original_max_position``,
    ``beta_fast``, ``beta_slow``) for a context extended that way.
    ``rotary_dim``: the LEADING part of each head that turns (even, at
    most D; None: all of it): its pairs are (x[..., i], x[..., i +
    rotary_dim/2]) at the frequencies of a head that wide, and the rest
    of the head passes as it is (a partial rotary factor).
    ``attention_factor``: cosines and sines are multiplied by it (YaRN's
    temperature where a model applies it there and not to the scores):
    the rotated part comes out that much longer, the rest does not.

    Green-field (the reference era predates RoPE; its positional story
    is learned position tables, reference:
    python/paddle/fluid/layers/nn.py position_encoding role).
    """
    d = x.shape[-1] if rotary_dim is None else int(rotary_dim)
    enforce(d % 2 == 0 and 0 < d <= x.shape[-1], "rotary needs an even "
            "width of at most the head's %s, got %s", x.shape[-1], d)
    if d < x.shape[-1]:
        turned = rotary_embedding(x[..., :d], positions, theta, yarn,
                                  attention_factor=attention_factor)
        return jnp.concatenate([turned, x[..., d:]], axis=-1)
    half = d // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = yarn_frequencies(half, theta, **dict(yarn))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., T, half)
    # insert the head axis before the feature axis; (T, half) inputs
    # broadcast over batch AND heads, (B, T, half) over heads only
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    if attention_factor != 1.0:
        cos, sin = cos * attention_factor, sin * attention_factor
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def _get_flash_decode():
    from .pallas.flash_decode import flash_decode

    return flash_decode


def decode_flash_ok(capacity: int, d: int,
                    pool_dtype: str = "f32",
                    page_size: Optional[int] = None) -> bool:
    """Dispatch gate for the single-position decode kernel
    (pallas/flash_decode.py): TPU backend (or force_flash), supported
    head dim, block-divisible cache capacity. A separate gate from
    flash_shape_ok — decode shapes (tq=1 against a fixed capacity)
    never satisfy the training kernel's block rules. ``pool_dtype``
    keys the tuned verdict per KV storage form ("f32" | "int8" — the
    int8 paged variant dequantizes in-kernel and has its own measured
    winner). ``page_size``: for paged pools the page IS the kernel
    block, fixed by the deployed pool rather than chosen at dispatch —
    a tuned entry carrying per-page verdicts (``use_flash_by_page``,
    tools/pallas_tune.py) answers for THAT page size; the aggregate
    ``use_flash`` (measured at the tuner's best page) only decides
    when the deployed page was never swept."""
    if not _FORCE_FLASH and jax.default_backend() != "tpu":
        return False
    from .pallas.flash_decode import decode_block_k

    if d not in _FLASH_HEAD_DIMS or decode_block_k(capacity) is None:
        return False
    from .pallas.tuning import get_tuned_decode

    tuned = get_tuned_decode(capacity, d, pool_dtype)
    if tuned is None:
        return True
    by_page = tuned.get("use_flash_by_page")
    if page_size is not None and by_page is not None:
        verdict = by_page.get(str(page_size))
        if verdict is not None:
            return bool(verdict)
    return tuned.get("use_flash", True)


def flash_operand_dtype(dtype):
    """The type q/k/v of ``dtype`` reach the flash kernels in under the
    active policy: its compute type where that is bfloat16 and narrower
    (mixed_bf16: f32 activations, bf16 matmuls, as every ``Linear``
    multiplies), else ``dtype`` itself. Read from what is observed — the
    ``float32`` and ``bfloat16`` policies narrow nothing, and neither
    does ``mixed_fp16``: Mosaic refuses float16 operands (compiled for a
    described v5e), so those kernels stay f32. ``flash_attention()``
    casts by it; the dispatch gate and the block table are keyed by it."""
    from ..core.dtypes import get_policy, to_dtype

    dtype = jnp.dtype(dtype)
    compute = to_dtype(get_policy().compute_dtype)
    if (compute == jnp.bfloat16 and jnp.issubdtype(dtype, jnp.floating)
            and compute.itemsize < dtype.itemsize):
        return compute
    return dtype


def _flash_ok(q, k, causal: bool = False, window=None) -> bool:
    """Flash kernel constraints for (B, T, H, D) operands — see
    flash_shape_ok for the actual gate."""
    return flash_shape_ok(q.shape[1], k.shape[1], q.shape[-1],
                          causal=causal, window=window,
                          dtype=flash_operand_dtype(q.dtype))


def flash_shape_ok(tq, tk, d, causal: bool = False, window=None,
                   dtype=jnp.float32) -> bool:
    """Flash kernel constraints: TPU backend, block-divisible seq lens,
    supported head dim — and the autotuner's measured verdict when one
    exists (tools/pallas_tune.py records use_flash=False for shape
    buckets where the XLA fallback won on-chip). Shape-level so the
    ring-attention dispatch (parallel/context_parallel.py) can gate on
    its PER-SHARD (t/sp) block shape. ``dtype``: the type q/k/v reach
    the kernel in (the table is keyed by it)."""
    if not _FORCE_FLASH and jax.default_backend() != "tpu":
        return False
    # 64-divisible seqs use block=64 (the tuner measures that shape too:
    # tools/pallas_tune.py short-seq fallback); the measured use_flash
    # verdict below still decides whether the kernel actually wins there
    if not (tq % 64 == 0 and tk % 64 == 0 and d in _FLASH_HEAD_DIMS):
        return False
    if window is not None and window < tk:
        # tuned verdicts are measured at DENSE attention; banded flash
        # skips out-of-band blocks (O(T*window)) while the XLA fallback
        # stays O(T^2) — a dense use_flash=False must not veto it.
        # window >= tk is dense in disguise: fall through to the verdict
        return True
    from .pallas.tuning import attention_key, get_tuned

    tuned = get_tuned(attention_key(tq, tk, d, causal, dtype=dtype))
    if tuned is not None and not tuned.get("use_flash", True):
        return False
    return True
