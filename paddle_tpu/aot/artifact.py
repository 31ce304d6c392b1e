"""AOT artifact: serialized compiled serving programs next to the
checkpoint.

The deployment unit here is the *compiled program*, not the Python
model (the reference's AnalysisPredictor stance, PAPER.md layer 8): an
artifact directory holds the ``jax.export``-serialized decode-step and
prefill-bucket executables of a warmed :class:`serving.BatchedDecoder`,
the weights/buffers snapshot they take as real arguments, and enough
host-side decoder config to rebuild the arena — so a serving replica
can boot from the artifact alone, without ever constructing (or
tracing through) the Python model object (``loader.load_decoder``).

Artifact layout (``aot_step_<N>`` next to the checkpoint's
``step_<N>``, or any standalone directory)::

    manifest.json        format, artifact id, compat fingerprint,
                         decoder config, program index, checksums,
                         plan shape, tuning-table snapshot
    state.npz            params + buffers (exotic dtypes bit-viewed)
    step_k<K>.jaxexp     serialized exported decode step (K tokens/dispatch)
    prefill_<LB>.jaxexp  serialized exported prefill, bucket length LB
    COMMITTED            written LAST in the staging dir (same two-phase
                         committed-write contract as checkpoint.py) —
                         an artifact is never observable torn

Compat: a serialized executable is only trusted under the producing
(jax, jaxlib, platform) triple — ``utils.compat.runtime_fingerprint``.
A mismatch raises :class:`AotCompatError`, which the serving bring-up
catches to fall back to the ordinary trace path (warn-once, typed
PT-AOT-601 diagnostic) rather than crash.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.enforce import EnforceError
from ..utils import compat as _compat
from ..utils.atomic import atomic_write_bytes, atomic_write_text

ARTIFACT_FORMAT = "paddle_tpu_aot/v1"
_MANIFEST = "manifest.json"
_STATE = "state.npz"
_COMMITTED = "COMMITTED"
# artifact dirs ride checkpoint naming: aot_step_<N> next to step_<N>
_AOT_RE = re.compile(r"^aot_step_(\d+)$")
_STEP_RE = re.compile(r"^step_(\d+)$")

# bit-view map for dtypes np.savez can't serialize natively — shared
# stance with checkpoint._EXOTIC (kept separate so an aot artifact
# never depends on checkpoint-module internals)
_EXOTIC = {"bfloat16": np.uint16, "float8_e4m3fn": np.uint8,
           "float8_e5m2": np.uint8}


class AotError(EnforceError):
    """Artifact unusable: missing, torn (no COMMITTED), checksum
    mismatch, or an unsupported decoder config at export."""


class AotCompatError(AotError):
    """Compat fingerprint mismatch: the artifact was produced under a
    different (jax, jaxlib, platform) triple. The serving bring-up
    treats this as "fall back to the trace path", never a crash."""


def _checksum(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def _require(cond, exc, msg: str, *args) -> None:
    """enforce() with a typed exception class — readers branch on
    AotError (skip/fallback) vs AotCompatError (trace-path fallback)."""
    if not cond:
        raise exc(msg % args if args else msg)


def _encode_state(mstate) -> tuple:
    """(params, buffers) dicts -> (npz arrays, per-key dtype meta).
    Exotic dtypes (bf16/f8) are stored bit-viewed; meta records the
    true dtype for the loader's inverse view."""
    params, buffers = mstate
    arrays: Dict[str, np.ndarray] = {}
    meta: Dict[str, Dict[str, Any]] = {}
    for prefix, d in (("p", params), ("b", buffers)):
        for k, v in d.items():
            key = f"{prefix}:{k}"
            arr = np.asarray(jax.device_get(v))
            dt = str(arr.dtype)
            meta[key] = {"dtype": dt}
            view = _EXOTIC.get(dt)
            arrays[key] = arr.view(view) if view is not None else arr
    return arrays, meta


def _decode_state(npz, meta) -> tuple:
    params: Dict[str, Any] = {}
    buffers: Dict[str, Any] = {}
    for key in npz.files:
        arr = npz[key]
        dt = meta.get(key, {}).get("dtype")
        if dt and _EXOTIC.get(dt) is not None:
            import ml_dtypes

            arr = arr.view(getattr(ml_dtypes, dt))
        prefix, _, name = key.partition(":")
        (params if prefix == "p" else buffers)[name] = jnp.asarray(arr)
    return params, buffers


def _tuning_snapshot() -> Dict[str, Any]:
    """Copy of the pallas tuning table at export time — the artifact
    records WHICH tuned blocks its programs were compiled with, so a
    perf drift after a table re-tune is attributable."""
    try:
        from ..ops.pallas import tuning as _tuning

        return dict(_tuning._load())
    except Exception:
        return {}


def _plan_shape() -> Dict[str, Any]:
    """Device topology the programs were exported under (the Plan shape
    of a serving replica: today single-replica SPMD over the local
    devices — recorded so a topology change reads as a compat event,
    not a silent mis-rehydrate)."""
    return {"device_count": jax.device_count(),
            "platform": jax.default_backend()}


def _sharding_strs(exported) -> Dict[str, List[str]]:
    """Best-effort input/output sharding record (observability — the
    rehydrated call re-applies them from the serialized program
    itself)."""
    out = {}
    for field in ("in_shardings_hlo", "out_shardings_hlo"):
        val = getattr(exported, field, None)
        if val is not None:
            out[field] = [str(s) for s in val]
    return out


def fingerprint() -> Dict[str, str]:
    """This process's compat fingerprint (funnels through
    ``utils.compat.runtime_fingerprint``)."""
    return _compat.runtime_fingerprint()


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def export_decoder(decoder, directory: str, *,
                   step: Optional[int] = None,
                   buckets: Optional[List[int]] = None,
                   model_tag: Optional[str] = None) -> str:
    """Serialize ``decoder``'s compiled serving programs into
    ``directory`` (two-phase committed write; returns the final path).

    Exports the decode-step executables for k in {1, decode_steps} (the
    SLO degrade lever needs the k=1 program next to the full-k one) and
    the prefill executables for every prompt bucket the decoder has
    compiled so far plus any explicitly requested ``buckets`` (prompt
    lengths; bucketed via the decoder's own rounding). The weights ride
    along in ``state.npz`` — the compiled programs are weight-free
    (weights are real arguments), so one artifact is both the program
    store and the serving weight snapshot.

    Unsupported (typed error, never a silent partial artifact):
    speculative decoding (draft model), chunked prefill, and the paged
    prefix cache — their extra executables are not serialized yet.
    """
    _require(decoder.draft is None, AotError,
             "aot export does not cover speculative decoding (the "
             "draft/verify executables are not serialized) — export a "
             "plain decoder")
    _require(decoder.prefill_chunk is None, AotError,
             "aot export does not cover chunked prefill — export a "
             "whole-bucket-prefill decoder")
    _require(not (decoder.paged and decoder.prefix_cache), AotError,
             "aot export does not cover the paged prefix cache (suffix/"
             "restep executables are not serialized)")
    exp_mod = jax.export
    gens = jnp.asarray(decoder._slot_gen.astype(np.uint32))

    blobs: Dict[str, bytes] = {}
    programs: Dict[str, Dict[str, str]] = {"steps": {}, "prefills": {}}
    shardings: Dict[str, Dict[str, List[str]]] = {}

    for kd in sorted({1, decoder.decode_steps}):
        fn = decoder._step_fns.get(kd)
        if fn is None:
            fn = decoder._step_fns[kd] = decoder._build_multi_step(kd)
        if decoder.paged:
            args = (decoder._mstate, decoder.pools,
                    jnp.asarray(decoder.table), decoder.tok, decoder.t,
                    gens)
        else:
            args = (decoder._mstate, decoder.caches, decoder.tok,
                    decoder.t, gens)
        exported = exp_mod.export(fn)(*args)
        fname = f"step_k{kd}.jaxexp"
        blobs[fname] = bytes(exported.serialize())
        programs["steps"][str(kd)] = fname
        shardings[fname] = _sharding_strs(exported)

    lbs = set()
    for key in decoder._prefill_cache:
        if decoder.paged and isinstance(key, tuple) and key[0] == "paged":
            lbs.add(int(key[1]))
        elif not decoder.paged and isinstance(key, int):
            lbs.add(key)
    for b in (buckets or ()):
        lbs.add(decoder._bucket_len(int(b)))
    # the router's warmup request always hits the smallest bucket —
    # cover it even on a never-warmed decoder
    lbs.add(decoder._bucket_len(1))
    for lb in sorted(lbs):
        padded = jnp.zeros((lb,), jnp.int32)
        if decoder.paged:
            fn = decoder._prefill_fn_paged(lb)
            row = jnp.zeros((decoder.n_log,), jnp.int32)
            args = (decoder._mstate, decoder.pools, row, padded, lb)
        else:
            fn = decoder._prefill_fn(lb)
            args = (decoder._mstate, decoder.caches, padded, lb, 0)
        exported = exp_mod.export(fn)(*args)
        fname = f"prefill_{lb}.jaxexp"
        blobs[fname] = bytes(exported.serialize())
        programs["prefills"][str(lb)] = fname
        shardings[fname] = _sharding_strs(exported)

    arrays, state_meta = _encode_state(decoder._mstate)

    attn_cfg: Dict[str, Any] = {"n_blocks": (
        len(decoder.pools) if decoder.paged else len(decoder.caches))}
    if decoder.paged:
        al = decoder._allocator
        attn_cfg.update(num_kv_heads=int(al.shape[2]),
                        head_dim=int(al.shape[3]))
        cache_spec = None
    else:
        # contiguous arenas: record each block's (k, v) leaf shapes so
        # the loader's model stub can mint identical zero arenas
        cache_spec = [[{"shape": list(leaf.shape),
                        "dtype": str(leaf.dtype)}
                       for leaf in jax.tree_util.tree_leaves(c)]
                      for c in decoder.caches]
    sampled_key = None
    if decoder.sampled:
        # the in-device pick chain baked the key into the exported
        # step; the HOST pick at activation needs the same key object
        try:
            sampled_key = np.asarray(
                jax.random.key_data(decoder.key)).tolist()
        except Exception:
            sampled_key = None
    decoder_cfg = {
        "slots": decoder.slots, "capacity": decoder.capacity,
        "prompt_bucket": decoder.bucket,
        "eos_id": decoder.eos_id,
        "temperature": decoder.temperature, "top_k": decoder.top_k,
        "top_p": decoder.top_p,
        "decode_steps": decoder.decode_steps,
        "paged": decoder.paged,
        "pages": (decoder._allocator.pages if decoder.paged else None),
        "page_size": (decoder.page_size if decoder.paged else None),
        "kv_dtype": (decoder._allocator.kv_dtype if decoder.paged
                     else None),
        "sampled_key": sampled_key,
        "cache_spec": cache_spec,
        **attn_cfg,
    }

    manifest = {
        "format": ARTIFACT_FORMAT,
        "step": step,
        "model_tag": model_tag,
        "fingerprint": fingerprint(),
        "plan": _plan_shape(),
        "tuning": _tuning_snapshot(),
        "decoder": decoder_cfg,
        "programs": programs,
        "shardings": shardings,
        "state_meta": state_meta,
        "checksums": {f: _checksum(b) for f, b in blobs.items()},
    }
    manifest["artifact_id"] = _checksum(json.dumps(
        {k: manifest[k] for k in ("fingerprint", "decoder", "checksums")},
        sort_keys=True).encode())[:16]
    text = json.dumps(manifest, indent=1)

    # two-phase committed write: every byte lands in the staging dir,
    # COMMITTED (carrying the manifest checksum) goes LAST, then ONE
    # atomic rename publishes marker and payload together — a reader
    # either sees a complete artifact or none (checkpoint.py contract)
    directory = os.path.abspath(directory)
    tmp = directory + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    for fname, data in blobs.items():
        atomic_write_bytes(os.path.join(tmp, fname), data)
    with open(os.path.join(tmp, _STATE), "wb") as f:
        np.savez(f, **arrays)
    atomic_write_text(os.path.join(tmp, _MANIFEST), text)
    atomic_write_text(
        os.path.join(tmp, _COMMITTED),
        json.dumps({"format": ARTIFACT_FORMAT,
                    "manifest_checksum": _checksum(text.encode())}))
    if os.path.isdir(directory):
        trash = directory + ".old"
        if os.path.exists(trash):
            shutil.rmtree(trash)
        os.rename(directory, trash)
        os.replace(tmp, directory)
        shutil.rmtree(trash, ignore_errors=True)
    else:
        os.replace(tmp, directory)
    return directory


# ---------------------------------------------------------------------------
# read side
# ---------------------------------------------------------------------------

def read_manifest(directory: str) -> Dict[str, Any]:
    """Parse + verify an artifact's manifest. Typed :class:`AotError`
    on a missing/torn/corrupt artifact (COMMITTED absent, checksum
    mismatch, wrong format) — the bench's skip-cause path and the
    serving fallback both key off this."""
    _require(os.path.isdir(directory), AotError,
             "aot artifact %s: no such directory", directory)
    cpath = os.path.join(directory, _COMMITTED)
    _require(os.path.exists(cpath), AotError,
             "aot artifact %s is torn: COMMITTED marker absent (export "
             "died mid-write; the artifact must be ignored)", directory)
    try:
        with open(cpath) as f:
            commit = json.load(f)
        with open(os.path.join(directory, _MANIFEST)) as f:
            text = f.read()
    except (OSError, ValueError) as e:
        raise AotError(f"aot artifact {directory}: unreadable "
                       f"manifest/commit record ({e})")
    _require(
        _checksum(text.encode()) == commit.get("manifest_checksum"),
        AotError,
        "aot artifact %s: manifest checksum mismatch vs COMMITTED "
        "(corrupt or hand-edited artifact)", directory)
    man = json.loads(text)
    _require(man.get("format") == ARTIFACT_FORMAT, AotError,
             "aot artifact %s: format %r, this build reads %r",
             directory, man.get("format"), ARTIFACT_FORMAT)
    return man


def check_fingerprint(manifest: Dict[str, Any],
                      directory: str = "<artifact>") -> None:
    """Raise :class:`AotCompatError` unless the artifact's producing
    toolchain matches this process."""
    want = manifest.get("fingerprint") or {}
    have = fingerprint()
    drift = {k: (want.get(k), have.get(k)) for k in
             sorted(set(want) | set(have))
             if want.get(k) != have.get(k)}
    if drift:
        raise AotCompatError(
            f"aot artifact {directory}: compat fingerprint mismatch "
            + ", ".join(f"{k}: artifact={w!r} vs runtime={h!r}"
                        for k, (w, h) in drift.items())
            + " — serialized executables are only trusted under the "
            "producing toolchain; falling back to the trace path")


def load_state(directory: str, manifest: Dict[str, Any]) -> tuple:
    """The artifact's (params, buffers) snapshot as jax arrays."""
    with np.load(os.path.join(directory, _STATE)) as npz:
        return _decode_state(npz, manifest.get("state_meta", {}))


def load_programs(directory: str, manifest: Dict[str, Any]):
    """Deserialize every exported program (checksum-verified) ->
    ``(step_fns: {k: callable}, prefill_fns: {lb: callable})``. Each
    callable is ``jax.jit(exported.call)`` — jit-wrapped ONCE so the
    serving loop's per-tick dispatch hits the jit cache instead of
    re-staging the call primitive — with the arena DONATED, as the
    traced programs it stands in for have it (``serving._arena_jit``):
    an exported module does not carry its source's donation, the jit
    around its call states it again. The arena is argument 1 of every
    exported program (step and prefill, contiguous and paged)."""
    exp_mod = jax.export
    checks = manifest.get("checksums", {})

    def _one(fname):
        try:
            with open(os.path.join(directory, fname), "rb") as f:
                data = f.read()
        except OSError as e:
            raise AotError(f"aot artifact {directory}: missing program "
                           f"{fname} ({e})")
        _require(_checksum(data) == checks.get(fname), AotError,
                 "aot artifact %s: checksum mismatch on %s (torn or "
                 "corrupt program blob)", directory, fname)
        exported = exp_mod.deserialize(bytearray(data))
        return jax.jit(exported.call, donate_argnums=(1,))

    progs = manifest["programs"]
    step_fns = {int(k): _one(f) for k, f in progs["steps"].items()}
    prefill_fns = {int(k): _one(f) for k, f in progs["prefills"].items()}
    return step_fns, prefill_fns


# ---------------------------------------------------------------------------
# checkpoint-adjacent placement + selection
# ---------------------------------------------------------------------------

def artifact_dir_for_step(root: str, step: int) -> str:
    """Canonical artifact path for checkpoint step N: ``aot_step_<N>``
    next to ``step_<N>`` (GC in checkpoint.CheckpointManager prunes the
    pair together)."""
    return os.path.join(root, f"aot_step_{int(step)}")


def _is_committed(path: str) -> bool:
    return os.path.exists(os.path.join(path, _COMMITTED))


def latest_artifact(root: str) -> Optional[str]:
    """Newest COMMITTED ``aot_step_<N>`` under ``root`` whose
    checkpoint step is still alive. An artifact whose ``step_<N>`` dir
    was GC'd (or never committed) is NEVER selected — a stale program
    over deleted weights is exactly the torn state the committed
    two-phase path exists to prevent. Standalone artifacts (exported
    with no ``step=``, any directory name) are addressed by path, not
    through this selector."""
    try:
        names = os.listdir(root)
    except OSError:
        return None
    steps = []
    for name in names:
        m = _AOT_RE.match(name)
        if m:
            steps.append(int(m.group(1)))
    for s in sorted(steps, reverse=True):
        apath = os.path.join(root, f"aot_step_{s}")
        if not _is_committed(apath):
            continue
        spath = os.path.join(root, f"step_{s}")
        if not os.path.exists(os.path.join(spath, "COMMITTED")):
            continue  # checkpoint gone/torn: stale artifact, skip
        return apath
    return None


def resolve_artifact(path: str) -> str:
    """``--from-artifact`` argument -> concrete artifact directory: a
    direct artifact dir passes through; a checkpoint root resolves via
    :func:`latest_artifact`. Typed :class:`AotError` when nothing
    selectable exists."""
    path = os.path.abspath(path)
    if os.path.exists(os.path.join(path, _MANIFEST)) or \
            os.path.exists(os.path.join(path, _COMMITTED)):
        return path
    got = latest_artifact(path)
    _require(got is not None, AotError,
             "no committed aot artifact under %s (no aot_step_<N> with "
             "a live checkpoint step; export one with "
             "aot.export_decoder)", path)
    return got
