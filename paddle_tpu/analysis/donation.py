"""Donation-safety analyzer — static provenance + aliasing checks for a
step about to be compiled with ``donate_argnums``.

The two worst bugs in this tree's history were donation bugs the
runtime only surfaced as intermittent heap corruption: the PR 6 SIGSEGV
(checkpoint-restored leaves ZERO-COPIED by the CPU PJRT client from
disk-loaded numpy temporaries, then DONATED by the next train step —
the runtime reused memory numpy still owned) and its snapshot-side twin
(``device_get`` views of live buffers saved while the step donated the
source). This module flags those classes *before the step runs*, as
typed :class:`..diagnostics.Diagnostic` errors.

Buffer-provenance classes (from PR 6):

- ``"numpy"``        — a host ``np.ndarray`` owning its data. Donating
  it is flagged: on the CPU backend the implicit ``device_put`` may
  zero-copy alias it, and donated state should be device-resident
  anyway.
- ``"host-view"``    — a host array that does NOT own its data
  (``device_get`` zero-copy views, slices). The most dangerous class:
  the donated buffer and the view share bytes.
- ``"host-backed"``  — a cpu-backend ``jax.Array`` *recorded* as
  zero-copying host memory (``note_transfer`` from ``Plan.place``, or
  anything created under :func:`track_host_transfers`).
- ``"owned"``        — recorded runtime-owned: the output of
  ``utils.memory.owned_on_device`` (the PR 6 fix — committed buffers
  the runtime allocated itself).
- ``"device"``       — a non-CPU-backend ``jax.Array``: the transfer
  copied host→HBM, always safe.
- ``"runtime"``      — a cpu ``jax.Array`` with no provenance record:
  the common safe case (any jnp computation result).

Provenance cannot be introspected from a live ``jax.Array`` (the CPU
client's zero-copy alias is invisible from the Python side), so it is
*recorded at the transfer site*: ``Plan.place`` notes its host→device
puts, ``owned_on_device`` notes its laundered copies, and
:func:`track_host_transfers` wraps ``jax.device_put`` /
``jax.make_array_from_callback`` for tests and forensics. Records live
in a ``WeakValueDictionary`` — they die with the array.

``Trainer.__init__`` runs :func:`check_donation` (provenance + alias
checks; no tracing) over its donated state once at compile time,
gated by ``FLAGS_static_verify`` — zero steady-state cost.
"""

from __future__ import annotations

import contextlib
import threading
import weakref
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from .diagnostics import Diagnostic

# id(array) -> (kind, weakref to the array). WeakValueDictionary drops
# the entry when the array dies, so a recycled id can never resolve to
# a stale kind; the kind string rides in a parallel dict pruned lazily.
_records: "weakref.WeakValueDictionary[int, Any]" = \
    weakref.WeakValueDictionary()
_kinds: dict = {}
_lock = threading.Lock()


def _note(x, kind: str) -> None:
    try:
        with _lock:
            _records[id(x)] = x
            _kinds[id(x)] = kind
            if len(_kinds) > 4 * (len(_records) + 64):
                # prune kinds whose arrays died (WeakValueDictionary
                # already dropped them)
                live = set(_records.keys())
                for k in list(_kinds):
                    if k not in live:
                        del _kinds[k]
    except TypeError:
        pass  # not weakref-able: nothing to record


def note_owned(x) -> Any:
    """Record ``x`` as runtime-owned (committed) — called by
    ``utils.memory.owned_on_device`` on its laundered copies."""
    _note(x, "owned")
    return x


def note_host_backed(x) -> Any:
    """Record ``x`` as a device array backed by host memory (the PR 6
    hazard class)."""
    _note(x, "host-backed")
    return x


def note_transfer(src, out) -> Any:
    """Record the provenance of one host→device transfer: when ``src``
    is a host array and ``out`` landed on the CPU backend, the client
    may have zero-copied — record ``out`` as host-backed until
    something launders it (``owned_on_device`` overrides the record).
    Non-fully-addressable results are NOT recorded: ``owned_on_device``
    deliberately passes them through unlaundered (it cannot copy leaves
    it only partially holds), so a record here would make the Trainer's
    compile-time check reject every multi-process placement."""
    import jax

    if (not isinstance(src, jax.Array)
            and isinstance(out, jax.Array) and _is_cpu(out)
            and getattr(out, "is_fully_addressable", True)):
        note_host_backed(out)
    return out


def _recorded_kind(x) -> Optional[str]:
    with _lock:
        got = _records.get(id(x))
        if got is not None and got is x:
            return _kinds.get(id(x))
    return None


def _is_cpu(x) -> bool:
    try:
        dev = next(iter(x.sharding.device_set))
    except Exception:
        return False
    return getattr(dev, "platform", None) == "cpu"


def classify_provenance(leaf) -> str:
    """Classify one leaf into the classes above (module docstring)."""
    import jax

    if isinstance(leaf, np.ndarray):
        if leaf.base is not None or not leaf.flags["OWNDATA"]:
            return "host-view"
        return "numpy"
    if not isinstance(leaf, jax.Array):
        return "numpy" if hasattr(leaf, "__array_interface__") else \
            "runtime"
    rec = _recorded_kind(leaf)
    if rec is not None:
        return rec
    if not _is_cpu(leaf):
        return "device"
    return "runtime"


@contextlib.contextmanager
def track_host_transfers():
    """Record host-backed provenance for every ``jax.device_put`` /
    ``jax.make_array_from_callback`` result created in the body (tests,
    forensic repros). Reentrant; patches module attributes, so confine
    to single-threaded setup code."""
    import jax

    orig_put = jax.device_put
    orig_cb = jax.make_array_from_callback

    def put(x, *args, **kwargs):
        out = orig_put(x, *args, **kwargs)
        try:
            jax.tree_util.tree_map(note_transfer, x, out)
        except Exception:
            pass  # structure mismatch (custom trees): skip recording
        return out

    def from_callback(shape, sharding, data_callback, *a, **kw):
        out = orig_cb(shape, sharding, data_callback, *a, **kw)
        # the callback's numpy results are zero-copy candidates on cpu
        if _is_cpu(out):
            note_host_backed(out)
        return out

    jax.device_put = put
    jax.make_array_from_callback = from_callback
    try:
        yield
    finally:
        jax.device_put = orig_put
        jax.make_array_from_callback = orig_cb


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def _leaves_with_paths(tree, prefix: str):
    import jax

    leaves_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in leaves_paths:
        name = prefix + jax.tree_util.keystr(path)
        out.append((name, leaf))
    return out


def _buffer_pointers(leaf) -> Tuple[int, ...]:
    """Best-effort backing-buffer addresses for alias detection: numpy
    data pointers and per-shard PJRT buffer pointers. Empty when the
    runtime doesn't expose them — the check degrades to identity."""
    import jax

    try:
        if isinstance(leaf, np.ndarray):
            return (leaf.__array_interface__["data"][0],)
        if isinstance(leaf, jax.Array) and getattr(
                leaf, "is_fully_addressable", False):
            return tuple(s.data.unsafe_buffer_pointer()
                         for s in leaf.addressable_shards)
    except Exception:
        pass
    return ()


_HAZARD_HINTS = {
    "numpy": "place the state on device (and through "
             "utils.memory.owned_on_device on the cpu backend) before "
             "donating it",
    "host-view": "copy the view to an owned array (np.array(x)) or "
                 "re-home it via utils.memory.owned_on_device",
    "host-backed": "launder through utils.memory.owned_on_device — the "
                   "cpu client zero-copied host memory into this "
                   "buffer (the PR 6 SIGSEGV class)",
}


def check_donation(args: Sequence[Any],
                   donate_argnums: Sequence[int],
                   fn=None, live: Any = None) -> List[Diagnostic]:
    """Static donation-safety check for ``fn(*args)`` compiled with
    ``donate_argnums``. ``fn`` is optional: with it, the step is traced
    once (``jax.make_jaxpr``) to flag donated-but-unused arguments;
    without it only the trace-free provenance + alias checks run (what
    the Trainer wires in at compile time). ``live`` is an optional
    pytree of buffers that must survive the step (staged prefetch
    batches, snapshot views) — a donated leaf aliasing one is an
    error. Nothing executes and nothing compiles."""
    import jax

    diags: List[Diagnostic] = []
    donate_set = set()
    for i in donate_argnums:
        j = int(i) + len(args) if int(i) < 0 else int(i)
        if 0 <= j < len(args):
            donate_set.add(j)
        else:
            diags.append(Diagnostic(
                code="PT-DON-103", severity="error",
                message=f"donate_argnums names argument {int(i)} but "
                        f"the step takes {len(args)}",
                hint="fix donate_argnums"))
    donate = sorted(donate_set)

    # -- provenance walk over donated leaves ----------------------------
    for i in donate:
        for name, leaf in _leaves_with_paths(args[i], f"arg{i}"):
            kind = classify_provenance(leaf)
            if kind in _HAZARD_HINTS:
                code = ("PT-DON-102" if kind == "host-view"
                        else "PT-DON-101")
                diags.append(Diagnostic(
                    code=code, severity="error", var=name,
                    message=f"donated leaf {name} is {kind}: donating "
                            f"hands memory the runtime does not own to "
                            f"the compiled step for reuse",
                    hint=_HAZARD_HINTS[kind]))

    # -- alias escapes: donated buffer reachable elsewhere --------------
    donated: List[Tuple[str, Any, Tuple[int, ...]]] = []
    others: List[Tuple[str, Any, Tuple[int, ...]]] = []
    for i, arg in enumerate(args):
        for name, leaf in _leaves_with_paths(arg, f"arg{i}"):
            if np.ndim(leaf) == 0 and not isinstance(leaf, np.ndarray):
                # eager scalars can legitimately be cached/shared by
                # the runtime; aliasing among them is not a hazard
                continue
            # pointers as a frozenset ONCE per leaf: the pairwise walk
            # below is O(P^2) and must not rebuild sets per comparison
            rec = (name, leaf, frozenset(_buffer_pointers(leaf)))
            (donated if i in donate else others).append(rec)
    if live is not None:
        for name, leaf in _leaves_with_paths(live, "live"):
            others.append((name, leaf,
                           frozenset(_buffer_pointers(leaf))))

    def _aliases(a, b) -> bool:
        (_, la, pa), (_, lb, pb) = a, b
        if la is lb:
            return True
        return bool(pa and pb and pa & pb)

    for j, rec in enumerate(donated):
        for other in donated[j + 1:]:
            if _aliases(rec, other):
                diags.append(Diagnostic(
                    code="PT-DON-104", severity="error", var=rec[0],
                    message=f"donated leaves {rec[0]} and {other[0]} "
                            f"share one buffer — the step would donate "
                            f"it twice",
                    hint="copy one of them before the call"))
        for other in others:
            if _aliases(rec, other):
                diags.append(Diagnostic(
                    code="PT-DON-104", severity="error", var=rec[0],
                    message=f"donated leaf {rec[0]} aliases {other[0]},"
                            f" which must survive the step — after "
                            f"donation that reference reads reused "
                            f"memory",
                    hint="copy the escaping reference (np.array / "
                         "jnp.copy) before donating"))

    # -- donated-but-unused (needs one trace) ---------------------------
    if fn is not None and donate:
        diags.extend(_check_unused(fn, args, donate))
    return diags


def _check_unused(fn, args, donate) -> List[Diagnostic]:
    import jax

    diags: List[Diagnostic] = []

    def absify(leaf):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return jax.ShapeDtypeStruct(np.shape(leaf), leaf.dtype)
        return leaf

    try:
        abs_args = jax.tree_util.tree_map(absify, tuple(args))
        closed = jax.make_jaxpr(lambda *a: fn(*a))(*abs_args)
    except Exception as e:
        diags.append(Diagnostic(
            code="PT-DON-103", severity="warning",
            message=f"could not trace the step for the unused-donation "
                    f"check: {type(e).__name__}: {e}",
            hint="pass concrete example args, or skip fn="))
        return diags
    # duck-typed Literal test (jax.core.Literal has moved between jax
    # releases): literals carry .val, Vars do not
    def is_var(v):
        return not hasattr(v, "val")

    used = set()
    for eqn in closed.jaxpr.eqns:
        used.update(id(v) for v in eqn.invars if is_var(v))
    used.update(id(v) for v in closed.jaxpr.outvars if is_var(v))
    invars = list(closed.jaxpr.invars)
    # map flat invars back to argnums by per-arg leaf counts
    counts = [len(jax.tree_util.tree_leaves(a)) for a in args]
    pos = 0
    for i, n in enumerate(counts):
        arg_vars = invars[pos:pos + n]
        pos += n
        if i not in donate or not arg_vars:
            continue
        unused = [v for v in arg_vars if id(v) not in used]
        if unused and len(unused) == len(arg_vars):
            diags.append(Diagnostic(
                code="PT-DON-103", severity="error",
                message=f"argument {i} is donated but the step never "
                        f"reads any of its {len(arg_vars)} leaf "
                        f"buffer(s) — the donation frees nothing and "
                        f"invalidates the caller's reference for no "
                        f"benefit",
                hint="drop it from donate_argnums"))
    return diags
