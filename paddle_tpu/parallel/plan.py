"""Declarative sharding plans — dp/fsdp/tp compilation for models bigger
than one chip.

The reference's distributed story rewrote the program per cluster role
(reference: transpiler/distribute_transpiler.py:164); the TensorFlow
paper's dataflow/placement split (PAPERS.md) is the design here: a
:class:`Plan` *declares* how state and data map onto a named mesh, and
:func:`compile_step` turns any pure step function into one partitioned
XLA executable — ``pjit`` with full ``in_shardings``/``out_shardings``/
``donate_argnums`` when the plan carries explicit shardings (the
Gemma-31B-on-TPU table-stakes setup), or a ``shard_map``-wrapped
``jax.jit`` for pure data parallelism (the SNIPPETS [1]-[3] pattern).

Axes (a plan mesh always carries the three core axes, degenerate sizes
included, so specs can name any axis regardless of the active
parallelism):

- ``dp``:   data parallel — batch split, params replicated
- ``fsdp``: fully-sharded data parallel — batch split AND params/opt
  moments sharded (ZeRO-3 style); the default rule shards each large
  param's largest divisible axis over ``fsdp``
- ``tp``:   tensor parallel — param dims split per explicit/pattern rules
  (``parallel.sharding.transformer_tp_rules`` compose directly)
- ``ep``:   embedding-table axis (opt-in: the mesh carries it only when
  ``ep > 1``) — params registered via ``tables=`` shard their ROWS
  (dim 0) over ``ep``, the parameter-server giant-table layout
  (reference: distribute_lookup_table.py) without a parameter server.
  Batch leaves never split over ``ep``; ids replicate across it and
  the lookup reduces over it (``parallel.sharded_embedding``).

Spec resolution per param name: **explicit map > pattern rules >
largest-axis-over-fsdp default > replicated.** Derived shardings:
buffers resolve through the same rules (default replicated), optimizer
moments inherit their param's spec (``zeros_like`` on a placed param —
ZeRO-style, never re-replicated), RNG keys and loss replicate, batch
leaves split their leading dim over ``(dp, fsdp)``.

Sharded-by-construction state: :meth:`Plan.place` stages each leaf from
HOST memory straight into its target sharding (``jax.device_put`` with a
``NamedSharding`` transfers only each device's shard), so an
fsdp-sharded init peaks per device at ~1/N of the replicated bytes —
the full array never materializes on any one device.
"""

from __future__ import annotations

import contextlib
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from ..core.enforce import enforce
from ..telemetry.trace import named

PLAN_AXES = ("dp", "fsdp", "tp")
# the opt-in table axis (present in the plan mesh only when ep > 1 so
# ep=1 plans keep the exact legacy 3-axis mesh)
TABLE_AXIS = "ep"

Rule = Tuple[str, P]


@telemetry.cached_instruments
def _plan_metrics(reg):
    """Plan instrument set (only reached when telemetry is on)."""
    return {
        "resharding_copies": reg.counter(
            "pt_resharding_copies_total",
            "device-to-device resharding copies caught by "
            "guard_no_resharding (a steady-state planned step must "
            "stay at 0 — a copy means in_shardings drifted from the "
            "live placement)"),
    }


class Plan:
    """Declarative sharding plan over a ``(dp, fsdp, tp)`` mesh.

    - ``rules``: ordered ``(regex, PartitionSpec)`` pattern rules (first
      match wins — ``parallel.sharding.transformer_tp_rules()`` slots in
      directly).
    - ``params``: explicit per-name spec map; beats every rule.
    - default: when ``fsdp > 1``, a param above ``min_shard_size``
      elements shards its largest fsdp-divisible axis over ``"fsdp"``;
      everything else replicates.
    - ``batch_axes``: mesh axes the batch leading dim splits over
      (default ``("dp", "fsdp")`` — the standard FSDP layout).
    - ``tables``: regex patterns naming embedding-table params whose
      ROWS shard over the ``ep`` axis (``P("ep", None)``) when
      ``ep > 1`` — resolved between the explicit map and the pattern
      rules, so a table registration beats ``transformer_tp_rules``
      but an explicit per-name spec still wins.

    A spec that names an axis the leaf's dim doesn't divide by is
    dropped to the next resolution tier (same divisibility contract as
    :func:`..sharding.infer_param_spec`).
    """

    def __init__(self, dp: int = 1, fsdp: int = 1, tp: int = 1, *,
                 ep: int = 1,
                 rules: Sequence[Rule] = (),
                 params: Optional[Dict[str, P]] = None,
                 tables: Sequence[str] = (),
                 min_shard_size: int = 1024,
                 batch_axes: Sequence[str] = ("dp", "fsdp"),
                 devices: Optional[Sequence[jax.Device]] = None,
                 mesh: Optional[Mesh] = None,
                 grad_compression: Optional[str] = None):
        for name, s in (("dp", dp), ("fsdp", fsdp), ("tp", tp),
                        (TABLE_AXIS, ep)):
            enforce(s >= 1, "plan axis %s must be >= 1, got %s", name, s)
        self.dp, self.fsdp, self.tp = int(dp), int(fsdp), int(tp)
        self.ep = int(ep)
        self.tables = [re.compile(pat) for pat in tables]
        # opt-in int8 gradient allreduce ("int8" | "int8_sr" stochastic
        # rounding): the Trainer compiles the quantized psum into the
        # pure-DP shard_map step / the wire-format round-trip into the
        # pjit reduce boundary (quant.collectives)
        from ..quant.collectives import check_mode

        self.grad_compression = check_mode(grad_compression)
        self.rules = [(re.compile(pat), spec) for pat, spec in rules]
        self.params = dict(params or {})
        self.min_shard_size = int(min_shard_size)
        for ax in batch_axes:
            enforce(ax in PLAN_AXES, "unknown batch axis %r (plan axes "
                    "are %s)", ax, PLAN_AXES)
        self.batch_axes = tuple(batch_axes)
        if mesh is not None:
            enforce(all(a in mesh.shape for a in PLAN_AXES),
                    "plan mesh must carry axes %s, got %s", PLAN_AXES,
                    tuple(mesh.axis_names))
            enforce(tuple(mesh.shape[a] for a in PLAN_AXES)
                    == (self.dp, self.fsdp, self.tp),
                    "mesh shape %s != plan (dp=%s, fsdp=%s, tp=%s)",
                    dict(mesh.shape), self.dp, self.fsdp, self.tp)
            # the ep axis is opt-in: an ep=1 plan accepts the legacy
            # 3-axis mesh; an ep>1 plan needs the table axis on it
            enforce(int(mesh.shape.get(TABLE_AXIS, 1)) == self.ep,
                    "mesh %s axis size %s != plan ep=%s", TABLE_AXIS,
                    int(mesh.shape.get(TABLE_AXIS, 1)), self.ep)
            self._mesh: Optional[Mesh] = mesh
        else:
            self._mesh = None
            self._devices = devices

    # -- mesh ----------------------------------------------------------------

    @property
    def mesh(self) -> Mesh:
        """The plan's mesh, built lazily over its devices (default: the
        first ``dp*fsdp*tp*ep`` of ``jax.devices()``). ``fsdp``/``tp``
        (and ``ep``, whose lookup psum is the hot collective) take the
        innermost (ICI-adjacent) positions, ``dp`` the outer (possibly
        DCN) one — the scaling-book layout. An ep=1 plan builds the
        exact legacy 3-axis mesh; the table axis appears only when
        ``ep > 1``."""
        if self._mesh is None:
            n = self.num_devices
            devices = self._devices
            if devices is None:
                devices = jax.devices()[:n]
            enforce(len(devices) == n,
                    "plan needs %s devices (dp=%s x fsdp=%s x tp=%s "
                    "x ep=%s), got %s", n, self.dp, self.fsdp, self.tp,
                    self.ep, len(devices))
            if self.ep > 1:
                self._mesh = Mesh(
                    np.asarray(devices).reshape(self.dp, self.fsdp,
                                                self.tp, self.ep),
                    axis_names=PLAN_AXES + (TABLE_AXIS,))
            else:
                self._mesh = Mesh(
                    np.asarray(devices).reshape(self.dp, self.fsdp,
                                                self.tp),
                    axis_names=PLAN_AXES)
        return self._mesh

    @property
    def num_devices(self) -> int:
        return self.dp * self.fsdp * self.tp * self.ep

    @property
    def explicit(self) -> bool:
        """True when the plan carries real shardings — fsdp/tp/ep axes
        or any per-param rule — and steps must compile through ``pjit``
        with full in/out shardings. A pure-DP plan (dp only) takes the
        ``shard_map`` fallback instead."""
        return (self.fsdp > 1 or self.tp > 1 or self.ep > 1
                or bool(self.rules) or bool(self.params))

    # -- spec resolution -----------------------------------------------------

    def is_table(self, name: str) -> bool:
        """True when ``name`` matches a registered ``tables=`` pattern
        — the leaves the ``ep`` axis row-shards (and the leaves
        ``analysis/shardcheck``'s PT-SHARD-204/205 table audits
        apply to)."""
        return any(pat.search(name) for pat in self.tables)

    def table_spec(self) -> P:
        """The row-sharded layout registered tables resolve to under an
        ``ep`` plan."""
        return P(TABLE_AXIS, None)

    def spec_for(self, name: str, value=None) -> P:
        """Resolve one param/buffer name: explicit > table > pattern >
        default.

        ``value`` (or anything with ``.shape``) gates divisibility and
        the default rule's size floor; without it, explicit/pattern
        specs are trusted as given and the default stays replicated
        (no shape to pick an axis from).
        """
        if name in self.params:
            spec = self.params[name]
            if self._divisible(value, spec):
                return spec
        if self.ep > 1 and self.is_table(name):
            spec = self.table_spec()
            if self._divisible(value, spec):
                return spec
            # indivisible vocab: fall through to rules/default (the
            # audit reports the drop as PT-SHARD-202/204)
        for pat, spec in self.rules:
            if pat.search(name):
                if self._divisible(value, spec):
                    return spec
                # first match wins even when undivisible (mirrors
                # infer_param_spec): the leaf falls to the default
                # tier below, which re-checks divisibility itself
                break
        return self._default_spec(value)

    def requested_spec(self, name: str) -> Optional[P]:
        """The spec the author *asked for* (explicit map, else table
        registration, else first matching rule) before any divisibility
        gating — ``None`` when only the default tier applies. Lives
        next to :meth:`spec_for` so the audit's notion of "requested"
        can never drift from the resolution order it checks
        (``analysis/shardcheck`` compares this against what
        :meth:`spec_for` actually resolves)."""
        if name in self.params:
            return self.params[name]
        if self.ep > 1 and self.is_table(name):
            return self.table_spec()
        for pat, spec in self.rules:
            if pat.search(name):
                return spec
        return None

    def _divisible(self, value, spec: P) -> bool:
        shape = getattr(value, "shape", None)
        if shape is None:
            return True
        for dim, axes in enumerate(spec):
            if axes is None or dim >= len(shape):
                continue
            axes = (axes,) if isinstance(axes, str) else axes
            n = 1
            for ax in axes:
                n *= int(self.mesh.shape.get(ax, 1))
            if n and shape[dim] % n:
                return False
        return True

    def _default_spec(self, value) -> P:
        """Largest-axis-over-fsdp default (ZeRO-3 style): shard the
        biggest fsdp-divisible dim; small/odd leaves replicate."""
        shape = getattr(value, "shape", None)
        if (self.fsdp <= 1 or shape is None or not len(shape)
                or int(np.prod(shape)) < self.min_shard_size):
            return P()
        order = sorted(range(len(shape)), key=lambda d: -int(shape[d]))
        for dim in order:
            if shape[dim] and shape[dim] % self.fsdp == 0:
                spec: List[Any] = [None] * len(shape)
                spec[dim] = "fsdp"
                return P(*spec)
        return P()

    # -- derived shardings ---------------------------------------------------

    def sharding_for(self, name: str, value=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(name, value))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding(self) -> NamedSharding:
        """Batch leaves: leading dim split over the active batch axes
        (degenerate axes dropped so a dp=1 fsdp=N plan still shards)."""
        axes = tuple(a for a in self.batch_axes
                     if int(self.mesh.shape[a]) > 1)
        return NamedSharding(self.mesh, P(axes) if axes else P())

    def param_shardings(self, params: Dict[str, Any]) -> Dict[str, NamedSharding]:
        return {name: self.sharding_for(name, value)
                for name, value in params.items()}

    # -- sharded-by-construction placement ----------------------------------

    def place(self, named: Dict[str, Any]) -> Dict[str, Any]:
        """Place a ``name -> leaf`` dict sharded-by-construction: each
        leaf is staged from host memory directly into its resolved
        sharding, so no device ever holds more than its shard (a leaf
        already on device is viewed host-side first — the CPU backend
        zero-copies that view, and other backends pay one D2H for the
        one-time init). Placed leaves are re-homed into runtime-owned
        buffers (:func:`..utils.memory.owned_on_device`) because every
        train step DONATES them — a cpu-backend zero-copy alias of the
        init-time host array would corrupt the heap on reuse."""
        from ..analysis.donation import note_transfer
        from ..utils.memory import owned_on_device

        out = {}
        for name, leaf in named.items():
            sh = self.sharding_for(name, leaf)
            host = np.asarray(leaf) if isinstance(leaf, jax.Array) else leaf
            placed = note_transfer(host, jax.device_put(host, sh))
            # note_transfer records the host-backed provenance of the
            # staging put; owned_on_device's copy is recorded owned —
            # so if the laundering were ever bypassed, the Trainer's
            # compile-time donation check (analysis/donation.py) flags
            # the placed state instead of the runtime corrupting later
            out[name] = owned_on_device(placed)
        return out

    def place_replicated(self, tree):
        """Re-place every leaf of an arbitrary pytree that is not
        already a mesh-placed array (optimizer step counters, loss-scale
        scalars, RNG key data) onto the plan mesh replicated. Leaves
        already carrying a ``NamedSharding`` on this mesh — e.g. opt
        moments born from ``zeros_like`` on a placed param — keep it."""
        rep = self.replicated()

        def put(leaf):
            sh = getattr(leaf, "sharding", None)
            if isinstance(sh, NamedSharding) and sh.mesh == self.mesh:
                return leaf
            return jax.device_put(leaf, rep)

        return jax.tree_util.tree_map(put, tree)

    # -- reporting -----------------------------------------------------------

    def describe(self, params: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Plan summary for ``/statusz`` and bench extras."""
        out: Dict[str, Any] = {
            "axes": {"dp": self.dp, "fsdp": self.fsdp, "tp": self.tp,
                     "ep": self.ep},
            "devices": self.num_devices,
            "batch_axes": list(self.batch_axes),
            "mode": "pjit" if self.explicit else "shard_map",
            "rules": len(self.rules),
            "explicit_params": len(self.params),
            "tables": len(self.tables),
            "grad_compression": self.grad_compression,
        }
        if params is not None:
            specs = {n: self.spec_for(n, v) for n, v in params.items()}
            sharded = {n: str(s) for n, s in specs.items() if s != P()}
            out["sharded_params"] = len(sharded)
            out["replicated_params"] = len(params) - len(sharded)
            out["param_specs"] = sharded
            # static plan audit (analysis/shardcheck): would-reshard /
            # dropped-spec / big-leaf-replicated findings ride along,
            # so /statusz's sharding section reports layout hazards
            # without any extra wiring
            from ..analysis.shardcheck import audit_plan, audit_summary

            out["audit"] = audit_summary(
                audit_plan(self, params, specs=specs))
        return out

    def __repr__(self):
        return (f"Plan(dp={self.dp}, fsdp={self.fsdp}, tp={self.tp}, "
                f"ep={self.ep}, rules={len(self.rules)}, "
                f"tables={len(self.tables)}, explicit={self.explicit})")


@contextlib.contextmanager
def host_init():
    """Build a model's eager init-time params in HOST memory.

    ``nn.Layer`` materializes parameters at construction on the default
    device — on a TPU runtime that is chip 0, so a model bigger than one
    chip's HBM could never even be built. Constructing it under this
    scope lands the arrays on the host cpu backend instead;
    :meth:`Plan.place` then stages host->shard and at no point does any
    chip hold more than its shard::

        with host_init():
            model = GPTForCausalLM(cfg)          # params in host RAM
        trainer = Trainer.supervised(model, opt, loss, plan=plan)

    A cpu-only runtime (tests, the 8-device sim) already inits on host;
    the scope is then inert.
    """
    try:
        cpu = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        yield  # no cpu backend exposed: nothing better to offer
        return
    with jax.default_device(cpu):
        yield


# ---------------------------------------------------------------------------
# per-device byte accounting (the OOM-gate evidence: planned per-device
# param+opt bytes ~= replicated / num_fsdp_shards)
# ---------------------------------------------------------------------------


def device_bytes(tree) -> Dict[int, int]:
    """Addressable bytes each device holds for ``tree`` (by device id).
    Replicated leaves count once per device; sharded leaves count each
    device's shard only — exactly the per-device HBM the state costs."""
    out: Dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        if not isinstance(leaf, jax.Array):
            continue
        for shard in leaf.addressable_shards:
            d = shard.device.id
            out[d] = out.get(d, 0) + int(shard.data.nbytes)
    return out


def max_device_bytes(tree) -> int:
    """Largest per-device footprint of ``tree`` (0 for an empty tree)."""
    per = device_bytes(tree)
    return max(per.values()) if per else 0


# ---------------------------------------------------------------------------
# resharding guard (tests + bench): steady-state planned steps must not
# pay device-to-device resharding copies
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def guard_no_resharding():
    """Assert no implicit device-to-device resharding copy happens in
    the body (``jax.transfer_guard_device_to_device("disallow")``). A
    steady-state planned step whose ``in_shardings`` match the live
    placement triggers none; a mismatch raises here and bumps
    ``pt_resharding_copies_total`` — the signal tier-1 tests pin to 0.
    """
    try:
        with jax.transfer_guard_device_to_device("disallow"):
            yield
    except Exception as e:
        # count ONLY sharding/transfer violations — an unrelated error
        # in the body (OOM, a test assertion) must not read as
        # in_shardings drift on /metrics
        msg = str(e).lower()
        if telemetry.enabled() and ("device-to-device" in msg
                                    or "transfer" in msg
                                    or "sharding" in msg):
            _plan_metrics()["resharding_copies"].inc()
        raise


# ---------------------------------------------------------------------------
# step compilation — ONE path for plain jit / pjit / shard_map fallback
# ---------------------------------------------------------------------------


def compile_step(plan: Optional[Plan], fn: Callable, *,
                 in_shardings=None, out_shardings=None,
                 donate_argnums: Sequence[int] = (),
                 batch_argnum: int = -1,
                 static_argnums: Sequence[int] = (),
                 name: Optional[str] = None):
    """Compile ``fn`` for the plan. Three regimes, one entry point:

    - ``plan`` is ``None`` (or a 1-device plan): plain
      ``jax.jit(fn, donate_argnums=...)`` — the single-chip path.
    - ``plan.explicit`` (fsdp/tp axes or param rules): ``pjit`` — i.e.
      ``jax.jit`` with full ``in_shardings`` / ``out_shardings`` /
      ``donate_argnums``, so XLA compiles against the declared layout
      and the steady-state step pays zero resharding copies.
    - pure-DP plan: ``shard_map``-wrapped ``jax.jit``. ``fn`` runs
      per-shard on the batch argument (``batch_argnum``) with all other
      arguments replicated, and MUST be collective-aware: reduce its
      loss/grads over ``jax.lax`` collectives on the batch axes (the
      Trainer threads ``pmean_axes`` for this). ``check_vma=False``
      because the post-``pmean`` replication is real but not statically
      inferable.

    The returned callable carries ``compiled_via`` in
    ``("jit", "pjit", "shard_map")`` so callers (and tests) can pin the
    selection. ``name`` is the program's stable name: the HLO module
    and a profile's ``XLA Modules`` event are ``jit_<name>`` in every
    regime (default: the function's own name).
    """
    donate = tuple(donate_argnums)
    if name:
        fn = named(fn, name)
    if plan is None or plan.num_devices == 1:
        compiled = jax.jit(fn, donate_argnums=donate,
                           static_argnums=tuple(static_argnums))
        compiled.compiled_via = "jit"
        return compiled
    if plan.explicit or in_shardings is not None:
        enforce(in_shardings is not None and out_shardings is not None,
                "explicit plans compile via pjit and need both "
                "in_shardings and out_shardings (derive them from the "
                "placed state)")
        compiled = jax.jit(fn, in_shardings=in_shardings,
                           out_shardings=out_shardings,
                           donate_argnums=donate,
                           static_argnums=tuple(static_argnums))
        compiled.compiled_via = "pjit"
        return compiled

    # pure-DP fallback: shard_map keeps map-style collective ergonomics
    from jax import shard_map

    enforce(not static_argnums,
            "static_argnums is not supported on the shard_map fallback "
            "(the static positions would be fed to shard_map as array "
            "operands) — close over the static values instead")
    mesh = plan.mesh
    batch_spec = plan.batch_sharding().spec

    def wrapped(*args):
        n = len(args)
        b = batch_argnum % n
        in_specs = tuple(batch_spec if i == b else P() for i in range(n))
        return shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)(*args)

    compiled = jax.jit(named(wrapped, name) if name else wrapped,
                       donate_argnums=donate)
    compiled.compiled_via = "shard_map"
    return compiled


def pmean_axes(plan: Optional[Plan]) -> Tuple[str, ...]:
    """The mesh axes a collective-aware step must reduce grads/loss
    over under the shard_map fallback (empty for explicit/absent plans,
    where GSPMD inserts the collectives itself)."""
    if plan is None or plan.explicit or plan.num_devices == 1:
        return ()
    return tuple(a for a in plan.batch_axes
                 if int(plan.mesh.shape[a]) > 1)
