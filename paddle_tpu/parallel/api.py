"""High-level parallel training — the ParallelExecutor/CompiledProgram/fleet
capability (reference: framework/parallel_executor.cc:195,
compiler.py:117 with_data_parallel, incubate/fleet/collective) as one object.

``Trainer`` owns (params, buffers, opt_state) placed on a mesh and a jitted
train step. Data parallelism is a *sharding*, not a program rewrite: params
replicated, batch split over "dp"; XLA inserts gradient all-reduces (the whole
multi_devices_graph_pass, reference: multi_devices_graph_pass.cc:450, becomes
compiler work). Buffers donate so updates are in-place in HBM.

With a :class:`..plan.Plan` the trainer goes multi-chip: state is placed
**sharded by construction** (params staged host->shard, opt moments born
sharded from ``zeros_like`` on placed params — no device ever holds the
replicated bytes), and every step variant (plain / gradient-merge /
scan-fused / eval) compiles through one :func:`..plan.compile_step`
path — ``pjit`` with full in/out shardings + donation for explicit
(fsdp/tp) plans, a ``shard_map``-wrapped ``jax.jit`` for pure DP.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import telemetry
from ..core import random as prandom
from ..core.config import BuildStrategy
from ..core.enforce import enforce
from ..core.mesh import get_mesh, mesh_scope
from ..nn.layer import Layer
from ..optimizer.optimizers import Optimizer
from ..telemetry import scopes as _scopes
from ..telemetry.trace import RecordEvent
from .plan import Plan, compile_step, pmean_axes


@telemetry.cached_instruments
def _trainer_metrics(reg):
    """Trainer instrument set (only reached when telemetry is on)."""
    return {
        "dispatch": reg.histogram(
            "pt_trainer_dispatch_seconds",
            "train_step dispatch wall time (unfenced)", unit="s"),
        # an expert layer with the bias rule, by layer (and held expert):
        # Trainer.router_telemetry sets them, at the caller's own fence
        "expert_pairs": lambda layer, expert: reg.gauge(
            "pt_trainer_expert_pairs",
            "(token, pick) pairs a held expert got in the last step",
            labels={"layer": layer, "expert": str(expert)}),
        "load_peak": lambda layer: reg.gauge(
            "pt_trainer_expert_load_peak_ratio",
            "the busiest router output's pairs over the mean over all "
            "outputs, last step", labels={"layer": layer}),
        "bias_max": lambda layer: reg.gauge(
            "pt_trainer_router_bias_max_abs",
            "largest magnitude of the selection bias as moved so far",
            labels={"layer": layer}),
        "windows": lambda layer: reg.gauge(
            "pt_trainer_expert_windows",
            "windows of held pairs the grouped expert body ran in the "
            "last step (1 under even routing; 0: the dense body)",
            labels={"layer": layer}),
    }


class Trainer:
    """Functional training driver.

    loss_builder(params, buffers, rng, batch) ->
        (loss, (metrics_dict, new_buffers))
    """

    def __init__(self, model: Layer, optimizer: Optimizer,
                 loss_builder: Callable, mesh=None,
                 build_strategy: Optional[BuildStrategy] = None,
                 param_spec: Optional[Dict[str, P]] = None,
                 opt_state_rules=None, amp: Optional[str] = None,
                 grad_accum_steps: int = 1, plan: Optional[Plan] = None,
                 grad_compression: Optional[str] = None):
        from ..quant.collectives import check_mode

        self.model = model
        self.optimizer = optimizer
        self.loss_builder = loss_builder
        self.plan = plan
        # compressed gradient allreduce (amp-style opt-in; "int8" |
        # "int8_sr"): trainer knob beats the plan's default. Applied at
        # the ONE reduce boundary every step variant shares (_step /
        # _accum_step / the scan-fused body), so plain, accum, and
        # fused steps all compile it in via the same compile_step path.
        self.grad_compression = check_mode(
            grad_compression if grad_compression is not None
            else (plan.grad_compression if plan is not None else None))
        if plan is not None:
            enforce(param_spec is None and opt_state_rules is None,
                    "plan subsumes param_spec/opt_state_rules — express "
                    "the specs as Plan rules instead")
            enforce(mesh is None or mesh is plan.mesh,
                    "pass either mesh or plan, not both (the plan owns "
                    "its mesh)")
            self.mesh = plan.mesh
        else:
            self.mesh = mesh or get_mesh()
        self.strategy = build_strategy or BuildStrategy()
        # amp: policy name ("mixed_bf16" / "mixed_fp16" / ...) applied at
        # trace time around the loss (reference: contrib/mixed_precision
        # decorator capability; bf16 needs no loss scaling — pair
        # "mixed_fp16" with amp.decorate()'d optimizer for scaling)
        self.amp_policy = amp
        # gradient merge (reference: fleet DistributedStrategy
        # gradient_merge / gradient accumulation): average grads over K
        # micro-steps, apply the optimizer on the K-th
        enforce(grad_accum_steps >= 1, "grad_accum_steps must be >= 1")
        self.grad_accum_steps = grad_accum_steps
        # axes the shard_map fallback reduces grads/loss over (empty for
        # plan-less and explicit-pjit compilation, where GSPMD inserts
        # the collectives)
        self._pmean_axes = pmean_axes(plan)
        if self.grad_compression is not None:
            enforce(plan is not None and plan.num_devices > 1,
                    "grad_compression compresses the gradient "
                    "allreduce — it needs a multi-device plan")

        rep = NamedSharding(self.mesh, P())

        if plan is not None:
            # sharded by construction: each param stages host->shard per
            # the plan (never materialized replicated on any device);
            # the model re-points at the placed arrays so the eager
            # init-time copies on the default device are released
            self.params = plan.place(model.named_parameters())
            model.set_parameters(self.params)
            self.buffers = plan.place(model.named_buffers())
            model.set_buffers(self.buffers)
        else:
            # same transfer discipline as Plan.place: record the put's
            # provenance (a cpu client may zero-copy a numpy-backed
            # leaf) and launder into runtime-owned buffers — these
            # leaves are about to be donated every step
            from ..analysis.donation import note_transfer
            from ..utils.memory import owned_on_device

            def place(tree):
                return jax.tree_util.tree_map(
                    lambda leaf: owned_on_device(note_transfer(
                        leaf, jax.device_put(leaf, rep))), tree)

            self.params = place(model.named_parameters())
            if param_spec:
                for name, spec in param_spec.items():
                    self.params[name] = jax.device_put(
                        self.params[name], NamedSharding(self.mesh, spec))
            self.buffers = place(model.named_buffers())
        # opt state inherits each param's sharding (init uses zeros_like on
        # the already-placed params) — re-placing replicated would defeat
        # the plan's/param_spec's memory sharding for the moments
        self.opt_state = optimizer.init(self.params)
        if plan is not None:
            # only non-mesh leaves (step counters, loss-scale scalars)
            # re-place; moments born sharded stay sharded (ZeRO-style)
            self.opt_state = plan.place_replicated(self.opt_state)
        elif opt_state_rules is not None:
            # ZeRO-style: shard large moment leaves over dp (the PS-sharded
            # optimizer-state capability, reference:
            # transpiler/distribute_transpiler.py:702)
            self.opt_state = opt_state_rules.place(self.opt_state, self.mesh)
        else:
            # the leaves the optimizer made itself (its step counter) join
            # the mesh as the moments have: left on the default device
            # their type is not the type the step hands back, and the
            # SECOND step would be traced, lowered and compiled (or
            # loaded from the cache) all over again
            self.opt_state = jax.tree_util.tree_map(
                lambda leaf: leaf if isinstance(
                    getattr(leaf, "sharding", None), NamedSharding)
                else jax.device_put(leaf, rep), self.opt_state)
        # static per-step collective payload for the host-side byte
        # counters (grads tree mirrors params; shapes never change
        # after init, so compute once and bump per dispatched step)
        self._comm_bytes = (0, 0)
        if self._pmean_axes:
            from ..quant.collectives import tree_payload_bytes

            ax_size = 1
            for a in self._pmean_axes:
                ax_size *= int(self.plan.mesh.shape[a])
            self._comm_bytes = tree_payload_bytes(
                self.params, ax_size, compression=self.grad_compression)
        self._rng = prandom.next_key()
        if plan is not None and plan.num_devices > 1:
            self._rng = jax.device_put(self._rng, rep)
        if self.grad_accum_steps > 1:
            self._accum = jax.tree_util.tree_map(jnp.zeros_like, self.params)
            self._accum_count = jnp.zeros((), jnp.int32)
            if plan is not None:
                self._accum_count = jax.device_put(self._accum_count, rep)
            donate = (0, 1, 2, 3, 4) if self.strategy.donate_inputs else ()
            self._jit_step = compile_step(
                plan, self._accum_step, donate_argnums=donate,
                name="pt_train_accum_step",
                **self._step_shardings(accum=True))
        else:
            donate = (0, 1, 2) if self.strategy.donate_inputs else ()
            self._jit_step = compile_step(
                plan, self._step, donate_argnums=donate,
                name="pt_train_step", **self._step_shardings())
        self._jit_eval = compile_step(plan, self._eval_step,
                                      name="pt_eval_step",
                                      **self._eval_shardings())
        self._multi_cache = {}
        self._check_donation_safety(donate)

    def _check_donation_safety(self, donate) -> None:
        """Compile-time donation-provenance check (analysis/donation):
        every leaf the jitted step will donate must be runtime-owned —
        a host-backed one (the PR 6 restore-SIGSEGV class: cpu client
        zero-copying numpy temporaries) corrupts the heap only
        *sometimes*, so it is flagged HERE, before the first dispatch.
        Once per Trainer construction, skippable via
        FLAGS_static_verify=0 — zero steady-state cost."""
        from ..core.config import FLAGS

        if not donate or not FLAGS.get("static_verify"):
            return
        from ..analysis.diagnostics import format_diagnostics
        from ..analysis.donation import check_donation

        if self.grad_accum_steps > 1:
            args = (self.params, self.buffers, self.opt_state,
                    self._accum, self._accum_count, self._rng)
        else:
            args = (self.params, self.buffers, self.opt_state,
                    self._rng)
        diags = [d for d in check_donation(args, donate)
                 if d.severity == "error"]
        enforce(not diags, "train state failed the donation-safety "
                "check (FLAGS_static_verify=0 skips):\n%s",
                format_diagnostics(diags))

    # --- plan sharding derivation -------------------------------------------

    @staticmethod
    def _sharding_tree(tree):
        """Mirror a placed state tree into its shardings (every leaf is
        a mesh-placed jax.Array after init, so this IS the truth the
        pjit in/out shardings must match for a zero-copy steady state)."""
        return jax.tree_util.tree_map(lambda x: x.sharding, tree)

    def _step_shardings(self, accum: bool = False) -> Dict[str, Any]:
        """``compile_step`` kwargs for the train-step signatures. Only
        explicit plans need them (pjit); plan-less and pure-DP
        compilation derives everything from placement/shard_map."""
        if self.plan is None or not self.plan.explicit:
            return {}
        rep = NamedSharding(self.mesh, P())
        p_sh = self._sharding_tree(self.params)
        b_sh = self._sharding_tree(self.buffers)
        o_sh = self._sharding_tree(self.opt_state)
        batch_sh = self.plan.batch_sharding()
        if accum:
            # (params, buffers, opt_state, accum, count, rng, batch)
            return {
                "in_shardings": (p_sh, b_sh, o_sh, p_sh, rep, rep,
                                 batch_sh),
                "out_shardings": (rep, rep, p_sh, b_sh, o_sh, p_sh, rep),
            }
        # (params, buffers, opt_state, rng, batch) ->
        # (loss, metrics, params, buffers, opt_state)
        return {
            "in_shardings": (p_sh, b_sh, o_sh, rep, batch_sh),
            "out_shardings": (rep, rep, p_sh, b_sh, o_sh),
        }

    def _eval_shardings(self) -> Dict[str, Any]:
        if self.plan is None or not self.plan.explicit:
            return {}
        rep = NamedSharding(self.mesh, P())
        return {
            "in_shardings": (self._sharding_tree(self.params),
                             self._sharding_tree(self.buffers),
                             self.plan.batch_sharding()),
            "out_shardings": (rep, rep),
        }

    # --- pure step functions ------------------------------------------------

    def _shard_rng(self, rng):
        """Per-shard RNG under the shard_map fallback: fold the batch
        axes' indices into the key so dropout draws differ per shard
        (the replicated key would repeat masks across the dp axis)."""
        for ax in self._pmean_axes:
            rng = jax.random.fold_in(rng, lax.axis_index(ax))
        return rng

    def _pmean(self, tree):
        """Reduce per-shard values over the batch axes under the
        shard_map fallback (no-op when GSPMD owns the collectives)."""
        if not self._pmean_axes:
            return tree
        return lax.pmean(tree, self._pmean_axes)

    def _reduce_grads(self, grads, rng):
        """THE gradient reduce boundary — every step variant (plain /
        accum / scan-fused) funnels its grads through here, so the
        grad_compression opt-in lands in all of them from the one
        compile path. Shard_map fallback: int8 ring pmean
        (quant.collectives.quantized_pmean_tree) when compressed, plain
        pmean otherwise. Explicit (pjit/GSPMD) plans: the int8
        wire-format round-trip at the reduce boundary. No plan / no
        compression: identity (zero-cost contract — no quant code in
        the trace)."""
        comp = self.grad_compression
        sr_key = (jax.random.fold_in(rng, 0x51C8)
                  if comp == "int8_sr" else None)
        if self._pmean_axes:
            if comp is None or len(self._pmean_axes) != 1:
                # no single ring over a multi-axis reduce; the plan
                # vocabulary can't produce one today (pure DP is
                # exactly ("dp",)) but fail soft, not wrong
                return lax.pmean(grads, self._pmean_axes)
            from ..quant.collectives import quantized_pmean_tree

            ax = self._pmean_axes[0]
            return quantized_pmean_tree(
                grads, ax, int(self.plan.mesh.shape[ax]), key=sr_key)
        if comp is not None:
            from ..quant.collectives import compress_grads

            return compress_grads(grads, key=sr_key)
        return grads

    def _step(self, params, buffers, opt_state, rng, batch):
        from ..amp import MixedPrecisionOptimizer
        from ..core.dtypes import policy_scope

        import contextlib

        scope = (policy_scope(self.amp_policy) if self.amp_policy
                 else contextlib.nullcontext())
        scaled = isinstance(self.optimizer, MixedPrecisionOptimizer)
        rng = self._shard_rng(rng)

        def lf(p):
            with scope:
                loss, (metrics, new_buffers) = self.loss_builder(
                    p, buffers, rng, batch)
            out_loss = (self.optimizer.scale_loss(loss, opt_state)
                        if scaled else loss)
            return out_loss, (loss, metrics, new_buffers)

        (_, (loss, metrics, new_buffers)), grads = jax.value_and_grad(
            lf, has_aux=True)(params)
        # shard_map fallback: the gradient all-reduce is OURS to write
        # (mean over batch shards == grad of the global-mean loss);
        # loss/metrics/buffer updates reduce the same way so every
        # shard applies an identical update and outputs stay replicated.
        # Grads go through the dedicated reduce boundary (int8 ring
        # when grad_compression is on).
        loss, metrics, new_buffers = self._pmean(
            (loss, metrics, new_buffers))
        grads = self._reduce_grads(grads, rng)
        with _scopes.scope("optimizer"):
            new_params, new_opt_state = self.optimizer.apply(
                params, grads, opt_state)
        return loss, metrics, new_params, new_buffers, new_opt_state

    def _accum_step(self, params, buffers, opt_state, accum, count, rng,
                    batch):
        """Gradient-merge micro-step: accumulate; apply on the K-th."""
        import contextlib

        from ..amp import MixedPrecisionOptimizer
        from ..core.dtypes import policy_scope

        scope = (policy_scope(self.amp_policy) if self.amp_policy
                 else contextlib.nullcontext())
        scaled = isinstance(self.optimizer, MixedPrecisionOptimizer)
        rng = self._shard_rng(rng)

        def lf(p):
            with scope:
                loss, (metrics, new_buffers) = self.loss_builder(
                    p, buffers, rng, batch)
            out_loss = (self.optimizer.scale_loss(loss, opt_state)
                        if scaled else loss)
            return out_loss, (loss, metrics, new_buffers)

        (_, (loss, metrics, new_buffers)), grads = jax.value_and_grad(
            lf, has_aux=True)(params)
        loss, metrics, new_buffers = self._pmean(
            (loss, metrics, new_buffers))
        grads = self._reduce_grads(grads, rng)
        k = self.grad_accum_steps
        accum = jax.tree_util.tree_map(lambda a, g: a + g, accum, grads)
        count = count + 1
        do_apply = count >= k
        mean_grads = jax.tree_util.tree_map(lambda a: a / k, accum)
        with _scopes.scope("optimizer"):
            cand_params, cand_opt = self.optimizer.apply(
                params, mean_grads, opt_state)
        sel = lambda new, old: jax.tree_util.tree_map(
            lambda n, o: jnp.where(do_apply, n, o), new, old)
        new_params = sel(cand_params, params)
        new_opt = sel(cand_opt, opt_state)
        accum = jax.tree_util.tree_map(
            lambda a: jnp.where(do_apply, jnp.zeros_like(a), a), accum)
        count = jnp.where(do_apply, 0, count)
        return (loss, metrics, new_params, new_buffers, new_opt, accum,
                count)

    def _eval_step(self, params, buffers, batch):
        import contextlib

        from ..core.dtypes import policy_scope

        scope = (policy_scope(self.amp_policy) if self.amp_policy
                 else contextlib.nullcontext())
        with scope:
            loss, (metrics, _) = self.loss_builder(params, buffers, None,
                                                   batch)
        return self._pmean((loss, metrics))

    # --- driver API ---------------------------------------------------------

    def _run(self, fn, *args):
        """Call a compiled step with the trainer's mesh ambient
        (``core.mesh``): kernels that partition themselves at TRACE time
        (the flash kernel's shard_map route on a multi-chip TPU, ring
        attention) read the mesh there, and the first call traces."""
        with mesh_scope(self.mesh):
            return fn(*args)

    def train_step(self, batch) -> Tuple[Any, Dict[str, Any]]:
        # op-level span parity (reference: RecordEvent pushed around every
        # op run, platform/profiler.h:81) — here one program span per
        # compiled step, in every profiler session, doubling as the
        # dispatch-time histogram when telemetry is on (async dispatch:
        # the fenced step time is train_loop's)
        hist = (_trainer_metrics()["dispatch"]
                if telemetry.enabled() else None)
        with RecordEvent("train_step", histogram=hist):
            self._rng, sub = jax.random.split(self._rng)
            if self.grad_accum_steps > 1:
                (loss, metrics, self.params, self.buffers, self.opt_state,
                 self._accum, self._accum_count) = self._run(
                    self._jit_step, self.params, self.buffers,
                    self.opt_state, self._accum, self._accum_count, sub,
                    batch)
            else:
                loss, metrics, self.params, self.buffers, self.opt_state = \
                    self._run(self._jit_step, self.params, self.buffers,
                              self.opt_state, sub, batch)
        if telemetry.enabled() and self._pmean_axes:
            from ..quant.collectives import record_payload_bytes

            record_payload_bytes(*self._comm_bytes)
        return loss, metrics

    def router_telemetry(self) -> Dict[str, Dict[str, Any]]:
        """What the last step's routers counted, by expert layer that has
        the bias rule (``nn.DroplessMoE.bias_update``): ``pairs`` (held,)
        the (token, pick) pairs each held expert got, ``load_peak`` the
        busiest of all the router's outputs over their mean, ``bias_max``
        the largest magnitude of ``score_bias + bias_shift``, ``windows``
        how many windows of held pairs the grouped expert body ran
        (``nn.DroplessMoE.windows_run``: host arithmetic on ``pairs``
        and the call's static counts; 1 under even routing). The step
        leaves these in its buffers on the device and fetches nothing:
        call this where the loop fences anyway (it is one
        ``device_get`` of a few hundred numbers, and waits for the step
        in flight). With telemetry on the values also land in the
        trainer's gauges (``pt_trainer_expert_pairs``,
        ``pt_trainer_expert_load_peak_ratio``,
        ``pt_trainer_router_bias_max_abs``,
        ``pt_trainer_expert_windows``)."""
        tail = ".expert_load"
        layers = [k[:-len(tail)] for k in self.buffers if k.endswith(tail)]
        if not layers:
            return {}
        got = jax.device_get({
            n: (self.buffers[n + tail], self.buffers[n + ".bias_shift"],
                self.params[n + ".score_bias"]) for n in layers})
        subs = dict(self.model.named_sublayers())
        out = {}
        for n, (load, shift, bias) in got.items():
            first, held = subs[n].experts_held
            pairs = load[first:first + held]
            out[n] = {"pairs": pairs,
                      "load_peak": float(load.max() / max(load.mean(), 1e-9)),
                      "bias_max": float(abs(bias + shift).max()),
                      "windows": subs[n].windows_run(
                          pairs.sum(), int(load.sum()) // subs[n].top_k)}
            if telemetry.enabled():
                m = _trainer_metrics()
                for e, pairs in enumerate(out[n]["pairs"], first):
                    m["expert_pairs"](n, e).set(pairs)
                m["load_peak"](n).set(out[n]["load_peak"])
                m["bias_max"](n).set(out[n]["bias_max"])
                m["windows"](n).set(out[n]["windows"])
        return out

    def lower_step(self, batch):
        """``jax.stages.Lowered`` of the program :meth:`train_step`
        dispatches for ``batch`` (same arguments; nothing runs, nothing
        is donated) — for cost analysis and compiled-text checks. Its
        ``compile()`` rides the persistent compile cache."""
        if self.grad_accum_steps > 1:
            return self._run(
                self._jit_step.lower, self.params, self.buffers,
                self.opt_state, self._accum, self._accum_count, self._rng,
                batch)
        return self._run(self._jit_step.lower, self.params, self.buffers,
                         self.opt_state, self._rng, batch)

    def train_steps(self, batch, n: int):
        """Run ``n`` fused update steps in ONE device dispatch via
        lax.scan — the reference's num_iteration_per_drop_scope /
        scope-buffered multi-iteration execution (ExecutionStrategy,
        details/scope_buffered_ssa_graph_executor.h:37) in compiled form.
        Cuts host→device round trips by n. The batch is reused for each
        inner step; feed-per-step loops should call train_step instead. Returns the
        last step's (loss, metrics)."""
        fn = self.steps_jit(n)
        with RecordEvent(f"train_steps[{n}]"):
            self._rng, sub = jax.random.split(self._rng)
            loss, metrics, self.params, self.buffers, self.opt_state = \
                self._run(fn, self.params, self.buffers, self.opt_state,
                          sub, batch)
        if telemetry.enabled() and self._pmean_axes:
            from ..quant.collectives import record_payload_bytes

            # the fused dispatch runs n reduces (one per inner step)
            record_payload_bytes(self._comm_bytes[0] * n,
                                 self._comm_bytes[1] * n)
        return loss, metrics

    def steps_jit(self, n: int):
        """The jitted ``n``-fused-step callable train_steps dispatches
        (built lazily, cached, NOT yet called — so callers may
        ``.lower()`` it for cost analysis before any donation happens).
        Signature: ``fn(params, buffers, opt_state, rng, batch)``."""
        enforce(self.grad_accum_steps == 1,
                "train_steps composes with plain steps only (use "
                "train_step for gradient merge)")
        enforce(n >= 1, "train_steps needs n >= 1, got %s", n)
        key = ("train_steps", int(n))
        fn = self._multi_cache.get(key)
        if fn is None:
            def many(params, buffers, opt_state, rng, batch):
                def body(carry, sub):
                    params, buffers, opt_state = carry
                    loss, metrics, params, buffers, opt_state = self._step(
                        params, buffers, opt_state, sub, batch)
                    return (params, buffers, opt_state), (loss, metrics)

                subs = jax.random.split(rng, n)
                (params, buffers, opt_state), (losses, metrics) = lax.scan(
                    body, (params, buffers, opt_state), subs)
                last = jax.tree_util.tree_map(lambda x: x[-1], metrics)
                return losses[-1], last, params, buffers, opt_state

            donate = (0, 1, 2) if self.strategy.donate_inputs else ()
            # the scan-fused step rides the SAME compile path as the
            # single step: pjit shardings / shard_map wrap carry over
            # (the scan body calls _step, which is collective-aware)
            fn = compile_step(self.plan, many, donate_argnums=donate,
                              name=f"pt_train_steps_{n}",
                              **self._step_shardings())
            self._multi_cache[key] = fn
        return fn

    def eval_step(self, batch):
        return self._run(self._jit_eval, self.params, self.buffers, batch)

    def sync_model(self) -> Layer:
        """Write current params/buffers back into the Layer (for save/export)."""
        self.model.set_parameters(jax.device_get(self.params))
        self.model.set_buffers(jax.device_get(self.buffers))
        return self.model

    def data_sharding(self) -> NamedSharding:
        """Sharding for input batches: the plan's batch sharding when
        one rides the trainer, else leading dim over dp (feed via
        DataFeeder(sharding=...) — the feed_and_split analog)."""
        if self.plan is not None:
            return self.plan.batch_sharding()
        return NamedSharding(self.mesh, P("dp"))

    # --- checkpoint/resume (SURVEY §5.4) ------------------------------------

    def state(self) -> Dict[str, Any]:
        """Full resumable training state (params + buffers + optimizer
        moments + RNG) — what the reference persists via save_persistables
        (params + optimizer accumulators, reference: io.py:460)."""
        st = {"params": self.params, "buffers": self.buffers,
              "opt_state": self.opt_state,
              "rng": jax.random.key_data(self._rng)}
        if self.grad_accum_steps > 1:
            st["grad_accum"] = {"accum": self._accum,
                                "count": self._accum_count}
        return st

    def save_checkpoint(self, manager_or_dir, step: Optional[int] = None):
        from ..checkpoint import CheckpointManager, save_state

        if isinstance(manager_or_dir, CheckpointManager):
            enforce(step is not None,
                    "save_checkpoint(manager) needs a step number")
            manager_or_dir.save(step, self.state())
        else:
            save_state(manager_or_dir, self.state())

    def state_shardings(self) -> Optional[Dict[str, Any]]:
        """Shardings of the live state (plan trainers only): what a
        restore must reshard saved leaves onto, regardless of the mesh
        the checkpoint was written from (dp=8 -> fsdp=4 x dp=2 works)."""
        if self.plan is None:
            return None
        sh: Dict[str, Any] = {
            "params": self._sharding_tree(self.params),
            "buffers": self._sharding_tree(self.buffers),
            "opt_state": self._sharding_tree(self.opt_state),
            "rng": self.plan.replicated(),
        }
        if self.grad_accum_steps > 1:
            sh["grad_accum"] = {
                "accum": self._sharding_tree(self._accum),
                "count": self.plan.replicated()}
        return sh

    def restore_checkpoint(self, manager_or_dir,
                           step: Optional[int] = None) -> None:
        """Restore in place, resharding saved leaves onto this trainer's
        mesh (works across mesh shapes — the survey's upgrade over the
        reference's shape-must-match load). Plan trainers reshard onto
        the PLAN's shardings, so a checkpoint written under any other
        plan shape restores straight into the declared layout."""
        from ..checkpoint import CheckpointManager, restore_state

        shardings = self.state_shardings()
        if isinstance(manager_or_dir, CheckpointManager):
            st = manager_or_dir.restore(step, mesh=self.mesh,
                                        shardings=shardings,
                                        target=self.state())
        else:
            st = restore_state(manager_or_dir, mesh=self.mesh,
                               shardings=shardings,
                               target=self.state())
        self.params = st["params"]
        self.buffers = st["buffers"]
        self.opt_state = st["opt_state"]
        if self.grad_accum_steps > 1 and "grad_accum" in st:
            self._accum = st["grad_accum"]["accum"]
            self._accum_count = st["grad_accum"]["count"]
        self._rng = jax.random.wrap_key_data(jnp.asarray(st["rng"]))

    @classmethod
    def supervised(cls, model: Layer, optimizer: Optimizer,
                   loss_fn: Callable, metrics_fn: Optional[Callable] = None,
                   mesh=None, aux_loss_weight: float = 0.0,
                   router_z_loss_weight: float = 0.0,
                   **kw) -> "Trainer":
        """Convenience for (x, label) batches: batch = dict(x=..., label=...)
        or tuple (x, label).

        ``aux_loss_weight``/``router_z_loss_weight`` add those multiples
        of every buffer named ``*aux_loss`` / ``*router_z_loss`` to the
        TRAINING objective (eval_step reports the pure task loss) — the
        MoE load-balance/stability terms ride the buffer mechanism
        (nn.moe.SwitchFFN); the Switch-paper weights are 0.01 and the
        ST-MoE z weight 1e-3."""

        def loss_builder(params, buffers, rng, batch):
            if isinstance(batch, dict):
                x, label = batch["x"], batch["label"]
            else:
                x, label = batch
            training = rng is not None
            out, new_buffers = model.functional_call(
                params, x, buffers=buffers, rng=rng, training=training)
            loss = loss_fn(out, label)
            metrics = metrics_fn(out, label) if metrics_fn else {}
            if training and (aux_loss_weight or router_z_loss_weight):
                # regularizers join only the OPTIMIZED loss; eval stays
                # comparable to task-only baselines
                if aux_loss_weight:
                    loss = loss + aux_loss_weight * sum(
                        v for k, v in new_buffers.items()
                        if k.endswith("aux_loss"))
                if router_z_loss_weight:
                    loss = loss + router_z_loss_weight * sum(
                        v for k, v in new_buffers.items()
                        if k.endswith("router_z_loss"))
            return loss, (metrics, new_buffers)

        return cls(model, optimizer, loss_builder, mesh=mesh, **kw)


class DataParallel:
    """Dygraph-style wrapper (reference: dygraph/parallel.py:79 DataParallel)
    — here just a Trainer factory over an all-device dp mesh."""

    def __new__(cls, model: Layer, optimizer: Optimizer, loss_fn: Callable,
                metrics_fn=None, devices=None):
        from ..core.mesh import auto_mesh

        mesh = auto_mesh(devices)
        return Trainer.supervised(model, optimizer, loss_fn, metrics_fn,
                                  mesh=mesh)
