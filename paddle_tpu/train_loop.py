"""Resumable training loop with failure detection — the elastic-recovery
design-add (SURVEY §5.3: the reference has NO elasticity — a lost trainer
hangs the sync barrier; graceful exit + checkpoint-notify was its whole
story. The TPU-native answer is a re-startable jitted step + frequent async
sharded checkpoints + a watchdog: any process can die and rejoin by
restarting the loop, which auto-resumes from the latest checkpoint).

Also covers: FLAGS_check_nan_inf parity (reference: framework/operator.cc
output checking) as a loss/grad guard with skip-or-raise policy, and
Executor::Close-style graceful shutdown (join async checkpoint writers).
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, Iterable, Optional, Union

import numpy as np

from . import telemetry
from .checkpoint import CheckpointManager
from .core.config import FLAGS
from .core.enforce import EnforceError, enforce
from .resilience import faults as _faults
from .resilience.controller import FleetController
from .resilience.preemption import PreemptionHandler, _preempt_metrics
from .telemetry import costs as _costs
from .telemetry import profiling as _profiling
from .telemetry import recompile as _recompile
from .telemetry import server as _dbg_server
from .telemetry import tracing as _tracing
from .telemetry.diag import AnomalyHalt, FlightRecorder

_NULL_CM = contextlib.nullcontext()


@telemetry.cached_instruments
def _train_metrics(reg):
    """Training instrument set, memoized against the registry
    generation (touched every step). Only reached when telemetry is
    enabled."""
    return {
        "steps": reg.counter("pt_train_steps_total",
                             "optimizer steps completed"),
        "step_time": reg.histogram(
            "pt_train_step_seconds",
            "wall time per training step (dispatch + loss fence)",
            unit="s"),
        "examples_per_sec": reg.gauge(
            "pt_train_examples_per_sec",
            "throughput over the last step (batch size / step time)"),
        "nan_skips": reg.counter(
            "pt_train_nan_skips_total",
            "steps dropped by the nan/inf guard"),
        "loss_scale": reg.gauge(
            "pt_train_loss_scale", "current dynamic loss scale"),
        "loss_scale_events": reg.counter(
            "pt_train_loss_scale_events_total",
            "dynamic loss-scale growth/backoff events"),
    }


def _batch_size(batch) -> int:
    """Leading dim of the first array leaf (0 when undeterminable)."""
    if isinstance(batch, dict):
        vals = [batch[k] for k in sorted(batch)]
    elif isinstance(batch, (list, tuple)):
        vals = list(batch)
    else:
        vals = [batch]
    for v in vals:
        shape = getattr(v, "shape", None)
        if shape:
            return int(shape[0])
    return 0


class NanInfError(EnforceError):
    """Raised when the nan/inf guard trips with policy='raise'."""


class Watchdog:
    """Step-progress watchdog: fires ``on_stall`` (default: print) if no
    heartbeat arrives within ``timeout_s``. The failure-detection role of
    the reference's rpc_deadline — but for compute progress, not RPC."""

    def __init__(self, timeout_s: float = 600.0,
                 on_stall: Optional[Callable[[float], None]] = None,
                 poll_s: Optional[float] = None):
        self.timeout_s = timeout_s
        self.on_stall = on_stall or (lambda age: print(
            f"[watchdog] no training progress for {age:.0f}s"))
        self._poll_s = poll_s if poll_s is not None else min(timeout_s / 4,
                                                             30.0)
        self._last_beat = time.monotonic()
        self._stop = threading.Event()
        # guards _fired/_last_beat: beat() (the training thread) and
        # _run() (the watchdog thread) both WRITE them — unlocked, a
        # beat racing the fire could strand _fired=True and suppress
        # the next stall's alert (PT-RACE-401)
        self._mu = threading.Lock()
        self._fired = False
        self._thread: Optional[threading.Thread] = None

    def start(self):
        self._last_beat = time.monotonic()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="pt-watchdog")
        self._thread.start()
        return self

    def beat(self):
        with self._mu:
            self._last_beat = time.monotonic()
            self._fired = False

    def _run(self):
        while not self._stop.wait(self._poll_s):
            with self._mu:
                age = time.monotonic() - self._last_beat
                fire = age > self.timeout_s and not self._fired
                if fire:
                    self._fired = True  # fire once per stall
            if fire:
                # user callback runs OUTSIDE the lock: a slow on_stall
                # must never block beat() (PT-RACE-403 discipline)
                self.on_stall(age)

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    @property
    def stalled(self) -> bool:
        return self._fired


class TrainLoop:
    """Drive a Trainer over a data stream with auto-resume.

    - resume: restores the latest checkpoint before the first step
    - checkpoint_every: periodic async sharded snapshot (params + opt state
      + rng), retention-GC'd by the manager
    - nan guard: FLAGS check_nan_inf equivalent; policy 'skip' drops the
      step's update by restoring the last checkpointed state, 'raise'
      raises NanInfError (both report the step)
    - watchdog: stall detection while the loop runs
    """

    def __init__(self, trainer, checkpoint_dir: str,
                 checkpoint_every: int = 1000, max_to_keep: int = 5,
                 nan_policy: str = "raise",
                 watchdog_timeout_s: Optional[float] = None,
                 on_stall: Optional[Callable] = None,
                 max_recoveries: int = 0,
                 recoverable: tuple = (RuntimeError, OSError)):
        enforce(nan_policy in ("raise", "skip", "off"),
                "nan_policy must be raise|skip|off, got %s", nan_policy)
        self.trainer = trainer
        self.manager = CheckpointManager(checkpoint_dir,
                                         max_to_keep=max_to_keep)
        self.checkpoint_every = checkpoint_every
        self.nan_policy = nan_policy
        self.step = 0
        self._watchdog = (Watchdog(watchdog_timeout_s, on_stall)
                          if watchdog_timeout_s else None)
        # elastic recovery (the SURVEY §5.3 design-add beyond the
        # reference's none): a step failing with a ``recoverable`` error
        # (XLA device/runtime faults surface as RuntimeError) rolls the
        # trainer back to the latest snapshot and continues, at most
        # ``max_recoveries`` times per run() call. Deterministic errors
        # (EnforceError and other RuntimeError subclasses that mean
        # "bug", not "fault") always propagate.
        enforce(max_recoveries >= 0, "max_recoveries must be >= 0")
        self.max_recoveries = max_recoveries
        self.recoverable = tuple(recoverable)
        self._recoveries_this_run = 0
        self._faulted = False
        self._last_loss_scale: Optional[float] = None
        self._backend_name: Optional[str] = None
        self._cost_registered = False
        self.debug_server = None  # set while run(debug_port=) is live
        # "idle" -> "running" -> "completed" | "preempted" | "faulted"
        self.status = "idle"
        self.history: Dict[str, Any] = {"resumed_from": None,
                                        "skipped_steps": [],
                                        "recoveries": []}

    def _is_recoverable(self, e: BaseException) -> bool:
        if isinstance(e, (EnforceError, NotImplementedError,
                          RecursionError)):
            return False  # deterministic bug/config errors, not faults
        return isinstance(e, self.recoverable)

    # -- lifecycle -----------------------------------------------------------

    def maybe_resume(self) -> Optional[int]:
        coord = self.manager._coord()
        if coord is not None:
            # multi-host: every rank must restore the SAME step, and
            # only one the whole fleet holds. Each rank publishes its
            # locally committed steps through the transport and the
            # fleet restores the newest COMMON one — then promotes it
            # to globally committed (the agreement itself is the
            # all-ranks-staged evidence a crash mid-commit may have
            # kept off disk). No common step → a consistent cold start
            # on every rank, never each rank's own newest.
            agreed = coord.agree_restore_step(
                self.manager.committed_steps())
            # promote the agreed step AND demote stale global markers
            # above it (or all of them on a cold start) — a dead
            # attempt's leftover marker would poison the fleet GC
            # floor and rollback restores
            self.manager.align_global(agreed)
            if agreed is None:
                return None
            # explicit-step restore: integrity errors on the agreed
            # step propagate loudly — one rank silently falling back
            # to an older step would diverge the fleet
            self.trainer.restore_checkpoint(self.manager, agreed)
            self.step = agreed
            self.history["resumed_from"] = agreed
            return agreed
        if self.manager.latest_step() is None:
            return None
        # step=None takes CheckpointManager's VERIFIED restore path: a
        # torn or bit-flipped latest step falls back to the newest
        # committed checksum-valid one instead of crashing the resume
        self.trainer.restore_checkpoint(self.manager, None)
        latest = self.manager.last_restored_step
        self.step = latest
        self.history["resumed_from"] = latest
        return latest

    def _note_rollback(self, restored: Optional[int],
                       expected: Optional[int], why: str) -> None:
        """After a rollback restore: when the verified restore fell
        back PAST the expected newest committed step (its bytes were
        corrupt), the step counter must follow what was actually
        restored (or the next periodic save would label old weights
        with the current step number) and the rewind is recorded. The
        normal rollback-to-latest case is a no-op here — plain skip
        semantics keep the counter."""
        if restored is None or restored == expected:
            return
        self.history["recoveries"].append(
            {"step": self.step, "rolled_back_to": restored,
             "error": why + " fell back past a corrupt step"})
        self.step = restored

    def _backend(self) -> str:
        """First device's platform, resolved once (sentinel key)."""
        if self._backend_name is None:
            import jax

            devs = jax.devices()
            self._backend_name = devs[0].platform if devs else "unknown"
        return self._backend_name

    def _register_step_cost(self, batch) -> None:
        """One-shot cost-ledger registration of the dispatched step
        program (telemetry is already known-on at the call site). The
        extra lower().compile() rides the persistent compile cache —
        same HLO as the executable the loop dispatches."""
        tr = self.trainer
        jf = getattr(tr, "_jit_step", None)
        if jf is None:
            return
        plan = getattr(tr, "plan", None)
        try:
            _costs.ensure_program(
                "train.step", jf,
                (tr.params, tr.buffers, tr.opt_state, tr._rng, batch),
                n_partitions=(plan.num_devices if plan is not None
                              else 1),
                origin="train_loop")
        except Exception:
            pass  # attribution must never fail a training step

    def _guard(self, loss) -> bool:
        """True if the step is clean; handles policy when not."""
        if self.nan_policy == "off" and not FLAGS.get("check_nan_inf"):
            return True
        if bool(np.isfinite(np.asarray(loss))):
            return True
        if self.nan_policy == "raise":
            raise NanInfError(
                f"non-finite loss at step {self.step}: {loss}")
        if telemetry.enabled():
            _train_metrics()["nan_skips"].inc()
        self.history["skipped_steps"].append(self.step)
        latest = self.manager.latest_step()
        if latest is not None:
            # roll back to the last good snapshot (the skip would
            # otherwise keep poisoned optimizer moments); step=None =
            # the VERIFIED fallback path — a corrupt newest committed
            # step falls back instead of killing a recoverable run
            self.trainer.restore_checkpoint(self.manager, None)
            self._note_rollback(self.manager.last_restored_step,
                                latest, "nan-skip rollback")
        return False

    def run(self, batches: Iterable, num_steps: Optional[int] = None,
            resume: bool = True,
            on_step: Optional[Callable[[int, Any, Dict], None]] = None,
            prefetch: Union[int, str, None] = None, bucket_by=None,
            pad_value=0, debug_port: Optional[int] = None,
            flight_recorder: Optional[FlightRecorder] = None,
            preemption: Union[bool, PreemptionHandler, None] = None,
            controller: Optional[FleetController] = None):
        """Train until ``num_steps`` (global, including resumed) or data
        exhaustion. Returns the final step count — which can end below
        ``num_steps`` after an elastic recovery, since the data stream
        is not replayable (see history["recoveries"]).

        Input pipeline (opt-in, ``data.device_loader``):

        - ``prefetch=N``: stage batches onto device N ahead via a
          background thread (double buffering at N=2), overlapping host
          work + transfer with the device's compute on the previous
          step. Batches land pre-placed with the trainer's
          ``data_sharding()`` when it has one. Donation-safe by
          construction: the Trainer step donates (params, buffers,
          opt_state) — never the batch — and the prefetcher copies any
          already-device-resident leaf, so a staged buffer can never be
          a donated one. ``prefetch="auto"`` starts at depth 2 and
          grows the staging depth while the host-wait p50 stays above
          threshold (capped — ``data.device_loader`` auto sizing).
        - ``bucket_by=...``: pad the batch axis up to a fixed bucket set
          ("pow2" or an ascending size list) so a ragged final batch
          reuses the compiled step instead of retracing it (visible in
          ``pt_jit_recompiles_total{site="train_loop.step"}``).
          ``pad_value`` fills the padded rows. Works with or without
          ``prefetch`` (alone it stages synchronously).

        Live diagnostics (opt-in, ``telemetry.server`` / ``.diag``):

        - ``debug_port=P``: serve /metrics /healthz /statusz /tracez
          /memz on 127.0.0.1:P (0 = ephemeral; ``self.debug_server``
          holds the running server) for the duration of the run.
          Starting the server ENABLES telemetry; the thread is joined
          before run() returns.
        - ``flight_recorder=FlightRecorder(...)``: record per-step
          loss / grad-norm / loss-scale / step-time / queue-depth into
          the recorder's ring and apply its policy on anomaly —
          ``record`` keeps going (the dump bundle is on disk),
          ``skip_step`` drops a NAN step like the nan guard (rollback
          to the last checkpoint; with NO checkpoint to roll back to
          it escalates to halt — the poisoned update already applied
          and continuing would train on it; finite anomalies —
          spike/stall — never roll back: the state is sound and a
          rollback would destroy real progress), ``halt`` raises
          :class:`telemetry.diag.AnomalyHalt`. Only consulted while
          telemetry is enabled — with telemetry off the loop executes
          no recorder code at all (the enabled-flag contract).

        Fault tolerance (opt-in, ``resilience``):

        - ``preemption=True`` installs a SIGTERM/SIGINT grace handler
          for the duration of the run (pass an existing
          :class:`resilience.PreemptionHandler` to share one across
          components). On signal the loop finishes the in-flight step,
          breaks out with ``self.status == "preempted"``, and close()
          writes the final checkpoint (joining async writers) — the
          run dies clean instead of mid-save. With the default
          ``preemption=None`` no handler exists and the hot path
          executes no resilience code (pinned by test).
        - an armed :class:`resilience.FaultInjector` (chaos tests) is
          consulted at the ``step.nan`` point after each step — a
          ``corrupt`` rule poisons the loss so the nan machinery can
          be driven deterministically; a raising rule simulates a
          device fault through the elastic-recovery path.
        - ``controller=FleetController(...)`` upgrades preemption from
          per-process to FLEET-COORDINATED (``resilience.controller``):
          a SIGTERM / metadata notice on ANY rank starts a
          preempt-at-step agreement over the coordination transport,
          every rank trains up to the agreed step (``max`` of all
          ranks' acks — nobody rewinds), commits ONE consistent
          checkpoint at that step, and confirms through the transport
          before reporting a clean ``preempted`` exit. The
          controller's handler doubles as the preemption handler (no
          separate ``preemption=`` needed); an expired agreement or
          commit confirmation raises the typed
          :class:`resilience.BarrierTimeoutError` naming the missing
          ranks instead of hanging the survivors.
        """
        if prefetch is not None or bucket_by is not None:
            from .data.device_loader import DevicePrefetcher

            sharding = None
            get_sh = getattr(self.trainer, "data_sharding", None)
            if callable(get_sh) and getattr(self.trainer, "mesh",
                                            None) is not None:
                # no blanket except: a broken data_sharding() (bad axis
                # name, ...) must fail loudly, not silently stage every
                # batch at default placement
                sharding = get_sh()
            # strings pass through raw so DevicePrefetcher's typed
            # "int or 'auto'" error fires on a typo'd mode, not a bare
            # int() ValueError here
            batches = DevicePrefetcher(batches,
                                       size=(prefetch
                                             if isinstance(prefetch, str)
                                             else int(prefetch or 0)),
                                       sharding=sharding,
                                       bucket_by=bucket_by,
                                       pad_value=pad_value)
        if flight_recorder is not None:
            # provenance for the dump bundle (never overrides what the
            # caller already recorded there)
            for k, v in (("checkpoint_dir", self.manager.directory),
                         ("nan_policy", self.nan_policy),
                         ("num_steps", num_steps),
                         ("checkpoint_every", self.checkpoint_every)):
                flight_recorder.run_config.setdefault(k, v)
        if controller is not None and \
                self.manager.coordinator is not controller:
            # wire BEFORE resume: periodic saves become fleet-level
            # two-phase transactions (checkpoint.CheckpointManager
            # fleet mode) and maybe_resume() runs the restore-step
            # agreement — every rank loads the same fleet-held step.
            # Re-binds on a NEW controller too: a second run() with a
            # fresh attempt's controller must not keep publishing into
            # the dead attempt's key namespace
            self.manager.coordinator = controller
        if resume:
            self.maybe_resume()
        self._recoveries_this_run = 0
        self._faulted = False
        self.debug_server = None
        self.status = "running"
        # resolved ONCE, outside the hot path: with no handler and no
        # armed injector both are None and each step pays two
        # None-checks — the zero-cost-when-disabled contract
        pre: Optional[PreemptionHandler] = None
        own_pre = False
        if preemption is not None and preemption is not False:
            pre = (PreemptionHandler() if preemption is True
                   else preemption)
            if not pre.installed:
                pre.install()
                own_pre = True
        ctl = controller
        if ctl is not None:
            if pre is not None:
                # explicit preemption= alongside a controller: share
                # ONE flag — the signal the user's handler receives
                # must be the same one that starts the fleet agreement
                ctl.handler = pre
            else:
                # the controller's handler IS the preemption handler:
                # its SIGTERM flag is what starts the fleet agreement
                pre = ctl.handler
                if not pre.installed:
                    try:
                        pre.install()
                        own_pre = True
                    except ValueError:
                        # not the main thread (signal.signal
                        # constraint): the controller still preempts
                        # via notices/peer acks
                        pass
        own_ctl = False
        if ctl is not None and not ctl.started:
            ctl.start()
            own_ctl = True
        inj = _faults.active()
        if self._watchdog:
            self._watchdog.start()
        try:
            if debug_port is not None:
                # started INSIDE the guarded block: the finally below
                # stops whatever got started, so no failure between
                # here and the loop can leak the daemon thread
                from .telemetry.server import DebugServer

                self.debug_server = DebugServer(
                    port=debug_port, owned=True,
                    run_config={"role": "train_loop",
                                "checkpoint_dir": self.manager.directory,
                                "nan_policy": self.nan_policy,
                                "num_steps": num_steps}).start()
                # on-demand bounded device capture (404->409->200; the
                # same handler the serving replicas mount)
                self.debug_server.add_post(
                    "/profilez", _profiling.make_profilez())
                if hasattr(batches, "current_depth"):
                    # the input pipeline's live knob on /statusz
                    pf = batches
                    self.debug_server.add_status(
                        "input_pipeline",
                        lambda: {"prefetch_depth": pf.current_depth,
                                 "auto": pf.auto,
                                 "queue_depth": pf.last_queue_depth,
                                 "last_real_rows": pf.last_real_rows})
                plan = getattr(self.trainer, "plan", None)
                if plan is not None:
                    # the sharding plan on /statusz: mesh axes, compile
                    # mode, and which params ride which spec
                    tp = self.trainer
                    self.debug_server.add_status(
                        "sharding_plan",
                        lambda: plan.describe(getattr(tp, "params", None)))
                if ctl is not None:
                    # pod-level aggregation: announce this rank's
                    # endpoint through the fleet transport and mount
                    # the controller's fan-out view on /podz and its
                    # trace fan-in on /tracez?trace_id= (rank-tagged
                    # step spans + preempt-agreement events, merged
                    # clock-aligned across the fleet)
                    ctl.publish_endpoint(self.debug_server.host,
                                         self.debug_server.port)
                    self.debug_server.set_fleet(ctl.podz)
                    self.debug_server.set_trace_fanin(
                        ctl.tracez_fanout)

            def _commit_preempt():
                # coordinated preemption epilogue: ONE consistent
                # checkpoint at the agreed step, confirmed through the
                # transport so no rank reports a clean exit before the
                # whole fleet's commit is on disk
                self.status = "preempted"
                self.history["preempted_at"] = self.step
                self.history["preempt_agreed_step"] = ctl.agreed_step
                self.manager.wait_until_finished()
                # a rank whose data ran dry BELOW the agreed step is
                # saving a step its peers will never stage: stage it
                # locally only, and announce done FIRST so the peers'
                # coordinated save at the agreed step doesn't hold for
                # this rank either
                below = (ctl.agreed_step is not None
                         and self.step < ctl.agreed_step)
                if below:
                    ctl.note_done(self.step)
                if self.step > 0 and \
                        self.step not in self.manager.committed_steps():
                    self.manager.save(self.step, self.trainer.state(),
                                      coordinate=not below)
                    self.manager.wait_until_finished()
                ctl.note_checkpoint(self.step)
                committed = ctl.confirm_committed(self.step)
                if committed and len(set(committed.values())) > 1:
                    # only reachable when a rank's data stream ran dry
                    # below the agreed step — worth an operator line
                    print(f"[fleet] ranks committed differing steps: "
                          f"{committed}", file=sys.stderr)

            # run-scoped trace: step spans land on ONE trace id per
            # run, tagged with this process's rank, so the fleet
            # /tracez fan-in merges rank-lanes of the same job (minted
            # lazily — a debug_port enables telemetry just above)
            run_trace = (_tracing.new_trace()
                         if telemetry.enabled() else None)
            if telemetry.enabled():
                # perf baselines live NEXT TO the checkpoints they
                # describe (same lifecycle: a fresh run dir re-arms
                # the sentinel; a resumed run alarms against the
                # previous run's recorded step times)
                _profiling.sentinel().attach(os.path.join(
                    self.manager.directory, "perf_baselines.json"))
            rank = ctl.rank if ctl is not None else 0
            self._cost_registered = False
            batches_it = iter(batches)
            while True:
                # host-input-wait: time this step spends BLOCKED on the
                # pipeline (goodput bucket 1); its own enabled() read —
                # `telem` resolves further down
                t_fetch = (time.perf_counter()
                           if telemetry.enabled() else None)
                try:
                    batch = next(batches_it)
                except StopIteration:
                    break
                input_wait = (time.perf_counter() - t_fetch
                              if t_fetch is not None else 0.0)
                if ctl is not None:
                    # fleet-coordinated preemption: check() is an Event
                    # peek + a throttled transport sample until a
                    # preemption is in flight, then publishes this
                    # rank's ack and HOLDS for the agreement; ranks
                    # below the agreed step keep training up to it
                    agreed = ctl.check(self.step)
                    if agreed is not None and self.step >= agreed:
                        _commit_preempt()
                        break
                elif pre is not None and pre.requested():
                    # preemption grace: the in-flight step already
                    # finished (top-of-body check also covers the
                    # nan-skip/recovery continue paths); break out
                    # clean and let close() write the final checkpoint
                    # (joining async writers) — never die mid-save
                    self.status = "preempted"
                    self.history["preempted_at"] = self.step
                    break
                if num_steps is not None and self.step >= num_steps:
                    break
                telem = telemetry.enabled()
                if telem:
                    # one abstract-signature record per step: a batch
                    # whose shapes/dtypes drift retraces the jitted
                    # step, and this is where it becomes visible
                    _recompile.record("train_loop.step", batch)
                    t0 = time.perf_counter()
                    if run_trace is None:
                        run_trace = _tracing.new_trace()
                step_cm = (_tracing.span("train.step", ctx=run_trace,
                                         rank=rank,
                                         step=self.step + 1)
                           if telem else _NULL_CM)
                try:
                    with step_cm:
                        loss, metrics = self.trainer.train_step(batch)
                    # dispatch stamp (goodput bucket 2): host time to
                    # hand the step to the runtime — everything until
                    # the loss fence below is device compute
                    t_disp = time.perf_counter() if telem else None
                    if inj is not None and inj.fire("step.nan"):
                        # corrupt rule: poison the loss so the nan
                        # guard / recorder path runs deterministically
                        # (a raising rule lands in the except below —
                        # the simulated-device-fault mode)
                        loss = np.float32(np.nan)
                except Exception as e:
                    if not self._is_recoverable(e) or \
                            self._recoveries_this_run >= \
                            self.max_recoveries:
                        self._faulted = True
                        raise
                    # an in-flight async snapshot may be newer than the
                    # last fully-renamed one — don't over-rewind
                    self.manager.wait_until_finished()
                    latest = self.manager.latest_step()
                    if latest is None:
                        # nothing to roll back to: with donated buffers
                        # the failed dispatch may have consumed the live
                        # state, so continuing would be undefined
                        self._faulted = True
                        raise
                    # slice-failure recovery: roll back to the latest
                    # snapshot and keep training (any process can do the
                    # same and rejoin — restartable-step elasticity).
                    # step=None = the verified fallback path (a corrupt
                    # newest step must not end a recoverable run).
                    # NOTE: the data stream is not rewound — batches
                    # consumed between the snapshot and the fault are
                    # skipped, so run() may end below num_steps.
                    self._recoveries_this_run += 1
                    self.trainer.restore_checkpoint(self.manager, None)
                    latest = self.manager.last_restored_step
                    self.history["recoveries"].append(
                        {"step": self.step, "rolled_back_to": latest,
                         "error": repr(e)})
                    self.step = latest
                    continue
                if telem and flight_recorder is not None:
                    # recorder sees the step BEFORE the nan guard: its
                    # anomaly watch + policy subsume the guard for runs
                    # that configure it (the guard still applies after,
                    # under its own nan_policy). float() fences, so the
                    # recorder only ever holds host scalars.
                    action = flight_recorder.record_step(
                        self.step + 1,
                        loss=float(np.asarray(loss)),
                        grad_norm=(metrics.get("grad_norm")
                                   if isinstance(metrics, dict) else None),
                        loss_scale=self._last_loss_scale,
                        step_time=time.perf_counter() - t0,
                        queue_depth=getattr(batches, "last_queue_depth",
                                            None))
                    if action == "halt":
                        # the post-anomaly live state is suspect (the
                        # update already applied) — close() must not
                        # snapshot it over the last good checkpoint
                        self._faulted = True
                        raise flight_recorder.halt_error(
                            f"step {self.step + 1}")
                    if action == "skip_step":
                        if not flight_recorder.anomalies[-1]["kind"] \
                                .startswith("nan"):
                            # finite anomaly (spike/stall): the applied
                            # update is numerically sound, and rolling
                            # back would destroy up to checkpoint_every
                            # steps of real progress over a GC pause —
                            # skip_step degrades to record here (the
                            # dump is the value)
                            pass
                        else:
                            # non-finite update: same remedy as the nan
                            # guard's skip — drop it by rolling back to
                            # the last snapshot (join in-flight async
                            # writes first: a still-renaming snapshot
                            # would read as "no checkpoint" and
                            # silently keep the poisoned state)
                            self.manager.wait_until_finished()
                            latest = self.manager.latest_step()
                            if latest is not None:
                                # bookkeeping parity with the _guard
                                # nan-skip this path subsumes: the
                                # history entry AND the nan-skip
                                # counter (dashboards alert on it);
                                # step=None = verified fallback restore
                                self.history["skipped_steps"].append(
                                    self.step)
                                _train_metrics()["nan_skips"].inc()
                                self.trainer.restore_checkpoint(
                                    self.manager, None)
                                self._note_rollback(
                                    self.manager.last_restored_step,
                                    latest, "recorder skip_step")
                            else:
                                # NOTHING to roll back to: continuing
                                # would train on poison — same
                                # latest-is-None-is-fatal stance as the
                                # exception-recovery path above
                                self._faulted = True
                                raise flight_recorder.halt_error(
                                    f"step {self.step + 1} (skip_step "
                                    f"with no checkpoint to roll back "
                                    f"to)")
                            continue
                if not self._guard(loss):
                    continue
                self.step += 1
                if telem:
                    # _guard's np.isfinite fetch already fenced the
                    # dispatch except under nan_policy='off'; fence
                    # explicitly so the histogram never records an
                    # async-dispatch mirage
                    np.asarray(loss)
                    dt = time.perf_counter() - t0
                    # performance attribution: register the step
                    # program's cost once, split this step into goodput
                    # buckets, and feed the regression sentinel
                    if not self._cost_registered:
                        self._cost_registered = True
                        self._register_step_cost(batch)
                    disp = (t_disp - t0) if t_disp is not None else 0.0
                    _profiling.goodput().note_step(
                        input_wait=input_wait, dispatch=disp,
                        device_compute=max(0.0, dt - disp))
                    _profiling.sentinel().observe(
                        "train.step", self._backend(), dt)
                    _costs.observe_step("train.step", dt)
                    tmet = _train_metrics()
                    tmet["steps"].inc()
                    tmet["step_time"].observe(dt)
                    # pre-pad row count when the batch came through the
                    # prefetcher: bucket padding must not inflate the
                    # examples/sec gauge
                    bs = (getattr(batches, "last_real_rows", None)
                          or _batch_size(batch))
                    if bs and dt > 0:
                        tmet["examples_per_sec"].set(bs / dt)
                    opt = getattr(self.trainer, "optimizer", None)
                    if opt is not None and hasattr(opt, "current_scale"):
                        try:
                            scale = float(np.asarray(opt.current_scale(
                                self.trainer.opt_state)))
                        except Exception:
                            scale = None
                        if scale is not None:
                            tmet["loss_scale"].set(scale)
                            if (self._last_loss_scale is not None
                                    and scale != self._last_loss_scale):
                                tmet["loss_scale_events"].inc()
                            self._last_loss_scale = scale
                if self._watchdog:
                    self._watchdog.beat()
                if telem:
                    # /healthz last-step age: stamp OUR server when we
                    # own one (a co-resident serving loop's stall must
                    # stay visible on its own endpoint), broadcast only
                    # for standalone servers
                    if self.debug_server is not None:
                        self.debug_server.note("step")
                    else:
                        _dbg_server.note("step")
                if on_step is not None:
                    on_step(self.step, loss, metrics)
                if self.checkpoint_every and \
                        self.step % self.checkpoint_every == 0:
                    t_ck = time.perf_counter() if telem else None
                    self.manager.save(self.step, self.trainer.state())
                    if t_ck is not None:
                        # goodput bucket 4: save() host time (async
                        # writers make this small; a sync save or a
                        # staging stall shows up here)
                        _profiling.goodput().note_checkpoint_stall(
                            time.perf_counter() - t_ck)
                    if ctl is not None:
                        ctl.note_checkpoint(self.step)
            if ctl is not None and self.status == "running" and \
                    ctl.agreed_step is not None:
                # the stream ran dry (or num_steps landed) below the
                # agreed step: still commit and confirm what we have —
                # peers are holding for this rank's commit record
                _commit_preempt()
        except BaseException:
            # OUR exception, not sys.exc_info(): run() called from a
            # caller's except block must not read the caller's
            # in-flight exception as its own fault
            self.status = "faulted"
            raise
        finally:
            if telemetry.enabled():
                # persist the sentinel's rolling baselines next to the
                # checkpoints (attach() above set the path; a run that
                # never enabled telemetry has nothing to write)
                _profiling.sentinel().save()
            if self.debug_server is not None:
                # joined before run() returns: no leaked daemon thread
                # (the object stays on self for post-run inspection)
                self.debug_server.stop()
            if own_pre:
                pre.uninstall()
            if self.status == "running":
                self.status = "completed"
            if ctl is not None and self.status == "completed":
                # announce the clean exit BEFORE leaving: without it,
                # a later preemption would hold the agreement for a
                # rank that finished its data and left (faulted exits
                # stay unannounced — the launcher marks those dead)
                ctl.note_done(self.step)
            if own_ctl:
                ctl.stop()
            self.close()
        if self.status == "preempted" and telemetry.enabled():
            # counted AFTER close(): the final checkpoint is on disk,
            # so this really was a clean preemption exit
            _preempt_metrics()["clean_exits"].inc()
        return self.step

    def close(self):
        """Graceful shutdown (Executor::Close parity, reference:
        framework/executor.cc:73): final snapshot + join async writers."""
        if self._watchdog:
            self._watchdog.stop()
        # join in-flight writes FIRST so all_steps() sees them — otherwise
        # a still-writing periodic snapshot of this same step would race
        # the final one on the shared .tmp staging dir. An earlier write's
        # failure must NOT abort the final snapshot (durability first):
        # defer it and re-raise after the final save attempt.
        deferred: Optional[BaseException] = None
        try:
            self.manager.wait_until_finished()
        except BaseException as e:
            deferred = e
        # never snapshot post-fault state: after an unrecovered device
        # fault the live buffers may be invalid (donation) or poisoned —
        # the next run resumes from the last GOOD checkpoint instead.
        # committed_steps (not all_steps): a torn dir for this step
        # must not satisfy the final-snapshot check
        # coordinate=False: the completion epilogue stages locally
        # only — ranks can finish at different final steps, and a
        # global commit here would hold each for a step its peers
        # never save (the preempt path's coordinated save already ran
        # through _commit_preempt; this is a no-op there)
        if self.step > 0 and not self._faulted and \
                self.step not in self.manager.committed_steps():
            self.manager.save(self.step, self.trainer.state(),
                              coordinate=False)
        self.manager.wait_until_finished()
        if deferred is not None:
            if sys.exc_info()[0] is None:
                raise deferred
            # close() ran from an exception's finally — don't mask the
            # original training error with the old write failure
            print(f"[train_loop] deferred checkpoint-write failure: "
                  f"{deferred!r}", file=sys.stderr)
