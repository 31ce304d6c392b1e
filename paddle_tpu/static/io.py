"""Static-graph save/load — fluid io.py capability surface (reference:
python/paddle/fluid/io.py: save_persistables:460, load_persistables:693,
save_inference_model:898, load_inference_model:1074).

TPU-native artifact design (SURVEY.md §7: "a thin Program artifact —
serialized HLO + metadata — keeps the save/load/C++-serve capability"):
``save_inference_model`` exports the pruned feed→fetch computation as a
**StableHLO portable artifact** via ``jax.export`` plus an ``.npz`` of
persistable vars and a JSON manifest. The artifact is loadable from
Python (this module) or any PJRT host (the C++ serving loader) — it
replaces the reference's ``__model__`` ProgramDesc + per-var files.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.enforce import enforce
from ..utils.atomic import atomic_write_text
from .executor import Executor, Scope, _exec_opnodes, _exec_program
from .program import Program, Var, _GradNode, _OpNode


def _prune(program: Program, fetch_names: Sequence[str]):
    """Backward-slice the node list to what `fetch_names` needs — the role
    of ProgramDesc pruning (reference: framework/prune.cc) before export."""
    needed = set(fetch_names)
    keep = [False] * len(program.nodes)
    for i in range(len(program.nodes) - 1, -1, -1):
        node = program.nodes[i]
        if any(o in needed for o in node.outputs):
            keep[i] = True
            if isinstance(node, _GradNode):
                # grads need the whole prefix + its params
                for j in range(node.prefix_len):
                    keep[j] = True
                needed.update(node.param_names)
                needed.add(node.loss_name)
            else:
                needed.update(node.inputs)
    # second pass: prefix nodes pulled in by a grad node add their inputs
    for i in range(len(program.nodes) - 1, -1, -1):
        if keep[i] and isinstance(program.nodes[i], _OpNode):
            needed.update(program.nodes[i].inputs)
    return [n for i, n in enumerate(program.nodes) if keep[i]], needed

_MANIFEST = "manifest.json"
_PARAMS = "params.npz"
_HLO = "program.stablehlo"
_MLIR_BC = "program.mlir.bc"


def save_persistables(executor: Executor, dirname: str,
                      main_program: Program) -> None:
    """reference: io.py save_persistables:460 — all scope-backed vars."""
    os.makedirs(dirname, exist_ok=True)
    arrs = {n: np.asarray(executor.scope.get(n))
            for n in main_program.persistable_names()
            if executor.scope.has(n)}
    np.savez(os.path.join(dirname, _PARAMS), **arrs)


def load_persistables(executor: Executor, dirname: str,
                      main_program: Optional[Program] = None) -> None:
    """reference: io.py load_persistables:693."""
    path = os.path.join(dirname, _PARAMS)
    enforce(os.path.exists(path), "no persistables at %s", dirname)
    with np.load(path) as data:
        for n in data.files:
            executor.scope.set(n, jnp.asarray(data[n]))


def save_inference_model(dirname: str, feed_target_names: Sequence[str],
                         fetch_targets: Sequence[Var], executor: Executor,
                         main_program: Optional[Program] = None,
                         example_feeds: Optional[dict] = None) -> None:
    """reference: io.py save_inference_model:898 — prune to feed→fetch and
    export. Params stay *inputs* of the exported module (shipped alongside
    in the .npz), so the artifact is weight-swappable like the reference's
    __model__ + separate param files.

    ``example_feeds`` (name → array, or a TUPLE of ints as an explicit
    shape): concrete shapes used when the program doesn't trace with
    symbolic dims (control-flow-heavy programs) — the fallback then
    fixes the artifact to these shapes instead of a placeholder batch
    of 8. Lists count as DATA (``np.shape`` of the value), so a run
    feed dict can be passed through unchanged."""
    from .program import default_main_program

    program = main_program or default_main_program()
    fetch_names = [f.name if isinstance(f, Var) else f for f in fetch_targets]
    for n in feed_target_names:
        enforce(n in program.vars and program.vars[n].is_feed,
                "feed target %s is not a data() var", n)
    nodes, needed = _prune(program, fetch_names)
    enforce(not any(isinstance(n, _GradNode) for n in nodes),
            "inference export reaches grad ops; fetch forward vars only")
    missing = [n for n in needed
               if n in program.vars and program.vars[n].is_feed
               and n not in feed_target_names]
    enforce(not missing,
            "pruned inference graph still needs feeds %s — add them to "
            "feed_target_names", missing)
    persist = [n for n in program.persistable_names()
               if executor.scope.has(n) and n in needed]
    params = {n: executor.scope.get(n) for n in persist}
    consts = {k: v for k, v in getattr(program, "_const_values", {}).items()
              if k in needed}

    def infer_fn(params, feeds):
        env = dict(consts)
        env.update(params)
        env.update(feeds)
        env = _exec_opnodes(nodes, env)
        return [env[f] for f in fetch_names]

    # -1 feed dims export as symbolic dimensions so the artifact stays
    # batch-polymorphic (the reference's ProgramDesc is shape-agnostic;
    # a fixed-shape StableHLO module would silently lose that capability).
    # ONE shared symbolic scope for every feed — per-feed scopes cannot
    # mix in a single export — and every feed's LEADING -1 shares the
    # batch symbol "b" (data() convention: dim 0 is the batch; feeds
    # like a sequence and its @LEN lengths companion must agree on it).
    n_sym = 0
    feed_specs, polymorphic = {}, False
    scope = jax.export.SymbolicScope()
    for n in feed_target_names:
        v = program.vars[n]
        if any(d == -1 for d in v.shape):
            polymorphic = True
            dims = []
            for i, d in enumerate(v.shape):
                if d == -1 and i == 0:
                    dims.append("b")
                elif d == -1:
                    dims.append(f"d{n_sym}")
                    n_sym += 1
                else:
                    dims.append(str(d))
            shape = jax.export.symbolic_shape(",".join(dims), scope=scope)
        else:
            shape = tuple(v.shape)
        feed_specs[n] = jax.ShapeDtypeStruct(shape, v.dtype)
    param_specs = {n: jax.ShapeDtypeStruct(np.shape(a),
                                           jnp.asarray(a).dtype)
                   for n, a in params.items()}
    try:
        exported = jax.export.export(jax.jit(infer_fn))(param_specs,
                                                        feed_specs)
    except Exception:
        if not polymorphic:
            raise
        # some recorded op doesn't trace symbolically — fall back to
        # fixed shapes (the caller's example_feeds when given) and say so
        # in the manifest rather than pretending
        polymorphic = False
        for n in list(feed_specs):
            v = program.vars[n]
            ex = (example_feeds or {}).get(n)
            if ex is not None:
                # tuples are explicit shapes; everything else (arrays,
                # lists, scalars) is data whose shape we take
                shape = tuple(ex) if isinstance(ex, tuple) \
                    else tuple(np.shape(ex))
            else:
                shape = tuple(8 if d == -1 else d for d in v.shape)
            feed_specs[n] = jax.ShapeDtypeStruct(shape, v.dtype)
        exported = jax.export.export(jax.jit(infer_fn))(param_specs,
                                                        feed_specs)

    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, _HLO), "wb") as f:
        f.write(exported.serialize())
    # raw StableHLO portable bytecode for non-Python PJRT hosts — the C++
    # serving predictor (native/src/predictor.cc) compiles this directly
    # via PJRT_Client_Compile, no jax.export runtime needed
    with open(os.path.join(dirname, _MLIR_BC), "wb") as f:
        f.write(exported.mlir_module_serialized)
    np.savez(os.path.join(dirname, _PARAMS),
             **{n: np.asarray(a) for n, a in params.items()})
    # calling convention for foreign hosts: flattened (params, feeds) —
    # jax flattens each dict in sorted-key order
    arg_order = ([f"param:{n}" for n in sorted(params)] +
                 [f"feed:{n}" for n in sorted(feed_specs)])
    atomic_write_text(os.path.join(dirname, _MANIFEST), json.dumps({
        "feed_target_names": list(feed_target_names),
        "fetch_target_names": fetch_names,
        "feed_shapes": {n: list(program.vars[n].shape)
                        if polymorphic else
                        list(feed_specs[n].shape)
                        for n in feed_target_names},
        "feed_dtypes": {n: np.dtype(feed_specs[n].dtype).name
                        for n in feed_specs},
        "arg_order": arg_order,
        "batch_polymorphic": polymorphic,
        "format": "stablehlo+npz/v2",
    }, indent=1))


class InferencePredictor:
    """Loaded artifact: ``run(feed_dict) -> [outputs]`` — the role of
    AnalysisPredictor::Run (reference: inference/api/analysis_predictor.h:46)
    minus the pass pipeline (XLA already optimized the module)."""

    def __init__(self, exported, params: Dict[str, jnp.ndarray],
                 feed_names: List[str], fetch_names: List[str]):
        self._exported = exported
        self._params = params
        self.feed_target_names = feed_names
        self.fetch_target_names = fetch_names

    def run(self, feed: Dict[str, np.ndarray]) -> List[np.ndarray]:
        feeds = {k: jnp.asarray(v) for k, v in feed.items()}
        enforce(set(feeds) == set(self.feed_target_names),
                "feed keys %s != expected %s", sorted(feeds),
                sorted(self.feed_target_names))
        out = self._exported.call(self._params, feeds)
        return [np.asarray(o) for o in out]


def load_inference_model(dirname: str) -> InferencePredictor:
    """reference: io.py load_inference_model:1074 → (program, feeds,
    fetches); here: a ready predictor over the StableHLO artifact."""
    with open(os.path.join(dirname, _MANIFEST)) as f:
        manifest = json.load(f)
    enforce(manifest.get("format") in ("stablehlo+npz/v1",
                                       "stablehlo+npz/v2"),
            "unknown inference-model format %s", manifest.get("format"))
    with open(os.path.join(dirname, _HLO), "rb") as f:
        exported = jax.export.deserialize(bytearray(f.read()))
    with np.load(os.path.join(dirname, _PARAMS)) as data:
        params = {n: jnp.asarray(data[n]) for n in data.files}
    return InferencePredictor(exported, params,
                              manifest["feed_target_names"],
                              manifest["fetch_target_names"])


_TRAIN_MANIFEST_FMT = "stablehlo+npz/train/v1"


def save_train_program(dirname: str, feed_target_names: Sequence[str],
                       loss, executor: Executor, main_program: Program
                       ) -> None:
    """Export a FULL train step (forward + backward + optimizer updates) as
    a StableHLO artifact runnable from any PJRT host — the Python-free
    *training* path (reference: paddle/fluid/train/demo/demo_trainer.cc
    runs startup+main ProgramDescs from C++; here the step is one compiled
    function ``(state..., feeds...) -> (new_state..., loss)``).

    ``main_program`` must already have optimizer updates appended
    (opt.minimize(loss)). State = every persistable var (params +
    optimizer accumulators), threaded through so the caller loops by
    feeding outputs back as inputs — C++ side: native/src/train_demo.cc.
    """
    loss_name = loss.name if isinstance(loss, Var) else loss
    program = main_program
    # auto-startup for uninitialized accumulators
    missing = [n for n in program.param_inits
               if not executor.scope.has(n)]
    if missing:
        executor.run_startup(program)
    state_names = sorted(n for n in program.persistable_names()
                         if executor.scope.has(n))
    state = {n: jnp.asarray(executor.scope.get(n)) for n in state_names}
    consts = dict(getattr(program, "_const_values", {}))

    from .executor import _exec_program

    def step_fn(state, feeds):
        env = dict(consts)
        env.update(state)
        env.update(feeds)
        env = _exec_program(program, env)
        new_state = {n: env[n] for n in state_names}
        return new_state, env[loss_name]

    feed_specs = {}
    for n in feed_target_names:
        v = program.vars[n]
        shape = tuple(8 if d == -1 else d for d in v.shape)  # fixed batch
        feed_specs[n] = jax.ShapeDtypeStruct(shape, v.dtype)
    state_specs = {n: jax.ShapeDtypeStruct(np.shape(a), a.dtype)
                   for n, a in state.items()}
    exported = jax.export.export(jax.jit(step_fn))(state_specs, feed_specs)

    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, _HLO), "wb") as f:
        f.write(exported.serialize())
    with open(os.path.join(dirname, _MLIR_BC), "wb") as f:
        f.write(exported.mlir_module_serialized)
    np.savez(os.path.join(dirname, _PARAMS),
             **{n: np.asarray(a) for n, a in state.items()})
    arg_order = ([f"param:{n}" for n in state_names] +
                 [f"feed:{n}" for n in sorted(feed_specs)])
    atomic_write_text(os.path.join(dirname, _MANIFEST), json.dumps({
        "feed_target_names": list(feed_target_names),
        "fetch_target_names": [loss_name],
        "feed_shapes": {n: list(feed_specs[n].shape)
                        for n in feed_specs},
        "feed_dtypes": {n: np.dtype(feed_specs[n].dtype).name
                        for n in feed_specs},
        "arg_order": arg_order,
        "state_names": state_names,
        # outputs: flattened (new_state dict sorted, loss) — first
        # len(state_names) outputs ARE the next step's params
        "num_state_outputs": len(state_names),
        "format": _TRAIN_MANIFEST_FMT,
    }, indent=1))


class TrainStepRunner:
    """Python-side driver for a saved train program (the C++ loop's
    reference semantics; used to validate artifacts + for Python serving
    of exported training)."""

    def __init__(self, dirname: str):
        with open(os.path.join(dirname, _MANIFEST)) as f:
            self.manifest = json.load(f)
        enforce(self.manifest.get("format") == _TRAIN_MANIFEST_FMT,
                "not a train program: %s", self.manifest.get("format"))
        with open(os.path.join(dirname, _HLO), "rb") as f:
            self._exported = jax.export.deserialize(bytearray(f.read()))
        with np.load(os.path.join(dirname, _PARAMS)) as data:
            self.state = {n: jnp.asarray(data[n])
                          for n in self.manifest["state_names"]}

    def step(self, feeds: Dict[str, np.ndarray]):
        feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
        new_state, loss = self._exported.call(self.state, feeds)
        self.state = new_state
        return float(loss)
