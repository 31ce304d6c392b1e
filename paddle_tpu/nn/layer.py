"""Layer: the module system — capability parity with fluid.dygraph.Layer
(reference: python/paddle/fluid/dygraph/layers.py) redesigned for JAX.

Design: a Layer is a *mutable container of arrays* (ergonomic, Paddle-style),
but every compiled entry point is *functional*: ``functional_call(params,
buffers, *args)`` injects state, runs forward, and returns updated buffers —
so ``jax.jit``/``grad``/``pjit`` see a pure function over pytrees. This is the
TPU-native answer to the reference's Tracer+VarBase machinery (reference:
paddle/fluid/imperative/tracer.h:44, layer.h:116): JAX *is* the tracer; the
Layer only has to organize state.

State collections:
  - params:  trainable (the reference's Parameter, framework.py:3476)
  - buffers: non-trainable persistent state (BN running stats)
Both are flat dicts keyed by dotted paths ("block1.conv.weight").
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import random as prandom
from ..core.dtypes import default_dtype, get_policy, to_dtype
from ..core.enforce import enforce, not_found
from ..telemetry.scopes import scope


class Layer:
    """Base class for all network modules."""

    def __init__(self, name_scope: Optional[str] = None):
        # use object.__setattr__ to dodge our own __setattr__ bookkeeping
        object.__setattr__(self, "_params", {})
        object.__setattr__(self, "_buffers", {})
        object.__setattr__(self, "_sublayers", {})
        object.__setattr__(self, "_compute_cast", set())
        # the pairs bound for one functional_call, and how deep this
        # layer's own code is running (see ``_cast_once``)
        object.__setattr__(self, "_narrow", {})
        object.__setattr__(self, "_own_depth", 0)
        object.__setattr__(self, "training", True)
        object.__setattr__(self, "_rng_ctx", None)

    # --- attribute plumbing -------------------------------------------------

    def __setattr__(self, name: str, value: Any) -> None:
        if isinstance(value, Layer):
            self._sublayers[name] = value
            object.__setattr__(self, name, value)
        elif isinstance(value, Parameter):
            self._params[name] = value.value
            object.__setattr__(self, name, None)  # real access goes via property
        elif name in self.__dict__.get("_params", {}):
            # re-assigning an existing parameter updates the registry, so
            # forward and state_dict/Trainer never desync
            self._params[name] = jnp.asarray(value)
        elif name in self.__dict__.get("_buffers", {}):
            self._buffers[name] = jnp.asarray(value)
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name: str):
        # only called when normal lookup fails or attr is None-placeholder
        params = self.__dict__.get("_params", {})
        if name in params:
            return params[name]
        buffers = self.__dict__.get("_buffers", {})
        if name in buffers:
            return buffers[name]
        raise AttributeError(f"{type(self).__name__} has no attribute {name!r}")

    def __getattribute__(self, name):
        val = object.__getattribute__(self, name)
        if val is None:
            # parameter/buffer placeholder — fetch live value
            d = object.__getattribute__(self, "__dict__")
            params = d.get("_params", {})
            if d.get("_own_depth"):
                pair = d["_narrow"].get(name)
                # a wrapper may have put another value in the stored
                # leaf's place since: the copy is of what it was cast from
                if pair is not None and pair.stored is params.get(name):
                    return pair.narrow
            if name in params:
                return params[name]
            buffers = d.get("_buffers", {})
            if name in buffers:
                return buffers[name]
        return val

    # --- parameter / buffer creation ---------------------------------------

    def create_parameter(self, name: str, shape, dtype=None,
                         initializer: Optional[Callable] = None,
                         is_bias: bool = False, compute_cast: bool = False):
        """LayerHelper.create_parameter analog (reference: layer_helper.py:29
        param creation + default initializers). ``compute_cast`` declares
        that the layer's own code reads the parameter ONLY through
        ``Policy.cast_to_compute`` (see :meth:`_cast_once`)."""
        from ..initializer import Constant, XavierUniform

        dtype = dtype or default_dtype()
        if initializer is None:
            initializer = Constant(0.0) if is_bias else XavierUniform()
        key = prandom.key_for(f"{type(self).__name__}.{name}",
                              prandom.next_key())
        value = initializer(key, tuple(shape), dtype)
        self._params[name] = value
        object.__setattr__(self, name, None)
        if compute_cast:
            self._compute_cast.add(name)
        return value

    def register_buffer(self, name: str, value) -> None:
        self._buffers[name] = jnp.asarray(value)
        object.__setattr__(self, name, None)

    def update_buffer(self, name: str, value) -> None:
        """Record a new buffer value during forward (BN running stats).
        Functional callers collect these via functional_call."""
        enforce(name in self._buffers, "unknown buffer %s", name)
        self._buffers[name] = value

    def add_sublayer(self, name: str, layer: "Layer") -> "Layer":
        self._sublayers[name] = layer
        object.__setattr__(self, name, layer)
        return layer

    # --- traversal ----------------------------------------------------------

    def named_sublayers(self, prefix: str = "") -> Iterator[Tuple[str, "Layer"]]:
        for name, sub in self._sublayers.items():
            path = f"{prefix}{name}"
            yield path, sub
            yield from sub.named_sublayers(prefix=f"{path}.")

    def sublayers(self) -> List["Layer"]:
        return [l for _, l in self.named_sublayers()]

    def named_parameters(self) -> Dict[str, Any]:
        out = {k: v for k, v in self._params.items()}
        for name, sub in self._sublayers.items():
            for k, v in sub.named_parameters().items():
                out[f"{name}.{k}"] = v
        return out

    def parameters(self) -> List[Any]:
        return list(self.named_parameters().values())

    def compute_cast_names(self) -> frozenset:
        """The dotted names of the parameters that their layers' own code
        reads only through ``Policy.cast_to_compute``: the leaves
        :meth:`_cast_once` may cast ahead."""
        out = set(self._compute_cast)
        for name, sub in self._sublayers.items():
            out.update(f"{name}.{k}" for k in sub.compute_cast_names())
        return frozenset(out)

    def named_buffers(self) -> Dict[str, Any]:
        out = {k: v for k, v in self._buffers.items()}
        for name, sub in self._sublayers.items():
            for k, v in sub.named_buffers().items():
                out[f"{name}.{k}"] = v
        return out

    # --- state dict (reference: dygraph/checkpoint.py save/load) ------------

    def state_dict(self) -> Dict[str, Any]:
        out = dict(self.named_parameters())
        out.update({f"_buffer.{k}": v for k, v in self.named_buffers().items()})
        return out

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        params = {k: v for k, v in state.items() if not k.startswith("_buffer.")}
        buffers = {k[len("_buffer."):]: v for k, v in state.items()
                   if k.startswith("_buffer.")}
        self.set_parameters(params)
        self.set_buffers(buffers)

    def set_parameters(self, flat: Dict[str, Any]) -> None:
        own = {k: v for k, v in flat.items() if "." not in k}
        for k, v in own.items():
            enforce(k in self._params, "unknown parameter %s on %s", k,
                    type(self).__name__)
            if isinstance(v, ComputeCast):
                v = self._narrow[k] = ComputeCast(jnp.asarray(v.stored),
                                                  v.narrow)
                self._params[k] = v.stored
            else:
                self._narrow.pop(k, None)
                self._params[k] = jnp.asarray(v)
        for name, sub in self._sublayers.items():
            prefix = f"{name}."
            subflat = {k[len(prefix):]: v for k, v in flat.items()
                       if k.startswith(prefix)}
            if subflat:
                sub.set_parameters(subflat)

    def set_buffers(self, flat: Dict[str, Any]) -> None:
        own = {k: v for k, v in flat.items() if "." not in k}
        for k, v in own.items():
            self._buffers[k] = jnp.asarray(v)
        for name, sub in self._sublayers.items():
            prefix = f"{name}."
            subflat = {k[len(prefix):]: v for k, v in flat.items()
                       if k.startswith(prefix)}
            if subflat:
                sub.set_buffers(subflat)

    # --- train/eval ---------------------------------------------------------

    def train(self) -> "Layer":
        object.__setattr__(self, "training", True)
        for sub in self._sublayers.values():
            sub.train()
        return self

    def eval(self) -> "Layer":
        object.__setattr__(self, "training", False)
        for sub in self._sublayers.values():
            sub.eval()
        return self

    # --- rng ----------------------------------------------------------------

    def rng(self, tag: str = "default"):
        """Fresh PRNG key for this layer during a functional call (dropout
        etc.). Outside functional_call falls back to the global stream."""
        ctx = _RNG_STACK[-1] if _RNG_STACK else None
        if ctx is None:
            return prandom.next_key()
        ctx["count"] += 1
        return jax.random.fold_in(
            jax.random.fold_in(ctx["key"], ctx["count"]),
            _stable_hash(tag))

    # --- calling ------------------------------------------------------------

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        with self._own_code():
            return self.forward(*args, **kwargs)

    @contextlib.contextmanager
    def _own_code(self):
        """While this layer's own code runs (``__call__``, or the method
        a ``functional_call`` names), its declared parameters read as
        the narrow copies a ``functional_call`` bound. Any other reader
        (a parent that takes ``child.weight``, a wrapper that goes
        through ``child._params`` or calls ``child.forward`` itself)
        gets the parameter as it is stored."""
        d = self.__dict__
        d["_own_depth"] += 1
        try:
            yield
        finally:
            d["_own_depth"] -= 1

    def functional_call(self, params: Dict[str, Any], *args,
                        buffers: Optional[Dict[str, Any]] = None,
                        rng: Optional[jax.Array] = None,
                        training: Optional[bool] = None,
                        method: str = "forward", **kwargs):
        """Pure-function entry point: run ``method`` (default forward) with
        `params`/`buffers` injected; returns (output, new_buffers). Safe to
        jit/grad over. The declared parameters that the policy narrows
        are cast here, once (:meth:`_cast_once`)."""
        saved_params = self._bound_parameters()
        saved_buffers = dict(self.named_buffers())
        saved_training = self.training
        try:
            self.set_parameters(self._cast_once(params))
            if buffers is not None:
                self.set_buffers(buffers)
            if training is not None:
                (self.train if training else self.eval)()
            ctx = {"key": rng if rng is not None else jax.random.key(0),
                   "count": 0}
            _RNG_STACK.append(ctx)
            try:
                with self._own_code():
                    out = getattr(self, method)(*args, **kwargs)
            finally:
                _RNG_STACK.pop()
            new_buffers = dict(self.named_buffers())
            return out, new_buffers
        finally:
            self.set_parameters(saved_params)
            self.set_buffers(saved_buffers)
            (self.train if saved_training else self.eval)()

    def _cast_once(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """``params`` with each declared leaf (:meth:`compute_cast_names`)
        that is wider than the policy's compute type beside its cast to
        it, a :class:`ComputeCast`; ``params`` itself where there is
        none. :meth:`functional_call` does this to what it is given and
        binds the pair: the declaring layer's own code reads the narrow
        copy, so every product of the call reads that leaf where each
        use would have converted the wide one again, and a gradient
        comes back through the one convert in the parameter's own type;
        every other reader gets the stored leaf (``_own_code``). A
        loop over stacked blocks (``scan_layers``, a pipeline) casts in
        its body, a call a slice: on a v5e that read faster than the
        stack cast before the loop, which is also WRONG where a block
        runs once a microbatch (its cotangents would meet in the
        compute type instead of the stored one).

        Each narrow copy passes a ``lax.optimization_barrier`` of its
        own: the compiler then keeps it as an array in memory instead
        of fusing the convert into every product again, and in the
        backward pass a weight's gradient product stands alone instead
        of sharing a fusion with the optimizer's update of that weight
        (on a v5e the train cell's ``mlp`` backward read 77 ms a step
        so, 104 without the barrier, 113 cast at each use). A barrier a
        leaf, not one over all: a gradient is free to be read as soon
        as it is made."""
        pol = get_policy()
        width = to_dtype(pol.compute_dtype).itemsize

        def wide(leaf):
            dtype = getattr(leaf, "dtype", None)
            return (dtype is not None and jnp.issubdtype(dtype, jnp.floating)
                    and dtype.itemsize > width)

        names = [k for k in sorted(self.compute_cast_names())
                 if wide(params.get(k))]
        if not names:
            return params
        with scope("weight_cast"):
            cast = {k: ComputeCast(params[k], jax.lax.optimization_barrier(
                pol.cast_to_compute(params[k]))) for k in names}
        return {**params, **cast}

    def _bound_parameters(self) -> Dict[str, Any]:
        """``named_parameters`` with the bound pairs as pairs: what
        ``set_parameters`` takes to put this state back."""
        out = {k: self._narrow[k] if k in self._narrow
               and self._narrow[k].stored is v else v
               for k, v in self._params.items()}
        for name, sub in self._sublayers.items():
            for k, v in sub._bound_parameters().items():
                out[f"{name}.{k}"] = v
        return out

    def apply_fn(self) -> Callable:
        """Returns f(params, *args) -> output — convenience for loss closures
        on models without buffers."""

        def f(params, *args, **kwargs):
            out, _ = self.functional_call(params, *args, **kwargs)
            return out

        return f


_RNG_STACK: List[Dict[str, Any]] = []


class ComputeCast:
    """A parameter as it is stored beside its copy in the compute type:
    what ``Layer._cast_once`` puts in a declared leaf's place and
    ``set_parameters`` binds."""

    def __init__(self, stored, narrow):
        self.stored, self.narrow = stored, narrow


@contextlib.contextmanager
def inject_state(*bindings):
    """Temporarily bind ``(model, params[, buffers])`` tuples — the
    multi-model sibling of Layer.functional_call for jit bodies that
    drive SEVERAL Layers at once (speculative decoding's target+draft,
    the serving arena's model+draft) or bound-method pipelines that
    functional_call's single-method entry can't express.

    Why it exists: a jitted closure over a Layer traces the weights as
    HLO CONSTANTS: a 100M-param model baked into every program bloats
    each executable (and every compile-cache entry) by the weights.
    Passing params/buffers through this context as jit ARGUMENTS keeps
    compiled programs weight-free. Restores the previous (concrete)
    state on exit — same discipline as functional_call."""
    saved = [(m, dict(m.named_parameters()), dict(m.named_buffers()))
             for m, *_ in bindings]
    try:
        for b in bindings:
            m, p = b[0], b[1]
            m.set_parameters(p)
            if len(b) > 2 and b[2]:
                m.set_buffers(b[2])
        yield
    finally:
        for m, p, bufs in saved:
            m.set_parameters(p)
            if bufs:
                m.set_buffers(bufs)


def stacked_parameters(layers) -> Dict[str, Any]:
    """Stack the params of structurally identical layers along a new
    leading axis — the uniform-block idiom shared by scan-over-layers
    encoders and the GPipe pipeline. Enforces matching param trees."""
    import jax.numpy as jnp

    from ..core.enforce import enforce

    per = [l.named_parameters() for l in layers]
    enforce(per, "stacked_parameters needs at least one layer")
    names = sorted(per[0])
    for i, p in enumerate(per[1:], 1):
        enforce(sorted(p) == names,
                "layer %s is not structurally identical to layer 0 "
                "(params %s vs %s)", i, sorted(p), names)
    return {k: jnp.stack([p[k] for p in per]) for k in names}


def _stable_hash(s: str) -> int:
    import zlib

    return zlib.crc32(s.encode()) & 0x7FFFFFFF


class Parameter:
    """Marker wrapper so `layer.w = Parameter(array)` registers a trainable."""

    def __init__(self, value):
        self.value = jnp.asarray(value)


class Sequential(Layer):
    """reference: dygraph Sequential."""

    def __init__(self, *layers: Layer):
        super().__init__()
        for i, l in enumerate(layers):
            self.add_sublayer(str(i), l)

    def forward(self, x):
        for l in self._sublayers.values():
            x = l(x)
        return x

    def __len__(self):
        return len(self._sublayers)

    def __getitem__(self, i: int) -> Layer:
        return self._sublayers[str(i)]


class LayerList(Layer):
    """reference: dygraph LayerList."""

    def __init__(self, layers=()):
        super().__init__()
        for i, l in enumerate(layers):
            self.add_sublayer(str(i), l)

    def append(self, layer: Layer) -> "LayerList":
        self.add_sublayer(str(len(self._sublayers)), layer)
        return self

    def __iter__(self):
        return iter(self._sublayers.values())

    def __len__(self):
        return len(self._sublayers)

    def __getitem__(self, i: int) -> Layer:
        return self._sublayers[str(i)]


# below every class on purpose, its import too: a serving program's
# Pallas kernels carry the line numbers of their innermost call sites,
# ``Layer.__call__`` among them, and the compile cache keys on them
import functools  # noqa: E402


@functools.lru_cache(maxsize=None)
def remat_policy():
    """What a block under ``jax.checkpoint`` keeps besides its inputs:
    the flash kernel's output ``o`` and log-sum-exp ``lse``, the two
    residuals its backward kernels take from the forward
    (``ops.pallas.flash_attention.REMAT_NAMES``; one (batch, seq, heads
    x value width) activation and one float32 (batch, heads, seq) row a
    call), so that the backward pass recomputes everything of the block
    but the kernel. The one meaning of ``remat=True`` in the model
    shells: ``jax.checkpoint(block, policy=remat_policy())``. A block
    with no flash kernel holds no such name and keeps what it kept, its
    inputs. One object for the process: ``jax.checkpoint`` caches its
    traces by the policy's identity."""
    from ..ops.pallas.flash_attention import REMAT_NAMES

    return jax.checkpoint_policies.save_only_these_names(*REMAT_NAMES)
