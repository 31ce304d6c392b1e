"""Install sanity check (reference: python/paddle/fluid/install_check.py —
trains a tiny fc model to validate the install + device stack).

Usage: python -c "import paddle_tpu; paddle_tpu.install_check.run_check()"
"""

from __future__ import annotations

import numpy as np


def run_check(verbose: bool = True) -> bool:
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import optimizer

    def log(msg):
        if verbose:
            print(msg)

    devs = jax.devices()
    log(f"paddle_tpu {pt.__version__} — platform {devs[0].platform}, "
        f"device_kind {devs[0].device_kind!r}, {len(devs)} device(s)")
    if devs[0].platform == "cpu":
        log("NOTE: this check ran on the CPU — no accelerator was "
            "exercised (python chip_smoke.py is the on-chip check)")

    pt.seed(0)
    model = pt.nn.Sequential(pt.nn.Linear(4, 8, act="relu"),
                             pt.nn.Linear(8, 1))
    params = model.named_parameters()
    opt = optimizer.SGD(0.1)
    state = opt.init(params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32))
    y = jnp.asarray((x.sum(axis=1, keepdims=True)))

    @jax.jit
    def step(params, state):
        def loss(p):
            out, _ = model.functional_call(p, x)
            return jnp.mean((out - y) ** 2)

        l, g = jax.value_and_grad(loss)(params)
        params, state = opt.apply(params, g, state)
        return params, state, l

    losses = []
    for _ in range(10):
        params, state, l = step(params, state)
        losses.append(float(l))
    ok = losses[-1] < losses[0] and np.isfinite(losses[-1])
    if ok:
        log(f"single-device train check ok (loss {losses[0]:.4f} -> "
            f"{losses[-1]:.4f})")
    else:
        log(f"FAILED: loss did not decrease ({losses})")

    if len(devs) > 1:
        mesh = pt.build_mesh(dp=len(devs))
        from jax.sharding import NamedSharding, PartitionSpec as P

        arr = jax.device_put(np.ones((len(devs) * 2, 4), np.float32),
                             NamedSharding(mesh, P("dp")))
        s = jax.jit(lambda a: a.sum())(arr)
        ok = ok and float(s) == len(devs) * 8
        log(f"multi-device sharding check ok over {len(devs)} devices")
    if ok:
        log("paddle_tpu is installed correctly!")
    return ok
