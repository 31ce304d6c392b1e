"""The replica every worker-spawning test serves, and the environment in
which a spawned worker finds it: a test fixture, so it lives with the
tests. Worker processes load it by name (``--spec`` ``SPEC``), which
needs this directory on their ``PYTHONPATH``: ``worker_env()``."""

import os

SPEC = "replica_spec:router_replica_spec"
_TESTS = os.path.dirname(os.path.abspath(__file__))


def worker_env():
    """A spawned worker's environment: the checkout and ``tests/`` on
    ``PYTHONPATH``, JAX on the CPU."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(_TESTS), _TESTS, env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    return env


def router_replica_spec(smoke=False, kv_dtype=None, slots=4,
                        seed=0, prefill_chunk=None):
    """Replica model contract for the router's worker processes
    (``python -m paddle_tpu.serving_router --worker --spec
    replica_spec:router_replica_spec``): every replica builds the SAME
    weights (fixed seed), so placement is invisible in the output."""
    import paddle_tpu as pt
    from paddle_tpu.models import gpt as G
    from paddle_tpu.serving import BatchedDecoder

    pt.seed(seed)
    cfg = G.GPTConfig.small()
    cap = 256
    if smoke:
        # 3 layers (not the usual smoke 2): the router A/B's signal is
        # the absolute ms a monolithic long-prompt prefill steals from
        # decode — one extra layer grows that effect past CI timing
        # noise at still-smoke cost
        cfg.vocab_size, cfg.num_layers = 1024, 3
        cap, slots = 128, max(2, slots // 2)
    cfg.max_position = cap
    model = G.GPTForCausalLM(cfg).eval()
    kw = {}
    if prefill_chunk:
        kw["prefill_chunk"] = prefill_chunk
    return BatchedDecoder(
        model, slots=slots, capacity=cap,
        pages=slots * (cap // 64) + 8, page_size=64,
        kv_dtype=kv_dtype, **kw)
