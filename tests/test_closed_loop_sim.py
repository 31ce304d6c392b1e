"""``tools/closed_loop_sim.py``: the closed loop of a serving cell as a
model over the harness's own deck."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def sim():
    spec = importlib.util.spec_from_file_location(
        "closed_loop_sim", os.path.join(ROOT, "tools", "closed_loop_sim.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


COSTS = dict(slots=4, bucket=16, window=10.0, step_s=0.01, position_s=0.0,
             prefill=lambda b: 0.002 * b)


def mix(lo, hi, out_lo, out_hi):
    return {"clients": 4, "pool": 8, "length_seed": 0, "ramp_s": 5.0,
            "prompt_tokens": {"dist": "uniform", "min": lo, "max": hi},
            "output_tokens": {"dist": "uniform", "min": out_lo,
                              "max": out_hi}}


def test_a_deck_of_one_length_reads_the_same_under_every_seed(sim):
    runs = [sim.simulate(mix(32, 32, 200, 200), seed, **COSTS)
            for seed in (1, 2, 2147497321)]
    assert len(set(runs)) == 1
    tps, p95, held, submitted = runs[0]
    # four rows in step: a cycle is 4 prefills of 64 ms and 199 steps of
    # 10 ms for 4 x 200 tokens; 2% of the gaps hold a prefill
    assert tps == pytest.approx(800 / (4 * 0.064 + 199 * 0.01), rel=0.05)
    assert p95 == pytest.approx(10.0) and 0 < held < 0.04
    assert submitted == pytest.approx(tps * 10 / 200, abs=4)


def test_the_seed_orders_the_deck_and_the_numbers_follow(sim):
    runs = {seed: sim.simulate(mix(16, 160, 8, 64), seed, **COSTS)
            for seed in range(12)}
    assert runs[3] == sim.simulate(mix(16, 160, 8, 64), 3, **COSTS)
    assert len({r[0] for r in runs.values()}) > 6
    assert 0 < sim.spread([r[0] for r in runs.values()]) < 1


def test_the_glm5_cells_gap_stands_on_a_bare_tick_and_its_tokens_swing(
        sim, capsys):
    """What took the cell off ``serve_tokens_per_s`` (PERF.md section 6,
    PR 45): over the decks of 24 seeds under 5% of a window's gaps hold
    a prefill, so the 95th percentile is a decode step, while tokens/s
    spreads by more than half its bound."""
    assert sim.main(["--seeds", "24"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["decks"] == 24 and line["prefill_gap_share_max"] < 0.045
    assert line["itl_p95_ms"]["spread_pct"] < 4
    assert line["serve_tokens_per_s"]["spread_pct"] > 5
    assert 300 < line["serve_tokens_per_s"]["median"] < 500
