"""Collective-traffic budget gate (VERDICT r4 #6): comm_report caught a
real bug in round 4 (the interleaved schedule all-to-all-ing weights
every step); this promotes it from a human-read report to a CI
regression gate — a sharding change that alters a config's collective
STRUCTURE (kinds present) or blows its bytes/flop budget fails the
suite, not a code review. Reference analog: the allreduce-insertion
correctness the reference got from multi_devices_graph_pass.cc:450 code
review.

Budgets carry ~2-5x headroom over the values measured at gate
introduction (r5, jax 0.9 CPU sim) — they exist to catch structural
regressions (a new gather of the whole weight stack, a lost ring
order), not compiler noise.
"""

import jax
import pytest

from conftest import load_tool

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 devices")


@pytest.fixture(scope="module")
def cr():
    return load_tool("comm_report")


def _kinds(rep):
    return set(rep["collectives"])


def test_dp_only_configs_reduce_gradients_only(cr):
    """Pure/2D data+tensor parallel BERT: every byte moves through
    all-reduce (grad buckets + tp activation reductions) — a gather or
    permute appearing here means a sharding rule broke."""
    for name, bpf_budget in (("dp8", 0.05), ("dp4tp2", 0.06)):
        rep = cr.report(name)
        assert _kinds(rep) == {"all-reduce"}, (name, rep["collectives"])
        assert rep["bytes_per_flop"] < bpf_budget, (name, rep)


def test_hybrid_pp_config_structure_and_budget(cr):
    """dp x tp x pp: neighbour permutes for the pipeline, all-reduce for
    dp/tp, and NO all-to-all — the r4 interleaved weight-shuffle bug
    class stays dead."""
    rep = cr.report("dp2tp2pp2")
    assert "collective-permute" in _kinds(rep), rep["collectives"]
    assert "all-to-all" not in _kinds(rep), rep["collectives"]
    assert rep["bytes_per_flop"] < 0.06, rep


def test_interleaved_traffic_equals_gpipe(cr):
    """Ring-order weight storage keeps the interleaved schedule's
    traffic EQUAL to GPipe's (the r4 regression this gate exists for)."""
    g = cr.report("dp2tp2pp2", layers=4)
    i = cr.report("dp2tp2pp2_interleaved")
    assert g["collectives"] == i["collectives"], (g["collectives"],
                                                  i["collectives"])


def test_resnet_dp_allreduce_matches_param_bytes(cr):
    """ResNet-20 pure DP: all-reduce only, and the reduced bytes track
    the parameter size (grad all-reduce ~ params; measured 1.02x at
    introduction) — a blowup means activations or opt state started
    crossing the mesh."""
    rep = cr.report("resnet20_dp8")
    assert _kinds(rep) == {"all-reduce"}, rep["collectives"]
    ar_bytes = rep["collectives"]["all-reduce"]["mbytes"] * 1e6
    assert 0.5 * rep["param_bytes"] < ar_bytes < 2.5 * rep["param_bytes"], \
        (ar_bytes, rep["param_bytes"])


def test_deepfm_ep_dispatch_budget(cr):
    """EP-sharded embeddings with dp-sharded ids: the dispatch is the
    masked local-gather + psum design (all-reduce of embedding
    partials); total traffic stays small (measured 0.04 MB)."""
    rep = cr.report("deepfm_ep4")
    assert "all-reduce" in _kinds(rep), rep["collectives"]
    assert rep["comm_mbytes_total"] < 0.2, rep


def test_bert_moe_ep_pp_structure(cr):
    """The r5 dp x pp x ep MoE composition: expert cross-layout movement
    (all-gather/all-to-all), the pp ring, and dp grad all-reduce in ONE
    module — with a bytes/flop budget."""
    rep = cr.report("bert_moe_ep")
    k = _kinds(rep)
    assert "collective-permute" in k and "all-reduce" in k, rep
    assert ("all-gather" in k) or ("all-to-all" in k), rep["collectives"]
    assert rep["bytes_per_flop"] < 0.03, rep


def test_gpt_hybrid_structure(cr):
    """The GPT 3D flagship shows the same collective structure as the
    BERT hybrid: all-reduce (dp grads + tp activations) and the
    pipeline's collective-permute, with nothing exotic sneaking in."""
    r = cr.report("gpt_dp2tp2pp2")
    kinds = _kinds(r)
    assert "all-reduce" in kinds and "collective-permute" in kinds
    # the r4 regression class this gate exists for: a sharding change
    # that all-to-alls weights every step must FAIL here
    assert "all-to-all" not in kinds
    assert r["gflops"] > 0
    # traffic stays within the same order as the BERT config on the
    # same mesh (shared budget philosophy: a sharding regression that
    # gathers weights would blow this by >10x)
    b = cr.report("dp2tp2pp2")
    assert r["comm_mbytes_total"] < 10 * max(b["comm_mbytes_total"], 1)
