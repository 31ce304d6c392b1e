"""Static verification plane (``paddle_tpu/analysis``): seeded defect
corpus. For EVERY checker there is at least one minimal program / step /
plan / source snippet that triggers it AND one clean twin that must pass
silently — the clean twins are the no-false-positive pin that keeps the
analyzers honest as the framework grows.

Also pins the wiring contracts: ``Executor.run`` verifies on first
compile only (a program-cache hit never re-verifies — zero steady-state
overhead), a bad fetch surfaces as a typed ``PT-FETCH-004`` diagnostic
instead of a bare KeyError, ``FLAGS_static_verify=0`` disables every
wired-in pass, and the repo's own tree lints clean (the ci.sh ``lint``
stage as a tier-1 test)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu.analysis import (Diagnostic, audit_plan, audit_summary,
                                 check_donation, classify_provenance,
                                 errors, format_diagnostics, has_errors,
                                 fetch_diagnostic, lint_paths, lint_source,
                                 track_host_transfers, verify_program)
from paddle_tpu.core.config import FLAGS
from paddle_tpu.core.enforce import EnforceError
from paddle_tpu.static.program import _OpNode, Var

from conftest import load_tool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _prog(with_backward=False):
    """fc -> mean over one feed: the minimal clean program."""
    prog = static.Program()
    with static.program_guard(prog):
        x = prog.data("x", (-1, 4))
        h = static.layers.fc(x, 3, act="relu")
        loss = static.layers.mean(h)
        if with_backward:
            static.append_backward(loss)
    return prog, x, loss


# ---------------------------------------------------------------------------
# Diagnostic record contract
# ---------------------------------------------------------------------------


class TestDiagnostics:
    def test_str_carries_code_location_hint(self):
        d = Diagnostic(code="PT-UBW-001", severity="error", node=3,
                       var="y", message="boom", hint="fix it")
        s = str(d)
        assert "PT-UBW-001" in s and "op[3]" in s and "'y'" in s
        assert "boom" in s and "fix it" in s

    def test_file_location_and_to_dict_drops_empty(self):
        d = Diagnostic(code="PT-LINT-303", severity="error",
                       message="m", path="a.py", line=7)
        assert d.location() == "a.py:7"
        assert d.to_dict() == {"code": "PT-LINT-303", "severity": "error",
                               "message": "m", "path": "a.py", "line": 7}

    def test_bad_severity_rejected(self):
        with pytest.raises(EnforceError):
            Diagnostic(code="X", severity="fatal", message="m")

    def test_format_orders_errors_first(self):
        w = Diagnostic(code="A", severity="warning", message="w")
        e = Diagnostic(code="B", severity="error", message="e")
        out = format_diagnostics([w, e])
        assert out.index("B error") < out.index("A warning")
        assert "1 error(s), 1 warning(s)" in out
        assert has_errors([w, e]) and errors([w, e]) == [e]


# ---------------------------------------------------------------------------
# Program IR verifier (analysis/verify.py)
# ---------------------------------------------------------------------------


class TestVerifier:
    def test_clean_program_passes_silently(self):
        prog, _, loss = _prog(with_backward=True)
        assert verify_program(prog, [loss.name]) == []

    def test_undefined_input_read_flagged(self):
        prog, _, _ = _prog()
        prog.nodes.append(_OpNode(lambda a: a, ["ghost"], ["o"], "relu"))
        prog.vars["o"] = Var(prog, "o", (8, 4), np.float32)
        prog.version += 1
        diags = verify_program(prog, check_shapes=False)
        assert [d.code for d in diags] == ["PT-UBW-001"]
        assert diags[0].var == "ghost" and diags[0].severity == "error"

    def test_use_before_write_flagged_with_both_ops_named(self):
        prog = static.Program()
        with static.program_guard(prog):
            x = prog.data("x", (4,))
            y = prog.apply(lambda a: a * 2, [x], name="scale")
            prog.apply(lambda a: a + 1, [y], name="inc")
        # reorder so the consumer precedes the producer
        prog.nodes.reverse()
        prog.version += 1
        diags = verify_program(prog, check_shapes=False)
        assert [d.code for d in diags] == ["PT-UBW-001"]
        assert "use-before-write" in diags[0].message
        assert diags[0].node == 0

    def test_declared_never_produced_flagged(self):
        prog = static.Program()
        with static.program_guard(prog):
            x = prog.data("x", (4,))
        # a var that exists but nothing writes, read by an op
        prog.vars["limbo"] = Var(prog, "limbo", (4,), np.float32)
        prog.nodes.append(_OpNode(lambda a, b: a + b,
                                  ["x", "limbo"], ["o"], "add"))
        prog.vars["o"] = Var(prog, "o", (4,), np.float32)
        prog.version += 1
        diags = verify_program(prog, check_shapes=False)
        assert [d.code for d in diags] == ["PT-UBW-001"]
        assert "never" in diags[0].message or "no op writes" in \
            diags[0].message

    def test_conflicting_rewrite_flagged_assign_clean(self):
        # defect: a non-assign op re-writes an existing var
        prog = static.Program()
        with static.program_guard(prog):
            x = prog.data("x", (4,))
            y = prog.apply(lambda a: a * 2, [x], name="scale")
        prog.nodes.append(_OpNode(lambda a: a + 1, ["x"], [y.name], "inc"))
        prog.version += 1
        diags = verify_program(prog, check_shapes=False)
        assert [d.code for d in diags] == ["PT-DUP-002"]
        assert diags[0].var == y.name

        # clean twin: the same re-write through Program.assign (the
        # sanctioned in-place update) passes silently
        clean = static.Program()
        with static.program_guard(clean):
            x = clean.data("x", (4,))
            y = clean.apply(lambda a: a * 2, [x], name="scale")
            z = clean.apply(lambda a: a + 1, [x], name="inc")
            clean.assign(y, z)
        assert verify_program(clean, check_shapes=False) == []

    def test_dynamic_dims_match_any_inferred_extent(self):
        # regression (block_dsl dynamic_rnn): declared -1 dims are
        # placeholders (TRACE_BATCH substitutes on the way in) — an op
        # whose output keeps them must not trip PT-SHAPE-005
        prog = static.Program()
        with static.program_guard(prog):
            x = prog.data("x", (-1, 4))
            y = prog.apply(lambda a: a * 2, [x], name="scale")
        y_var = prog.vars[y.name]
        y_var.shape = (-1, 4)
        assert verify_program(prog) == []

    def test_while_write_back_carries_are_clean(self):
        # regression (fluid_book_mt beam decode): a `while` node's outputs
        # ARE its carried inputs — that write-back is the loop contract,
        # not a PT-DUP-002 conflict
        prog = static.Program()
        with static.program_guard(prog):
            c = prog.apply(lambda: np.float32(1.0), [], name="fill")
        prog.nodes.append(_OpNode(lambda a: a - 1, [c.name], [c.name],
                                  "while"))
        prog.version += 1
        assert verify_program(prog, check_shapes=False) == []

    def test_param_mutation_outside_update_ops_flagged(self):
        prog = static.Program()
        with static.program_guard(prog):
            x = prog.data("x", (-1, 4))
            h = static.layers.fc(x, 3)
        pname = prog.param_names()[0]
        prog.nodes.append(_OpNode(lambda a: a * 0.5, [h.name], [pname],
                                  "scale"))
        prog.version += 1
        codes = {d.code for d in verify_program(prog, check_shapes=False)}
        assert "PT-MUT-006" in codes

        # clean twin: assign into the param is the sanctioned path
        clean = static.Program()
        with static.program_guard(clean):
            x = clean.data("x", (-1, 4))
            static.layers.fc(x, 3)
        p = clean.param_names()[0]
        with static.program_guard(clean):
            nv = clean.apply(lambda a: a, [x], name="identity")
        clean.assign(clean.vars[p], nv)
        diags = verify_program(clean, check_shapes=False)
        assert not [d for d in diags if d.code == "PT-MUT-006"]

    def test_dead_op_flagged_for_fetch_slice_only(self):
        prog = static.Program()
        with static.program_guard(prog):
            x = prog.data("x", (4,))
            y = prog.apply(lambda a: a * 2, [x], name="scale")
            z = prog.apply(lambda a: a + 1, [x], name="inc")
        diags = verify_program(prog, [y.name])
        dead = [d for d in diags if d.code == "PT-DEAD-003"]
        assert len(dead) == 1 and dead[0].severity == "warning"
        assert dead[0].var == z.name
        # clean twin: fetch both outputs — nothing is dead
        assert verify_program(prog, [y.name, z.name]) == []
        # and with no fetch list the check is off (every terminal op is
        # a legitimate output)
        assert verify_program(prog) == []

    def test_unknown_fetch_has_close_name_hint(self):
        prog, _, loss = _prog()
        diags = verify_program(prog, [loss.name + "x"])
        assert [d.code for d in diags] == ["PT-FETCH-004"]
        assert loss.name in diags[0].hint  # did-you-mean

    def test_unreachable_fetch_after_test_clone(self):
        # the classic: clone(for_test=True) cuts backward ops but keeps
        # their @GRAD vars — fetching one used to KeyError mid-trace
        prog, _, loss = _prog(with_backward=True)
        gname = prog.param_names()[0] + "@GRAD"
        test_prog = prog.clone(for_test=True)
        assert gname in test_prog.vars
        diags = verify_program(test_prog, [gname])
        fetch = [d for d in diags if d.code == "PT-FETCH-004"]
        assert len(fetch) == 1
        assert "never produced" in fetch[0].message
        # the train program produces it: clean
        assert not [d for d in verify_program(prog, [gname])
                    if d.code == "PT-FETCH-004"]

    def test_tampered_shape_and_dtype_flagged(self):
        prog, _, loss = _prog()
        assert verify_program(prog, [loss.name]) == []  # pre-tamper pin
        prog.vars[loss.name].shape = (17,)
        diags = [d for d in verify_program(prog, [loss.name])
                 if d.code == "PT-SHAPE-005"]
        assert diags and diags[0].var == loss.name
        assert "(17,)" in diags[0].message
        prog.vars[loss.name].shape = ()
        prog.vars[loss.name].dtype = jnp.dtype(np.int32)
        diags = [d for d in verify_program(prog, [loss.name])
                 if d.code == "PT-SHAPE-005"]
        assert diags and "dtype" in diags[0].message

    def test_grad_var_shape_must_mirror_param(self):
        prog, _, loss = _prog(with_backward=True)
        gname = prog.param_names()[0] + "@GRAD"
        prog.vars[gname].shape = (1, 1)
        diags = [d for d in verify_program(prog, check_shapes=True)
                 if d.code == "PT-SHAPE-005"]
        assert diags and diags[0].var == gname


# ---------------------------------------------------------------------------
# Executor wiring: verify-on-first-compile, typed fetch errors, opt-out
# ---------------------------------------------------------------------------


class TestExecutorWiring:
    def test_bad_fetch_is_typed_diagnostic_not_keyerror(self):
        prog, _, loss = _prog()
        exe = static.Executor(scope=static.Scope())
        with pytest.raises(EnforceError) as ei:
            exe.run(prog, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[loss.name + "x"])
        msg = str(ei.value)
        assert "PT-FETCH-004" in msg
        assert loss.name in msg  # close-name hint survives the raise
        assert exe.last_diagnostics and \
            exe.last_diagnostics[0].code == "PT-FETCH-004"

    def test_malformed_program_fails_before_compile(self):
        prog, _, _ = _prog()
        prog.nodes.append(_OpNode(lambda a: a, ["ghost"], ["o"], "relu"))
        prog.vars["o"] = Var(prog, "o", (8, 4), np.float32)
        prog.version += 1
        exe = static.Executor(scope=static.Scope())
        with pytest.raises(EnforceError) as ei:
            exe.run(prog, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=["o"])
        assert "PT-UBW-001" in str(ei.value)
        assert "static verification" in str(ei.value)

    def test_verify_once_per_program_version(self, monkeypatch):
        """The acceptance pin: verify runs on the FIRST compile only —
        a program-cache hit (and a new feed of the same verified slice)
        pays one set lookup, not a verifier walk."""
        import paddle_tpu.analysis.verify as verify_mod

        calls = []
        real = verify_mod.verify_program
        monkeypatch.setattr(verify_mod, "verify_program",
                            lambda *a, **k: calls.append(1) or real(*a, **k))
        prog, _, loss = _prog()
        exe = static.Executor(scope=static.Scope())
        feed = {"x": np.ones((2, 4), np.float32)}
        exe.run(prog, feed=feed, fetch_list=[loss])
        assert len(calls) == 1
        # cache hit: no re-verify
        exe.run(prog, feed=feed, fetch_list=[loss])
        assert len(calls) == 1
        # new batch size = new compile signature, same program version:
        # the memo still skips the verifier
        exe.run(prog, feed={"x": np.ones((5, 4), np.float32)},
                fetch_list=[loss])
        assert len(calls) == 1
        # mutating the program bumps version -> re-verify once
        with static.program_guard(prog):
            prog.apply(lambda a: a * 2, [prog.vars[loss.name]],
                       name="scale")
        exe.run(prog, feed=feed, fetch_list=[loss])
        assert len(calls) == 2

    def test_flag_opt_out_skips_verifier(self, monkeypatch):
        import paddle_tpu.analysis.verify as verify_mod

        calls = []
        monkeypatch.setattr(verify_mod, "verify_program",
                            lambda *a, **k: calls.append(1) or [])
        FLAGS.set("static_verify", False)
        try:
            prog, _, loss = _prog()
            exe = static.Executor(scope=static.Scope())
            exe.run(prog, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[loss])
            assert calls == []
        finally:
            FLAGS.reset("static_verify")


# ---------------------------------------------------------------------------
# Donation-safety analyzer (analysis/donation.py)
# ---------------------------------------------------------------------------


class TestDonation:
    def test_provenance_classes(self):
        owned_np = np.ones((4, 4), np.float32)
        assert classify_provenance(owned_np) == "numpy"
        assert classify_provenance(owned_np[1:]) == "host-view"
        arr = jnp.ones((4, 4))
        assert classify_provenance(arr) == "runtime"
        assert classify_provenance(jax.device_get(arr)) == "host-view"
        from paddle_tpu.utils.memory import owned_on_device

        assert classify_provenance(owned_on_device(arr)) == "owned"

    def test_numpy_state_donated_flagged_device_state_clean(self):
        host_state = {"w": np.ones((8,), np.float32)}
        diags = check_donation((host_state, jnp.ones((8,))), (0,))
        assert [d.code for d in diags] == ["PT-DON-101"]
        assert "w" in diags[0].var
        # clean twin: runtime-computed device state
        dev_state = {"w": jnp.ones((8,))}
        assert check_donation((dev_state, jnp.ones((8,))), (0,)) == []

    def test_host_view_donated_flagged(self):
        view = jax.device_get(jnp.ones((8,)))
        diags = check_donation(({"w": view},), (0,))
        assert [d.code for d in diags] == ["PT-DON-102"]

    def test_pr6_restore_class_flagged_then_laundered_clean(self):
        """The PR 6 SIGSEGV repro, caught statically: a checkpoint
        restore device_puts disk-loaded numpy temporaries (the cpu
        client may zero-copy them), the next train step donates the
        result — flagged BEFORE the step runs; laundering through
        utils.memory.owned_on_device (the PR 6 fix) passes."""
        from paddle_tpu.utils.memory import owned_on_device

        disk = np.random.default_rng(0).standard_normal((64,)).astype(
            np.float32)
        with track_host_transfers():
            restored = jax.device_put(disk)  # restore-path put
        assert classify_provenance(restored) == "host-backed"
        diags = check_donation(({"w": restored},), (0,))
        assert [d.code for d in diags] == ["PT-DON-101"]
        assert "PR 6" in diags[0].hint or "owned_on_device" in diags[0].hint
        # the fix: re-homed into a runtime-owned buffer -> clean
        fixed = {"w": owned_on_device(restored)}
        assert check_donation((fixed,), (0,)) == []

    def test_snapshot_view_alias_escape_flagged(self):
        """The snapshot-side twin: a device_get view of donated state
        held across the step (async checkpoint writer) reads reused
        memory after donation."""
        state = jnp.arange(16, dtype=jnp.float32)
        snapshot = jax.device_get(state)  # zero-copy view on cpu
        diags = check_donation((state,), (0,), live=snapshot)
        assert [d.code for d in diags] == ["PT-DON-104"]
        # clean twin: an owned host copy survives donation fine
        owned_snap = np.array(jax.device_get(state))
        assert check_donation((state,), (0,), live=owned_snap) == []

    def test_same_buffer_donated_twice_flagged(self):
        x = jnp.ones((8,))
        diags = check_donation((x, x), (0, 1))
        assert [d.code for d in diags] == ["PT-DON-104"]
        assert check_donation((x, jnp.ones((8,))), (0, 1)) == []

    def test_donated_but_unused_needs_trace(self):
        args = (jnp.ones((4,)), jnp.ones((4,)))
        diags = check_donation(args, (0,),
                               fn=lambda s, b: jnp.sum(b))
        assert [d.code for d in diags] == ["PT-DON-103"]
        assert check_donation(args, (0,),
                              fn=lambda s, b: s + b) == []
        # without fn= the unused check (which needs a trace) is off
        assert check_donation(args, (0,)) == []

    def test_trainer_state_passes_compile_time_check(self):
        """Integration pin: a real Trainer's donated state (placed and
        laundered by construction) passes the wired-in compile-time
        donation check — i.e. the analyzer agrees the PR 6 fix holds
        on the live path."""
        import paddle_tpu as pt
        from paddle_tpu import optimizer, parallel
        from paddle_tpu.models import mnist as M

        pt.seed(0)
        mesh = pt.build_mesh(dp=1, devices=jax.devices()[:1])
        trainer = parallel.Trainer.supervised(
            M.MnistMLP(hidden1=16, hidden2=8), optimizer.Adam(1e-3),
            M.loss_fn, mesh=mesh)
        # construction ran _check_donation_safety without raising; the
        # donated leaves classify owned/runtime (never host-backed)
        for leaf in jax.tree_util.tree_leaves(trainer.params):
            assert classify_provenance(leaf) in ("owned", "runtime",
                                                 "device")


# ---------------------------------------------------------------------------
# Static plan audit (analysis/shardcheck.py)
# ---------------------------------------------------------------------------


class TestShardcheck:
    def test_would_reshard_flagged_plan_placed_clean(self, eight_devices):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.parallel.plan import Plan

        plan = Plan(fsdp=8)
        big = np.ones((2048, 4), np.float32)
        # defect: placed replicated while the plan resolves fsdp-sharded
        placed = jax.device_put(big, NamedSharding(plan.mesh, P()))
        diags = audit_plan(plan, {"w": placed})
        assert [d.code for d in diags] == ["PT-SHARD-201"]
        assert diags[0].severity == "error"
        # clean twin: placed exactly as the plan resolves
        ok = jax.device_put(big, plan.sharding_for("w", big))
        assert audit_plan(plan, {"w": ok}) == []

    def test_dropped_spec_flagged_divisible_clean(self, eight_devices):
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.parallel.plan import Plan

        plan = Plan(fsdp=8, params={"w": P("fsdp", None)})
        # 10 % 8 != 0: the explicit spec silently falls through
        diags = audit_plan(plan, {
            "w": jax.ShapeDtypeStruct((10, 4), np.float32)})
        assert [d.code for d in diags] == ["PT-SHARD-202"]
        assert "fell through" in diags[0].message
        # clean twin: divisible shape keeps the requested spec
        assert audit_plan(plan, {
            "w": jax.ShapeDtypeStruct((16, 4), np.float32)}) == []

    def test_big_leaf_replicated_flagged_sharded_clean(self, eight_devices):
        from paddle_tpu.parallel.plan import Plan

        plan = Plan(fsdp=8)
        # odd dims: nothing divides by 8 -> replicated; > 1 MiB -> flag
        big = jax.ShapeDtypeStruct((1031, 257), np.float32)
        diags = audit_plan(plan, {"w": big})
        assert [d.code for d in diags] == ["PT-SHARD-203"]
        # clean twins: a shardable big leaf, and a small replicated one
        assert audit_plan(plan, {
            "w": jax.ShapeDtypeStruct((1024, 512), np.float32)}) == []
        assert audit_plan(plan, {
            "b": jax.ShapeDtypeStruct((7,), np.float32)}) == []
        # threshold is tunable
        assert audit_plan(plan, {"w": big},
                          byte_threshold=1 << 30) == []

    def test_describe_embeds_audit_summary(self, eight_devices):
        from paddle_tpu.parallel.plan import Plan

        plan = Plan(fsdp=8)
        desc = plan.describe({
            "w": jax.ShapeDtypeStruct((1031, 257), np.float32)})
        audit = desc["audit"]
        assert audit["warnings"] == 1 and audit["errors"] == 0
        assert any("PT-SHARD-203" in f for f in audit["findings"])

    def test_audit_summary_truncates(self):
        diags = [Diagnostic(code="PT-SHARD-203", severity="warning",
                            message=f"leaf {i}") for i in range(20)]
        s = audit_summary(diags, limit=4)
        assert len(s["findings"]) == 4 and s["truncated"] == 16
        assert s["warnings"] == 20


# ---------------------------------------------------------------------------
# Repo linter (analysis/lint.py + tools/lint.py)
# ---------------------------------------------------------------------------


class TestLint:
    def test_torn_state_write_flagged_atomic_clean(self):
        src = (
            "import json\n"
            "def save(path, d):\n"
            "    with open(path, 'w') as f:\n"
            "        json.dump(d, f)\n")
        diags = lint_source(src, "x.py")
        assert [d.code for d in diags] == ["PT-LINT-301"]
        assert diags[0].line == 4
        # clean twins: atomic helper, and a self-staging writer
        clean = (
            "import json\n"
            "from paddle_tpu.utils.atomic import atomic_write_text\n"
            "def save(path, d):\n"
            "    atomic_write_text(path, json.dumps(d))\n")
        assert lint_source(clean, "x.py") == []
        staged = (
            "import json, os\n"
            "def save(path, d):\n"
            "    tmp = path + '.tmp'\n"
            "    with open(tmp, 'w') as f:\n"
            "        json.dump(d, f)\n"
            "    os.replace(tmp, path)\n")
        assert lint_source(staged, "x.py") == []

    def test_wall_clock_in_span_flagged_outside_clean(self):
        src = (
            "import time\n"
            "def f():\n"
            "    with Span('step'):\n"
            "        t = time.time()\n")
        diags = lint_source(src, "x.py")
        assert [d.code for d in diags] == ["PT-LINT-302"]
        clean = (
            "import time\n"
            "def f():\n"
            "    t0 = time.time()\n"
            "    with Span('step'):\n"
            "        t = time.perf_counter()\n")
        assert lint_source(clean, "x.py") == []

    def test_unnamed_thread_flagged_named_clean(self):
        src = ("import threading\n"
               "t = threading.Thread(target=print)\n")
        diags = lint_source(src, "x.py")
        assert [d.code for d in diags] == ["PT-LINT-303"]
        clean = ("import threading\n"
                 "t = threading.Thread(target=print, name='pt-x')\n")
        assert lint_source(clean, "x.py") == []

    def test_device_get_into_donating_call_flagged_copy_clean(self):
        src = (
            "import jax\n"
            "def f(state):\n"
            "    view = jax.device_get(state)\n"
            "    return train_step(view)\n")
        diags = lint_source(src, "x.py")
        assert [d.code for d in diags] == ["PT-LINT-304"]
        # inline form too
        inline = ("import jax\n"
                  "def f(s):\n"
                  "    return _jit_train(jax.device_get(s))\n")
        assert [d.code for d in lint_source(inline, "x.py")] == \
            ["PT-LINT-304"]
        clean = (
            "import jax\n"
            "import numpy as np\n"
            "def f(state):\n"
            "    snap = np.array(jax.device_get(state))\n"
            "    keep(snap)\n"
            "    return train_step(state)\n")
        assert lint_source(clean, "x.py") == []

    def test_leftover_debug_hooks_flagged(self):
        src = ("import jax\n"
               "def f(x):\n"
               "    jax.debug.print('x={}', x)\n"
               "    breakpoint()\n"
               "    return x\n")
        diags = lint_source(src, "x.py")
        assert [d.code for d in diags] == ["PT-LINT-305", "PT-LINT-305"]
        assert lint_source("def f(x):\n    return x\n", "x.py") == []

    def test_suppression_requires_reason(self):
        flagged = ("import threading\n"
                   "t = threading.Thread(target=print)"
                   "  # pt-lint: disable=PT-LINT-303\n")
        diags = lint_source(flagged, "x.py")
        assert len(diags) == 1 and "require a reason" in diags[0].message
        ok = ("import threading\n"
              "t = threading.Thread(target=print)"
              "  # pt-lint: disable=PT-LINT-303 interp-owned helper\n")
        assert lint_source(ok, "x.py") == []
        # the line-above form works too
        above = ("import threading\n"
                 "# pt-lint: disable=PT-LINT-303 interp-owned helper\n"
                 "t = threading.Thread(target=print)\n")
        assert lint_source(above, "x.py") == []
        # a suppression for a DIFFERENT code does not silence the hit
        wrong = ("import threading\n"
                 "t = threading.Thread(target=print)"
                 "  # pt-lint: disable=PT-LINT-305 nope\n")
        assert len(lint_source(wrong, "x.py")) == 1

    def test_quantized_pool_branch_outside_boundary_flagged(self):
        """PT-LINT-308: isinstance dispatch on QuantizedPool belongs
        to ops/paged_kv.py (THE storage-form boundary); anywhere else
        it re-opens the dual-dispatch drift hazard. Constructing or
        importing the class is fine — only the isinstance branch is
        the dispatch."""
        src = ("from paddle_tpu.ops.paged_kv import QuantizedPool\n"
               "def attend_like(pool):\n"
               "    if isinstance(pool, QuantizedPool):\n"
               "        return 1\n"
               "    return 0\n")
        diags = lint_source(src, "paddle_tpu/serving.py")
        assert [d.code for d in diags] == ["PT-LINT-308"]
        # tuple-of-classes form flags too
        tup = ("def f(pool):\n"
               "    return isinstance(pool, (tuple, QuantizedPool))\n")
        assert [d.code for d in lint_source(tup, "x.py")] == \
            ["PT-LINT-308"]
        # clean twins: the boundary file itself, and non-branch uses
        assert lint_source(src, "paddle_tpu/ops/paged_kv.py") == []
        mk = ("from paddle_tpu.ops.paged_kv import QuantizedPool\n"
              "def build(q, s):\n"
              "    return QuantizedPool(q, s)\n")
        assert lint_source(mk, "paddle_tpu/serving.py") == []

    def test_unfenced_timing_delta_flagged_fenced_clean(self):
        """PT-LINT-309: a perf_counter delta around a jitted dispatch
        with no device fence before the stop-stamp measures dispatch,
        not compute (the async-dispatch mirage)."""
        src = ("import time, jax\n"
               "def bench(f, x):\n"
               "    g = jax.jit(f)\n"
               "    t0 = time.perf_counter()\n"
               "    out = g(x)\n"
               "    t1 = time.perf_counter()\n"
               "    return t1 - t0\n")
        diags = lint_source(src, "x.py")
        assert [d.code for d in diags] == ["PT-LINT-309"]
        assert diags[0].line == 7
        # clean twin: block_until_ready fences before the stop stamp
        clean = ("import time, jax\n"
                 "def bench(f, x):\n"
                 "    g = jax.jit(f)\n"
                 "    t0 = time.perf_counter()\n"
                 "    out = g(x)\n"
                 "    jax.block_until_ready(out)\n"
                 "    t1 = time.perf_counter()\n"
                 "    return t1 - t0\n")
        assert lint_source(clean, "x.py") == []

    def test_unfenced_timing_fence_forms_and_direct_dispatch(self):
        # float(loss) inside the timed loop is a fence; a direct
        # jax.jit(f)(x) dispatch with no fence flags
        looped = ("import time, jax\n"
                  "def run(step, batches):\n"
                  "    s = jax.jit(step)\n"
                  "    t0 = time.perf_counter()\n"
                  "    for b in batches:\n"
                  "        loss = s(b)\n"
                  "        total = float(loss)\n"
                  "    dt = time.perf_counter() - t0\n"
                  "    return dt\n")
        assert lint_source(looped, "x.py") == []
        direct = ("import time, jax\n"
                  "def bench(f, x):\n"
                  "    t0 = time.perf_counter()\n"
                  "    y = jax.jit(f)(x)\n"
                  "    dt = time.perf_counter() - t0\n"
                  "    return dt\n")
        diags = lint_source(direct, "x.py")
        assert [d.code for d in diags] == ["PT-LINT-309"]

    def test_unfenced_timing_local_fence_helper_recognized(self):
        """A file-local helper whose body fences (the tools' idiom:
        ``def _fence(out): float(jax.device_get(out))``) counts as a
        fence at its call sites — the dogfood false-positive class."""
        src = ("import time, jax\n"
               "def _fence(out):\n"
               "    float(jax.device_get(out))\n"
               "def bench(f, x):\n"
               "    g = jax.jit(f)\n"
               "    t0 = time.perf_counter()\n"
               "    out = g(x)\n"
               "    _fence(out)\n"
               "    dt = time.perf_counter() - t0\n"
               "    return dt\n")
        assert lint_source(src, "x.py") == []

    def test_unbounded_network_call_flagged_timeout_clean(self):
        """PT-LINT-310: a serving/telemetry/resilience-module network
        call without an explicit timeout= is an unbounded hop — one
        SIGSTOP'd peer wedges the caller forever (the gray-failure
        plane's whole premise is that every hop is bounded)."""
        src = ("import urllib.request\n"
               "def fetch(url):\n"
               "    with urllib.request.urlopen(url) as r:\n"
               "        return r.read()\n")
        diags = lint_source(src, "paddle_tpu/telemetry/server.py")
        assert [d.code for d in diags] == ["PT-LINT-310"]
        assert diags[0].line == 3
        assert lint_source(src, "paddle_tpu/serving_router.py") != []
        # clean twins: timeout kwarg, and the positional form
        kw = ("import urllib.request\n"
              "def fetch(url):\n"
              "    with urllib.request.urlopen(url, timeout=5.0) as r:\n"
              "        return r.read()\n")
        assert lint_source(kw, "paddle_tpu/telemetry/server.py") == []
        pos = ("from urllib.request import urlopen\n"
               "def fetch(url, body):\n"
               "    return urlopen(url, body, 5.0).read()\n")
        assert lint_source(pos, "paddle_tpu/resilience/faults.py") == []
        # outside the serving/telemetry/resilience planes: not flagged
        # (an offline tool may legitimately block)
        assert lint_source(src, "paddle_tpu/utils/fetch.py") == []
        assert lint_source(src, "tools/timeline.py") == []

    def test_unbounded_socket_connect_flagged_timeout_clean(self):
        src = ("import socket\n"
               "def dial(addr):\n"
               "    return socket.create_connection(addr)\n")
        diags = lint_source(src, "paddle_tpu/autoscale/scaler.py")
        assert [d.code for d in diags] == ["PT-LINT-310"]
        kw = ("import socket\n"
              "def dial(addr, t):\n"
              "    return socket.create_connection(addr, timeout=t)\n")
        assert lint_source(kw, "paddle_tpu/autoscale/scaler.py") == []
        pos = ("import socket\n"
               "def dial(addr):\n"
               "    return socket.create_connection(addr, 2.0)\n")
        assert lint_source(pos, "paddle_tpu/autoscale/scaler.py") == []

    def test_unparsable_file_is_a_finding(self):
        diags = lint_source("def f(:\n", "broken.py")
        assert len(diags) == 1 and "does not parse" in diags[0].message

    def test_lint_paths_walks_trees(self, tmp_path):
        (tmp_path / "a.py").write_text(
            "import threading\nt = threading.Thread(target=print)\n")
        sub = tmp_path / "sub"
        sub.mkdir()
        (sub / "b.py").write_text("breakpoint()\n")
        (sub / "notes.txt").write_text("not python\n")
        diags = lint_paths([str(tmp_path)])
        assert [d.code for d in diags] == ["PT-LINT-303", "PT-LINT-305"]

    def test_repo_tree_lints_clean(self):
        """The dogfood gate as a tier-1 test: every pre-existing finding
        in paddle_tpu/ was fixed (atomic writes, thread names) — a new
        violation fails here AND in the ci.sh lint stage."""
        findings = lint_paths([os.path.join(REPO, "paddle_tpu")])
        assert findings == [], format_diagnostics(findings)

    def test_cli_json_and_select(self, tmp_path, capsys):
        lint_tool = load_tool("lint")
        (tmp_path / "a.py").write_text("breakpoint()\n")
        rc = lint_tool.main(["--format=json", str(tmp_path)])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1 and out["count"] == 1
        assert out["findings"][0]["code"] == "PT-LINT-305"
        assert out["findings"][0]["line"] == 1
        # select filters to the named codes
        rc = lint_tool.main(["--select=PT-LINT-303", str(tmp_path)])
        assert rc == 0 and "lint clean" in capsys.readouterr().out
        # unknown code is a usage error
        assert lint_tool.main(["--select=PT-BOGUS-9", str(tmp_path)]) == 2
