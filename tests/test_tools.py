"""Tooling tests: API.spec freeze check, timeline merge, program
printer/dot export, install_check, profiler chrome-trace roundtrip."""

import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestApiSpec:
    def test_api_surface_matches_spec(self):
        """The API-stability test itself (reference: tools/diff_api.py in
        CI). If this fails you changed the public surface — intentional
        changes re-run tools/print_signatures.py --update."""
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "print_signatures.py"), "--check"],
            capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr


class TestTimeline:
    def test_merge_two_ranks(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import timeline

        r0 = [{"name": "step", "ph": "X", "ts": 1000.0, "dur": 5.0,
               "pid": 77, "tid": 1}]
        r1 = [{"name": "step", "ph": "X", "ts": 2000.0, "dur": 6.0,
               "pid": 88, "tid": 1}]
        p0, p1 = tmp_path / "r0.json", tmp_path / "r1.json"
        p0.write_text(json.dumps(r0))
        p1.write_text(json.dumps(r1))
        out = tmp_path / "merged.json"
        assert timeline.main([str(p0), str(p1),
                              "--output", str(out)]) == 0
        data = json.loads(out.read_text())["traceEvents"]
        xs = [e for e in data if e.get("ph") == "X"]
        assert {e["pid"] for e in xs} == {0, 1}  # remapped lanes
        assert all(e["ts"] == 0.0 for e in xs)  # aligned to common zero
        metas = [e for e in data if e.get("ph") == "M"]
        assert len(metas) == 2

    def test_profiler_dump_feeds_timeline(self, tmp_path):
        import importlib

        # core/__init__ re-exports a `profiler` context-manager function
        # under the same name; import the module itself
        prof = importlib.import_module("paddle_tpu.core.profiler")

        prof.start_profiler()
        with prof.record_event("fwd"):
            pass
        with prof.record_event("bwd"):
            pass
        dump = tmp_path / "prof.json"
        events = prof.stop_profiler(timeline_path=str(dump))
        assert len(events) == 2
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import timeline

        out = tmp_path / "m.json"
        assert timeline.main([str(dump), "--output", str(out)]) == 0
        names = {e["name"] for e in
                 json.loads(out.read_text())["traceEvents"]}
        assert {"fwd", "bwd"} <= names


class TestDebug:
    def _program(self):
        from paddle_tpu import static

        prog = static.Program()
        with static.program_guard(prog):
            x = prog.data("x", (-1, 4))
            h = static.layers.fc(x, 3, act="relu")
            static.layers.mean(h)
        return prog

    def test_program_to_string(self):
        from paddle_tpu import debug

        s = debug.program_to_string(self._program())
        assert "param" in s and "ops:" in s and "fc" in s.lower() or "mul" in s

    def test_program_to_dot(self, tmp_path):
        from paddle_tpu import debug

        prog = self._program()
        dot = debug.program_to_dot(prog)
        assert dot.startswith("digraph") and dot.rstrip().endswith("}")
        assert '"v_x"' in dot
        path = tmp_path / "g.dot"
        debug.draw_program(prog, str(path))
        assert path.exists()


class TestInstallCheck:
    def test_run_check(self, capsys):
        import paddle_tpu as pt

        assert pt.install_check.run_check(verbose=True)
        out = capsys.readouterr().out
        assert "installed correctly" in out


class TestOpFrequence:
    def test_counts_program_ops(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        from op_frequence import op_freq_statistic

        from paddle_tpu import static

        prog = static.Program()
        with static.program_guard(prog):
            x = prog.data("x", (-1, 4))
            h = static.layers.fc(x, 4, act="relu")
            h2 = static.layers.fc(h, 2)
            loss = static.layers.mean(h2)
            static.SGD(0.1).minimize(loss)
        stats = op_freq_statistic(prog)
        assert stats.get("fc", 0) == 2
        assert stats.get("backward", 0) == 1
        assert sum(stats.values()) == len(prog.nodes)


class TestOpBench:
    def test_hot_op_cases_file_runs(self, tmp_path):
        """The shipped hot-op case set (tools/op_bench_cases.json) stays
        loadable and each case executes — including the typed int specs
        for labels and int8 operands."""
        root = REPO
        # a reduced inline config keeps the test fast while covering the
        # same materialize paths (float list, typed int dict, scalar)
        cases = [
            {"op": "ops.math.matmul", "args": {"x": [8, 8], "y": [8, 8]},
             "grad": True},
            {"op": "ops.fused_loss.mean_linear_cross_entropy",
             "args": {"hidden": [16, 8], "weight": [8, 50], "bias": [50],
                      "labels": {"shape": [16], "dtype": "int32",
                                 "low": 0, "high": 50}},
             "kwargs": {"chunk": 16}, "grad": True},
            {"op": "ops.pallas.quant_matmul",
             "args": {"a_i8": {"shape": [8, 8], "dtype": "int8",
                               "low": -127, "high": 127},
                      "b_i8": {"shape": [8, 8], "dtype": "int8",
                               "low": -127, "high": 127},
                      "a_scale": 0.01, "b_scale": 0.02}},
        ]
        cfg = str(tmp_path / "cases.json")
        with open(cfg, "w") as f:
            json.dump(cases, f)
        r = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "op_bench.py"),
             "--config", cfg, "--repeat", "1", "--platform", "cpu"],
            capture_output=True, text=True, timeout=500)
        lines = [json.loads(l) for l in r.stdout.splitlines()
                 if l.startswith("{")]
        assert len(lines) == 3, r.stdout + r.stderr
        assert all("forward_ms" in l for l in lines)
        assert sum("grad_ms" in l for l in lines) == 2
        # the shipped file parses and names resolvable ops
        with open(os.path.join(root, "tools", "op_bench_cases.json")) as f:
            shipped = json.load(f)
        from tools.op_bench import resolve

        for case in shipped:
            assert callable(resolve(case["op"]))


class TestCommReport:
    def test_collective_traffic_parses_scalar_and_tuple_ops(self):
        """The HLO tally behind tools/comm_report.py: scalar-result,
        TUPLE-result (grad-bucket all-reduces), async -start/-done pairs
        (counted once), and non-collective lines."""
        from conftest import load_tool

        cr = load_tool("comm_report")

        hlo = "\n".join([
            "  %ar.1 = f32[8,64]{1,0} all-reduce(%p0), replica_groups={}",
            "  %ar.2 = (f32[128]{0}, bf16[64,2]{1,0}) all-reduce(%a, %b)",
            # real async form: the -start result tuple carries the
            # operand alias + context scalars; only the -done's result
            # is the output payload
            "  %cp.s = (f32[4,4]{1,0}, f32[4,4]{1,0}, u32[], u32[]) "
            "collective-permute-start(%x)",
            "  %cp.d = f32[4,4]{1,0} collective-permute-done(%cp.s)",
            "  %add = f32[8]{0} add(%y, %z)",
        ])
        got = cr.collective_traffic(hlo)
        assert got["all-reduce"][0] == 2
        assert got["all-reduce"][1] == 8 * 64 * 4 + 128 * 4 + 64 * 2 * 2
        # async pair counted ONCE, at the -done payload
        assert got["collective-permute"] == (1, 4 * 4 * 4)
        assert "add" not in got and len(got) == 2
