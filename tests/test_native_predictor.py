"""C++ serving predictor tests — hermetic coverage of the native artifact
parsing (manifest JSON, npz/zip/npy reading) through the real C ABI, plus
graceful typed failure when no PJRT device exists (CI has none; on a TPU VM
``compile(libtpu.so)`` + ``run`` serve the model — exercised by the ptserve
demo binary there)."""

import os
import subprocess

import numpy as np
import pytest

import paddle_tpu as pt

NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu", "native")


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    """Export a small static-graph model with save_inference_model."""
    from paddle_tpu import static

    d = str(tmp_path_factory.mktemp("serving_model"))
    prog = static.Program()
    with static.program_guard(prog):
        x = prog.data("x", (-1, 8))
        h = static.layers.fc(x, 6, act="relu")
        out = static.layers.fc(h, 3, act="softmax")
    exe = static.Executor(scope=static.Scope())  # isolate from global scope
    exe.run_startup(prog)
    static.save_inference_model(d, ["x"], [out], exe, prog)
    return d


class TestArtifactParsing:
    def test_load_and_introspect(self, model_dir):
        from paddle_tpu.native import NativePredictor

        p = NativePredictor(model_dir)
        assert p.feed_names == ["x"]
        assert len(p.fetch_names) == 1
        assert p.num_params() == 4  # 2x weight + 2x bias
        p.close()

    def test_npz_params_match_numpy(self, model_dir):
        """The C++ zip/npy reader must agree byte-for-byte with numpy."""
        from paddle_tpu.native import NativePredictor

        ref = dict(np.load(os.path.join(model_dir, "params.npz")))
        p = NativePredictor(model_dir)
        for name, arr in ref.items():
            got = p.param(name)
            assert got.dtype == arr.dtype
            np.testing.assert_array_equal(got, arr)
        p.close()

    def test_missing_dir_fails_typed(self, tmp_path):
        from paddle_tpu.native import NativePredictor

        with pytest.raises(RuntimeError, match="manifest"):
            NativePredictor(str(tmp_path / "nope"))

    def test_corrupt_npz_fails_typed(self, model_dir, tmp_path):
        import shutil

        from paddle_tpu.native import NativePredictor

        bad = tmp_path / "bad"
        shutil.copytree(model_dir, bad)
        (bad / "params.npz").write_bytes(b"not a zip file")
        with pytest.raises(RuntimeError, match="zip|EOCD|npz"):
            NativePredictor(str(bad))

    def test_run_without_compile_fails_typed(self, model_dir):
        from paddle_tpu.native import NativePredictor

        p = NativePredictor(model_dir)
        with pytest.raises(RuntimeError, match="not compiled"):
            p.run({"x": np.zeros((2, 8), np.float32)})
        p.close()


class TestPythonPredictorParity:
    def test_python_predictor_runs_artifact(self, model_dir):
        """The same artifact serves through the Python path (jax.export)."""
        from paddle_tpu import static

        pred = static.load_inference_model(model_dir)
        out = pred.run({"x": np.ones((4, 8), np.float32)})
        assert out[0].shape == (4, 3)
        np.testing.assert_allclose(out[0].sum(axis=1), 1.0, rtol=1e-5)

    def test_manifest_v2_fields(self, model_dir):
        import json

        with open(os.path.join(model_dir, "manifest.json")) as f:
            m = json.load(f)
        assert m["format"] == "stablehlo+npz/v2"
        assert m["arg_order"][0].startswith("param:")
        assert m["arg_order"][-1] == "feed:x"
        assert m["feed_dtypes"] == {"x": "float32"}
        assert os.path.exists(os.path.join(model_dir, "program.mlir.bc"))


class TestServeDemoBinary:
    def test_builds_and_reports_clean_error_without_device(self, model_dir):
        """ptserve (demo_trainer.cc parity) must build; without TPU hardware
        it should fail at compile/client stage with a clean message, not
        crash."""
        r = subprocess.run(["make", "-C", NATIVE_DIR, "ptserve"],
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr
        binary = os.path.join(NATIVE_DIR, "ptserve")
        import libtpu

        plugin = os.path.join(os.path.dirname(libtpu.__file__), "libtpu.so")
        # healthy outcomes return within the bound: full serve on a real
        # TPU VM (tiny model, first compile 20-40s — the default keeps
        # ~3x margin for a loaded VM / cold libtpu cache) or a typed
        # client/compile error with no device (also when another
        # process holds libtpu's lock: tests/test_tpu_aot_compile.py on
        # another xdist worker). A PJRT client that never comes up is an
        # environment condition, not a predictor defect, and it must
        # not eat minutes of the tier-1 budget. PTSERVE_TIMEOUT tunes
        # the bound for slow hardware.
        bound = float(os.environ.get("PTSERVE_TIMEOUT", "120"))
        try:
            r = subprocess.run([binary, model_dir, plugin, "2"],
                               capture_output=True, text=True,
                               timeout=bound)
        except subprocess.TimeoutExpired:
            pytest.skip(f"ptserve PJRT client init did not return within "
                        f"{bound:.0f}s — TPU unreachable "
                        f"(raise PTSERVE_TIMEOUT on slow hardware)")
        if r.returncode == 0:
            assert "ok" in r.stdout  # real TPU present: full serve worked
        else:
            # no local TPU: must be the typed compile/client error path
            assert r.returncode in (1, 2), (r.returncode, r.stdout, r.stderr)
            assert "model loaded" in r.stdout


class TestNativeCppUnits:
    def test_cpp_unit_tests_pass(self):
        """Run the C++ parser unit tests (reference *_test.cc convention)."""
        r = subprocess.run(["make", "-C", NATIVE_DIR, "test"],
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "predictor_test: all ok" in r.stdout


class TestControlFlowArtifact:
    def test_while_decode_artifact_parses_natively(self, tmp_path):
        """A block-DSL While program's artifact loads through the C++
        predictor's parsers (manifest + StableHLO bytecode + params) —
        control flow is plain StableHLO to the native serving path; the
        compile/run leg runs on a PJRT device (ptserve on a TPU VM)."""
        import importlib.util

        from paddle_tpu import static
        from paddle_tpu.native import NativePredictor

        spec = importlib.util.spec_from_file_location(
            "mtmod", os.path.join(os.path.dirname(NATIVE_DIR), "..",
                                  "tests", "test_fluid_book_mt.py"))
        mt = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mt)
        prog, ids = mt._greedy_decode_program()
        exe = static.Executor(scope=static.Scope())
        exe.run_startup(prog)
        d = str(tmp_path / "decode_artifact")
        static.save_inference_model(
            d, ["src_word_id", "src_word_id@LEN"], [ids], exe,
            main_program=prog)
        assert os.path.exists(os.path.join(d, "program.mlir.bc"))
        p = NativePredictor(d)
        assert p.feed_names == ["src_word_id", "src_word_id@LEN"]
        assert len(p.fetch_names) == 1
        assert p.num_params() > 0  # vemb + decoder weights
        p.close()
