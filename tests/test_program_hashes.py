"""``tools/program_hashes.py``: a program's lowered text with its Pallas
kernels' bodies cut, or printed with their locations and the checkout's
root written ``<ROOT>``, so that two checkouts can be compared. Also
the fact the tool exists for: a kernel's body carries the file and line
of its call sites, this test's own among them, so the same call made
from another line is another program to the compile cache."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))
import program_hashes as H  # noqa: E402

FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
HERE = '<ROOT>/tests/test_program_hashes.py":'


def one_line(q, k, v):
    return FA.flash_attention(q, k, v, causal=True)     # CALL SITE 1


def another_line(q, k, v):
    return FA.flash_attention(q, k, v, causal=True)     # CALL SITE 2


def line_of(mark):
    with open(__file__) as f:
        return 1 + [i for i, ln in enumerate(f) if ln.endswith(mark + "\n")][0]


@pytest.fixture()
def lowered(monkeypatch):
    """The flash forward lowered for the TPU (no chip needed) from two
    lines of this file."""
    monkeypatch.setattr(FA, "_use_interpret", lambda: False)
    q = jnp.zeros((1, 256, 4, 128), jnp.bfloat16)
    return [jax.export.export(jax.jit(f), platforms=["tpu"])(
        q, q, q).mlir_module() for f in (one_line, another_line)]


def test_a_kernels_body_is_cut_or_printed_with_its_call_sites(lowered):
    text = lowered[0]
    assert len(H._BODY.findall(text)) == 1
    cut = H.cut(text)
    assert len(cut) < len(text) // 2 and not H._BODY.findall(cut)
    said = H.located(text, ROOT)
    assert "<ROOT>/paddle_tpu/ops/pallas/flash_attention.py" in said
    assert ROOT + "/" not in said
    assert HERE + f'{line_of("# CALL SITE 1")}:' in said


def test_the_same_call_from_another_line_is_another_program(lowered):
    one, other = (H.located(t, ROOT) for t in lowered)
    assert HERE + f'{line_of("# CALL SITE 2")}:' in other
    assert H._sha(one) != H._sha(other)
