#!/usr/bin/env python3
"""Whether two checkouts hand the compiler the same programs, kernels'
line numbers included.

    JAX_PLATFORMS=cpu python3 tools/program_hashes.py [--root CHECKOUT] [cell ...]

Lowers each cell's programs as ``benchmark/rehearse_compile.py`` does (a
described ``v5e:2x2``, no chip attached) and compiles nothing: one line a
program with two hashes of its lowered text. **``cut``**: every Pallas
kernel's serialised body taken out, so the program around the kernels.
**``located``**: every body parsed and printed WITH its locations (the
file, line and column of the kernel's own lines and of its ten innermost
call sites), the checkout's root written ``<ROOT>``. A raw text hash
cannot be compared across two checkouts (the bodies hold their paths);
``located`` can, and it is what the chip's compile cache keys on besides
the path: equal ``located`` hashes on a parent under ``build/parent`` and
on the change mean that the change's serving programs find the parent's
executables in a cache made from the same path, and an edit that moves a
line above a kernel's call site shows here before it shows as
``setup_s`` on the chip.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import os
import re
import sys

_BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def cut(text: str) -> str:
    """``text`` with every kernel's serialised body taken out."""
    return _BODY.sub(r"\1\3", text)


def located(text: str, root: str) -> str:
    """``text`` with every kernel's serialised body replaced by its
    assembly, locations included, ``root`` written ``<ROOT>``."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def asm(m):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            body = ir.Module.parse(base64.b64decode(m.group(2)))
            said = body.operation.get_asm(enable_debug_info=True)
        return m.group(1) + said + m.group(3)

    return _BODY.sub(asm, text).replace(root, "<ROOT>")


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout to lower")
    ap.add_argument("cells", nargs="*")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    os.chdir(root)
    import benchmark.rehearse_compile as rehearse

    def report(name, lowered):
        text = lowered.as_text()
        print(f"{name}: cut {_sha(cut(text))} located "
              f"{_sha(located(text, root))} "
              f"({len(_BODY.findall(text))} kernels)", flush=True)

    rehearse.report = report
    return rehearse.main(args.cells)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
