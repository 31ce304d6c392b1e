#!/usr/bin/env python3
"""What each product of a compiled program reads, and what the compiler
expects it to cost.

    JAX_PLATFORMS=cpu python3 tools/product_operands.py [--save DIR] [--quiet] <cell> ...
    python3 tools/product_operands.py --text <compiled text> ...

For a cell's programs as ``benchmark/rehearse_compile.py`` builds them
(a described ``v5e:2x2``, no chip attached; the compiler is the chip's)
or for a compiled program's text (``compiled.as_text()``), one line a
**product fusion**, a fusion that holds a ``convolution``, which is what
a ``dot_general`` compiles to: its output, each operand's type and
where it comes from (an entry ``parameter`` by its name, a ``prefetched``
copy into fast memory, another ``fusion`` by its root's operation, a
loop's ``carry``), the compiler's own ``estimated_cycles`` at 1.5 GHz
beside the product's time at the bf16 peak, and the scope and pass of
its ``op_name`` (``benchmark/harness/scope_table.py``'s reading). An
operand that no ``convolution`` of the fusion reads is marked
``(beside)``: it belongs to the arithmetic fused around the product.
Then the sums by scope and pass, and by the matrix parameter that feeds
the product (float32 read from HBM in the fusion, float32 prefetched,
narrow, none). It reads compiled text only: nothing here is a time
measured on a chip.
"""

from __future__ import annotations

import argparse
import math
import os
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)    # benchmark.harness, benchmark.rehearse_compile
CLOCK_HZ, PEAK_FLOPS = 1.5e9, 197e12        # a v5e core; its bf16 peak

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%(\S+)\s+\(.*\)\s+->\s+.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%(\S+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
_SHAPE = re.compile(r"^\(?([a-z]+[0-9]*)\[([0-9,]*)\]")
_SEE_THROUGH = ("bitcast", "copy", "reshape", "transpose")


class Instruction(NamedTuple):
    name: str
    type: str           # as printed, layout and memory space included
    opcode: str
    operands: Tuple[str, ...]
    args: str           # what stands between the opcode's parentheses
    rest: str           # everything after them
    computation: str

    @property
    def op_name(self) -> str:
        m = re.search(r'op_name="((?:[^"\\]|\\.)*)"', self.rest)
        return m.group(1).replace("\\'", "'") if m else ""

    @property
    def dtype(self) -> str:
        m = _SHAPE.match(self.type)
        return m.group(1) if m else "?"

    @property
    def dims(self) -> Tuple[int, ...]:
        m = _SHAPE.match(self.type)
        return tuple(int(d) for d in m.group(2).split(",") if d) if m else ()

    @property
    def shape(self) -> str:
        """``bf16[8,2048,8192]``, with ``S(1)`` where the layout places
        it in fast memory."""
        m = _SHAPE.match(self.type)
        fast = " S(1)" if re.search(r"S\(1\)\}", self.type) else ""
        return (f"{m.group(1)}[{m.group(2)}]{fast}" if m
                else self.type.split("{")[0])


def _balanced(text: str, start: int) -> int:
    """Index of the parenthesis that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        depth += text[i] == "("
        depth -= text[i] == ")"
        if depth == 0:
            return i
    return len(text) - 1


def parse(text: str) -> Tuple[Dict[str, Dict[str, Instruction]], str]:
    """({computation: {instruction name: Instruction}}, the entry's name)
    of a compiled program's text."""
    comps: Dict[str, Dict[str, Instruction]] = {}
    entry, current = "", None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = head.group(2)
            comps[current] = {}
            entry = current if head.group(1) else entry
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line) if current else None
        if not m:
            continue
        name, body = m.groups()
        op = _OPCODE.search(" " + body)
        if not op:
            continue
        open_at = op.end() - 2      # body is one shorter than " " + body
        close_at = _balanced(body, open_at)
        comps[current][name] = Instruction(
            name, body[:op.start()].strip(), op.group(1),
            tuple(re.findall(r"%([^\s,()]+)", body[open_at:close_at])),
            body[open_at + 1:close_at], body[close_at + 1:], current)
    return comps, entry


def _called(ins: Instruction) -> Optional[str]:
    m = re.search(r"calls=%([^\s,]+)", ins.rest)
    return m.group(1) if m else None


def convolutions(comps, computation: str) -> List[Instruction]:
    """The ``convolution``s of a fused computation, nested fusions'
    included."""
    out = []
    for ins in comps.get(computation, {}).values():
        if ins.opcode == "convolution":
            out.append(ins)
        elif ins.opcode == "fusion" and _called(ins):
            out.extend(convolutions(comps, _called(ins)))
    return out


def feeds(comps, computation: str) -> set:
    """The indices of a fused computation's parameters that one of its
    ``convolution``s reads, through whatever converts, layout changes
    and nested fusions lie between: the operands of the PRODUCT, as
    against those of the arithmetic fused around it (a weight-gradient
    product shares its fusion with Adam's update of that weight)."""
    scope, seen = comps.get(computation, {}), {}

    def reach(name):
        if name not in seen:
            ins = scope.get(name)
            seen[name] = set()          # HLO has no cycles; a guard
            if ins is None:
                return seen[name]
            if ins.opcode == "parameter":
                seen[name] = {int(ins.args)}
            else:
                seen[name] = set().union(
                    *(reach(o) for o in ins.operands)) \
                    if ins.operands else set()
        return seen[name]

    out = set()
    for ins in scope.values():
        if ins.opcode == "convolution":
            out |= set().union(*(reach(o) for o in ins.operands))
        elif ins.opcode == "fusion" and _called(ins):
            inner = feeds(comps, _called(ins))
            out |= set().union(set(), *(
                reach(o) for i, o in enumerate(ins.operands) if i in inner))
    return out


def product_flops(comps, conv: Instruction) -> float:
    """2 x output elements x contracted size: every number of the right
    operand but its output features is contracted (no grouped product
    in these programs)."""
    rhs = comps[conv.computation].get(conv.operands[1])
    labels = re.search(r"dim_labels=\w+_(\w+)->", conv.rest)
    if rhs is None or not labels or not rhs.dims:
        return float("nan")
    o_size = rhs.dims[labels.group(1).index("o")]
    return 2.0 * math.prod(conv.dims) * math.prod(rhs.dims) / o_size


def origin(comps, entry: str, ins: Instruction) -> Tuple[str, Instruction]:
    """(where an operand comes from, the instruction at that end):
    ``parameter <name>`` for an argument of the program, ``prefetched
    <...>`` through a ``copy-start`` / ``copy-done`` pair, ``fusion
    <root operation>``, ``carry`` for a loop's or a call's own
    parameter, else the opcode. Layout changes are seen through."""
    scope = comps[ins.computation]
    if ins.opcode == "copy-done":
        start = scope.get(ins.operands[0])
        src = scope.get(start.operands[0]) if start else None
        what, end = origin(comps, entry, src) if src else ("?", ins)
        return f"prefetched {what}", end
    if ins.opcode in _SEE_THROUGH and ins.operands[0] in scope:
        return origin(comps, entry, scope[ins.operands[0]])
    if ins.opcode == "parameter":
        if ins.computation != entry:
            return "carry", ins
        return f"parameter {ins.op_name or ins.name}", ins
    if ins.opcode == "get-tuple-element":
        return "carry", ins
    if ins.opcode == "fusion":
        return f"fusion {ins.op_name.rsplit('/', 1)[-1] or ins.name}", ins
    return ins.opcode, ins


class Product(NamedTuple):
    fusion: Instruction
    output: str
    # of each operand: shape, origin, the instruction at that end, and
    # whether a convolution of the fusion reads it (``feeds``)
    operands: Tuple[Tuple[str, str, Instruction, bool], ...]
    estimate_ms: float      # nan where the compiler gave no estimate
    peak_ms: float
    scope: Optional[str]
    which: str              # forward / recompute / backward


def products(text: str) -> List[Product]:
    """The product fusions of a compiled program, in the text's order."""
    from benchmark.harness import scope_table

    listed = scope_table.listed_scopes() or ()
    comps, entry = parse(text)
    fused = {_called(o) for c in comps.values() for o in c.values()
             if o.opcode == "fusion"}
    out = []
    for comp_name, comp in comps.items():
        if comp_name in fused:
            continue        # a fusion nested in a fusion is its parent's
        for ins in comp.values():
            if ins.opcode != "fusion" or not _called(ins):
                continue
            convs = convolutions(comps, _called(ins))
            if not convs:
                continue
            cycles = re.search(r'"estimated_cycles":"(\d+)"', ins.rest)
            ops, fed = [], feeds(comps, _called(ins))
            for i, name in enumerate(ins.operands):
                operand = comp.get(name)
                if operand is None:
                    continue
                what, end = origin(comps, entry, operand)
                ops.append((operand.shape, what, end, i in fed))
            scope, which = scope_table.place(ins.op_name, listed)
            out.append(Product(
                ins, ins.shape, tuple(ops),
                int(cycles.group(1)) / CLOCK_HZ * 1e3 if cycles
                else float("nan"),
                sum(product_flops(comps, c) for c in convs)
                / PEAK_FLOPS * 1e3, scope, which))
    return out


def parameter_name(end: Instruction) -> Optional[str]:
    """``blocks.0.ffn.up.weight`` of an entry parameter named
    ``params['blocks.0.ffn.up.weight']``; None for any other."""
    m = re.fullmatch(r"params\['([^']+)'\]", end.op_name) \
        if end.opcode == "parameter" else None
    return m.group(1) if m else None


def wide_parameters(product: Product, names=None) -> List[str]:
    """The float32 entry parameters (of ``names`` if given) that the
    fusion's PRODUCT reads, prefetched or not."""
    out = []
    for _, _, end, fed in product.operands:
        name = parameter_name(end)
        if fed and name and end.dtype == "f32" \
                and (names is None or name in names):
            out.append(name)
    return out


def weight_class(product: Product) -> str:
    """Where the matrix parameter that the product reads lives, if it
    reads one: the split of ISSUE 46's table."""
    for _, what, end, fed in product.operands:
        if fed and parameter_name(end) and len(end.dims) > 1:
            if end.dtype != "f32":
                return f"{end.dtype} parameter"
            return ("float32 parameter prefetched to fast memory"
                    if what.startswith("prefetched")
                    else "float32 parameter read from HBM in the fusion")
    return "no matrix parameter feeds the product"


def show(name: str, found: List[Product], lines: bool, file=sys.stdout):
    say = lambda *a: print(*a, file=file)
    say(f"== {name}: {len(found)} product fusions "
        f"({sum(not math.isnan(p.estimate_ms) for p in found)} with the "
        "compiler's estimated_cycles)")
    if lines:
        for p in found:
            ops = "; ".join(f"{'' if fed else '(beside) '}{s} <- {w}"
                            for s, w, _, fed in p.operands)
            say(f"%{p.fusion.name} {p.output} [{p.scope or '-'} "
                f"{p.which}] est {p.estimate_ms:.3f} ms, at the peak "
                f"{p.peak_ms:.3f}: {ops}")
    for title, key in (("by scope and pass",
                        lambda p: f"{p.scope or '(none)'} {p.which}"),
                       ("by the weight operand", weight_class)):
        say(f"-- {title}: count, the compiler's estimate ms, ms at the "
            "bf16 peak (products with an estimate only)")
        rows: Dict[str, list] = {}
        for p in found:
            row = rows.setdefault(key(p), [0, 0.0, 0.0, 0])
            if not math.isnan(p.estimate_ms):
                row[0] += 1
                row[1] += p.estimate_ms
                row[2] += p.peak_ms
            else:
                row[3] += 1
        for k in sorted(rows):
            n, est, peak, bare = rows[k]
            say(f"{k:>52}: {n:3d}  {est:8.2f}  {peak:8.2f}"
                + (f"  (+{bare} without an estimate)" if bare else ""))
        say(f"{'all':>52}: {sum(r[0] for r in rows.values()):3d}  "
            f"{sum(r[1] for r in rows.values()):8.2f}  "
            f"{sum(r[2] for r in rows.values()):8.2f}")


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*", help="cells of BENCHMARK.json")
    ap.add_argument("--text", action="append", default=[],
                    help="a compiled program's text to read instead")
    ap.add_argument("--save", help="directory to keep each compiled text")
    ap.add_argument("--quiet", action="store_true",
                    help="the sums only, not a line a product")
    args = ap.parse_args(argv)
    for path in args.text:
        with open(path) as f:
            show(path, products(f.read()), not args.quiet)
    if args.text and not args.cells:
        return 0
    import benchmark.rehearse_compile as R

    def report(name, lowered):
        text = lowered.compile().as_text()
        if args.save:
            os.makedirs(args.save, exist_ok=True)
            with open(os.path.join(args.save, re.sub(
                    r"[^\w.-]+", "_", name) + ".txt"), "w") as f:
                f.write(text)
        show(name, products(text), not args.quiet)

    R.report = report       # the programs are rehearse_compile's own
    return R.main(args.cells)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
