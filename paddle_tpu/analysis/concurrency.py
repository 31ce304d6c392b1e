"""Concurrency verifier — a whole-repo AST pass over the threaded half
of the framework.

PRs 9-13 made this a genuinely concurrent system (fleet watcher
threads, async checkpoint writers, router claim lanes, per-stream SSE
pumps, prefetcher queues) and its worst historical bugs are exactly
this class: the PR 1 writer-thread use-after-free, the PR 9
survivor-wedged-in-a-dead-rank's-barrier hang, the PR 11
serial-fan-in-on-a-wedged-peer stall. This pass builds a per-module
*concurrency model* — thread entry points (``threading.Thread(target=
...)``, ``ThreadPoolExecutor.submit``), lock objects and the functions
that acquire them, attributes written from thread bodies — and emits
the ``PT-RACE-4xx`` family through the shared :class:`Diagnostic`
currency (codes in ``diagnostics.py``):

- **PT-RACE-401** — a shared attribute written from a thread entry and
  written elsewhere with no common lock (write/write race), or written
  from a thread entry under NO lock at all while read/written elsewhere
  (unsynchronized shared mutation). A thread-side write that holds a
  lock and is merely *read* lock-free elsewhere is NOT flagged — that
  is the sanctioned publication-read pattern this codebase uses for
  stats snapshots (CPython reference stores are atomic; the lock
  serializes the writers).
- **PT-RACE-402** — lock-order inversion: the per-module
  lock-acquisition graph (edge A→B = B acquired while A held, lexically
  or through a one-module call chain) has a cycle. Both witness paths
  are named — the pair of functions that acquire the same locks in
  opposite orders is tomorrow's deadlock.
- **PT-RACE-403** — a blocking call (``join()`` / ``queue.get()`` /
  ``queue.put()`` on a bounded queue / ``Event.wait()`` /
  ``Condition.wait()`` on a *different* condition) without a timeout
  while a lock is held: one wedged peer turns a lock into a system-wide
  stall (the PR 11 fan-in class). ``Condition.wait`` on the condition
  itself is the sanctioned pattern and exempt (wait releases it).
- **PT-RACE-404** — ``Condition.wait`` outside a predicate loop
  (``while``): condition waits are spec'd to wake spuriously and after
  stolen wakeups; an ``if``-guarded wait acts on stale state.
  ``wait_for`` carries its own loop and is exempt.
- **PT-RACE-405** — a non-daemon thread that is never ``join``-ed
  anywhere in its module: on interpreter shutdown it blocks process
  exit forever (or leaks, under daemonized parents).

Scope and honesty: the model is per-module and intentionally
flow-insensitive — it names every *structurally possible* hazard, not
every dynamically reachable one. False positives are suppressed like
every other analysis code: ``# pt-lint: disable=PT-RACE-401 <reason>``
on (or above) the flagged line, reason REQUIRED.

The runtime companion (``telemetry/lockwatch.py``) instruments real
lock acquisitions at test time and validates this pass's lock graph
against observed orderings — :func:`lock_order_graph` is the interface
between the two.

``tools/lint.py --select PT-RACE`` runs just this family; the ci.sh
``race smoke`` stage gates it repo-wide.
"""

from __future__ import annotations

import ast
import os
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from .diagnostics import Diagnostic
from .lint import _dotted, _suppressions, _terminal

RACE_CODES = {
    "PT-RACE-401": "shared attribute written in a thread entry without "
                   "a common lock",
    "PT-RACE-402": "lock-order inversion (cyclic lock-acquisition "
                   "graph)",
    "PT-RACE-403": "timeout-less blocking call while holding a lock",
    "PT-RACE-404": "Condition.wait outside a predicate loop",
    "PT-RACE-405": "non-daemon thread never joined",
}

# constructors that make a lock-like object (anything you can hold
# while blocking someone else). Condition doubles as a lock (``with
# cond:`` acquires its inner lock).
_LOCK_CTORS = {"Lock", "RLock"}
_COND_CTORS = {"Condition"}
_EVENT_CTORS = {"Event"}
_QUEUE_CTORS = {"Queue", "LifoQueue", "PriorityQueue", "SimpleQueue"}
_THREAD_CTORS = {"Thread"}

# attribute kinds the model tracks (values of _Symbols maps)
_KIND_LOCK = "lock"
_KIND_COND = "condition"
_KIND_EVENT = "event"
_KIND_QUEUE = "queue"
_KIND_THREAD = "thread"

# blocking receiver kinds for PT-RACE-403, by method name
_BLOCKING_METHODS = {
    "join": (_KIND_THREAD,),
    "get": (_KIND_QUEUE,),
    "put": (_KIND_QUEUE,),
    "wait": (_KIND_EVENT, _KIND_COND),
}

# sync-primitive kinds: attributes holding these are themselves
# thread-safe (or lifecycle-managed) — rebinding one is initialization,
# not shared-state mutation, so PT-RACE-401 skips them
_SYNC_KINDS = {_KIND_LOCK, _KIND_COND, _KIND_EVENT, _KIND_QUEUE,
               _KIND_THREAD}


def _ctor_kind(call: ast.Call) -> Optional[str]:
    """The sync-primitive kind a constructor call produces, if any.
    Matched by terminal name (``threading.Lock`` / bare ``Lock`` /
    ``queue.Queue``), the same posture as the rest of the linter —
    false negatives from exotic aliasing beat false positives from
    guessing."""
    name = _terminal(call.func)
    if name in _LOCK_CTORS:
        return _KIND_LOCK
    if name in _COND_CTORS:
        return _KIND_COND
    if name in _EVENT_CTORS:
        return _KIND_EVENT
    if name in _QUEUE_CTORS:
        return _KIND_QUEUE
    if name in _THREAD_CTORS:
        return _KIND_THREAD
    if name == "WatchedLock":  # the runtime watchdog's wrapper IS a lock
        return _KIND_LOCK
    return None


def _has_timeout(call: ast.Call, method: str) -> bool:
    """True when the blocking call is bounded — positional timeout
    slots differ per primitive, so the method name matters:
    ``join``/``wait`` take timeout FIRST, ``queue.get(block,
    timeout)`` takes ``block`` first (so ``get(True)`` is still
    unbounded but ``get(False)`` never blocks), and ``queue.put(item,
    block, timeout)``'s first positional is the ITEM (a bare
    ``put(x)`` is unbounded). An explicit ``None`` timeout — keyword
    or positional — is the unbounded spelling, not a bound."""

    def bounds(node: ast.AST) -> bool:
        # a literal None is unbounded; any other expression is taken
        # as a real bound (a variable timeout can't be judged here)
        return not (isinstance(node, ast.Constant)
                    and node.value is None)

    for kw in call.keywords:
        if kw.arg == "timeout" and bounds(kw.value):
            return True
        if kw.arg == "block" and isinstance(kw.value, ast.Constant) \
                and kw.value.value is False:
            return True
    args = call.args
    if method in ("join", "wait"):
        return bool(args) and bounds(args[0])
    if method == "get":
        if len(args) >= 2:
            return bounds(args[1])  # get(block, timeout)
        return bool(args) and isinstance(args[0], ast.Constant) \
            and args[0].value is False  # get(False) never blocks
    if method == "put":
        if len(args) >= 3:
            return bounds(args[2])  # put(item, block, timeout)
        return len(args) == 2 and isinstance(args[1], ast.Constant) \
            and args[1].value is False  # put(item, False)
    return bool(args)


class _FnInfo:
    """Everything the checkers need to know about one function body."""

    def __init__(self, qual: str, node: ast.AST, cls: Optional[str]):
        self.qual = qual            # "Class.method" or "function"
        self.node = node
        self.cls = cls
        self.line = node.lineno
        # [(attr, line, locks_held, is_write, is_read)]
        self.attr_accesses: List[Tuple[str, int, frozenset, bool, bool]] = []
        # [(lock_id, line)] every acquisition site (with / .acquire())
        self.acquires: List[Tuple[str, int]] = []
        # [(held_lock, acquired_lock, line)] lexical nesting edges
        self.nested: List[Tuple[str, str, int]] = []
        # [(callee_qual, line, locks_held)]
        self.calls: List[Tuple[str, int, frozenset]] = []
        # [(desc, line, locks_held, receiver_kind)]
        self.blocking: List[Tuple[str, int, frozenset, str]] = []
        # [(cond_id, line, in_while)]
        self.cond_waits: List[Tuple[str, int, bool]] = []
        # [(line, daemon, binding, target_qual)] threads created here
        self.threads: List[Tuple[int, bool, Optional[str],
                                 Optional[str]]] = []
        # names of local functions defined in this body (closures)
        self.local_fns: Dict[str, ast.AST] = {}


def _queue_put_blocks(ctor: ast.Call) -> bool:
    """Can ``put()`` on a queue built by this constructor block? Only
    a BOUNDED queue's put blocks: ``Queue()`` / ``Queue(0)`` /
    ``SimpleQueue()`` never do. A non-literal maxsize is taken as
    bounded (the common reason to pass one)."""
    if _terminal(ctor.func) == "SimpleQueue":
        return False
    size = None
    if ctor.args:
        size = ctor.args[0]
    for kw in ctor.keywords:
        if kw.arg == "maxsize":
            size = kw.value
    if size is None:
        return False  # default maxsize=0: unbounded
    if isinstance(size, ast.Constant):
        return bool(size.value)  # 0/None stay unbounded
    return True


class _ModuleModel:
    """The per-module concurrency model the checkers consume."""

    def __init__(self, modname: str, path: str):
        self.modname = modname
        self.path = path
        # symbol tables: "Class.attr" / "mod.name" -> kind
        self.symbols: Dict[str, str] = {}
        # queue symbols whose put() can actually block (maxsize > 0)
        self.bounded_queues: Set[str] = set()
        self.functions: Dict[str, _FnInfo] = {}
        # thread entry qualnames (targets of Thread()/submit())
        self.thread_entries: Set[str] = set()
        # qualnames with .join() called on their thread binding
        self.joined_bindings: Set[str] = set()
        # "Class.method" -> "Class.attr": @contextmanager methods that
        # hold ``self.attr`` across their yield (``with self.m(...):``
        # then acquires that lock like ``with self.attr:`` does)
        self.lock_cms: Dict[str, str] = {}


# ---------------------------------------------------------------------------
# pass 1: symbol collection (locks / conditions / events / queues /
# threads, keyed by class attribute or module-level name)
# ---------------------------------------------------------------------------


class _SymbolCollector(ast.NodeVisitor):
    def __init__(self, model: _ModuleModel):
        self.model = model
        self._cls: Optional[str] = None

    def visit_ClassDef(self, node):
        prev, self._cls = self._cls, node.name
        self.generic_visit(node)
        self._cls = prev

    def _record(self, target: ast.AST, value: ast.AST) -> None:
        if not isinstance(value, ast.Call):
            return
        kind = _ctor_kind(value)
        if kind is None:
            return
        if (isinstance(target, ast.Attribute)
                and isinstance(target.value, ast.Name)
                and target.value.id == "self" and self._cls):
            sym = f"{self._cls}.{target.attr}"
        elif isinstance(target, ast.Name):
            # module-level or function-local: both get recorded; the
            # analyzer resolves locals first by lexical preference
            sym = f"{self.model.modname}.{target.id}"
        else:
            return
        self.model.symbols[sym] = kind
        if kind == _KIND_QUEUE and _queue_put_blocks(value):
            self.model.bounded_queues.add(sym)

    def visit_Assign(self, node):
        for t in node.targets:
            self._record(t, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node):
        if node.value is not None:
            self._record(node.target, node.value)
        self.generic_visit(node)

    def visit_FunctionDef(self, node):
        # a @contextmanager method that calls self.<attr>.acquire() is
        # a lock-holding context manager (whether <attr> is a lock is
        # resolved at the ``with`` site, once every symbol is known)
        if self._cls and any(_terminal(d) == "contextmanager"
                             for d in node.decorator_list):
            for sub in ast.walk(node):
                if (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr == "acquire"
                        and isinstance(sub.func.value, ast.Attribute)
                        and isinstance(sub.func.value.value, ast.Name)
                        and sub.func.value.value.id == "self"):
                    self.model.lock_cms[f"{self._cls}.{node.name}"] = \
                        f"{self._cls}.{sub.func.value.attr}"
                    break
        self.generic_visit(node)


# ---------------------------------------------------------------------------
# pass 2: per-function analysis with lexical lock-hold tracking
# ---------------------------------------------------------------------------


class _FnAnalyzer:
    """Walk one function body tracking the lexically-held lock set."""

    def __init__(self, model: _ModuleModel, info: _FnInfo):
        self.model = model
        self.info = info

    # -- id resolution -------------------------------------------------------

    def _sym_id(self, node: ast.AST) -> Optional[str]:
        """Resolve an expression to a tracked symbol id, or None."""
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self" and self.info.cls):
            key = f"{self.info.cls}.{node.attr}"
            return key if key in self.model.symbols else None
        if isinstance(node, ast.Name):
            key = f"{self.model.modname}.{node.id}"
            return key if key in self.model.symbols else None
        return None

    def _with_sym(self, expr: ast.AST) -> Optional[str]:
        """The symbol a ``with`` item acquires: the lock itself, or the
        lock a ``self.<method>(...)`` lock-holding context manager of
        this class holds (``_ModuleModel.lock_cms``)."""
        if (isinstance(expr, ast.Call)
                and isinstance(expr.func, ast.Attribute)
                and isinstance(expr.func.value, ast.Name)
                and expr.func.value.id == "self" and self.info.cls):
            sym = self.model.lock_cms.get(
                f"{self.info.cls}.{expr.func.attr}")
            return sym if sym in self.model.symbols else None
        return self._sym_id(expr)

    def _kind_of(self, sym: Optional[str]) -> Optional[str]:
        return self.model.symbols.get(sym) if sym else None

    # -- the walk ------------------------------------------------------------

    def run(self) -> None:
        body = getattr(self.info.node, "body", [])
        # pre-scan this scope's nested defs so a Thread(target=worker)
        # lexically BEFORE `def worker` still resolves scope-qualified
        self._scan_local_defs(body)
        for stmt in body:
            self._walk(stmt, held=(), loops=0)

    def _scan_local_defs(self, body) -> None:
        work = list(body)
        while work:
            node = work.pop()
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                self.info.local_fns[node.name] = node
                continue  # deeper defs belong to THAT scope
            work.extend(ast.iter_child_nodes(node))

    def _walk(self, node: ast.AST, held: Tuple[str, ...],
              loops: int) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a nested def's body runs later (possibly on a thread):
            # it gets its own _FnInfo via the module visitor; here we
            # only note its existence
            self.info.local_fns[node.name] = node
            return
        if isinstance(node, ast.With):
            acquired: List[str] = []
            for item in node.items:
                sym = self._with_sym(item.context_expr)
                kind = self._kind_of(sym)
                if kind in (_KIND_LOCK, _KIND_COND):
                    self.info.acquires.append((sym, node.lineno))
                    for h in held + tuple(acquired):
                        if h != sym:
                            self.info.nested.append((h, sym, node.lineno))
                    acquired.append(sym)
            inner = held + tuple(a for a in acquired if a not in held)
            for stmt in node.body:
                self._walk(stmt, inner, loops)
            return
        if isinstance(node, (ast.While, ast.For, ast.AsyncFor)):
            for child in ast.iter_child_nodes(node):
                self._walk(child, held, loops + 1)
            return
        if isinstance(node, ast.Call):
            self._call(node, held, loops)
            for child in ast.iter_child_nodes(node):
                self._walk(child, held, loops)
            return
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            self._assign(node, held, loops)
            return
        if isinstance(node, ast.Attribute):
            self._attr_read(node, held)
            for child in ast.iter_child_nodes(node):
                self._walk(child, held, loops)
            return
        for child in ast.iter_child_nodes(node):
            self._walk(child, held, loops)

    # -- attribute accesses (PT-RACE-401 raw material) -----------------------

    def _self_attr(self, node: ast.AST) -> Optional[str]:
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self" and self.info.cls):
            return node.attr
        return None

    def _attr_read(self, node: ast.Attribute, held) -> None:
        attr = self._self_attr(node)
        if attr is not None and isinstance(node.ctx, ast.Load):
            self.info.attr_accesses.append(
                (attr, node.lineno, frozenset(held), False, True))

    def _assign(self, node, held, loops) -> None:
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        for t in targets:
            for sub in ast.walk(t):
                attr = self._self_attr(sub)
                if attr is not None:
                    is_aug = isinstance(node, ast.AugAssign)
                    self.info.attr_accesses.append(
                        (attr, node.lineno, frozenset(held), True,
                         is_aug))
        if getattr(node, "value", None) is not None:
            self._walk(node.value, held, loops)

    # -- calls: acquisitions, blocking calls, thread spawns, call graph ------

    def _call(self, node: ast.Call, held, loops) -> None:
        func = node.func
        term = _terminal(func)
        dotted = _dotted(func)

        # explicit .acquire() — treated as an acquisition site for the
        # order graph (hold extent approximated as the whole function;
        # this repo overwhelmingly uses `with`)
        if term == "acquire" and isinstance(func, ast.Attribute):
            sym = self._sym_id(func.value)
            if self._kind_of(sym) in (_KIND_LOCK, _KIND_COND):
                self.info.acquires.append((sym, node.lineno))
                for h in held:
                    if h != sym:
                        self.info.nested.append((h, sym, node.lineno))

        # thread creation
        if term in _THREAD_CTORS and dotted in ("threading.Thread",
                                                "Thread"):
            self._thread_ctor(node)

        # executor.submit(fn, ...) — the pool's workers are thread
        # entries too
        if term == "submit" and node.args:
            tq = self._target_qual(node.args[0])
            if tq is not None:
                self.model.thread_entries.add(tq)

        # .join() on a tracked thread binding: feeds PT-RACE-405 and,
        # timeout-less under a lock, PT-RACE-403. Blocking sites are
        # recorded with the LEXICAL held set even when it is empty —
        # the checker widens it with the caller-held entry context
        # (a private helper only ever called under a lock blocks
        # under that lock just the same).
        if term == "join" and isinstance(func, ast.Attribute):
            sym = self._sym_id(func.value)
            if self._kind_of(sym) == _KIND_THREAD:
                self.model.joined_bindings.add(sym)
                if not _has_timeout(node, "join"):
                    self.info.blocking.append(
                        (f"{sym}.join()", node.lineno, frozenset(held),
                         _KIND_THREAD))

        # blocking queue ops / event waits / condition waits. put()
        # blocks only on a BOUNDED queue (the default maxsize=0 and
        # SimpleQueue never do)
        if term in ("get", "put") and isinstance(func, ast.Attribute):
            sym = self._sym_id(func.value)
            if (self._kind_of(sym) == _KIND_QUEUE
                    and not _has_timeout(node, term)
                    and (term == "get"
                         or sym in self.model.bounded_queues)):
                self.info.blocking.append(
                    (f"{sym}.{term}()", node.lineno, frozenset(held),
                     _KIND_QUEUE))
        if term == "wait" and isinstance(func, ast.Attribute):
            sym = self._sym_id(func.value)
            kind = self._kind_of(sym)
            if kind == _KIND_COND:
                self.info.cond_waits.append((sym, node.lineno,
                                             loops > 0))
                if not _has_timeout(node, "wait"):
                    others = frozenset(h for h in held if h != sym)
                    self.info.blocking.append(
                        (f"{sym}.wait()", node.lineno, others,
                         _KIND_COND))
            elif kind == _KIND_EVENT and not _has_timeout(node,
                                                           "wait"):
                self.info.blocking.append(
                    (f"{sym}.wait()", node.lineno, frozenset(held),
                     _KIND_EVENT))

        # intra-module call graph (for 401 reachability + 402 edges
        # through one call level): self.method() and bare-name calls
        cq = self._callee_qual(func)
        if cq is not None:
            self.info.calls.append((cq, node.lineno, frozenset(held)))

    def _callee_qual(self, func: ast.AST) -> Optional[str]:
        if (isinstance(func, ast.Attribute)
                and isinstance(func.value, ast.Name)
                and func.value.id == "self" and self.info.cls):
            return f"{self.info.cls}.{func.attr}"
        if isinstance(func, ast.Name):
            # a local closure shadows any module function of the same
            # name — and gets a scope-qualified name so two functions'
            # same-named `worker` closures never collide in the model
            if func.id in self.info.local_fns:
                return f"{self.info.qual}.<locals>.{func.id}"
            return func.id
        return None

    def _target_qual(self, target: ast.AST) -> Optional[str]:
        """Resolve a Thread(target=X) / submit(X) expression to a
        function qualname the model may know."""
        if (isinstance(target, ast.Attribute)
                and isinstance(target.value, ast.Name)
                and target.value.id == "self" and self.info.cls):
            return f"{self.info.cls}.{target.attr}"
        if isinstance(target, ast.Name):
            if target.id in self.info.local_fns:
                return f"{self.info.qual}.<locals>.{target.id}"
            return target.id
        return None

    def _thread_ctor(self, node: ast.Call) -> None:
        daemon = False
        target_qual = None
        for kw in node.keywords:
            if kw.arg == "daemon" and isinstance(kw.value, ast.Constant):
                daemon = bool(kw.value.value)
            if kw.arg == "target":
                target_qual = self._target_qual(kw.value)
        if target_qual is not None:
            self.model.thread_entries.add(target_qual)
        self.info.threads.append((node.lineno, daemon, None,
                                  target_qual))


# ---------------------------------------------------------------------------
# module driver
# ---------------------------------------------------------------------------


def _collect_functions(model: _ModuleModel, tree: ast.Module) -> None:
    """Register every function body: module functions by bare name,
    methods as Class.method, and nested defs (closures) by bare name
    scoped to their module — thread workers in this codebase are
    closures (`def worker(): ...; Thread(target=worker)`), and their
    self-attribute accesses belong to the enclosing class."""

    def add(node, qual: str, cls: Optional[str]):
        info = _FnInfo(qual, node, cls)
        model.functions[qual] = info
        _FnAnalyzer(model, info).run()
        # nested defs analyze with the ENCLOSING class context (a
        # closure inside a method mutates self through its cell) and a
        # scope-qualified name — two functions' same-named `worker`
        # closures must never overwrite each other in the model
        for name, sub in list(info.local_fns.items()):
            add(sub, f"{qual}.<locals>.{name}", cls)

    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            add(node, node.name, None)
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    add(sub, f"{node.name}.{sub.name}", node.name)


def _module_name(path: str) -> str:
    """Collision-safe module identity: ``<parent_dir>.<stem>`` when the
    path carries a parent (this tree has four same-named module pairs —
    static/io.py vs fluid/io.py, telemetry/metrics.py vs metrics.py,
    ... — which must not share a symbol namespace or lock_order_graph
    keys), bare stem otherwise."""
    norm = path.replace("\\", "/")
    stem = os.path.splitext(os.path.basename(norm))[0]
    parent = os.path.basename(os.path.dirname(norm))
    return f"{parent}.{stem}" if parent not in ("", ".") else stem


def _build_model(src: str, path: str) -> Optional[_ModuleModel]:
    try:
        tree = ast.parse(src)
    except SyntaxError:
        return None  # lint.py already reports unparseable files
    model = _ModuleModel(_module_name(path), path)
    _SymbolCollector(model).visit(tree)
    _collect_functions(model, tree)
    return model


def _thread_reachable(model: _ModuleModel) -> Set[str]:
    """Qualnames reachable from any thread entry through the
    intra-module call graph (cycle-safe BFS)."""
    seen: Set[str] = set()
    work = [q for q in model.thread_entries if q in model.functions]
    while work:
        q = work.pop()
        if q in seen:
            continue
        seen.add(q)
        info = model.functions.get(q)
        if info is None:
            continue
        for callee, _, _ in info.calls:
            if callee in model.functions and callee not in seen:
                work.append(callee)
    return seen


def _entry_contexts(model: _ModuleModel) -> Dict[str, frozenset]:
    """Caller-held lock context per function: the set of locks held at
    EVERY intra-module call site (a ``_tick_locked``-style private
    helper runs under its caller's lock even though it never acquires
    one itself). Applied only to private functions (one leading
    underscore): a public function is callable from other modules the
    model can't see, so it gets the empty context — assuming otherwise
    would hide real races. Thread entries always get the empty context
    (the runtime calls them with nothing held). Computed to fixpoint;
    monotone (contexts only grow), so it terminates."""
    sites: Dict[str, List[Tuple[str, frozenset]]] = {
        q: [] for q in model.functions}
    for caller, info in model.functions.items():
        for callee, _, held in info.calls:
            if callee in sites:
                sites[callee].append((caller, held))

    def is_seeded_empty(q: str) -> bool:
        name = q.rsplit(".", 1)[-1]
        return (q in model.thread_entries
                or name in model.thread_entries
                or not name.startswith("_")
                or name.startswith("__")
                or not sites[q])

    ctx: Dict[str, frozenset] = {q: frozenset()
                                 for q in model.functions}
    changed = True
    while changed:
        changed = False
        for q in model.functions:
            if is_seeded_empty(q):
                continue
            acc: Optional[frozenset] = None
            for caller, held in sites[q]:
                eff = held | ctx.get(caller, frozenset())
                acc = eff if acc is None else (acc & eff)
            new = acc or frozenset()
            if new != ctx[q]:
                ctx[q] = new
                changed = True
    return ctx


def _transitive_acquires(model: _ModuleModel
                         ) -> Dict[str, Set[Tuple[str, int]]]:
    """For each function: every lock it (or anything it calls, within
    the module) acquires — the call-chain half of the 402 edge set."""
    memo: Dict[str, Set[Tuple[str, int]]] = {}

    def visit(q: str, stack: Set[str]) -> Set[Tuple[str, int]]:
        if q in memo:
            return memo[q]
        if q in stack:
            return set()
        info = model.functions.get(q)
        if info is None:
            return set()
        stack.add(q)
        out: Set[Tuple[str, int]] = set(info.acquires)
        for callee, _, _ in info.calls:
            out |= visit(callee, stack)
        stack.discard(q)
        memo[q] = out
        return out

    for q in model.functions:
        visit(q, set())
    return memo


# ---------------------------------------------------------------------------
# checkers
# ---------------------------------------------------------------------------


def _check_401(model: _ModuleModel, reachable: Set[str],
               ctx: Dict[str, frozenset]) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    # group accesses per (class, attr)
    per_attr: Dict[Tuple[str, str], Dict[str, list]] = {}
    for qual, info in model.functions.items():
        if info.cls is None:
            continue
        side = "thread" if qual in reachable else "main"
        # __init__ runs happens-before thread start: initialization
        # writes are invisible to the race model
        if qual.endswith(".__init__"):
            continue
        entry = ctx.get(qual, frozenset())
        for attr, line, lex_held, is_write, is_read in \
                info.attr_accesses:
            held = lex_held | entry
            key = (info.cls, attr)
            if model.symbols.get(f"{info.cls}.{attr}") in _SYNC_KINDS:
                continue
            if f"{info.cls}.{attr}" in model.functions:
                continue  # method/property access, not shared state
            rec = per_attr.setdefault(key, {"thread": [], "main": []})
            rec[side].append((qual, line, held, is_write, is_read))
    for (cls, attr), rec in sorted(per_attr.items()):
        t_writes = [r for r in rec["thread"] if r[3]]
        if not t_writes:
            continue
        m_writes = [r for r in rec["main"] if r[3]]
        m_reads = [r for r in rec["main"] if not r[3]]
        flagged = None
        # write/write race: no common lock between any write pair —
        # the peer write may live on the main side OR in a DIFFERENT
        # thread entry path (two worker loops racing each other is the
        # classic form; same-function pairs are skipped because a
        # single entry's multiplicity is invisible statically)
        for tq, tl, th, _, _ in t_writes:
            peers = m_writes + [r for r in t_writes if r[0] != tq]
            for mq, ml, mh, _, _ in peers:
                if not (th & mh):
                    flagged = (tq, tl, mq, ml, "written")
                    break
            if flagged:
                break
        if flagged is None:
            # unsynchronized thread-side write + ANY other access: a
            # locked thread write read lock-free elsewhere is the
            # sanctioned publication pattern and stays silent
            for tq, tl, th, _, _ in t_writes:
                if th:
                    continue
                others = m_writes + m_reads
                for mq, ml, mh, _, _ in others:
                    if not (th & mh):
                        flagged = (tq, tl, mq, ml, "accessed")
                        break
                if flagged:
                    break
        if flagged is None:
            continue
        tq, tl, mq, ml, verb = flagged
        out.append(Diagnostic(
            code="PT-RACE-401", severity="error", path=model.path,
            line=tl, var=f"{cls}.{attr}",
            message=(f"self.{attr} written from thread entry path "
                     f"{tq} (line {tl}) and {verb} in {mq} (line {ml}) "
                     f"with no common lock"),
            hint=("guard both sides with one lock, or make the "
                  "elsewhere side read-only under a locked writer "
                  "(the publication pattern); suppress with a reason "
                  "if the accesses are provably not concurrent")))
    return out


def _check_402(model: _ModuleModel,
               ctx: Dict[str, frozenset]) -> List[Diagnostic]:
    # edges: (A, B) -> witness description
    edges: Dict[Tuple[str, str], str] = {}
    trans = _transitive_acquires(model)
    for qual, info in model.functions.items():
        for a, b, line in info.nested:
            edges.setdefault((a, b), f"{qual} ({model.path}:{line}) "
                                     f"acquires {b} while holding {a}")
        # caller-held context: a private helper's acquisitions order
        # AFTER whatever its callers always hold
        for lock, line in info.acquires:
            for h in ctx.get(qual, frozenset()):
                if h != lock:
                    edges.setdefault(
                        (h, lock),
                        f"{qual} ({model.path}:{line}) acquires "
                        f"{lock} with {h} held by every caller")
        for callee, line, held in info.calls:
            if not held or callee not in model.functions:
                continue
            for lock, lline in trans.get(callee, ()):
                for h in held:
                    if h != lock:
                        edges.setdefault(
                            (h, lock),
                            f"{qual} ({model.path}:{line}) calls "
                            f"{callee} (which acquires {lock} at line "
                            f"{lline}) while holding {h}")
    # cycle detection over the small per-module graph; report each
    # 2-cycle (the overwhelmingly common inversion) once, canonically
    out: List[Diagnostic] = []
    seen: Set[frozenset] = set()
    adj: Dict[str, Set[str]] = {}
    for (a, b) in edges:
        adj.setdefault(a, set()).add(b)

    def reachable_from(start: str, goal: str) -> Optional[List[str]]:
        # BFS path start -> goal
        work = [(start, [start])]
        visited = {start}
        while work:
            cur, p = work.pop(0)
            for nxt in adj.get(cur, ()):
                if nxt == goal:
                    return p + [nxt]
                if nxt not in visited:
                    visited.add(nxt)
                    work.append((nxt, p + [nxt]))
        return None

    for (a, b), witness in sorted(edges.items()):
        key = frozenset((a, b))
        if key in seen:
            continue
        path_back = reachable_from(b, a)
        if path_back is None:
            continue
        seen.add(key)
        # witness for the return path: chain the first edge of it
        back_edges = list(zip(path_back, path_back[1:]))
        back_witness = "; ".join(edges[e] for e in back_edges
                                 if e in edges)
        line = None
        info_line = witness.rfind(":")
        if info_line != -1:
            tail = witness[info_line + 1:].split(")")[0]
            line = int(tail) if tail.isdigit() else None
        out.append(Diagnostic(
            code="PT-RACE-402", severity="error", path=model.path,
            line=line, var=" -> ".join([a, b]),
            message=(f"lock-order inversion between {a} and {b}: "
                     f"[{witness}] vs [{back_witness}]"),
            hint=("pick ONE global order for these locks and make "
                  "every path acquire in it (or collapse to a single "
                  "lock); the runtime watchdog "
                  "(telemetry.lockwatch) can confirm which orders "
                  "execute")))
    return out


def _check_403(model: _ModuleModel,
               ctx: Dict[str, frozenset]) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for qual, info in sorted(model.functions.items()):
        entry = ctx.get(qual, frozenset())
        for desc, line, lex_held, kind in info.blocking:
            held = lex_held | entry
            if kind == _KIND_COND:
                # waiting on the condition itself releases it — only
                # OTHER held locks stall peers
                held = held - {desc.split(".wait")[0]}
            if not held:
                continue
            locks = ", ".join(sorted(held))
            out.append(Diagnostic(
                code="PT-RACE-403", severity="error", path=model.path,
                line=line, var=desc,
                message=(f"{qual} blocks on {desc} with no timeout "
                         f"while holding {locks}: a wedged peer turns "
                         f"the lock into a system-wide stall"),
                hint=("pass a timeout (loop on expiry) or move the "
                      "blocking call outside the lock")))
    return out


def _check_404(model: _ModuleModel) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for qual, info in sorted(model.functions.items()):
        for cond, line, in_while in info.cond_waits:
            if in_while:
                continue
            out.append(Diagnostic(
                code="PT-RACE-404", severity="error", path=model.path,
                line=line, var=cond,
                message=(f"{qual} calls {cond}.wait() outside a "
                         f"predicate loop: spurious/stolen wakeups "
                         f"make the post-wait state unchecked"),
                hint=("wrap in `while not predicate: cond.wait(...)` "
                      "or use cond.wait_for(predicate, ...)")))
    return out


def _check_405(model: _ModuleModel) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for qual, info in sorted(model.functions.items()):
        for line, daemon, _, target in info.threads:
            if daemon:
                continue
            # joined anywhere in the module (on any tracked thread
            # binding of the enclosing class, or any .join() textual
            # hit on a thread symbol)? The binding-level model: a
            # non-daemon thread is acceptable ONLY if some module code
            # joins a thread object — conservative at module scope.
            if model.joined_bindings:
                continue
            tgt = f" (target {target})" if target else ""
            out.append(Diagnostic(
                code="PT-RACE-405", severity="error", path=model.path,
                line=line, var=qual,
                message=(f"{qual} starts a non-daemon thread{tgt} that "
                         f"no code in this module ever joins: "
                         f"interpreter shutdown blocks on it forever"),
                hint=("pass daemon=True (and bound its loop on a stop "
                      "Event), or keep the Thread object and join it "
                      "on every shutdown path")))
    return out


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def analyze_source(src: str, path: str = "<string>"
                   ) -> List[Diagnostic]:
    """Run every PT-RACE checker over one module's source. Unparseable
    files return no findings here (``lint_source`` owns that
    diagnosis). Suppressions: ``# pt-lint: disable=PT-RACE-4xx
    <reason>`` on or above the flagged line (shared grammar with the
    repo linter; reason required)."""
    model = _build_model(src, path)
    if model is None:
        return []
    reachable = _thread_reachable(model)
    ctx = _entry_contexts(model)
    findings = (_check_401(model, reachable, ctx)
                + _check_402(model, ctx)
                + _check_403(model, ctx) + _check_404(model)
                + _check_405(model))
    findings.sort(key=lambda d: (d.line or 0, d.code))
    sup = _suppressions(src)
    out: List[Diagnostic] = []
    for d in findings:
        entries = [e for e in (sup.get(d.line),
                               sup.get((d.line or 0) - 1))
                   if e is not None and d.code in e[0]]
        if any(reason for _, reason in entries):
            continue
        if entries:
            d.message += (" [suppression ignored: pt-lint disable "
                          "comments require a reason]")
        out.append(d)
    return out


def analyze_file(path: str) -> List[Diagnostic]:
    with open(path, encoding="utf-8") as f:
        return analyze_source(f.read(), path)


def _py_files(paths: Sequence[str],
              exclude: Sequence[str]) -> List[str]:
    """Deterministic ``*.py`` discovery shared by :func:`analyze_paths`
    and :func:`lock_order_graph` — ONE walk, so the watchdog's static
    graph is always built from the same file set the diagnostics pass
    covered."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, names in os.walk(p):
                dirs[:] = sorted(d for d in dirs if d not in exclude)
                files.extend(os.path.join(root, n)
                             for n in sorted(names)
                             if n.endswith(".py"))
        elif p.endswith(".py"):
            files.append(p)
    return files


def analyze_paths(paths: Sequence[str],
                  exclude: Sequence[str] = ("__pycache__",)
                  ) -> List[Diagnostic]:
    """Analyze files and directory trees (``*.py`` only), deterministic
    order — the repo-wide entry ``tools/lint.py --select PT-RACE``
    drives."""
    out: List[Diagnostic] = []
    for f in _py_files(paths, exclude):
        out.extend(analyze_file(f))
    return out


def lock_order_graph(paths: Sequence[str]
                     ) -> Dict[Tuple[str, str], str]:
    """The static lock-acquisition graph over ``paths``: ``(A, B) ->
    witness`` meaning some code acquires B while holding A. Lock names
    are ``<parent_dir.stem>:<Class.attr|module.name>`` (see
    :func:`_module_name` — collision-safe across this tree's
    same-named modules) — the contract the runtime watchdog's
    :meth:`~paddle_tpu.telemetry.lockwatch.LockOrderWatchdog.
    verify_static` matches observed orderings against."""
    graph: Dict[Tuple[str, str], str] = {}
    for fpath in _py_files(paths, ("__pycache__",)):
        with open(fpath, encoding="utf-8") as f:
            src = f.read()
        model = _build_model(src, fpath)
        if model is None:
            continue
        trans = _transitive_acquires(model)
        for qual, info in model.functions.items():
            for a, b, line in info.nested:
                key = (f"{model.modname}:{a}", f"{model.modname}:{b}")
                graph.setdefault(key, f"{qual} {fpath}:{line}")
            for callee, line, held in info.calls:
                if not held or callee not in model.functions:
                    continue
                for lock, lline in trans.get(callee, ()):
                    for h in held:
                        if h != lock:
                            key = (f"{model.modname}:{h}",
                                   f"{model.modname}:{lock}")
                            graph.setdefault(
                                key, f"{qual} {fpath}:{line} via "
                                     f"{callee}")
    return graph
