"""Concurrency verification plane — static half
(``paddle_tpu/analysis/concurrency.py``).

The ``test_analysis.py`` convention applied to the PT-RACE family: for
EVERY code a minimal source snippet that triggers it AND a clean twin
that must pass silently (the no-false-positive pin), plus the model
refinements that keep the pass honest on this codebase (caller-held
lock context for ``_locked``-style private helpers, the
publication-read exemption, ``__init__`` happens-before), the
suppression contract, the ``tools/lint.py --select PT-RACE`` family
CLI, the watchdog-facing :func:`lock_order_graph` contract, and the
dogfood gate: the repo's own threaded half analyzes clean."""

import json
import os
import textwrap

from paddle_tpu.analysis import (analyze_paths, analyze_source,
                                 format_diagnostics, lock_order_graph)
from paddle_tpu.analysis.concurrency import RACE_CODES

from conftest import load_tool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(src, path="x.py"):
    return [d.code for d in analyze_source(textwrap.dedent(src), path)]


# ---------------------------------------------------------------------------
# PT-RACE-401 — shared attribute written from a thread entry
# ---------------------------------------------------------------------------


class TestRace401:
    TRIGGER = """
        import threading
        class C:
            def __init__(self):
                self.count = 0
            def start(self):
                threading.Thread(target=self._run, daemon=True,
                                 name="pt-x").start()
            def _run(self):
                self.count = self.count + 1
            def snapshot(self):
                return self.count
    """

    def test_unguarded_thread_write_flagged(self):
        diags = analyze_source(textwrap.dedent(self.TRIGGER), "x.py")
        assert [d.code for d in diags] == ["PT-RACE-401"]
        d = diags[0]
        assert d.var == "C.count" and d.severity == "error"
        # both sites named: the thread-side write and the other access
        assert "C._run" in d.message and "C.snapshot" in d.message

    def test_both_sides_locked_clean(self):
        src = """
            import threading
            class C:
                def __init__(self):
                    self._mu = threading.Lock()
                    self.count = 0
                def start(self):
                    threading.Thread(target=self._run, daemon=True,
                                     name="pt-x").start()
                def _run(self):
                    with self._mu:
                        self.count = self.count + 1
                def snapshot(self):
                    with self._mu:
                        return self.count
        """
        assert _codes(src) == []

    def test_write_write_needs_common_lock_even_when_each_locked(self):
        # each side holds A lock — but not the SAME lock
        src = """
            import threading
            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self.x = 0
                def start(self):
                    threading.Thread(target=self._run, daemon=True,
                                     name="pt-x").start()
                def _run(self):
                    with self._a:
                        self.x = 1
                def poke(self):
                    with self._b:
                        self.x = 2
        """
        assert _codes(src) == ["PT-RACE-401"]

    def test_publication_read_of_locked_write_is_clean(self):
        # thread-side write holds the lock; elsewhere only READS,
        # lock-free — the sanctioned stats-snapshot pattern
        src = """
            import threading
            class C:
                def __init__(self):
                    self._mu = threading.Lock()
                    self.count = 0
                def start(self):
                    threading.Thread(target=self._run, daemon=True,
                                     name="pt-x").start()
                def _run(self):
                    with self._mu:
                        self.count += 1
                def snapshot(self):
                    return self.count
        """
        assert _codes(src) == []

    def test_init_writes_are_happens_before(self):
        # __init__ initializes what the thread later writes: no race
        src = """
            import threading
            class C:
                def __init__(self):
                    self.state = "cold"
                def start(self):
                    threading.Thread(target=self._run, daemon=True,
                                     name="pt-x").start()
                def _run(self):
                    self.state = "hot"
        """
        assert _codes(src) == []

    def test_caller_held_lock_context_covers_private_helpers(self):
        # the _tick_locked convention: the helper's writes ARE guarded
        # — by the lock every caller holds
        src = """
            import threading
            class C:
                def __init__(self):
                    self._mu = threading.Lock()
                    self.count = 0
                def start(self):
                    threading.Thread(target=self._loop, daemon=True,
                                     name="pt-x").start()
                def _loop(self):
                    with self._mu:
                        self._tick_locked()
                def _tick_locked(self):
                    self.count += 1
                def snapshot(self):
                    with self._mu:
                        return self.count
        """
        assert _codes(src) == []

    def test_lock_holding_context_manager_counts_as_the_lock(self):
        # LocalReplica._locked: a @contextmanager method that acquires
        # self._mu (under a span that times the wait), yields, and
        # releases — ``with self._locked(...)`` holds _mu like
        # ``with self._mu`` does
        src = """
            import contextlib
            import threading
            class C:
                def __init__(self):
                    self._mu = threading.RLock()
                    self.count = 0
                @contextlib.contextmanager
                def _locked(self, who):
                    self._mu.acquire()
                    try:
                        yield
                    finally:
                        self._mu.release()
                def start(self):
                    threading.Thread(target=self._loop, daemon=True,
                                     name="pt-x").start()
                def _loop(self):
                    with self._locked("loop"):
                        self._tick_locked()
                def _tick_locked(self):
                    self.count += 1
                def snapshot(self):
                    with self._locked("other"):
                        return self.count
        """
        assert _codes(src) == []
        # the same shape without the acquire guards nothing
        assert "PT-RACE-401" in _codes(src.replace(
            "self._mu.acquire()", "pass"))

    def test_two_thread_entries_racing_each_other_flagged(self):
        # the peer write can live in ANOTHER thread entry — two worker
        # loops racing is the classic write/write form
        src = """
            import threading
            class C:
                def __init__(self):
                    self.n = 0
                def start(self):
                    threading.Thread(target=self._w1, daemon=True,
                                     name="pt-1").start()
                    threading.Thread(target=self._w2, daemon=True,
                                     name="pt-2").start()
                def _w1(self):
                    self.n += 1
                def _w2(self):
                    self.n += 1
        """
        assert _codes(src) == ["PT-RACE-401"]
        # clean twin: both workers share one lock
        clean = src.replace(
            "self.n = 0",
            "self.n = 0\n        self._mu = threading.Lock()").replace(
            "self.n += 1",
            "with self._mu:\n            self.n += 1")
        assert _codes(clean) == []

    def test_sync_primitive_rebinds_exempt(self):
        # assigning a fresh Event from the thread is lifecycle churn,
        # not shared-state mutation
        src = """
            import threading
            class C:
                def start(self):
                    threading.Thread(target=self._run, daemon=True,
                                     name="pt-x").start()
                def _run(self):
                    self._evt = threading.Event()
                def wait(self):
                    return self._evt
        """
        assert _codes(src) == []


# ---------------------------------------------------------------------------
# PT-RACE-402 — lock-order inversion
# ---------------------------------------------------------------------------


class TestRace402:
    def test_lexical_inversion_flagged_with_both_witnesses(self):
        src = """
            import threading
            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def f(self):
                    with self._a:
                        with self._b:
                            pass
                def g(self):
                    with self._b:
                        with self._a:
                            pass
        """
        diags = analyze_source(textwrap.dedent(src), "x.py")
        assert [d.code for d in diags] == ["PT-RACE-402"]
        msg = diags[0].message
        # BOTH witness paths named, with their functions
        assert "C.f" in msg and "C.g" in msg
        assert "C._a" in msg and "C._b" in msg

    def test_consistent_order_clean(self):
        src = """
            import threading
            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def f(self):
                    with self._a:
                        with self._b:
                            pass
                def g(self):
                    with self._a:
                        with self._b:
                            pass
        """
        assert _codes(src) == []

    def test_inversion_through_call_chain_flagged(self):
        # f holds A and calls helper() which takes B; g nests B then A
        src = """
            import threading
            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def f(self):
                    with self._a:
                        self.helper()
                def helper(self):
                    with self._b:
                        pass
                def g(self):
                    with self._b:
                        with self._a:
                            pass
        """
        diags = analyze_source(textwrap.dedent(src), "x.py")
        assert [d.code for d in diags] == ["PT-RACE-402"]
        assert "helper" in diags[0].message

    def test_reentrant_same_lock_not_a_cycle(self):
        src = """
            import threading
            class C:
                def __init__(self):
                    self._mu = threading.RLock()
                def f(self):
                    with self._mu:
                        with self._mu:
                            pass
        """
        assert _codes(src) == []


# ---------------------------------------------------------------------------
# PT-RACE-403 — blocking while holding a lock
# ---------------------------------------------------------------------------


class TestRace403:
    def test_bare_queue_get_under_lock_flagged_timeout_clean(self):
        src = """
            import threading, queue
            class C:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._q = queue.Queue()
                def f(self):
                    with self._mu:
                        return self._q.get()
        """
        diags = analyze_source(textwrap.dedent(src), "x.py")
        assert [d.code for d in diags] == ["PT-RACE-403"]
        assert "C._mu" in diags[0].message
        clean = src.replace(".get()", ".get(timeout=1.0)")
        assert _codes(clean) == []

    def test_join_and_event_wait_under_lock_flagged(self):
        src = """
            import threading
            class C:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._evt = threading.Event()
                    self._t = threading.Thread(target=print,
                                               name="pt-t",
                                               daemon=True)
                def f(self):
                    with self._mu:
                        self._t.join()
                def g(self):
                    with self._mu:
                        self._evt.wait()
        """
        assert _codes(src) == ["PT-RACE-403", "PT-RACE-403"]

    def test_wait_on_held_condition_is_sanctioned(self):
        # cond.wait() releases the condition it waits on — the classic
        # pattern must stay silent; a timeout keeps even that bounded
        src = """
            import threading
            class C:
                def __init__(self):
                    self._cond = threading.Condition()
                    self.ready = False
                def f(self):
                    with self._cond:
                        while not self.ready:
                            self._cond.wait(0.1)
        """
        assert _codes(src) == []

    def test_wait_on_foreign_condition_under_lock_flagged(self):
        src = """
            import threading
            class C:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._cond = threading.Condition()
                def f(self):
                    with self._mu:
                        with self._cond:
                            while True:
                                self._cond.wait()
        """
        # holding _mu across a _cond.wait stalls every _mu user
        diags = analyze_source(textwrap.dedent(src), "x.py")
        assert [d.code for d in diags] == ["PT-RACE-403"]
        assert "C._mu" in diags[0].message

    def test_blocking_in_private_helper_called_under_lock_flagged(self):
        src = """
            import threading, queue
            class C:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._q = queue.Queue()
                def f(self):
                    with self._mu:
                        self._drain()
                def _drain(self):
                    return self._q.get()
        """
        assert _codes(src) == ["PT-RACE-403"]

    def test_explicit_none_timeout_is_unbounded(self):
        # timeout=None (keyword or positional) is the UNBOUNDED
        # spelling of the same stall, not a bound
        src = """
            import threading, queue
            class C:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._q = queue.Queue()
                    self._t = threading.Thread(target=print,
                                               name="pt-t",
                                               daemon=True)
                def f(self):
                    with self._mu:
                        return self._q.get(timeout=None)
                def g(self):
                    with self._mu:
                        self._t.join(None)
        """
        assert _codes(src) == ["PT-RACE-403", "PT-RACE-403"]

    def test_queue_put_item_arg_is_not_a_timeout(self):
        # put's first positional is the ITEM; put(x) under a lock on a
        # bounded queue blocks unbounded
        src = """
            import threading, queue
            class C:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._q = queue.Queue(4)
                def f(self, item):
                    with self._mu:
                        self._q.put(item)
        """
        assert _codes(src) == ["PT-RACE-403"]
        # clean twins: non-blocking and bounded forms
        assert _codes(src.replace("put(item)",
                                  "put(item, False)")) == []
        assert _codes(src.replace("put(item)",
                                  "put(item, timeout=1.0)")) == []
        # put on an UNBOUNDED queue (default maxsize=0 / SimpleQueue)
        # never blocks — no finding
        assert _codes(src.replace("Queue(4)", "Queue()")) == []
        assert _codes(src.replace("Queue(4)", "SimpleQueue()")) == []
        # but get() on those still blocks
        geton = src.replace("Queue(4)", "Queue()").replace(
            "self._q.put(item)", "self._q.get()")
        assert _codes(geton) == ["PT-RACE-403"]

    def test_blocking_without_lock_clean(self):
        src = """
            import queue
            class C:
                def __init__(self):
                    self._q = queue.Queue()
                def f(self):
                    return self._q.get()
        """
        assert _codes(src) == []


# ---------------------------------------------------------------------------
# PT-RACE-404 — Condition.wait outside a predicate loop
# ---------------------------------------------------------------------------


class TestRace404:
    def test_if_guarded_wait_flagged_while_clean(self):
        src = """
            import threading
            class C:
                def __init__(self):
                    self._cond = threading.Condition()
                    self.ready = False
                def f(self):
                    with self._cond:
                        if not self.ready:
                            self._cond.wait(0.1)
        """
        diags = analyze_source(textwrap.dedent(src), "x.py")
        assert [d.code for d in diags] == ["PT-RACE-404"]
        assert "predicate loop" in diags[0].message
        clean = src.replace("if not self.ready:",
                            "while not self.ready:")
        assert _codes(clean) == []

    def test_wait_for_carries_its_own_loop(self):
        src = """
            import threading
            class C:
                def __init__(self):
                    self._cond = threading.Condition()
                    self.ready = False
                def f(self):
                    with self._cond:
                        self._cond.wait_for(lambda: self.ready, 1.0)
        """
        assert _codes(src) == []


# ---------------------------------------------------------------------------
# PT-RACE-405 — non-daemon thread never joined
# ---------------------------------------------------------------------------


class TestRace405:
    def test_fire_and_forget_non_daemon_flagged(self):
        src = """
            import threading
            def spawn():
                t = threading.Thread(target=print, name="pt-t")
                t.start()
        """
        diags = analyze_source(textwrap.dedent(src), "x.py")
        assert [d.code for d in diags] == ["PT-RACE-405"]
        assert "non-daemon" in diags[0].message

    def test_daemon_clean_and_joined_clean(self):
        daemon = """
            import threading
            def spawn():
                t = threading.Thread(target=print, name="pt-t",
                                     daemon=True)
                t.start()
        """
        assert _codes(daemon) == []
        joined = """
            import threading
            def spawn():
                t = threading.Thread(target=print, name="pt-t")
                t.start()
                t.join(timeout=5)
        """
        assert _codes(joined) == []


# ---------------------------------------------------------------------------
# shared machinery: suppressions, CLI, lock_order_graph, dogfood
# ---------------------------------------------------------------------------


class TestPlumbing:
    def test_registry_covers_all_codes(self):
        assert set(RACE_CODES) == {"PT-RACE-401", "PT-RACE-402",
                                   "PT-RACE-403", "PT-RACE-404",
                                   "PT-RACE-405"}

    def test_suppression_requires_reason(self):
        flagged = ("import threading\n"
                   "def spawn():\n"
                   "    t = threading.Thread(target=print, name='x')"
                   "  # pt-lint: disable=PT-RACE-405\n"
                   "    t.start()\n")
        diags = analyze_source(flagged, "x.py")
        assert len(diags) == 1 and "require a reason" in diags[0].message
        ok = flagged.replace("disable=PT-RACE-405",
                             "disable=PT-RACE-405 interp-owned helper")
        assert analyze_source(ok, "x.py") == []

    def test_unparseable_source_defers_to_lint(self):
        # lint_source owns the parse diagnosis; this pass stays silent
        assert analyze_source("def f(:\n", "broken.py") == []

    def test_cli_family_select(self, tmp_path, capsys):
        lint_tool = load_tool("lint")
        (tmp_path / "a.py").write_text(textwrap.dedent("""
            import threading
            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def f(self):
                    with self._a:
                        with self._b:
                            pass
                def g(self):
                    with self._b:
                        with self._a:
                            pass
        """))
        rc = lint_tool.main(["--select=PT-RACE", "--format=json",
                             str(tmp_path)])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1 and out["count"] == 1
        assert out["findings"][0]["code"] == "PT-RACE-402"
        # family select filters OUT the lint family
        (tmp_path / "b.py").write_text("breakpoint()\n")
        rc = lint_tool.main(["--select=PT-RACE", "--format=json",
                             str(tmp_path)])
        out = json.loads(capsys.readouterr().out)
        assert out["count"] == 1  # the 305 hit is not selected
        # and the full run reports both families
        rc = lint_tool.main(["--format=json", str(tmp_path)])
        out = json.loads(capsys.readouterr().out)
        codes = {f["code"] for f in out["findings"]}
        assert {"PT-RACE-402", "PT-LINT-305"} <= codes

    def test_lock_order_graph_contract(self, tmp_path):
        (tmp_path / "m.py").write_text(textwrap.dedent("""
            import threading
            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def f(self):
                    with self._a:
                        with self._b:
                            pass
        """))
        graph = lock_order_graph([str(tmp_path)])
        # module identity is <parent_dir>.<stem> — collision-safe
        # across this tree's same-named modules (static/io.py vs
        # fluid/io.py, ...)
        mod = f"{os.path.basename(str(tmp_path))}.m"
        assert (f"{mod}:C._a", f"{mod}:C._b") in graph
        assert "C.f" in graph[(f"{mod}:C._a", f"{mod}:C._b")]

    def test_repo_threaded_half_analyzes_clean(self):
        """The dogfood gate as a tier-1 test: every true positive the
        pass found in paddle_tpu/ was fixed (Watchdog._fired lock,
        FleetController._req_mu, ...) or suppressed with a reason — a
        new race-shaped regression fails here AND in the ci.sh race
        smoke stage."""
        findings = analyze_paths([os.path.join(REPO, "paddle_tpu")])
        assert findings == [], format_diagnostics(findings)

    def test_threadpool_without_prefix_flagged(self):
        """The PT-LINT-303 pool extension rides the same dogfood: an
        anonymous executor produces unattributable lanes in merged
        chrome-traces."""
        from paddle_tpu.analysis import lint_source

        src = ("from concurrent.futures import ThreadPoolExecutor\n"
               "def f(xs):\n"
               "    with ThreadPoolExecutor(max_workers=2) as ex:\n"
               "        return list(ex.map(str, xs))\n")
        assert [d.code for d in lint_source(src, "x.py")] == \
            ["PT-LINT-303"]
        clean = src.replace(
            "max_workers=2",
            "max_workers=2, thread_name_prefix='pt-map'")
        assert lint_source(clean, "x.py") == []
