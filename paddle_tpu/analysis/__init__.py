"""Static verification plane — ahead-of-execution analyzers.

The reference Fluid verified nothing before the op loop ran (a
malformed ProgramDesc died mid-run, reference: framework/executor.cc);
this package is the opposite posture: pure static passes over the
program IR, buffer provenance, sharding plans, and the repo's own
source, each returning typed :class:`Diagnostic` records *before*
anything executes.

- :mod:`.verify` — Program IR verifier (use-before-write, conflicting
  writes, dead ops, unreachable fetches, shape/dtype drift, param
  mutation). Wired into ``Executor.run`` as verify-on-first-compile.
- :mod:`.donation` — donation-safety analyzer (host-owned / view /
  zero-copy-host-backed buffers donated; unused donations; alias
  escapes — the PR 6 SIGSEGV classification). Wired into ``Trainer`` at
  compile time.
- :mod:`.shardcheck` — static Plan audit (would-reshard, dropped
  specs, big-leaf-replicated). Rendered by ``Plan.describe`` and
  /statusz.
- :mod:`.lint` — AST linter for repo invariants (atomic state writes,
  span clocks, thread names, device_get-into-donation, debug
  leftovers). ``tools/lint.py`` CLI + the ci.sh ``lint`` stage.
- :mod:`.concurrency` — whole-repo concurrency verifier (the
  ``PT-RACE-4xx`` family: unsynchronized shared writes from thread
  entries, lock-order inversions with witness paths, blocking calls
  under locks, non-looped condition waits, unjoined non-daemon
  threads). ``tools/lint.py --select PT-RACE`` + the ci.sh ``race
  smoke`` stage; :func:`~.concurrency.lock_order_graph` feeds the
  runtime lock-order watchdog (``telemetry/lockwatch.py``).

Opt out of the wired-in passes with ``FLAGS_static_verify=0`` (env or
``core.config.FLAGS``); the analyzers stay importable/callable either
way.
"""

from .concurrency import (RACE_CODES, analyze_file, analyze_paths,
                          analyze_source, lock_order_graph)
from .diagnostics import (Diagnostic, errors, format_diagnostics,
                          has_errors)
from .donation import (check_donation, classify_provenance,
                       note_host_backed, note_owned, note_transfer,
                       track_host_transfers)
from .lint import LINT_CODES, lint_file, lint_paths, lint_source
from .shardcheck import audit_plan, audit_summary
from .verify import fetch_diagnostic, verify_program

__all__ = [
    "Diagnostic", "errors", "format_diagnostics", "has_errors",
    "verify_program", "fetch_diagnostic",
    "check_donation", "classify_provenance", "note_owned",
    "note_host_backed", "note_transfer", "track_host_transfers",
    "audit_plan", "audit_summary",
    "lint_source", "lint_file", "lint_paths", "LINT_CODES",
    "analyze_source", "analyze_file", "analyze_paths", "RACE_CODES",
    "lock_order_graph",
]
