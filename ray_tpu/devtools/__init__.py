"""Framework-aware developer tooling: static analysis + lock diagnostics.

Two halves (reference: the semgrep/pyrefly CI rules and absl mutex
annotations the reference repo leans on — here the discipline is in-tree
and understands ``ray_tpu`` semantics):

* ``ray_tpu.devtools.lint`` — an AST rule engine behind ``ray-tpu lint``.
  User-code rules (RT1xx) catch the documented anti-patterns — blocking
  ``get()`` inside a ``@remote`` body, ``get()``-per-item loops, large or
  unserializable captures, actor self-calls.  Framework-internal rules
  (RT2xx) enforce invariants over ``ray_tpu/`` itself — no blocking call
  under a lock, no silently swallowed exceptions in the control plane,
  monotonic-clock durations, telemetry names from the catalog, protocol
  messages with registered handlers.  ``tests/test_lint.py`` keeps the
  tree self-lint-clean (tier-1 gate).

* ``ray_tpu.devtools.lockdebug`` — opt-in runtime lock instrumentation,
  two modes sharing one wrapper stack.  ``RAY_TPU_DEBUG_LOCKS=1`` is
  the full lock-order detector: a per-process acquisition-order graph
  flags cycles (AB/BA potential deadlocks) and sleeps under a held
  lock.  ``RAY_TPU_LOCK_PROFILE=1`` is the lighter contention
  profiler: per-creation-site wait/hold histograms only,
  reported by ``contention_report()``, published to the
  ``ray_tpu_lock_{wait,hold}_seconds`` catalog series, dumped into
  flight-recorder bundles as ``lock_contention.json`` and rendered by
  ``ray-tpu lint --lock-report``.

* ``ray_tpu.devtools.rules_concurrency`` — the RT4xx guarded-by family
  over the same CFG machinery: per class, infer which attributes are
  guarded by which locks (``_locked``-contract and private-helper entry
  assumptions solved to a fixpoint) and flag inconsistent guarding
  (RT401), check-then-act outside the lock (RT402), release
  mid-iteration (RT403), callbacks/publishes under hot control-plane
  locks (RT404) and ``_locked`` methods called bare (RT405).

* ``ray_tpu.devtools.dataflow`` — a per-function CFG builder + an
  acquire/release pairing analysis over it; the RT3xx rule family
  (``rules_dataflow``) runs on top: resources released on every path
  (RT301), no dangling ObjectRefs (RT302, ``# ray-tpu: detached``
  marker), KV prefixes with a delete/GC story (RT303), except paths
  that keep the happy path's releases (RT304).  Its runtime twin is the
  leak sanitizer in ``ray_tpu/_private/sanitizer.py``
  (``RAY_TPU_SANITIZE=1``), on for the whole tier-1 suite.

* ``ray_tpu.devtools.chaos`` — the chaos SLA harness: scripted
  kill/preempt/add schedules replayed against a live cluster, so drain
  SLAs and goodput-under-preemption are measured, not asserted from a
  single hand-timed kill.
"""

from .lint import (Finding, LintResult, Rule, iter_rules, lint_paths,
                   lint_source)

__all__ = ["Finding", "LintResult", "Rule", "iter_rules", "lint_paths",
           "lint_source"]
