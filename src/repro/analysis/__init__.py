"""Project-specific static analysis (the `dtmlint` pass).

Three layers, mirroring MATADOR-style design-rule checking before
synthesis (arXiv 2403.10538) for our jax_pallas stack:

* :mod:`repro.analysis.lint` — AST rules (DTM001..) codifying invariants
  that earlier PRs fixed by hand: unsized dynamic shapes, stray env
  reads, hot-path syncs, tracer branches, dtype promotion against the
  packed layout, writeable cached arrays, interpret-default drift,
  silent exception fallbacks, unlocked stats reads.
* :mod:`repro.analysis.kernel_check` — static Pallas kernel contract
  checker: grid x index-map coverage, Mosaic's (8, 128) block rule and
  per-tile VMEM footprints for every tile plan the autotuner can emit,
  against the scoped VMEM limit each launch gets.
* :mod:`repro.analysis.trace_audit` — runtime trace contract: the
  five-TMSpec-kind scenario matrix under ``jax.checking_leaks`` +
  ``jax.transfer_guard("disallow")``, jit cache sizes and dispatch
  tables diffed against the committed ``ANALYSIS_baseline.json``.

``tools/dtmlint`` is the CLI over all three.
"""

from repro.analysis.lint import RULES, Finding, lint_paths, lint_source

__all__ = ["RULES", "Finding", "lint_paths", "lint_source"]
