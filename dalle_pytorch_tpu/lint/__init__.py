"""graftlint — TPU/JAX static analysis distilled from this repo's bug history.

Five review rounds each spent scarce chip time rediscovering bug classes
that are statically detectable on CPU in seconds (ISSUE 2): raw env-var
truthiness treating ``FLAG=0`` as ON, ``hash()`` seeds that don't reproduce
across processes, module-level backend queries that make a mere import
claim the chip, mixed-dtype dots whose f32-accumulation contract held only
by convention, host syncs inside traced code, and broad excepts swallowing
XLA errors.  This package is the rule
engine; ``tools/graftlint.py`` is the CLI and ``tools/contract_check.py``
is the companion dynamic-contract checker (``jax.eval_shape``, zero FLOPs).

Every rule supports an inline suppression pragma **with a mandatory
justification**::

    if os.environ.get("ADDR"):  # graftlint: disable=ENV001 (address-valued)

A pragma without a parenthesized reason is itself an error (PRAGMA001) —
suppressions document *why* the rule does not apply, or they don't count.
"""
from .engine import (FINDINGS_JSON_SCHEMA, Finding, filter_baseline,
                     findings_to_json, findings_to_sarif, fingerprint,
                     fix_env001, iter_python_files, lint_paths, lint_source,
                     load_baseline, prune_baseline, stale_baseline_entries,
                     write_baseline)
from .rules import RULES

__all__ = [
    "Finding", "RULES", "lint_source", "lint_paths", "fingerprint",
    "iter_python_files",
    "load_baseline", "write_baseline", "filter_baseline", "fix_env001",
    "stale_baseline_entries", "prune_baseline",
    "findings_to_json", "findings_to_sarif", "FINDINGS_JSON_SCHEMA",
]
