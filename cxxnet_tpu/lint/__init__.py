"""cxxlint: framework-aware static analysis for the cxxnet_tpu tree.

The worst bugs in a threaded JAX stack are invisible at runtime:
an unlocked cross-thread mutation loses one counter a week, a fifth
duplicated AOT call site recompiles silently in the serve hot path,
a new telemetry kind ships without a schema validator. cxxlint is the
mechanical memory of those past bugs — each check encodes an invariant
a previous PR had to retrofit by hand (doc/static_analysis.md has the
full catalogue and the history behind every code).

Usage (CLI)::

    python -m cxxnet_tpu.lint cxxnet_tpu/ tools/
    python -m cxxnet_tpu.lint --format json --select CXL002,CXL006

Exit codes: 0 clean, 1 findings, 2 usage error.

Suppressions are inline and must carry a reason::

    x = np.asarray(loss)  # cxxlint: disable=<code> -- <why>

(with the real ``CXL00N`` code; doc/static_analysis.md shows worked
examples.)

Grandfathered findings live in a committed baseline file
(``cxxnet_tpu/lint/baseline.json``); the tier-1 gate keeps the merged
tree at zero unsuppressed, unbaselined findings.
"""

from .core import (Finding, LintError, LintResult, all_checks, register,
                   run_lint)

__all__ = ["Finding", "LintError", "LintResult", "all_checks",
           "register", "run_lint"]
