"""apexlint — TPU tracing-hazard and kernel-constraint static analysis.

Usage (CLI)::

    python -m apex_tpu.lint apex_tpu/ [--format text|json]
        [--baseline tools/apexlint_baseline.json]
        [--select APX1,APX301] [--ignore APX5] [--list-rules]

Usage (API)::

    from apex_tpu import lint
    findings, suppressed = lint.lint_source(src, path="x.py")
    findings, stats = lint.lint_paths(["apex_tpu/"])

Rule families (catalogue with bad/good snippets: docs/api/lint.md):

* **APX1xx** tracing/recompile hazards (control flow, concretization,
  host numpy on traced values; static_argnums hygiene)
* **APX2xx** donation/aliasing (use-after-donation, donated buffers not
  re-threaded through loops)
* **APX3xx** Pallas kernel constraints ((8, 128) tiling, index-map arity,
  interpret-mode fallback convention, materialized O(s²) bias into fused
  attention)
* **APX4xx** collective/axis hygiene (axis names outside dp/tp/pp/cp/ep)
* **APX5xx** PRNG and precision discipline (dropout without a key,
  constant PRNG keys, bf16/fp32 cast mixing)

Beyond the AST rules, ``python -m apex_tpu.lint --jaxpr`` checks **JXP
contracts** over *traced programs* (``apex_tpu.lint.contracts`` /
``jaxpr_check``): scan geometry (JXP1xx), donation honored at the jit
level (JXP2xx), forbidden aval shapes (JXP3xx), collective inventory —
ppermute present, no full-width all_gather, collective-free regions
(JXP4xx), and fp32 accumulation (JXP5xx) — against the registered
flagship entrypoints (``apex_tpu.lint.entrypoints``), with the same
walk also emitting the planner's ``static_cost`` artifact.

Suppression: ``# apexlint: disable=APX101`` (comma-separated, or ``all``)
on the flagged line; repo-wide intentional findings live in
``tools/apexlint_baseline.json`` — every entry carries a ``reason``
(jaxpr findings baseline by ``(path="jaxpr:<entrypoint>", code)``).

The lint package itself imports only the stdlib (``ast``/``json``) — the
analysis cannot be confused by the jax version it vets. The
``python -m apex_tpu.lint`` CLI does ride the parent ``apex_tpu`` import
(which imports jax); see ``core.py``'s docstring for driving the engine
jax-free.
"""

from apex_tpu.lint.core import (  # noqa: F401
    Finding,
    KNOWN_MESH_AXES,
    PARSE_ERROR_CODE,
    REGISTRY,
    REPORT_VERSION,
    Rule,
    apply_baseline,
    build_report,
    lint_paths,
    lint_source,
    load_baseline,
    validate_report,
)

# importing the rule modules populates REGISTRY
from apex_tpu.lint import (  # noqa: E402,F401
    rules_collectives,
    rules_donation,
    rules_pallas,
    rules_prng,
    rules_tracing,
)

# the jaxpr-level layer (`--jaxpr`): stdlib-only like the AST rules —
# contracts/jaxpr_check walk duck-typed jaxpr objects; only
# lint.entrypoints (imported lazily by the CLI) touches jax
from apex_tpu.lint import contracts, jaxpr_check  # noqa: E402,F401
from apex_tpu.lint.contracts import (  # noqa: F401
    Contract,
    ContractFinding,
    assert_contracts,
    check_jaxpr,
)
from apex_tpu.lint.jaxpr_check import static_cost  # noqa: F401


def iter_rules():
    """Registered rules in code order."""
    return [REGISTRY[c] for c in sorted(REGISTRY)]
