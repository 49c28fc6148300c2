"""Lint configuration: per-rule scopes, allowlists, and budgets.

Every allowlist entry here is a *documented design decision*, not an escape
hatch -- each one names the contract it carves out and why the carve-out is
sound (docs/CONTRACTS.md holds the long-form rationale).  One-off local
exemptions use the inline ``# genielint: ignore[rule]`` syntax instead, so
blanket suppressions never accumulate silently in config.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class LintConfig:
    """Knobs and allowlists consumed by the rules (tools/genielint/rules_*).

    Paths are repo-relative POSIX paths under the scan root (``src/``), e.g.
    ``repro/core/plan.py``; prefixes end with ``/``.
    """

    # -- executor-sovereignty ----------------------------------------------
    # The only modules allowed to *call* the selection/merge/pad-mask
    # machinery: the executor itself plus the modules that define it.
    # Everything else must delegate through core/plan.execute.
    executor_modules: frozenset = frozenset({
        "repro/core/plan.py",    # the executor: the one orchestration site
        "repro/core/select.py",  # defines select_topk (method dispatch)
        "repro/core/cpq.py",     # defines topk_from_candidates + CPQ select
        "repro/core/spq.py",     # SPQ selection method (calls the CPQ merge)
        "repro/core/merge.py",   # defines merge_ragged / merge_topk
    })
    # The call names whose call sites the rule governs.
    governed_calls: frozenset = frozenset({
        "select_topk", "merge_ragged", "merge_topk",
        "_mask_pad_counts", "_mask_invalid", "topk_from_candidates",
    })

    # -- pallas-kernel-contract --------------------------------------------
    kernel_prefix: str = "repro/kernels/"
    # VMEM is ~16 MiB/core on current TPUs; the budget leaves headroom for
    # Pallas' double-buffered input windows and scratch.  Configurable via
    # --vmem-budget-mb.
    vmem_budget_bytes: int = 12 * 1024 * 1024
    # Conservative stand-in for tile dims the resolver cannot fold to a
    # constant (data-dependent widths like the signature length m): GENIE
    # signature/feature widths are <= 512 everywhere (configs/, packing
    # word counts are 32x smaller still).  A block's unfoldable dims share
    # this one width between them: the column-sweep kernels split it as
    # (Mp/G, TQ, G).
    assume_dim: int = 512
    # The registry's count-dtype policy (core/engines.py::MatchModel): match
    # kernels accumulate and emit exact int32 counts; any narrowing happens
    # *after* the kernel via as_count_dtype (Bitmap-Counter, paper III-C).
    # A float out_shape reintroduces the 2^24 rounding bound PR 6 removed
    # from the cosine kernel.  tests/test_lint.py cross-checks this set
    # against the live registry policy.
    kernel_out_dtypes: frozenset = frozenset({"int32"})

    # -- retrace-hygiene ----------------------------------------------------
    # Modules whose jitted/kernel function bodies must stay retrace-free:
    # the executor and every Pallas kernel module.
    traced_modules: frozenset = frozenset({"repro/core/plan.py"})
    traced_prefixes: tuple = ("repro/kernels/",)
    # QueryPlan fields that legitimately do not appear verbatim in
    # describe(): each is derived from fields that DO appear, so a cache-key
    # change is still always visible in the description.
    describe_derived: frozenset = frozenset({
        "match",      # resolved from engine x use_kernel x signature_layout
                      # x tile_overrides (core/autotune.py tuned tiles bind
                      # memoized callables; overrides surface verbatim)
        "params",     # expanded into the k / method / use_kernel keys
        "pad_value",  # resolved from engine x signature_layout
    })

    # -- lock-discipline ----------------------------------------------------
    lock_modules: frozenset = frozenset({
        "repro/serve/frontend.py",
        "repro/serve/scheduler.py",
        "repro/serve/metrics.py",
    })

    # -- wall-clock ----------------------------------------------------------
    # time.time() is banned for durations; fault-tolerance heartbeats keep it
    # BY DESIGN -- deadlines are compared across processes on the same
    # machine, and perf_counter's epoch is process-local (PR 8 comment in
    # runtime/fault_tolerance.py).
    wall_clock_allow: frozenset = frozenset({
        "repro/runtime/fault_tolerance.py",
    })

    # -- broad-except --------------------------------------------------------
    # No file-level allowlist: the two by-design catch-alls (the dry-run's
    # record-the-bug-loudly boundary, the serving dispatch loop's
    # scatter-don't-die boundary) carry inline ignores at the site, where
    # the justification lives next to the code.
    broad_except_allow: frozenset = frozenset()


DEFAULT = LintConfig()
