#!/usr/bin/env python
"""Codebase-level registry lint: primitive registry + ``__all__`` audit.

The static-program analysis layer (paddle_tpu/static/analysis) checks
captured programs; this script applies the same discipline to the code
that *defines* the ops. It verifies, over the fully-imported package:

1. every ``dispatch.PRIMITIVES`` entry has a callable ``forward``
   (backward-only registrations — ``pylayer::*``, ``recompute::replay``
   — with a callable ``vjp`` are the one sanctioned exception);
2. grad wiring is mutually consistent: ``save`` without a ``vjp`` is
   dead weight (the fallback path saves inputs itself), and ``vjp``/
   ``save`` must be callables whose signatures can accept the engine's
   calling convention (``vjp(grads_out, saved, **static)``,
   ``save(arrays_in, outs)``);
3. every name in each imported ``paddle_tpu`` module's ``__all__``
   actually resolves on that module;
4. every metric registered at import time in the observability registry
   is unique, documented, matches the ``subsystem.noun_verb`` naming
   scheme, and its subsystem prefix is claimed in
   ``observability.metrics.CLAIMED_SUBSYSTEMS`` (the metric analog of
   the ``PTLxxx`` diagnostic-code claiming convention);
5. the diagnostic-code registry is closed both ways: every registered
   lint (``lint.LINTS``, the sharding lints) and every lint-fix rewrite
   pass claims a code documented in ``diagnostics.CODES``, and every
   documented ``PTLxxx`` code is exercised by at least one test under
   ``tests/`` — a code nothing can trigger (or nothing proves
   triggerable) is registry rot either way.

Exits non-zero listing every violation — wired into the test session via
a session-scoped fixture in tests/conftest.py (skippable with
``PADDLE_TPU_SKIP_REGISTRY_LINT=1``), so registry drift fails tier-1
instead of surfacing as an AttributeError in production.
"""
from __future__ import annotations

import os
import sys
from typing import List

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def _can_take_two(capacity) -> bool:
    """capacity is dispatch.positional_capacity's (min, max|None)."""
    if capacity is None:
        return True  # opaque signature: give the benefit of the doubt
    _min, _max = capacity
    if _min is None:
        return True
    return _max is None or _max >= 2


def check_primitives() -> List[str]:
    from paddle_tpu.core import dispatch

    problems = []
    for name, prim in sorted(dispatch.PRIMITIVES.items()):
        meta = dispatch.primitive_metadata(name)
        if prim.forward is None:
            # sanctioned backward-only registrations (pylayer::*,
            # recompute::replay) carry the op through the eager tape and
            # exist solely for their custom vjp — the vjp must be there
            if callable(prim.vjp):
                continue
            problems.append(
                f"primitive {name!r}: forward is None and there is no "
                f"callable vjp (backward-only registrations must provide "
                f"one; everything else must provide a forward)")
            continue
        if not callable(prim.forward):
            problems.append(
                f"primitive {name!r}: forward is not callable "
                f"({type(prim.forward).__name__})")
        if prim.vjp is not None and not callable(prim.vjp):
            problems.append(f"primitive {name!r}: vjp is not callable")
        if prim.save is not None and not callable(prim.save):
            problems.append(f"primitive {name!r}: save is not callable")
        if prim.save is not None and prim.vjp is None:
            problems.append(
                f"primitive {name!r}: has save= but no vjp — the generic "
                f"jax.vjp fallback ignores save and rematerializes from "
                f"inputs, so the save hook is dead weight (add the vjp or "
                f"drop the save)")
        if callable(prim.vjp) and not _can_take_two(meta["vjp_capacity"]):
            problems.append(
                f"primitive {name!r}: vjp cannot accept "
                f"(grads_out, saved) — dispatch.call_vjp passes two "
                f"positionals")
        if callable(prim.save) and not _can_take_two(meta["save_capacity"]):
            problems.append(
                f"primitive {name!r}: save cannot accept "
                f"(arrays_in, outs) — the engine passes two "
                f"positionals at forward time")
    return problems


def check_all_exports() -> List[str]:
    problems = []
    for mod_name in sorted(sys.modules):
        if not (mod_name == "paddle_tpu" or
                mod_name.startswith("paddle_tpu.")):
            continue
        mod = sys.modules[mod_name]
        if mod is None:
            continue
        exported = getattr(mod, "__all__", None)
        if not exported:
            continue
        for sym in exported:
            if not isinstance(sym, str):
                problems.append(
                    f"{mod_name}.__all__ contains a non-string entry "
                    f"{sym!r}")
            elif not hasattr(mod, sym):
                problems.append(
                    f"{mod_name}.__all__ exports {sym!r} but the module "
                    f"has no such attribute")
    return problems


#: comm.collective_* series MUST carry these labels — an unlabeled
#: collective metric cannot be attributed to a mesh axis, which defeats
#: the per-mesh accounting the subsystem exists for.
COLLECTIVE_REQUIRED_LABELS = ("group", "op")

#: same discipline for the elastic recovery series: a restart that can't
#: say WHY, or a peer death that can't say WHO, is an alert nobody can
#: act on. Keys are metric names, values the labels every recorded
#: series must carry.
ELASTIC_REQUIRED_LABELS = {
    "elastic.restarts": ("reason",),
    "elastic.peer_deaths": ("peer",),
}

#: lint->rewrite driver label discipline (static/analysis/rewrite.py):
#: a fixed/remaining count that can't say WHICH code, or a rewrite
#: timing that can't say WHICH pass, defeats the measured-benefit
#: scheduling the opt. subsystem exists for.
OPT_REQUIRED_LABELS = {
    "opt.findings_fixed": ("code",),
    "opt.findings_remaining": ("code",),
    "opt.rewrite_seconds": ("name",),
    "opt.passes_skipped": ("name",),
}

#: cost/memory-analysis label discipline (static/analysis/cost.py +
#: memory.py): every predicted/measured series must say WHICH program
#: it describes — a predicted-vs-measured table with unattributable
#: rows cannot catch cost-model rot per workload.
COST_REQUIRED_LABELS = {
    "cost.predicted_flops": ("name",),
    "cost.measured_flops": ("name",),
    "cost.model_flops_error_pct": ("name",),
    "cost.predicted_peak_hbm_bytes": ("name",),
    "cost.measured_peak_hbm_bytes": ("name",),
    "cost.predicted_oom": ("name",),
    "cost.estimate_seconds": ("kind",),
    # step-time model + comm cost (static/analysis/comm_cost.py): the
    # comm series additionally say WHICH collective kind, so the
    # per-collective table in observability/report.py can render
    "cost.predicted_step_seconds": ("name",),
    "cost.measured_step_seconds": ("name",),
    "cost.model_step_error_pct": ("name",),
    "cost.comm_predicted_bytes": ("kind", "name"),
    "cost.comm_predicted_seconds": ("kind", "name"),
}

#: fleet-telemetry label discipline (observability/fleet.py): per-rank
#: series must say WHICH rank, ship failures must say WHY. Additionally
#: no ``fleet.`` GAUGE may record an unlabeled series at all — an
#: unattributable fleet gauge (no rank, no job) is exactly the
#: single-process myopia the subsystem exists to end.
FLEET_REQUIRED_LABELS = {
    "fleet.clock_offset_seconds": ("rank",),
    "fleet.snapshots_shipped": ("rank",),
    "fleet.snapshots_received": ("rank",),
    "fleet.rank_step_seconds": ("rank",),
    "fleet.stragglers_detected": ("rank",),
    "fleet.ship_failures": ("reason",),
    "fleet.ranks_reporting": ("job",),
    "fleet.step_skew_seconds": ("job",),
    "fleet.slowest_rank": ("job",),
}

#: serving-engine label discipline (serve/engine.py): every series must
#: say WHICH engine (multi-replica serving merges registries through the
#: fleet plane, and an unattributable server metric is useless there);
#: finish/reject/preempt/stall series must additionally carry the WHY.
SERVE_REQUIRED_LABELS = {
    "serve.requests_finished": ("engine", "reason"),
    "serve.requests_rejected": ("engine", "reason"),
    "serve.preemptions": ("engine", "reason"),
    "serve.admission_stalls": ("engine", "reason"),
    "serve.requests_admitted": ("engine",),
    "serve.tokens_generated": ("engine",),
    "serve.decode_steps": ("engine",),
    "serve.decode_traces": ("engine",),
    "serve.prefill_traces": ("engine",),
    "serve.ttft_seconds": ("engine",),
    "serve.request_seconds": ("engine",),
    "serve.decode_step_seconds": ("engine",),
    "serve.prefill_seconds": ("engine",),
    "serve.prefix_hits": ("engine",),
    "serve.prefix_blocks_shared": ("engine",),
    "serve.cow_copies": ("engine",),
    "serve.burst_tokens": ("engine",),
    "serve.host_roundtrips": ("engine",),
    "serve.moe_tokens_routed": ("engine",),
    "serve.moe_assignments_held": ("engine",),
    "serve.moe_expert_tokens_max": ("engine", "layer"),
    "serve.moe_expert_tokens_sum": ("engine", "layer"),
    "serve.paged_pages_live": ("engine",),
    "serve.paged_pages_table": ("engine",),
}

#: request-tracing / SLO label discipline (observability/tracing.py +
#: slo.py): per-phase series must say WHICH phase, breaches WHICH rule,
#: malformed-tree findings WHICH reason, exemplar retention WHICH kind —
#: and everything says WHICH engine, same as the serve. subsystem it
#: instruments.
TRACE_REQUIRED_LABELS = {
    "trace.requests_traced": ("engine",),
    "trace.spans_recorded": ("engine", "phase"),
    "trace.phase_seconds": ("engine", "phase"),
    "trace.decode_gap_seconds": ("engine",),
    "trace.exemplars_kept": ("engine", "kind"),
    "trace.spans_malformed": ("engine", "reason"),
    "trace.overhead_pct": ("engine",),
    "trace.slo_breaches": ("engine", "rule"),
}

#: op-profiler label discipline (observability/opprof.py): every series
#: attributes the profile name (which program was measured), and the
#: per-op series say WHICH primitive class — the join key the
#: cost-model calibration fits against.
OPPROF_REQUIRED_LABELS = {
    "opprof.steps_profiled": ("name",),
    "opprof.steps_skipped": ("name",),
    "opprof.op_seconds": ("name", "prim"),
    "opprof.step_seconds": ("name",),
    "opprof.attributed_pct": ("name",),
    "opprof.overhead_pct": ("name",),
    "opprof.drift_ratio": ("name", "prim"),
}

HEALTH_REQUIRED_LABELS = {
    "health.alerts": ("rule", "series"),
    "health.evaluations": ("rule",),
    "ts.points_recorded": ("series",),
}

#: one audit loop serves every per-subsystem required-labels table —
#: add the next subsystem as a row here, not as another copied loop
REQUIRED_LABEL_TABLES = (
    (ELASTIC_REQUIRED_LABELS, "elastic recovery series must attribute "
                              "the incident (who died / why the restart)"),
    (OPT_REQUIRED_LABELS, "opt. series must attribute the PTL code / "
                          "rewrite pass"),
    (COST_REQUIRED_LABELS, "cost. series must attribute the program "
                           "(or the analysis kind)"),
    (FLEET_REQUIRED_LABELS, "fleet series must attribute the rank (or "
                            "the reason/job)"),
    (SERVE_REQUIRED_LABELS, "serve series must attribute the engine "
                            "(and the reason where one applies)"),
    (TRACE_REQUIRED_LABELS, "trace series must attribute the engine "
                            "(and the phase/rule/reason/kind where one "
                            "applies)"),
    (OPPROF_REQUIRED_LABELS, "opprof series must attribute the profile "
                             "name (and the prim for per-op series)"),
    (HEALTH_REQUIRED_LABELS, "health/ts series must attribute the "
                             "detector rule and/or the recorded series"),
)

#: gauge-prefix discipline: no gauge under these prefixes may record an
#: UNLABELED series — a fleet gauge without rank/job, or a serve gauge
#: without engine=, cannot be attributed once registries merge.
NO_UNLABELED_GAUGE_PREFIXES = {
    "fleet.": "every fleet gauge must carry at least a rank= or job= "
              "label",
    "serve.": "every serve gauge must carry at least an engine= label",
    "cost.": "every cost gauge must carry at least a name= label (the "
             "program the prediction describes)",
    "trace.": "every trace gauge must carry at least an engine= label "
              "(serve-trace series merge through the fleet plane too)",
    "opprof.": "every opprof gauge must carry at least a name= label "
               "(the profile the measurement attributes)",
    "health.": "every health gauge must carry at least a rule= or "
               "series= label (an unlabeled health series cannot be "
               "attributed to a detector once registries merge)",
}


def check_metric_registry() -> List[str]:
    from paddle_tpu import observability
    # the runtime-telemetry modules register their metrics at import;
    # pull them in explicitly so the audit always covers the train./
    # device./comm./io. subsystems even when the workload under test
    # never touched them
    import paddle_tpu.distributed.communication.watchdog  # noqa: F401
    import paddle_tpu.distributed.elastic  # noqa: F401
    import paddle_tpu.io.dataloader  # noqa: F401
    import paddle_tpu.observability.fleet  # noqa: F401
    import paddle_tpu.observability.health  # noqa: F401
    import paddle_tpu.observability.opprof  # noqa: F401
    import paddle_tpu.observability.runtime  # noqa: F401
    import paddle_tpu.observability.slo  # noqa: F401
    import paddle_tpu.observability.timeseries  # noqa: F401
    import paddle_tpu.observability.tracing  # noqa: F401
    import paddle_tpu.serve  # noqa: F401
    from paddle_tpu.observability.metrics import (CLAIMED_SUBSYSTEMS,
                                                  NAME_RE)

    problems = []
    # the registry is define-or-get, so a reused name silently SHARES one
    # series family; uniqueness is audited via definition sites instead —
    # a name claimed from two different modules is an accidental collision
    for name, sites in sorted(observability.registry
                              .definition_sites().items()):
        if len(sites) > 1:
            problems.append(
                f"metric {name!r}: defined from {len(sites)} different "
                f"modules ({', '.join(sites)}) — metric names are claimed "
                f"per subsystem; pick a name under your own prefix")
    for m in observability.registry:
        if not NAME_RE.match(m.name):
            problems.append(
                f"metric {m.name!r}: does not match the "
                f"'subsystem.noun_verb' naming scheme ({NAME_RE.pattern})")
            continue
        subsystem = m.name.split(".", 1)[0]
        if subsystem not in CLAIMED_SUBSYSTEMS:
            problems.append(
                f"metric {m.name!r}: subsystem {subsystem!r} is not "
                f"claimed in observability.metrics.CLAIMED_SUBSYSTEMS — "
                f"claim the prefix next to your first metric (the PTLxxx "
                f"code-claiming convention)")
        if not m.doc:
            problems.append(
                f"metric {m.name!r}: registered without a doc string")
        if m.name.startswith("comm.collective"):
            for labels in m.labelsets():
                missing = [k for k in COLLECTIVE_REQUIRED_LABELS
                           if k not in labels]
                if missing:
                    problems.append(
                        f"metric {m.name!r}: series {labels!r} is missing "
                        f"required label(s) {missing} — collective metrics "
                        f"must be attributable to a mesh axis (label every "
                        f"record with op= and group=)")
        for table, why in REQUIRED_LABEL_TABLES:
            required = table.get(m.name)
            if not required:
                continue
            for labels in m.labelsets():
                missing = [k for k in required if k not in labels]
                if missing:
                    problems.append(
                        f"metric {m.name!r}: series {labels!r} is missing "
                        f"required label(s) {missing} — {why}")
        if m.kind == "gauge":
            for prefix, why in NO_UNLABELED_GAUGE_PREFIXES.items():
                if not m.name.startswith(prefix):
                    continue
                for labels in m.labelsets():
                    if not labels:
                        problems.append(
                            f"metric {m.name!r}: recorded an UNLABELED "
                            f"gauge series — {why}")
    return problems


def check_diagnostic_registry() -> List[str]:
    """The PTLxxx registry, closed both ways: every lint and lint-fix
    pass claims a documented code; every documented code is exercised
    by at least one test (string-presence scan over ``tests/``)."""
    from paddle_tpu.distributed import passes as passes_mod
    from paddle_tpu.distributed.passes.lint_fix_passes import LintFixPass
    from paddle_tpu.observability import health as health_mod
    from paddle_tpu.observability import opprof as opprof_mod
    from paddle_tpu.observability import slo as slo_mod
    from paddle_tpu.observability import tracing as tracing_mod
    from paddle_tpu.static.analysis import cost as cost_mod
    from paddle_tpu.static.analysis import diagnostics, serve_trace_lint
    from paddle_tpu.static.analysis import sharding_lint
    from paddle_tpu.static.analysis import lint as lint_mod

    problems = []
    for code, _severity, fn in lint_mod.LINTS:
        if code not in diagnostics.CODES:
            problems.append(
                f"lint {fn.__name__!r}: emits code {code!r} which is not "
                f"documented in diagnostics.CODES — claim the code next "
                f"to the registration")
    for code in sharding_lint.SHARDING_LINT_CODES:
        if code not in diagnostics.CODES:
            problems.append(
                f"sharding lint code {code!r} is not documented in "
                f"diagnostics.CODES")
    for code in cost_mod.COST_ANALYSIS_CODES:
        if code not in diagnostics.CODES:
            problems.append(
                f"cost-analysis code {code!r} is not documented in "
                f"diagnostics.CODES")
    for claimed_by, codes in (
            ("serve_trace_lint", serve_trace_lint.SERVE_TRACE_LINT_CODES),
            ("observability.tracing", tracing_mod.TRACE_CODES),
            ("observability.slo", slo_mod.SLO_CODES),
            ("observability.opprof", opprof_mod.OPPROF_CODES),
            ("observability.health", health_mod.HEALTH_CODES)):
        for code in codes:
            if code not in diagnostics.CODES:
                problems.append(
                    f"{claimed_by} code {code!r} is not documented in "
                    f"diagnostics.CODES")
    for name, cls in sorted(passes_mod._PASS_REGISTRY.items()):
        if isinstance(cls, type) and issubclass(cls, LintFixPass):
            code = getattr(cls, "code", "")
            if not code:
                problems.append(
                    f"rewrite pass {name!r}: LintFixPass subclass with no "
                    f"claimed code — a lint-fix pass must name the PTL "
                    f"code it fixes")
            elif code not in diagnostics.CODES:
                problems.append(
                    f"rewrite pass {name!r}: claims code {code!r} which "
                    f"is not documented in diagnostics.CODES")

    tests_dir = os.path.join(_REPO_ROOT, "tests")
    corpus = []
    try:
        for fn_ in sorted(os.listdir(tests_dir)):
            if fn_.endswith(".py"):
                with open(os.path.join(tests_dir, fn_),
                          errors="replace") as f:
                    corpus.append(f.read())
    except OSError as e:
        return problems + [f"cannot scan tests/ for PTL codes: {e}"]
    corpus = "\n".join(corpus)
    for code in sorted(diagnostics.CODES):
        if code not in corpus:
            problems.append(
                f"diagnostic code {code!r} has no test that references "
                f"it — every documented PTLxxx code needs at least one "
                f"test triggering (or asserting the absence of) it")
    return problems


def main(argv=None) -> int:
    import paddle_tpu  # noqa: F401 — populates the registry + sys.modules
    from paddle_tpu.core import dispatch

    problems = (check_primitives() + check_all_exports()
                + check_metric_registry() + check_diagnostic_registry())
    n_mods = sum(1 for m in sys.modules
                 if m == "paddle_tpu" or m.startswith("paddle_tpu."))
    from paddle_tpu import observability

    if problems:
        print(f"lint_registry: {len(problems)} violation(s) over "
              f"{len(dispatch.PRIMITIVES)} primitives / {n_mods} modules / "
              f"{len(observability.registry)} metrics:",
              file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        return 1
    print(f"lint_registry: OK ({len(dispatch.PRIMITIVES)} primitives, "
          f"{n_mods} modules, {len(observability.registry)} metrics "
          f"audited)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
