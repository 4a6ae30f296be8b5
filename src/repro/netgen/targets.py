"""Target registry: every execution backend as a first-class object.

A `Target` unifies what used to be ad-hoc knowledge spread across
`backends/` and its callers: the compile entry point, the artifact kind
(callable predictor / text source / cost report), the declared options
the bracket syntax accepts, and the optional multi-net (stacked) form
used by the serving layer. Targets are addressed by the same
`name[opt=value,...]` item syntax as pipeline passes:

    jnp                      jitted adds-only predictor (the oracle)
    pallas                   per-layer binary_matvec TPU kernel chain
    fused                    single-launch whole-net kernel (2-layer)
    verilog[style=legacy]    the paper's combinational module source
    cost                     IR walk -> logic-cell estimate vs Figure 7

`resolve_target` parses an item string (or takes a bare name plus an
opts dict), validates options against the target's declaration, and
returns (Target, opts). `target_string` renders the canonical form that
keys the ArtifactStore. `list_targets` enumerates the registry.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping

from repro.netgen.pipeline import check_opt_string, parse_item, render_opts

__all__ = [
    "Target", "get_target", "list_targets", "register_target",
    "resolve_target", "target_string",
]


@dataclasses.dataclass(frozen=True)
class Target:
    """One execution target. `compile` maps (circuit, **opts) to the
    artifact; `kind` says what that artifact is ("callable", "text",
    "report"); `opts` declares the accepted options as (name, type)
    pairs; `compile_multi`, when present, builds the stacked multi-net
    dispatch (a stacked `repro.netgen.plan.ExecutionPlan` plus the same
    declared opts -> callable); `wants_pass_trace` asks the Session
    driver to hand the pipeline's per-pass circuit trace to `compile`
    as `_pass_trace`; `wants_tuner` asks every compile entry point
    (single and multi) to receive the caller's `repro.netgen.tune
    .KernelTuner` as `_tuner` — how `Session(tune_store=...)` threads
    persisted tuning records into `tuned=true` kernel builds; and
    `wants_analysis` asks the driver to hand its pre-backend
    `repro.netgen.analysis.RangeAnalysis` to `compile` as `_analysis`,
    so width-consuming backends (verilog, cost) emit the proven widths
    instead of re-deriving them."""
    name: str
    kind: str
    description: str
    compile: Callable
    opts: tuple = ()                       # ((opt_name, type), ...)
    compile_multi: Callable | None = None
    wants_pass_trace: bool = False
    wants_tuner: bool = False
    wants_analysis: bool = False

    @property
    def callable(self) -> bool:
        return self.kind == "callable"


_REGISTRY: dict[str, Target] = {}


def register_target(target: Target) -> Target:
    _REGISTRY[target.name] = target
    return target


def get_target(name: str) -> Target:
    t = _REGISTRY.get(name)
    if t is None:
        raise ValueError(
            f"unknown target {name!r} (registered: "
            f"{', '.join(sorted(_REGISTRY))})")
    return t


def list_targets() -> tuple[Target, ...]:
    """Every registered target, sorted by name."""
    return tuple(_REGISTRY[k] for k in sorted(_REGISTRY))


def resolve_target(target, extra_opts: Mapping | None = None
                   ) -> tuple[Target, dict]:
    """Resolve a target reference into (Target, validated opts).

    `target` is a Target, a bare name, or an item string with bracketed
    options ("verilog[style=legacy]"); `extra_opts` (e.g. keyword
    arguments of `compile_net`) are merged on top and validated the same
    way. Unknown targets, unknown options, and ill-typed option values
    raise ValueError.
    """
    if isinstance(target, Target):
        t, opts = target, {}
    else:
        name, opts = parse_item(str(target))
        t = get_target(name)
    merged = dict(opts)
    for k, v in (extra_opts or {}).items():
        if k in merged and merged[k] != v:
            raise ValueError(
                f"option {k!r} given twice for target {t.name!r}: "
                f"{merged[k]!r} in the target string vs {v!r} as a keyword")
        merged[k] = v
    declared = dict(t.opts)
    for k, v in merged.items():
        if k not in declared:
            raise ValueError(
                f"unknown option {k!r} for target {t.name!r} "
                f"(declared: {', '.join(sorted(declared)) or 'none'})")
        want = declared[k]
        if want is bool and not isinstance(v, bool):
            raise ValueError(
                f"option {k!r} of target {t.name!r} wants true/false, "
                f"got {v!r}")
        if want is int and (isinstance(v, bool) or not isinstance(v, int)):
            raise ValueError(
                f"option {k!r} of target {t.name!r} wants an integer, "
                f"got {v!r}")
        if want is str:
            if not isinstance(v, str):
                raise ValueError(
                    f"option {k!r} of target {t.name!r} wants a string, "
                    f"got {v!r}")
            check_opt_string(v, f"option {k!r} of target {t.name!r}")
    return t, merged


def target_string(target: Target, opts: Mapping) -> str:
    """Canonical `name[k=v,...]` form — one axis of the store key."""
    return f"{target.name}{render_opts(opts)}"


# ---------------------------------------------------------------------------
# Built-in targets (imports deferred to keep jax off the parse path)
# ---------------------------------------------------------------------------

def _compile_jnp(circuit, **opts):
    from repro.netgen.backends.jnp import compile_jnp
    return compile_jnp(circuit, **opts)


def _compile_jnp_multi(plan, **opts):
    from repro.netgen.backends.jnp import compile_jnp_multi
    return compile_jnp_multi(plan, **opts)


def _compile_pallas(circuit, **opts):
    from repro.netgen.backends.pallas import compile_pallas
    return compile_pallas(circuit, **opts)


def _compile_pallas_multi(plan, **opts):
    from repro.netgen.backends.pallas import compile_pallas_multi
    return compile_pallas_multi(plan, **opts)


def _compile_fused(circuit, **opts):
    from repro.netgen.backends.pallas import compile_fused
    return compile_fused(circuit, **opts)


def _compile_verilog(circuit, **opts):
    from repro.netgen.backends.verilog import emit_verilog
    return emit_verilog(circuit, **opts)


def _compile_cost(circuit, **opts):
    from repro.netgen.backends.cost import compile_cost
    return compile_cost(circuit, **opts)


register_target(Target(
    name="jnp", kind="callable",
    description="jitted adds-only predictor, weights as XLA literals "
                "(the oracle backend)",
    compile=_compile_jnp, compile_multi=_compile_jnp_multi))
register_target(Target(
    name="pallas", kind="callable",
    description="per-layer binary_matvec TPU kernel chain "
                "(compiled through Mosaic on a TPU, interpreted where "
                "JAX's backend is the CPU; packed=true chains bit-packed "
                "activations end to end, planes=true additionally "
                "decomposes weights into packed bit-planes accumulated "
                "by popcount, fusednet=true runs the whole planes-form "
                "net as ONE persistent megakernel launch — any depth, "
                "stacked or single, weights resident and activations "
                "never leaving VMEM — tuned=true grid-searches the form "
                "and the bm/bn/bkw block sizes per plan shape and "
                "persists the winner; explored=true resolves the "
                "design-space explorer's persisted winner for the plan "
                "shape when one exists, see Session.explore)",
    compile=_compile_pallas,
    opts=(("interpret", bool), ("packed", bool), ("planes", bool),
          ("fusednet", bool), ("tuned", bool), ("explored", bool),
          ("bm", int), ("bn", int), ("bkw", int)),
    compile_multi=_compile_pallas_multi, wants_tuner=True))
register_target(Target(
    name="fused", kind="callable",
    description="single-launch whole-net Pallas kernel (2-layer only; "
                "tuned=true searches the bm batch tile)",
    compile=_compile_fused,
    opts=(("interpret", bool), ("tuned", bool), ("bm", int)),
    wants_tuner=True))
register_target(Target(
    name="verilog", kind="text",
    description="the paper's clockless combinational Verilog module",
    compile=_compile_verilog,
    opts=(("module_name", str), ("style", str), ("addend", bool)),
    wants_analysis=True))
register_target(Target(
    name="cost", kind="report",
    description="logic-cell estimate of the circuit vs paper Figure 7",
    compile=_compile_cost, wants_pass_trace=True, wants_analysis=True))
