"""jnp backend: execute an ExecutionPlan as a jitted adds-only predictor.

The TPU analogue of the paper's weights-as-wiring: the integer weight
matrices of the plan lowered from the (pruned) circuit are embedded as
XLA literals, and every layer is the masked column-sum identity

    x @ W  ==  sum of W rows where x == 1      (x in {0,1})

realized as `where` + `sum` — adds only, no multiplies, no MXU. Works
for any depth. This is the oracle backend the pallas kernels are
checked against; it always executes the dense plan form.

Registered as the `jnp` target (kind "callable", no options) with
`compile_jnp_multi` as its multi-net form; see `repro.netgen.targets`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.netgen.graph import Circuit
from repro.netgen.plan import ExecutionPlan, lower_circuit

__all__ = ["compile_jnp", "compile_jnp_multi"]


def compile_jnp(circuit: Circuit):
    """Return a jitted fn: uint8 images (B, n_in) -> int predictions (B,)."""
    plan = lower_circuit(circuit)
    plan.require_dense("the jnp target")
    return _execute_plan(plan)


def _execute_plan(plan: ExecutionPlan):
    """The dense-plan executor: one masked column-sum per layer."""
    ws = [jnp.asarray(l.weights, jnp.int32) for l in plan.layers]
    thr = plan.input_threshold

    @jax.jit
    def predict(x_uint8):
        a = x_uint8.astype(jnp.int32) > thr
        for w in ws[:-1]:
            hi = jnp.sum(jnp.where(a[:, :, None], w[None], 0), axis=1)
            a = hi > 0
        fi = jnp.sum(jnp.where(a[:, :, None], ws[-1][None], 0), axis=1)
        return jnp.argmax(fi, axis=-1)

    return predict


def compile_jnp_multi(plan: ExecutionPlan):
    """Multi-net dispatch: one jitted call serving M model versions.

    `plan` is a *stacked* ExecutionPlan (`repro.netgen.plan.stack_plans`):
    per-layer (M, fan_in, fan_out) weights along a leading model axis.
    Returns a jitted fn mapping uint8 images (M, B, n_in) to predictions
    (M, B): the same masked column-sum arithmetic as `compile_jnp`,
    batched over the model axis, so serving M versions costs one XLA
    dispatch instead of M.
    """
    if not plan.stacked:
        raise ValueError("compile_jnp_multi needs a stacked ExecutionPlan")
    ws = [jnp.asarray(l.weights, jnp.int32) for l in plan.layers]
    thr = plan.input_threshold

    @jax.jit
    def predict(x_uint8):
        a = x_uint8.astype(jnp.int32) > thr          # (M, B, K)
        for w in ws[:-1]:
            hi = jnp.sum(jnp.where(a[..., None], w[:, None], 0), axis=2)
            a = hi > 0
        fi = jnp.sum(jnp.where(a[..., None], ws[-1][:, None], 0), axis=2)
        return jnp.argmax(fi, axis=-1)               # (M, B)

    return predict
