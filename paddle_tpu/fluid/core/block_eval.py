"""Shared op-sequence interpreter: the ONE place that runs lowerings.

Used by the executor's traced block body, the recompute_segment composite
op, and eager initializer evaluation — any change to lowering conventions
(ctx fields, slot handling, diagnostics) lands here once.
"""

from __future__ import annotations

from .registry import get_op_def


def _nan_guard(op_type, out_name, val):
    """Per-op NaN/Inf localization (reference
    `details/nan_inf_utils_detail.cc` via FLAGS_check_nan_inf): a host
    callback raises naming the exact op + output var, from inside the
    compiled program."""
    import jax
    import jax.numpy as jnp

    if not hasattr(val, "dtype") or not jnp.issubdtype(val.dtype,
                                                       jnp.floating):
        return val

    def cb(bad):
        if bool(bad):
            raise FloatingPointError(
                "NaN/Inf detected in output '%s' of op '%s' "
                "(FLAGS_check_nan_inf)" % (out_name, op_type)
            )

    jax.debug.callback(cb, ~jnp.all(jnp.isfinite(val)))
    return val


def run_ops(ops, env, ctx):
    """Run a sequence of ops over a name->value env (mutated in place).

    ops: framework.Operator objects OR serialized dicts
    (framework.Operator.to_dict form: {"type", "inputs", "outputs", "attrs"}).
    """
    from ..flags import get_flags

    check_nan = bool(
        get_flags(["FLAGS_check_nan_inf"]).get("FLAGS_check_nan_inf")
    )
    for op in ops:
        if isinstance(op, dict):
            op_type, op_ins, op_outs, op_attrs = (
                op["type"], op["inputs"], op["outputs"], op["attrs"]
            )
        else:
            op_type, op_ins, op_outs, op_attrs = (
                op.type, op.inputs, op.outputs, op.attrs
            )
        opdef = get_op_def(op_type)
        try:
            ins = {
                slot: [env[n] for n in names] for slot, names in op_ins.items()
            }
        except KeyError as e:
            raise RuntimeError(
                "op '%s' reads var %s which is not materialized in this "
                "execution environment" % (op_type, e)
            ) from None
        outs = opdef.lower(ctx, ins, op_attrs)
        for slot, names in op_outs.items():
            for n, val in zip(names, outs[slot]):
                if check_nan:
                    val = _nan_guard(op_type, n, val)
                env[n] = val
    return env
