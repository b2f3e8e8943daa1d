"""Fused-epilogue GEMM: tiled MXU matmul with bias+activation applied
in-register before the HBM writeback, and a custom VJP whose backward
fuses dact·dY into the dX/dW GEMMs.

The unfused matmul -> elementwise_add -> activation chain (the exact
pattern `analysis.perf_rules`'s ``unfused-epilogue`` lint flags, and
PERF.md's trace breakdown bills at 57%% matmul-fusion efficiency on the
BERT FFN) round-trips the [M, N] intermediate through HBM twice: the
matmul writes Z, the bias add reads Z and writes Z', the activation
reads Z' and writes Y — 3 writes + 2 reads of [M, N] for one GEMM's
worth of useful FLOPs.  Here the epilogue runs on the f32 accumulator
tile while it is still in VMEM, so the forward writes [M, N] exactly
once (data-movement minimization, Ivanov et al. 2021).

Backward: dZ = dY * act'(z) never materializes either.  Each backward
GEMM recomputes the [bm, bn] dZ tile in-register from the dY block and
the saved residual, feeds it straight into the MXU contraction
(dX = dZ @ W^T row-parallel, dW = X^T @ dZ column-parallel), and the
dW kernel computes dbias as a column-sum reduction epilogue on the
same tiles — no separate dact or reduce pass over HBM.

Residual policy (what the VJP saves besides x/w):
  * ``none``       — nothing (dZ = dY);
  * ``relu``/``tanh`` — the OUTPUT y (relu' = [y>0], tanh' = 1-y^2:
    derivative recoverable from y, so no extra forward output);
  * ``gelu``       — the pre-activation z, emitted by the forward
    kernel as a second output in the output dtype (gelu' needs z; one
    extra [M, N] write in training, none in inference).

Contraction is strictly 2-D [M, K] x [K, N] with f32 accumulation
(``preferred_element_type``) over f32 or bf16 operands — bf16
operands are held to looser, documented bounds
(tests/test_pallas_matmul.py).  Batched
or transposed callers flatten/transpose outside (the ``matmul_bias_act``
op lowering does; it falls back to the naive jnp composition when a
transpose flag or non-tileable shape rules the kernel out).

Block sizes follow the flash_attention contract exactly so
``tune.search_gemm_blocks`` can grid-search them: explicit
``block_m``/``block_n``/``block_k`` args are a hard contract (they win
over the env and a non-divisor RAISES — a tuner must never time a
different grid than it requested); ``PADDLE_TPU_GEMM_BLOCKS="bm,bn,bk"``
overrides the heuristic when it divides (warns and falls back
otherwise); the heuristic takes the largest of 512/256/128 that
divides each dim.  Dims no block divides fall back to the naive
composition (never silently truncate).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ACTIVATIONS = ("none", "relu", "tanh", "gelu")

# block ladder the heuristic draws from (matches attention._pick_block)
GEMM_BLOCKS = (512, 256, 128)

# shapes already warned about falling back to the naive composition
_FALLBACK_WARNED: set = set()


def _pick_block(n):
    for b in GEMM_BLOCKS:
        if n % b == 0:
            return b
    return None


def _parse_env_blocks():
    ov = os.getenv("PADDLE_TPU_GEMM_BLOCKS")
    if not ov:
        return None
    try:
        bm, bn, bk = (int(t) for t in ov.split(","))
    except ValueError:
        raise ValueError(
            "PADDLE_TPU_GEMM_BLOCKS must be 'bm,bn,bk' (three ints), "
            "got %r" % ov) from None
    if bm <= 0 or bn <= 0 or bk <= 0:
        # 0 would divide-by-zero in the divisibility checks; a negative
        # block passes `dim % b == 0` and yields a negative pallas grid
        raise ValueError(
            "PADDLE_TPU_GEMM_BLOCKS must be three POSITIVE ints, got %r"
            % ov)
    return bm, bn, bk


def _block_sizes(m, n, k, block_m=None, block_n=None, block_k=None):
    """Resolve (bm, bn, bk) with the flash-attention precedence
    contract: explicit args RAISE on non-divisors and win over the env;
    a side not given explicitly takes the env override when it divides
    (warning otherwise) and the heuristic last."""
    explicit = (block_m, block_n, block_k)
    env = _parse_env_blocks()
    if any(b is not None for b in explicit):
        out = []
        for label, dim, exp, env_b in zip(
                ("block_m", "block_n", "block_k"), (m, n, k), explicit,
                env or (None,) * 3):
            if exp is not None:
                b = int(exp)
                if not b or dim % b:
                    raise ValueError(
                        "explicit GEMM block size %s=%r must divide its "
                        "dim %d (operands [%d,%d]x[%d,%d])"
                        % (label, exp, dim, m, k, k, n))
            else:
                b = (env_b if env_b and dim % env_b == 0
                     else _pick_block(dim))
                if not b:
                    # the failing dim is one the CALLER left to the
                    # heuristic — the explicit blocks cannot be honored
                    # because there is no kernel at this shape at all
                    raise ValueError(
                        "cannot honor explicit GEMM block sizes: dim "
                        "%s=%d (operands [%d,%d]x[%d,%d]) is not a "
                        "multiple of 128, so no pallas tile exists for "
                        "it; drop the explicit blocks to fall back to "
                        "the unfused composition"
                        % (label.replace("block_", "").upper(), dim,
                           m, k, k, n))
            out.append(b)
        return tuple(out)
    if env is not None:
        bm, bn, bk = env
        if m % bm == 0 and n % bn == 0 and k % bk == 0:
            return bm, bn, bk
        import warnings

        warnings.warn(
            "PADDLE_TPU_GEMM_BLOCKS=%s does not divide (M=%d, N=%d, "
            "K=%d); falling back to the default block sizes"
            % (os.getenv("PADDLE_TPU_GEMM_BLOCKS"), m, n, k),
            stacklevel=3)
    return _pick_block(m), _pick_block(n), _pick_block(k)


# ---------------------------------------------------------------------------
# activations and their derivatives (f32, in-register)
# ---------------------------------------------------------------------------

_SQRT_2 = 1.4142135623730951
_SQRT_2_OVER_PI = 0.7978845608028654
_INV_SQRT_2PI = 0.3989422804014327
_GELU_C = 0.044715


def _apply_act(z, act, approx):
    if act == "relu":
        return jnp.maximum(z, 0.0)
    if act == "tanh":
        return jnp.tanh(z)
    if act == "gelu":
        if approx:
            return 0.5 * z * (1.0 + jnp.tanh(
                _SQRT_2_OVER_PI * (z + _GELU_C * z * z * z)))
        return 0.5 * z * (1.0 + jax.lax.erf(z / _SQRT_2))
    return z


def _dact_from_residual(g, res, act, approx):
    """dZ from dY and the residual (y for relu/tanh, z for gelu)."""
    if act == "relu":
        return g * (res > 0.0).astype(g.dtype)
    if act == "tanh":
        return g * (1.0 - res * res)
    if act == "gelu":
        z = res
        if approx:
            inner = _SQRT_2_OVER_PI * (z + _GELU_C * z * z * z)
            t = jnp.tanh(inner)
            dinner = _SQRT_2_OVER_PI * (1.0 + 3.0 * _GELU_C * z * z)
            return g * (0.5 * (1.0 + t) + 0.5 * z * (1.0 - t * t) * dinner)
        cdf = 0.5 * (1.0 + jax.lax.erf(z / _SQRT_2))
        pdf = _INV_SQRT_2PI * jnp.exp(-0.5 * z * z)
        return g * (cdf + z * pdf)
    return g


def _residual_kind(act):
    """Which tensor the VJP must save to recompute act' blockwise."""
    if act == "gelu":
        return "z"
    if act in ("relu", "tanh"):
        return "y"
    return None


def _mxu_dot(a, b, dims):
    """float32-accumulating dot.  Only float32 operands take the
    process's `jax_default_matmul_precision`: narrower ones multiply
    exactly on the MXU, and "highest" would reach Mosaic as an fp32
    contract on bf16 operands, which it refuses ("Bad lhs type")."""
    f32 = a.dtype == jnp.float32 and b.dtype == jnp.float32
    return jax.lax.dot_general(
        a, b, dims, precision=None if f32 else jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, act, approx, nk, has_bias, emit_z):
    refs = list(refs)
    x_ref, w_ref = refs[:2]
    idx = 2
    b_ref = None
    if has_bias:
        b_ref = refs[idx]
        idx += 1
    if emit_z:
        o_ref, z_ref, acc_ref = refs[idx:]
    else:
        (o_ref, acc_ref), z_ref = refs[idx:], None
    kblk = pl.program_id(2)

    @pl.when(kblk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _mxu_dot(x_ref[...], w_ref[...],
                             (((1,), (0,)), ((), ())))

    @pl.when(kblk == nk - 1)
    def _epilogue():
        z = acc_ref[...]
        if b_ref is not None:
            z = z + b_ref[0, :].astype(jnp.float32)[None, :]
        if z_ref is not None:
            z_ref[...] = z.astype(z_ref.dtype)
        o_ref[...] = _apply_act(z, act, approx).astype(o_ref.dtype)


def _fwd(x, w, bias, act, approx, interpret, bm, bn, bk, emit_z):
    m, k = x.shape
    n = w.shape[1]
    nm, nn, nk = m // bm, n // bn, k // bk
    has_bias = bias is not None

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kb: (i, kb)),
        pl.BlockSpec((bk, bn), lambda i, j, kb: (kb, j)),
    ]
    args = [x, w]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kb: (0, j)))
        args.append(bias.reshape(1, n))
    out_specs = [pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j))]
    out_shape = [jax.ShapeDtypeStruct((m, n), x.dtype)]
    if emit_z:
        out_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)))
        out_shape.append(jax.ShapeDtypeStruct((m, n), x.dtype))
    res = pl.pallas_call(
        functools.partial(_fwd_kernel, act=act, approx=approx, nk=nk,
                          has_bias=has_bias, emit_z=emit_z),
        grid=(nm, nn, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*args)
    if emit_z:
        return res[0], res[1]
    return res[0], None


# ---------------------------------------------------------------------------
# backward kernels: dX (row-parallel) and dW + dbias (column-parallel)
# ---------------------------------------------------------------------------


def _bwd_dx_kernel(g_ref, res_ref, w_ref, dx_ref, acc_ref, *, act, approx,
                   nn):
    """Grid (nm, nkb, nn), n innermost: dX[i,kb] accumulates
    dZ(i,j) @ W(kb,j)^T with dZ recomputed in-register per tile."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...].astype(jnp.float32)
    dz = (_dact_from_residual(g, res_ref[...].astype(jnp.float32), act,
                              approx)
          if res_ref is not None else g)
    acc_ref[...] += _mxu_dot(dz, w_ref[...], (((1,), (1,)), ((), ())))

    @pl.when(j == nn - 1)
    def _finalize():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def _bwd_dw_kernel(x_ref, g_ref, res_ref, dw_ref, db_ref, dw_acc, db_acc,
                   *, act, approx, nm, nkb, has_bias):
    """Grid (nn, nkb, nm), m innermost: dW[kb,j] accumulates
    X(m,kb)^T @ dZ(m,j); dbias[j] is a column-sum reduction epilogue on
    the SAME dZ tiles, accumulated once (during the kb==0 sweep) and
    written when the j column finishes."""
    kb = pl.program_id(1)
    mm = pl.program_id(2)

    @pl.when(mm == 0)
    def _init():
        dw_acc[...] = jnp.zeros_like(dw_acc)

    g = g_ref[...].astype(jnp.float32)
    dz = (_dact_from_residual(g, res_ref[...].astype(jnp.float32), act,
                              approx)
          if res_ref is not None else g)
    dw_acc[...] += _mxu_dot(x_ref[...], dz, (((0,), (0,)), ((), ())))

    @pl.when(mm == nm - 1)
    def _write_dw():
        dw_ref[...] = dw_acc[...].astype(dw_ref.dtype)

    if has_bias:
        @pl.when(jnp.logical_and(kb == 0, mm == 0))
        def _init_db():
            db_acc[...] = jnp.zeros_like(db_acc)

        @pl.when(kb == 0)
        def _accum_db():
            db_acc[0:1, :] = db_acc[0:1, :] + jnp.sum(dz, axis=0)[None, :]

        @pl.when(jnp.logical_and(kb == nkb - 1, mm == nm - 1))
        def _write_db():
            db_ref[...] = db_acc[0:1, :].astype(db_ref.dtype)


def _bwd(x, w, bias, res, g, act, approx, interpret, bm, bn, bk):
    m, k = x.shape
    n = w.shape[1]
    nm, nn, nkb = m // bm, n // bn, k // bk
    has_bias = bias is not None
    has_res = res is not None

    # dX: grid (nm, nkb, nn)
    dx_specs = [
        pl.BlockSpec((bm, bn), lambda i, kb, j: (i, j)),       # g
    ]
    dx_args = [g]
    if has_res:
        dx_specs.append(pl.BlockSpec((bm, bn), lambda i, kb, j: (i, j)))
        dx_args.append(res)
    dx_specs.append(pl.BlockSpec((bk, bn), lambda i, kb, j: (kb, j)))  # w
    dx_args.append(w)

    def _dx_kernel(*refs, **kw):
        if has_res:
            g_ref, res_ref, w_ref, dx_ref, acc_ref = refs
        else:
            (g_ref, w_ref, dx_ref, acc_ref), res_ref = refs, None
        return _bwd_dx_kernel(g_ref, res_ref, w_ref, dx_ref, acc_ref, **kw)

    dx = pl.pallas_call(
        functools.partial(_dx_kernel, act=act, approx=approx, nn=nn),
        grid=(nm, nkb, nn),
        in_specs=dx_specs,
        out_specs=pl.BlockSpec((bm, bk), lambda i, kb, j: (i, kb)),
        out_shape=jax.ShapeDtypeStruct((m, k), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        interpret=interpret,
    )(*dx_args)

    # dW (+ dbias): grid (nn, nkb, nm) — j OUTERMOST so the (0, j) dbias
    # output window only switches when its column sum is complete
    dw_specs = [
        pl.BlockSpec((bm, bk), lambda j, kb, mm: (mm, kb)),    # x
        pl.BlockSpec((bm, bn), lambda j, kb, mm: (mm, j)),     # g
    ]
    dw_args = [x, g]
    if has_res:
        dw_specs.append(pl.BlockSpec((bm, bn), lambda j, kb, mm: (mm, j)))
        dw_args.append(res)
    dw_out_specs = [pl.BlockSpec((bk, bn), lambda j, kb, mm: (kb, j))]
    dw_out_shape = [jax.ShapeDtypeStruct((k, n), w.dtype)]
    scratch = [pltpu.VMEM((bk, bn), jnp.float32)]
    if has_bias:
        dw_out_specs.append(
            pl.BlockSpec((1, bn), lambda j, kb, mm: (0, j)))
        dw_out_shape.append(jax.ShapeDtypeStruct((1, n), bias.dtype))
        scratch.append(pltpu.VMEM((8, bn), jnp.float32))

    def _dw_kernel(*refs, **kw):
        refs = list(refs)
        x_ref, g_ref = refs[:2]
        idx = 2
        res_ref = None
        if has_res:
            res_ref = refs[idx]
            idx += 1
        if has_bias:
            dw_ref, db_ref, dw_acc, db_acc = refs[idx:]
        else:
            (dw_ref, dw_acc), db_ref, db_acc = refs[idx:], None, None
        return _bwd_dw_kernel(x_ref, g_ref, res_ref, dw_ref, db_ref,
                              dw_acc, db_acc, **kw)

    res_out = pl.pallas_call(
        functools.partial(_dw_kernel, act=act, approx=approx, nm=nm,
                          nkb=nkb, has_bias=has_bias),
        grid=(nn, nkb, nm),
        in_specs=dw_specs,
        out_specs=dw_out_specs,
        out_shape=dw_out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*dw_args)
    if has_bias:
        dw, db2d = res_out
        db = db2d.reshape(n)
    else:
        (dw,), db = res_out, None
    return dx, dw, db


# ---------------------------------------------------------------------------
# custom-vjp wrapper + public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _mba_core(x, w, bias, act, approx, interpret, bm, bn, bk):
    out, _ = _fwd(x, w, bias, act, approx, interpret, bm, bn, bk,
                  emit_z=False)
    return out


def _mba_core_fwd(x, w, bias, act, approx, interpret, bm, bn, bk):
    kind = _residual_kind(act)
    out, z = _fwd(x, w, bias, act, approx, interpret, bm, bn, bk,
                  emit_z=(kind == "z"))
    res = z if kind == "z" else (out if kind == "y" else None)
    return out, (x, w, bias, res)


def _mba_core_bwd(act, approx, interpret, bm, bn, bk, residuals, g):
    x, w, bias, res = residuals
    dx, dw, db = _bwd(x, w, bias, res, g, act, approx, interpret,
                      bm, bn, bk)
    return dx, dw, db


_mba_core.defvjp(_mba_core_fwd, _mba_core_bwd)


def naive_matmul_bias_act(x, w, bias=None, activation="none",
                          approximate=False):
    """The unfused jnp composition — the oracle the kernel is tested
    against and the fallback for shapes/platforms the kernel rejects.
    Rejects unknown activations like the kernel does: the CPU fallback
    must never silently return un-activated output for an activation
    the TPU path would raise on."""
    if activation not in ACTIVATIONS:
        raise ValueError(
            "matmul_bias_act activation must be one of %s, got %r"
            % (ACTIVATIONS, activation))
    z = jnp.matmul(x, w)
    if bias is not None:
        z = z + bias
    if activation == "gelu":
        return jax.nn.gelu(z, approximate=approximate)
    if activation == "relu":
        return jax.nn.relu(z)
    if activation == "tanh":
        return jnp.tanh(z)
    return z


def matmul_bias_act(x, w, bias=None, activation="none", approximate=False,
                    interpret=None, block_m=None, block_n=None,
                    block_k=None):
    """Fused [M, K] x [K, N] GEMM with an in-register bias+activation
    epilogue and a fused-backward custom VJP.

    ``activation``: one of {"none", "relu", "tanh", "gelu"}
    (``approximate`` selects the tanh gelu).  ``bias``: [N] or None.
    ``block_m``/``block_n``/``block_k`` pin the tile sizes (the knob
    ``paddle_tpu.tune.search_gemm_blocks`` searches); they must divide
    M/N/K or a ValueError is raised, and they win over the
    ``PADDLE_TPU_GEMM_BLOCKS=bm,bn,bk`` env override, which in turn
    wins over the largest-divisor heuristic.  Dims no supported block
    divides fall back to the naive composition with a one-time warning
    (never a silent truncate)."""
    if activation not in ACTIVATIONS:
        raise ValueError(
            "matmul_bias_act activation must be one of %s, got %r"
            % (ACTIVATIONS, activation))
    if x.ndim != 2 or w.ndim != 2:
        raise ValueError(
            "matmul_bias_act is a 2-D kernel: x %s, w %s — flatten "
            "batch dims outside (the op lowering does)"
            % (x.shape, w.shape))
    if bias is not None and (bias.ndim != 1
                             or bias.shape[0] != w.shape[1]):
        raise ValueError(
            "bias must be 1-D [N=%d], got shape %s"
            % (w.shape[1], tuple(bias.shape)))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = x.shape
    n = w.shape[1]
    bm, bn, bk = _block_sizes(m, n, k, block_m, block_n, block_k)
    if bm is None or bn is None or bk is None:
        import warnings

        key = ("naive-fallback", m, n, k)
        if key not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add(key)
            warnings.warn(
                "matmul_bias_act falling back to the unfused composition "
                "for shape [%d,%d]x[%d,%d]: every dim must be a multiple "
                "of 128 for the pallas kernel. This is a PERFORMANCE "
                "fallback, not an error." % (m, k, k, n),
                stacklevel=2)
        return naive_matmul_bias_act(x, w, bias, activation, approximate)
    return _mba_core(x, w, bias, activation, bool(approximate),
                     bool(interpret), bm, bn, bk)
