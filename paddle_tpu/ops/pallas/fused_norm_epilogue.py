"""Fused residual + bias + norm (+ activation) epilogue as one Pallas kernel.

TPU-native rebuild of the reference's epilogue fusions
(phi/kernels/fusion/: fused_bias_residual_layernorm,
fused_layernorm_residual_dropout_bias): between a matmul and the next
norm, XLA emits the residual add, the bias broadcast, and the norm
reductions as separate HBM-bound passes over the [B*T, H] activation.
This kernel streams one [bt, H] row block through VMEM and produces BOTH
epilogue outputs in a single pass:

    r = x + sub + bias          (the updated residual stream, input dtype)
    y = norm(r) * gain (+ beta) (the next sublayer's input)

``norm`` is ``"rms"`` (models/llama.py rms_norm) or ``"layer"``
(models/gpt.py _layer_norm); the in-kernel expressions replicate those
functions term for term — fp32 accumulation, cast back to the input
dtype — so the kernel arm is BIT-IDENTICAL to the unfused composition
(pinned by tests/test_fused_norm_epilogue.py, both arms).

Backward is deliberately XLA: the custom_vjp saves only (r, gain, beta)
— the same live set as the unfused graph, no extra residuals — and
pulls dy back through ``jax.vjp`` of the reference norm expression at
``r``; the residual/bias adds are linear, so dx = dsub = dr and
dbias = dr.sum(rows).  Norm backward is elementwise + row reductions,
which XLA already fuses well; the HBM win of this fusion is the forward
epilogue pass.

Mosaic constraints hit (PERF.md "Fusion catalog"): the [H] gain/bias
vectors ride as (1, H) blocks (block == array dim satisfies the
(8, 128) tiling rule) and broadcast against the [bt, H] rows as rank-1
operands — 2-D broadcast ``jnp.where`` is avoided per the known v5e
lowering bug (see fused_ce.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import _interpret_mode, _row_blocks, _tpu_params

__all__ = ["fused_norm_epilogue", "fused_norm_epilogue_supported"]

def _bt_candidates(n: int, h: int, itemsize: int) -> list[int]:
    # per row: x/sub in, r/y out (input dtype, double buffered) + ~3 fp32
    # temporaries
    return _row_blocks(n, h * (8 * itemsize + 12))


def fused_norm_epilogue_supported(n: int, h: int, dtype) -> bool:
    """Gate: lane-aligned hidden and a row block that tiles the row
    count and keeps one [bt, h] block set VMEM-feasible."""
    dt = jnp.dtype(dtype)
    return (h % 128 == 0 and n > 0
            and dt in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and bool(_bt_candidates(n, h, dt.itemsize)))


def _norm_ref(r, gain, beta, norm: str, eps: float, act, one=None):
    """The unfused norm, replicated term for term (rms_norm /
    _layer_norm in the models) — the kernel's numerics contract AND the
    backward's differentiated expression.

    ``one`` is a runtime-opaque 1.0 the kernel arm threads in: inside a
    fused kernel body the backend contracts ``y * gain + beta`` into an
    fma, skipping the product rounding the op-by-op reference performs.
    Multiplying the product by an operand the compiler cannot prove is
    1.0 leaves ``fma(prod, one, beta)`` as the only contraction — which
    rounds exactly like the separate multiply-then-add.
    """
    r32 = r.astype(jnp.float32)
    if norm == "rms":
        y = r32 * lax.rsqrt((r32 * r32).mean(-1, keepdims=True) + eps)
        y = y * gain.astype(jnp.float32)
    else:
        mu = r32.mean(-1, keepdims=True)
        var = r32.var(-1, keepdims=True)
        y = (r32 - mu) * lax.rsqrt(var + eps)
        y = y * gain.astype(jnp.float32)
        if one is not None:
            y = y * one
        y = y + beta.astype(jnp.float32)
    y = y.astype(r.dtype)
    if act == "gelu":
        y = jax.nn.gelu(y, approximate=True)
    return y


def _epilogue_xla(x, sub, bias, gain, beta, norm, eps, act):
    """XLA fallback arm — also the literal unfused model composition."""
    r = x
    if sub is not None:
        r = r + sub
    if bias is not None:
        r = r + bias.astype(x.dtype)
    return r, _norm_ref(r, gain, beta, norm, eps, act)


def _epilogue_kernel(*refs, norm, eps, act, has_sub, has_bias, has_beta,
                     exact):
    """``exact`` (interpret mode, i.e. the body is compiled by XLA:CPU)
    adds the two guards that pin the body bitwise to the eager op-by-op
    graph.  Mosaic lowers neither ``reduce_precision`` nor
    ``optimization_barrier`` and needs neither: it does not run XLA's
    convert-pair simplification, and what it contracts is its own
    business — the compiled kernel is held to a tolerance on the chip
    (chip_smoke.py), not to bits."""
    dtype = refs[0].dtype
    # XLA fuses the whole kernel body and would elide the bf16 rounding
    # between the adds and the fp32 norm (convert-pair simplification),
    # silently computing a DIFFERENT r than the unfused op-by-op graph.
    # reduce_precision is the one narrowing XLA never removes, so each
    # add rounds exactly like its eager counterpart and r32 lands on the
    # bf16 grid — the later astype round-trips are then value-exact.
    if exact and dtype == jnp.bfloat16:
        rp = lambda v: lax.reduce_precision(v, 8, 7)  # noqa: E731
    else:
        rp = lambda v: v                              # noqa: E731
    idx = 0
    acc = refs[idx][...].astype(jnp.float32)         # [bt, H]
    idx += 1
    if has_sub:
        acc = rp(acc + refs[idx][...].astype(jnp.float32))
        idx += 1
    if has_bias:
        # eager form is `r + bias.astype(x.dtype)`: round the bias first
        acc = rp(acc + rp(refs[idx][0, :].astype(jnp.float32)))
        idx += 1
    gain = refs[idx][0, :]
    idx += 1
    beta = one = None
    if has_beta:
        beta = refs[idx][0, :]
        idx += 1
        if exact:
            # the barrier keeps the 1.0 runtime-opaque even when the
            # operand is a compile-time constant (it always is under jit:
            # the ones array is created inside this traced call) —
            # without it XLA folds the *one mul away and fma contraction
            # skips the product rounding (see _norm_ref)
            one = lax.optimization_barrier(refs[idx][0, 0])
            idx += 1
    r_ref, y_ref = refs[idx], refs[idx + 1]
    r = acc.astype(dtype)
    r_ref[...] = r
    y_ref[...] = _norm_ref(r, gain, beta, norm, eps, act, one=one)


def _epilogue_call(x, sub, bias, gain, beta, *, norm, eps, act, bt):
    import jax.experimental.pallas as pl

    N, H = x.shape
    exact = _interpret_mode()
    row = pl.BlockSpec((bt, H), lambda i: (i, 0))
    vec = pl.BlockSpec((1, H), lambda i: (0, 0))
    ops, specs = [x], [row]
    if sub is not None:
        ops.append(sub)
        specs.append(row)
    if bias is not None:
        ops.append(bias.reshape(1, H))
        specs.append(vec)
    ops.append(gain.reshape(1, H))
    specs.append(vec)
    if beta is not None:
        ops.append(beta.reshape(1, H))
        specs.append(vec)
        if exact:
            # runtime-opaque 1.0 (see _norm_ref docstring)
            ops.append(jnp.ones((1, 1), jnp.float32))
            specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0)))
    return pl.pallas_call(
        functools.partial(_epilogue_kernel, norm=norm, eps=eps, act=act,
                          has_sub=sub is not None, has_bias=bias is not None,
                          has_beta=beta is not None, exact=exact),
        grid=(N // bt,),
        in_specs=specs,
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((N, H), x.dtype)] * 2,
        interpret=exact,
        compiler_params=_tpu_params(0),
        name=f"fused_{norm}_epilogue",
    )(*ops)


_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(_epilogue_kernel, _epilogue_call)
    return _SRC


def _tuned_bt(n: int, h: int, dtype, norm: str) -> int:
    """Row-block size via the autotune registry; candidates[0] (256
    wherever it fits) is the hand default no-sweep backends use."""
    from . import autotune

    cands = _bt_candidates(n, h, jnp.dtype(dtype).itemsize)
    if not cands:
        return 0

    def measure(bt):
        xz = jnp.zeros((n, h), dtype)
        gz = jnp.zeros((h,), dtype)
        beta = gz if norm == "layer" else None
        fn = jax.jit(functools.partial(_epilogue_call, norm=norm, eps=1e-5,
                                       act=None, bt=int(bt)))
        return autotune.time_candidate(lambda: fn(xz, xz, None, gz, beta))

    return int(autotune.tuned("fused_norm_epilogue", f"n{n}_h{h}_{norm}",
                              str(jnp.dtype(dtype)), cands, measure=measure,
                              source=_autotune_source()))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fused(operands, cfg):
    return _fused_fwd(operands, cfg)[0]


def _fused_fwd(operands, cfg):
    norm, eps, act, use_kernel, bt, _has_sub, _bias_dtype = cfg
    x = operands["x"]
    sub = operands.get("sub")
    bias = operands.get("bias")
    gain = operands["gain"]
    beta = operands.get("beta")
    if use_kernel and bt:
        r, y = _epilogue_call(x, sub, bias, gain, beta, norm=norm, eps=eps,
                              act=act, bt=bt)
    else:
        r, y = _epilogue_xla(x, sub, bias, gain, beta, norm, eps, act)
    return (r, y), (r, gain, beta)


def _fused_bwd(cfg, res, cts):
    norm, eps, act, _use_kernel, _bt, has_sub, bias_dtype = cfg
    r, gain, beta = res
    dr_out, dy = cts
    # dy pulled back through the SAME expression the forward evaluated;
    # the adds are linear, so dr fans out to every residual operand.
    if beta is not None:
        _, vjp = jax.vjp(
            lambda rr, gg, bb: _norm_ref(rr, gg, bb, norm, eps, act),
            r, gain, beta)
        dr_n, dgain, dbeta = vjp(dy)
    else:
        _, vjp = jax.vjp(
            lambda rr, gg: _norm_ref(rr, gg, None, norm, eps, act),
            r, gain)
        dr_n, dgain = vjp(dy)
        dbeta = None
    dr = dr_out + dr_n
    grads = {"x": dr, "gain": dgain}
    if has_sub:
        grads["sub"] = dr
    if bias_dtype is not None:
        # sum in dr.dtype then cast: the broadcast/astype vjp order of
        # the unfused graph
        grads["bias"] = dr.sum(0).astype(bias_dtype)
    if dbeta is not None:
        grads["beta"] = dbeta
    return (grads,)


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_norm_epilogue(x, sub=None, bias=None, gain=None, beta=None, *,
                        norm: str = "rms", eps: float = 1e-5, act=None,
                        use_kernel: bool | None = None):
    """Fused epilogue over arbitrary leading dims: returns
    ``(r, y) = (x + sub + bias, norm(r) * gain (+ beta) [act])`` with the
    shapes of ``x``.  ``use_kernel=None`` routes by
    :func:`fused_norm_epilogue_supported`; ``False`` pins the XLA arm
    (parity tests)."""
    if gain is None:
        raise ValueError("fused_norm_epilogue requires a gain vector")
    if norm not in ("rms", "layer"):
        raise ValueError(f"unknown norm '{norm}'")
    if norm == "layer" and beta is None:
        raise ValueError("layer norm requires beta")
    shape = x.shape
    H = shape[-1]
    xf = x.reshape(-1, H)
    sf = sub.reshape(-1, H) if sub is not None else None
    N = xf.shape[0]
    if use_kernel is None:
        use_kernel = fused_norm_epilogue_supported(N, H, x.dtype)
    bt = _tuned_bt(N, H, x.dtype, norm) if use_kernel else 0
    operands = {"x": xf, "gain": gain}
    if sf is not None:
        operands["sub"] = sf
    if bias is not None:
        operands["bias"] = bias
    if beta is not None:
        operands["beta"] = beta
    cfg = (norm, float(eps), act, bool(use_kernel), int(bt),  # tpu-lint: disable=TPL101 -- eps/use_kernel are static Python config (shape-derived gate), never traced arrays
           sf is not None, str(bias.dtype) if bias is not None else None)
    r, y = _fused(operands, cfg)
    return r.reshape(shape), y.reshape(shape)
