"""Fused FFN activation epilogues: bias+gelu and swiglu as one VMEM pass.

These are the first catalog entries nobody hand-wired: the compiler pass
(paddle_tpu/compiler/) discovered both chains in the models' jaxprs —
``gelu(h + fc_b)`` between the two GPT FFN matmuls and
``silu(gate).astype * up`` between the LLaMA gate/up and down matmuls —
and routes them here. Between two matmuls XLA emits the bias broadcast,
the activation polynomial and the gating multiply as separate HBM-bound
passes over the [B*T, F] activation (F = 4H / ffn_hidden, the widest
activation in the block); this kernel streams one [bt, F] row block
through VMEM and applies the whole chain in a single pass.

The in-kernel expressions replicate the model compositions term for term
(same dtypes per op, fp32 only where the eager chain is fp32), so the
kernel arm is BIT-IDENTICAL to the unfused composition — pinned by
tests/test_fused_bias_act.py, both arms, same scheme as
fused_norm_epilogue.py (reduce_precision so convert-pair simplification
cannot elide a bf16 rounding the op-by-op graph performs).

Backward is deliberately XLA: the custom_vjp saves only the raw inputs —
the same live set as the unfused graph — and pulls the cotangent back
through ``jax.vjp`` of the reference composition, so gradients are
bitwise the unfused graph's gradients.

Single-program gate: like fused_ce.py, pallas custom calls have no GSPMD
partitioning rule, so the kernel arm is restricted to programs built on
a one-device mesh; multichip programs keep the unfused composition
(which shards cleanly).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import (_interpret_mode, _row_blocks, _tpu_params,
                              single_device_program)

__all__ = ["fused_bias_gelu", "fused_swiglu", "fused_bias_act_supported"]

def _bt_candidates(n: int, f: int, itemsize: int) -> list[int]:
    # per row: two operands in, one out (input dtype, double buffered) +
    # ~2 fp32 temporaries
    return _row_blocks(n, f * (6 * itemsize + 8))


def fused_bias_act_supported(n: int, f: int, dtype) -> bool:
    """Gate: lane-aligned ffn width, a row block that tiles the row
    count and keeps one [bt, f] block set VMEM-feasible, and a
    single-device program: a pallas custom call has no GSPMD
    partitioning rule, so under a mesh of more than one device the
    unfused composition (which shards cleanly) stays.  The mesh is the
    ambient one the step was built on (train_step sets it), never the
    host's device count."""
    dt = jnp.dtype(dtype)
    return (f % 128 == 0 and n > 0
            and dt in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and bool(_bt_candidates(n, f, dt.itemsize))
            and single_device_program())


def _rp(v):
    """Interpret mode only (the body is then compiled by XLA:CPU): the
    one narrowing XLA never removes (see fused_norm_epilogue.py) pins
    bf16 values to the bf16 grid inside the fused body.  Mosaic has no
    lowering for it and does no convert-pair simplification."""
    if v.dtype == jnp.bfloat16 and _interpret_mode():
        return lax.reduce_precision(v, 8, 7)
    return v


def _bias_gelu_ref(x, bias):
    """The unfused model chain (models/gpt.py FFN), term for term: the
    bias rounds to the activation dtype first, the add and the tanh-gelu
    polynomial all run in the activation dtype."""
    return jax.nn.gelu(x + bias.astype(x.dtype), approximate=True)


def _swiglu_ref(gate, up):
    """The unfused model chain (models/llama.py FFN), term for term:
    silu in fp32, cast back, gate the up projection in the activation
    dtype."""
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def _bias_gelu_kernel(x_ref, b_ref, y_ref):
    x = x_ref[...]
    b = _rp(b_ref[0, :].astype(x.dtype))
    y_ref[...] = jax.nn.gelu(_rp(x + b), approximate=True)


def _swiglu_kernel(g_ref, u_ref, y_ref):
    g32 = g_ref[...].astype(jnp.float32)
    h = _rp(jax.nn.silu(g32).astype(g_ref.dtype))
    y_ref[...] = _rp(h * u_ref[...])


def _act_call(kernel, ops, specs, n, f, dtype, bt, name):
    import jax.experimental.pallas as pl

    row = pl.BlockSpec((bt, f), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(n // bt,),
        in_specs=specs,
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((n, f), dtype),
        interpret=_interpret_mode(),
        compiler_params=_tpu_params(0),
        name=name,
    )(*ops)


def _bias_gelu_call(x, bias, *, bt):
    import jax.experimental.pallas as pl

    n, f = x.shape
    row = pl.BlockSpec((bt, f), lambda i: (i, 0))
    vec = pl.BlockSpec((1, f), lambda i: (0, 0))
    return _act_call(_bias_gelu_kernel, [x, bias.reshape(1, f)],
                     [row, vec], n, f, x.dtype, bt, "fused_bias_gelu")


def _swiglu_call(gate, up, *, bt):
    import jax.experimental.pallas as pl

    n, f = gate.shape
    row = pl.BlockSpec((bt, f), lambda i: (i, 0))
    return _act_call(_swiglu_kernel, [gate, up], [row, row], n, f,
                     gate.dtype, bt, "fused_swiglu")


_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(_bias_gelu_kernel, _swiglu_kernel,
                                    _act_call)
    return _SRC


def _tuned_bt(kernel_name: str, n: int, f: int, dtype, call) -> int:
    """Row-block size via the autotune registry; candidates[0] (256
    wherever it fits) is the hand default no-sweep backends use."""
    from . import autotune

    cands = _bt_candidates(n, f, jnp.dtype(dtype).itemsize)
    if not cands:
        return 0

    def measure(bt):
        a = jnp.zeros((n, f), dtype)
        fn = jax.jit(functools.partial(call, bt=int(bt)))
        b = jnp.zeros((f,), dtype) if kernel_name == "fused_bias_gelu" else a
        return autotune.time_candidate(lambda: fn(a, b))

    return int(autotune.tuned(kernel_name, f"n{n}_f{f}",
                              str(jnp.dtype(dtype)), cands, measure=measure,
                              source=_autotune_source()))


# -- bias + gelu -------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _bias_gelu(x, bias, cfg):
    return _bias_gelu_fwd(x, bias, cfg)[0]


def _bias_gelu_fwd(x, bias, cfg):
    use_kernel, bt = cfg
    if use_kernel and bt:
        y = _bias_gelu_call(x, bias, bt=bt)
    else:
        y = _bias_gelu_ref(x, bias)
    return y, (x, bias)


def _bias_gelu_bwd(cfg, res, dy):
    x, bias = res
    _, vjp = jax.vjp(_bias_gelu_ref, x, bias)
    return vjp(dy)


_bias_gelu.defvjp(_bias_gelu_fwd, _bias_gelu_bwd)


def fused_bias_gelu(x, bias, *, use_kernel: bool | None = None):
    """``gelu(x + bias, approximate=True)`` over arbitrary leading dims
    (bias broadcasts over rows). ``use_kernel=None`` routes by
    :func:`fused_bias_act_supported`; ``False`` pins the XLA arm
    (parity tests)."""
    shape = x.shape
    f = shape[-1]
    if bias.shape != (f,):
        raise ValueError(f"bias must be [{f}], got {bias.shape}")
    xf = x.reshape(-1, f)
    n = xf.shape[0]
    if use_kernel is None:
        use_kernel = fused_bias_act_supported(n, f, x.dtype)
    bt = _tuned_bt("fused_bias_gelu", n, f, x.dtype,
                   _bias_gelu_call) if use_kernel else 0
    cfg = (bool(use_kernel), int(bt))
    return _bias_gelu(xf, bias, cfg).reshape(shape)


# -- swiglu ------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _swiglu(gate, up, cfg):
    return _swiglu_fwd(gate, up, cfg)[0]


def _swiglu_fwd(gate, up, cfg):
    use_kernel, bt = cfg
    if use_kernel and bt:
        y = _swiglu_call(gate, up, bt=bt)
    else:
        y = _swiglu_ref(gate, up)
    return y, (gate, up)


def _swiglu_bwd(cfg, res, dy):
    gate, up = res
    _, vjp = jax.vjp(_swiglu_ref, gate, up)
    return vjp(dy)


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def fused_swiglu(gate, up, *, use_kernel: bool | None = None):
    """``silu(gate.astype(f32)).astype(dtype) * up`` over arbitrary
    leading dims. ``use_kernel=None`` routes by
    :func:`fused_bias_act_supported`; ``False`` pins the XLA arm."""
    if gate.shape != up.shape:
        raise ValueError(f"gate/up shape mismatch: {gate.shape} vs "
                         f"{up.shape}")
    shape = gate.shape
    f = shape[-1]
    gf = gate.reshape(-1, f)
    uf = up.reshape(-1, f)
    n = gf.shape[0]
    if use_kernel is None:
        use_kernel = fused_bias_act_supported(n, f, gate.dtype)
    bt = _tuned_bt("fused_swiglu", n, f, gate.dtype,
                   _swiglu_call) if use_kernel else 0
    cfg = (bool(use_kernel), int(bt))
    return _swiglu(gf, uf, cfg).reshape(shape)
