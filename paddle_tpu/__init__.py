"""paddle_tpu: a TPU-native deep-learning framework.

A ground-up re-design of the capabilities of the reference framework
(PaddlePaddle; see SURVEY.md) for TPU: eager define-by-run autograd recorded
over XLA-traceable ops, whole-step program capture (``paddle_tpu.jit``),
Pallas kernels for the fused hot set, and hybrid parallelism (DP/TP/SP/PP/
ZeRO/EP + SPMD auto-parallel) expressed as shardings over a
``jax.sharding.Mesh`` with XLA collectives over ICI/DCN.
"""

from __future__ import annotations

from .core import (
    OP_REGISTRY,
    Parameter,
    Tensor,
    backward,
    enable_grad,
    get_flags,
    grad,
    is_grad_enabled,
    no_grad,
    set_flags,
    set_grad_enabled,
)
from .core.device import (
    device_count,
    get_device,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    is_compiled_with_xpu,
    set_device,
)
from .core.dtype import (
    bfloat16,
    bool_,
    complex64,
    complex128,
    float16,
    float32,
    float64,
    int8,
    int16,
    int32,
    int64,
    uint8,
)
from .core.random import seed
from .ops import *  # noqa: F401,F403
from .ops import sum, max, min, all, any, abs, pow, slice  # noqa: A004,F401

from . import autograd, framework, version

__version__ = version.__version__

in_dynamic_mode = framework.in_dynamic_mode
save = framework.save
load = framework.load

# Subpackages (nn, optimizer, amp, io, jit, distributed, ...) are imported
# lazily on first attribute access to keep core import light.
_LAZY_SUBMODULES = (
    "nn",
    "optimizer",
    "amp",
    "io",
    "jit",
    "metric",
    "static",
    "vision",
    "distributed",
    "incubate",
    "profiler",
    "distribution",
    "sparse",
    "device",
    "models",
    "hapi",
    "text",
    "audio",
    "geometric",
    "quantization",
    "onnx",
    "signal",
    "inference",
    "parallel",
    "testing",
)


# reference runtime-misc surface (places, dtype utilities, rng state,
# printoptions, static-mode switches)
from .static import CPUPlace, CUDAPlace, TPUPlace  # noqa: E402,F401


class CUDAPinnedPlace:  # parity alias; host memory is jax-managed
    pass


class LazyGuard:
    """Parity shim: lazy parameter init is immediate here (XLA arrays
    materialize on creation)."""

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_DEFAULT_DTYPE = ["float32"]


def set_default_dtype(d):
    _DEFAULT_DTYPE[0] = str(d).replace("paddle.", "")


def get_default_dtype():
    return _DEFAULT_DTYPE[0]


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    import numpy as _np

    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


def get_rng_state():
    from .core import random as _r

    return [_r.default_generator().get_state()]


def set_rng_state(state):
    from .core import random as _r

    if state:
        _r.default_generator().set_state(state[0])


get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state


def disable_signal_handler():
    pass  # jax installs no custom signal handlers


def enable_static():
    raise RuntimeError(
        "paddle_tpu is dygraph+capture only: use paddle_tpu.jit.to_static "
        "for compiled programs (the paddle.static Program shim in "
        "paddle_tpu.static serves porting needs)")


def disable_static():
    pass  # dygraph is always on


class finfo:
    def __init__(self, dtype):
        import numpy as _np

        from .core.dtype import convert_dtype

        info = _np.finfo(_np.dtype(convert_dtype(dtype)))
        self.dtype = str(dtype)
        self.bits = info.bits
        self.eps = float(info.eps)
        self.min = float(info.min)
        self.max = float(info.max)
        self.tiny = float(info.tiny)
        self.smallest_normal = float(info.tiny)
        self.resolution = float(info.resolution)


class iinfo:
    def __init__(self, dtype):
        import numpy as _np

        from .core.dtype import convert_dtype

        info = _np.iinfo(_np.dtype(convert_dtype(dtype)))
        self.dtype = str(dtype)
        self.bits = info.bits
        self.min = int(info.min)
        self.max = int(info.max)


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """reference paddle.create_parameter: a free-standing Parameter."""
    import numpy as _np

    from .core.dtype import convert_dtype
    from .core.tensor import Parameter
    import jax.numpy as _jnp

    if default_initializer is not None:
        from .nn.layer.layers import Layer

        helper = Layer()
        return helper.create_parameter(list(shape), attr=attr,
                                       is_bias=is_bias,
                                       default_initializer=default_initializer)
    arr = _jnp.zeros(tuple(shape), convert_dtype(dtype)) if is_bias else         _jnp.asarray(_np.random.normal(
            0, 0.02, tuple(shape)).astype(convert_dtype(dtype)))
    return Parameter(arr)


def batch(reader, batch_size, drop_last=False):
    """reference paddle.batch (legacy reader combinator)."""

    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batched


# generated in-place variants (x.add_(y) family)
from .ops.extra2 import install_inplace_variants as _iiv  # noqa: E402

_INPLACE_NAMES = _iiv(globals())


def _install_reference_method_surface():
    """Bind every reference Tensor-method name to its module function
    (tensor-first convention) unless a hand-written method already
    exists."""
    from .core.tensor import Tensor as _T
    from .ops.method_table import TENSOR_METHODS

    g = globals()
    installed = []
    for name in TENSOR_METHODS:
        if hasattr(_T, name):
            continue
        fn = g.get(name)
        if fn is None or not callable(fn):
            continue

        def method(s, *a, _fn=fn, **k):
            return _fn(s, *a, **k)

        method.__name__ = name
        setattr(_T, name, method)
        installed.append(name)
    return installed


_install_reference_method_surface()


def __getattr__(name):
    if name == "DataParallel":
        from .distributed.parallel import DataParallel

        globals()["DataParallel"] = DataParallel
        return DataParallel
    if name == "ParamAttr":
        from .nn.layer.layers import ParamAttr

        globals()["ParamAttr"] = ParamAttr
        return ParamAttr
    if name == "dtype":
        globals()["dtype"] = str
        return str
    if name in ("bool", "float8_e4m3fn", "float8_e5m2"):
        globals()[name] = name  # dtype strings (core.dtype resolves them)
        return name
    if name in _LAZY_SUBMODULES:
        import importlib

        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    if name == "Model":
        from .hapi import Model

        globals()["Model"] = Model
        return Model
    if name in ("summary", "flops"):
        from .hapi.summary import flops, summary

        globals()["summary"] = summary
        globals()["flops"] = flops
        return globals()[name]
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")
