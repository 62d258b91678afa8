"""Custom C++ op build system.

Reference: python/paddle/utils/cpp_extension/cpp_extension.py (setuptools
``CppExtension``/``BuildExtension`` + JIT ``load``) and the C++ macro side
``PD_BUILD_OP`` (paddle/phi/api/ext/op_meta_info.h:1140).

TPU translation: a custom op's device code cannot be CUDA — the
accelerator path belongs to XLA/Pallas (write a pure-jax/Pallas lowering
and register it with ``paddle_tpu.core.dispatch.op``). What this module
keeps native is the HOST custom-op path: C++ sources are JIT-compiled
with g++ into a content-hash-cached shared library (same machinery as
core/native), bound via ctypes (no pybind11 in this build), and exposed
as framework ops through ``custom_op`` — executed inside traced programs
via ``jax.pure_callback`` (the host-callback analog of the reference's
custom CPU kernels), with an optional C backward.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Callable, Optional, Sequence

import numpy as np

__all__ = ["CppExtension", "CUDAExtension", "BuildExtension", "load",
           "get_build_directory", "custom_op"]

_CB_SUPPORTED = None


def _callbacks_supported() -> bool:
    """Probe once whether the active backend supports host callbacks."""
    global _CB_SUPPORTED
    if _CB_SUPPORTED is None:
        import jax
        import jax.numpy as jnp

        try:
            jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct((), jnp.float32),
                jnp.zeros((), jnp.float32)).block_until_ready()
            _CB_SUPPORTED = True
        except Exception:
            _CB_SUPPORTED = False
    return _CB_SUPPORTED


def get_build_directory() -> str:
    d = os.environ.get("PADDLE_EXTENSION_DIR") or os.path.join(
        os.path.expanduser("~"), ".cache", "paddle_tpu_extensions")
    os.makedirs(d, exist_ok=True)
    return d


class CppExtension:
    """setuptools Extension descriptor (reference CppExtension): use with
    ``BuildExtension`` in a setup.py, or skip setuptools entirely with
    :func:`load`."""

    def __init__(self, sources: Sequence[str], *args, **kwargs):
        self.sources = list(sources)
        self.extra_compile_args = kwargs.get("extra_compile_args", [])
        self.include_dirs = kwargs.get("include_dirs", [])
        self.name = kwargs.get("name", "paddle_tpu_custom_op")


def CUDAExtension(*args, **kwargs):
    raise RuntimeError(
        "CUDAExtension is not supported on the TPU build: device kernels "
        "are XLA/Pallas lowerings — register them with "
        "paddle_tpu.core.dispatch.op; use CppExtension/load for host C++.")


class BuildExtension:
    """Minimal setuptools cmdclass shim (reference BuildExtension.with_options):
    builds each CppExtension with g++ at install time."""

    @classmethod
    def with_options(cls, **options):
        return cls

    def __init__(self, dist=None, **kw):
        self.extensions = []

    def build_extension(self, ext: CppExtension):
        return _compile(ext.sources, ext.extra_compile_args,
                        ext.include_dirs)


def _compile(sources, extra_cflags=None, include_dirs=None,
             build_directory=None, verbose=False) -> str:
    """g++ -> cached .so keyed by source+flag content hash."""
    build_dir = build_directory or get_build_directory()
    h = hashlib.sha256()
    for s in sources:
        with open(s, "rb") as f:
            h.update(f.read())
    h.update(" ".join(extra_cflags or []).encode())
    # include dirs participate in the key, including header contents, so
    # header edits don't serve a stale cached .so
    for d in sorted(include_dirs or []):
        h.update(d.encode())
        if os.path.isdir(d):
            for fn in sorted(os.listdir(d)):
                if fn.endswith((".h", ".hpp", ".hh", ".cuh")):
                    with open(os.path.join(d, fn), "rb") as f:
                        h.update(f.read())
    so = os.path.join(build_dir, f"ext_{h.hexdigest()[:16]}.so")
    if os.path.exists(so):
        return so
    # build to a temp name then rename: a killed/concurrent g++ must not
    # leave a half-written .so that existence-checking would trust forever
    tmp = f"{so}.tmp.{os.getpid()}"
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-o", tmp]
    for d in include_dirs or []:
        cmd += ["-I", d]
    cmd += list(extra_cflags or []) + list(sources)
    if verbose:
        print("cpp_extension:", " ".join(cmd))
    try:
        subprocess.run(cmd, check=True, capture_output=not verbose)
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return so


class CModule:
    """Loaded extension: C functions reachable as attributes (ctypes)."""

    def __init__(self, so_path: str):
        self._so_path = so_path
        self._lib = ctypes.CDLL(so_path)

    def __getattr__(self, name):
        return getattr(self._lib, name)


def load(name: str, sources: Sequence[str], extra_cflags=None,
         extra_include_paths=None, build_directory=None,
         verbose: bool = False) -> CModule:
    """JIT-build and load (reference cpp_extension.load)."""
    so = _compile(list(sources), extra_cflags, extra_include_paths,
                  build_directory, verbose)
    return CModule(so)


def _elementwise_caller(cfunc) -> Callable[[np.ndarray], np.ndarray]:
    """Wrap a C function with signature
    ``void f(const float* x, float* out, int64_t n)`` as ndarray->ndarray."""
    cfunc.argtypes = [ctypes.POINTER(ctypes.c_float),
                      ctypes.POINTER(ctypes.c_float), ctypes.c_int64]
    cfunc.restype = None

    def call(x: np.ndarray) -> np.ndarray:
        x = np.ascontiguousarray(x, dtype=np.float32)
        out = np.empty_like(x)
        cfunc(x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
              out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
              ctypes.c_int64(x.size))
        return out

    return call


def custom_op(name: str, forward_cfunc, grad_cfunc=None):
    """Register a host C++ elementwise op as a framework op.

    ``forward_cfunc``/``grad_cfunc`` follow the C contract
    ``void f(const float* x, float* out, int64_t n)`` (the grad takes the
    upstream cotangent through a second pass: dx = grad_f(x) * g, with
    grad_cfunc computing grad_f(x)). The op executes through
    ``jax.pure_callback`` so it also runs inside captured programs — the
    role of the reference's custom CPU kernel dispatch (op_meta_info.h
    PD_BUILD_OP + custom operator registry).
    """
    import jax
    import jax.numpy as jnp

    from ..core.dispatch import op

    fwd_call = _elementwise_caller(forward_cfunc)
    grad_call = _elementwise_caller(grad_cfunc) if grad_cfunc is not None \
        else None

    def _run_host(call, x):
        """Run the C function: through pure_callback where the backend
        supports host callbacks (CPU, standard TPU runtimes), else via an
        eager host round-trip (a PJRT backend may lack send/recv
        callbacks — eager mode still works there; captured programs need
        callback support)."""
        if _callbacks_supported():
            return jax.pure_callback(
                call, jax.ShapeDtypeStruct(x.shape, jnp.float32),
                x.astype(jnp.float32), vmap_method="sequential")
        if isinstance(x, jax.core.Tracer):
            raise RuntimeError(
                f"custom op '{name}': this backend does not support host "
                "callbacks, so host C++ ops cannot run inside traced "
                "programs here; call it eagerly")
        return jnp.asarray(call(np.asarray(x)))

    def fwd_host(x):
        return _run_host(fwd_call, x)

    if grad_call is None:
        return op(name, differentiable=False)(fwd_host)

    @jax.custom_vjp
    def fn(x):
        return fwd_host(x)

    def fn_fwd(x):
        return fn(x), x

    def fn_bwd(x, g):
        gf = _run_host(grad_call, x)
        return (gf * g,)

    fn.defvjp(fn_fwd, fn_bwd)
    return op(name)(fn)
