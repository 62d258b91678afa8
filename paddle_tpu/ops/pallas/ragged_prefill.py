"""Back-compat shim: ragged chunked-prefill attention as a special case
of the unified ragged-paged-attention step.

PR 7 generalized this module into ragged_paged_attention.py, where every
grid row carries an explicit valid-token count (decode is a 1-token
chunk).  A page-aligned prefill chunk is exactly the n_valid == qb case
— every row is valid, qpos(i) = pos0 + i, and no row is zeroed as
padding — so the historical entry points below simply delegate (the
kernel at one page a grid step, the dispatcher at the autotune's
choice).  See ragged_paged_attention.py for the kernel, the XLA arm,
and the full contract.
"""

from __future__ import annotations

import jax.numpy as jnp

from .ragged_paged_attention import (
    _ragged_paged_xla,
    ragged_paged_attention,
    ragged_paged_attention_kernel,
    ragged_paged_supported,
)

__all__ = ["ragged_prefill_attention", "ragged_prefill_supported"]


def ragged_prefill_supported(kt_pages_shape, n_q_heads: int,
                             itemsize: int = 2) -> bool:
    """Historical gate: a prefill chunk is qb == page_size query tokens."""
    return ragged_paged_supported(kt_pages_shape, n_q_heads,
                                  kt_pages_shape[3], itemsize)


def _full_valid(q):
    return jnp.full((q.shape[0],), q.shape[1], jnp.int32)


def ragged_prefill_attention_kernel(q, kt_pages, v_pages, rows, pos0,
                                    sm_scale: float):
    return ragged_paged_attention_kernel(q, kt_pages, v_pages, rows,
                                         pos0, _full_valid(q), sm_scale)


def _ragged_prefill_xla(q, k_pages, v_pages, rows, pos0, sm_scale,
                        k_layout):
    return _ragged_paged_xla(q, k_pages, v_pages, rows, pos0,
                             _full_valid(q), sm_scale, k_layout)


def ragged_prefill_attention(q, k_pages, v_pages, rows, pos0,
                             sm_scale: float, k_layout: str = "d_major"):
    return ragged_paged_attention(q, k_pages, v_pages, rows, pos0,
                                  _full_valid(q), sm_scale, k_layout)
