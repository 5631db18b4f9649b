"""jit-friendly kernel entry points with backend selection.

Models call these; the backend is chosen once per process:

* ``"ref"``       — pure-jnp oracles (the default: CPU execution, dry-run
                    lowering, compiled HLO that stays backend-portable);
* ``"pallas"``    — Pallas kernels, compiled (TPU only);
* ``"interpret"`` — Pallas kernels in the Pallas interpreter (correctness
                    validation on CPU).  Never chosen for the caller.

Gradients always flow through the ref formulation (``custom_vjp`` with the
oracle backward), which keeps training correct while the forward hot-path
uses the kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref as _ref

_BACKEND = "ref"


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in ("ref", "pallas", "interpret"):
        raise ValueError(name)
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


def _interpret() -> bool:
    return _BACKEND == "interpret"


# --------------------------------------------------------------------------- #
# flash attention                                                              #
# --------------------------------------------------------------------------- #
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, scale: Optional[float] = None):
    if _BACKEND != "ref":
        from .flash_attention import flash_attention as fa

        fwd = functools.partial(fa, causal=causal, window=window,
                                q_offset=q_offset, scale=scale,
                                interpret=_interpret())
        ref_fn = functools.partial(_ref.flash_attention_ref, causal=causal,
                                   window=window, q_offset=q_offset, scale=scale)

        @jax.custom_vjp
        def op(q, k, v):
            return fwd(q, k, v)

        def op_fwd(q, k, v):
            return fwd(q, k, v), (q, k, v)

        def op_bwd(res, g):
            _, vjp = jax.vjp(ref_fn, *res)
            return vjp(g)

        op.defvjp(op_fwd, op_bwd)
        return op(q, k, v)
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                    q_offset=q_offset, scale=scale)


def flash_decode(q, k_cache, v_cache, cur_len, *, scale: Optional[float] = None):
    if _BACKEND != "ref":
        from .flash_attention import flash_decode as fd

        return fd(q, k_cache, v_cache, cur_len, scale=scale,
                  interpret=_interpret())
    return _ref.flash_decode_ref(q, k_cache, v_cache, cur_len, scale=scale)


# --------------------------------------------------------------------------- #
# RWKV6 WKV scan                                                               #
# --------------------------------------------------------------------------- #
def wkv6(r, k, v, w, u, s0):
    if _BACKEND != "ref" and r.shape[1] > 1:
        from .rwkv6_scan import wkv6 as kk

        fwd = functools.partial(kk, interpret=_interpret())

        @jax.custom_vjp
        def op(r, k, v, w, u, s0):
            return fwd(r, k, v, w, u, s0)

        def op_fwd(*args):
            return fwd(*args), args

        def op_bwd(res, g):
            _, vjp = jax.vjp(_ref.wkv6_ref, *res)
            return vjp(g)

        op.defvjp(op_fwd, op_bwd)
        return op(r, k, v, w, u, s0)
    return _ref.wkv6_ref(r, k, v, w, u, s0)


# --------------------------------------------------------------------------- #
# scheduler allocation matvec (§4.6 water-filling inner loop)                  #
# --------------------------------------------------------------------------- #
def alloc_matvec(weight, x):
    """Sequential masked matvec over job columns — bit-exact vs the numpy
    CSR accumulation (see ``kernels/alloc_matvec.py``, which also says why
    the compiled kernel cannot take float64 on TPU).  No custom_vjp: the
    scheduler path is forward-only f64 arithmetic, never differentiated."""
    if _BACKEND != "ref":
        from .alloc_matvec import alloc_matvec as kk

        return kk(weight, x, interpret=_interpret())
    return _ref.alloc_matvec_ref(weight, x)


# --------------------------------------------------------------------------- #
# RG-LRU linear recurrence                                                     #
# --------------------------------------------------------------------------- #
def linear_recurrence(a, b, h0):
    if _BACKEND != "ref" and a.shape[1] > 1:
        from .rglru_scan import rglru_scan as kk

        fwd = functools.partial(kk, interpret=_interpret())

        @jax.custom_vjp
        def op(a, b, h0):
            return fwd(a, b, h0)

        def op_fwd(*args):
            return fwd(*args), args

        def op_bwd(res, g):
            _, vjp = jax.vjp(_ref.linear_recurrence_ref, *res)
            return vjp(g)

        op.defvjp(op_fwd, op_bwd)
        return op(a, b, h0)
    return _ref.linear_recurrence_ref(a, b, h0)
