"""Pallas kernel for the water-filling inner matvec (§4.6, batched).

The batched max-min water-filling (``repro.core.alloc_jax``) spends its
rounds in one primitive: a *sequential* masked matvec — for every lane and
node, accumulate ``weight[n, j] * x[j]`` over job columns ``j`` in strictly
ascending order.  The order is the bit-identity contract: the numpy oracle
(``CSRIncidence.matvec``) accumulates left to right, so any reformulation
(pairwise ``jnp.sum``, ``dot``) rounds differently.

Both implementations here keep that contract, in the same two-step shape:

1. materialize every product with one vectorized multiply, **outside** the
   accumulation loop;
2. run an adds-only ``fori_loop`` over columns.

Step 1 is not a style choice — it is what makes the result bit-exact.  XLA
CPU contracts a ``mul`` feeding an ``add`` inside one loop body into a
single-rounding FMA (``fma(a, b, acc)`` instead of ``round(a*b) + acc``),
which is 1 ulp off the numpy sequence on ~12% of operand triples, and
``lax.optimization_barrier`` does not prevent it.  A multiply whose result
crosses the ``fori_loop``/``pallas`` computation boundary cannot be
contracted, and an adds-only loop reproduces numpy's operation sequence
exactly (padding columns contribute an exact ``+0.0``, which never changes
a finite partial sum).  ``tests/test_alloc_jax.py`` pins this down.

The lane runs :func:`alloc_matvec_ref`.  :func:`alloc_matvec` is checked
in the Pallas interpreter only (``interpret=True``, which the caller
passes).  It does not compile for TPU in the lane's float64: XLA's TPU
x64 rewrite refuses any Pallas custom call with f64 operands
(``tests/test_tpu_compile.py`` pins that refusal).  The body is a second
obstacle: under x64 its Pallas TPU lowering recurses without end, and in
float32 Mosaic cannot lower the ``fori_loop``'s dynamic column slice.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

__all__ = ["alloc_matvec", "alloc_matvec_ref"]


def alloc_matvec_ref(weight, x):
    """Sequential masked matvec, pure jnp (the oracle formulation).

    weight: (B, N, W); x: (B, W).  Returns (B, N): per-lane per-node
    left-to-right accumulation of ``weight[b, n, j] * x[b, j]`` over j.
    """
    weight, x = jnp.asarray(weight), jnp.asarray(x)  # numpy in → traceable
    B, N, W = weight.shape
    if W == 0:                              # static: fori_loop traces its
        return jnp.zeros((B, N), weight.dtype)  # body even over 0 columns
    prods = weight * x[:, None, :]          # one multiply, materialized
    def body(j, acc):
        return acc + prods[:, :, j]         # adds only: no FMA contraction
    return lax.fori_loop(0, W, body, jnp.zeros((B, N), weight.dtype))


def _mv_kernel(w_ref, x_ref, o_ref):
    w = w_ref[0]                            # (N, W)
    x = x_ref[0]                            # (1, W)
    prods = w * x                           # separate multiply (see module doc)
    N, W = w.shape
    def body(j, acc):
        return acc + prods[:, j]
    o_ref[0] = lax.fori_loop(0, W, body, jnp.zeros((N,), w.dtype))[None, :]


def alloc_matvec(weight, x, *, interpret: bool):
    """Pallas version of :func:`alloc_matvec_ref`: grid over lanes, one
    sequential accumulation per (lane, node) block.  ``x`` and the output
    carry a unit middle axis so that every block's last two dimensions
    equal the array's, as the TPU tiling requires."""
    weight, x = jnp.asarray(weight), jnp.asarray(x)
    B, N, W = weight.shape
    if W == 0:
        return jnp.zeros((B, N), weight.dtype)
    return pl.pallas_call(
        _mv_kernel,
        out_shape=jax.ShapeDtypeStruct((B, 1, N), weight.dtype),
        grid=(B,),
        in_specs=[pl.BlockSpec((1, N, W), lambda b: (b, 0, 0)),
                  pl.BlockSpec((1, 1, W), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, N), lambda b: (b, 0, 0)),
        interpret=interpret,
    )(weight, x[:, None, :])[:, 0]
