"""What the entries share: the warm-up of the cell's device shapes."""
from __future__ import annotations

import itertools

import numpy as np


def warm_shapes(n_nodes: int, warm: dict) -> None:
    """Solve one batch of every (B, W) the traffic lists, through the
    program's own allocator, so the window compiles nothing.  Requests are
    ``W`` jobs side by side on the first node: they pad to exactly the
    bucket (B, n_nodes, W) and answer through the same jitted programs."""
    from repro.core.alloc_jax import BatchedAllocator
    from repro.core.alloc_kernels import build_csr

    alloc = BatchedAllocator()
    for opt, b, w in itertools.product(warm["opt"], warm["batch"],
                                       warm["width"]):
        inc = build_csr(np.full(w, 1.0 / w), [[0]] * w, n_nodes)
        cols = np.arange(w)
        alloc.allocate_many([(inc, cols, opt)] * b)
