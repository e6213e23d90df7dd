"""Row blocks and a running top-k over them, so that a program over all
base rows holds one block at a time and still gives what one ``top_k``
over every row gives: the lowest values, ties to the lowest id."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def row_block(j, n: int, rows: int):
    """Block ``j`` of ``rows`` rows of ``n``: (first row, ids, new).  The
    last block ends at row n, so every block has ``rows`` rows; ``new``
    marks the rows no earlier block holds."""
    start = jnp.minimum(j * rows, n - rows)
    ids = start + jnp.arange(rows, dtype=jnp.int32)
    return start, ids, ids >= j * rows


def merge_topk(best, best_ids, vals, ids, k: int):
    """The k lowest (value, id) pairs of each row of the best so far
    (q, k) and a new block's values (q, rows) with ids (rows,), all
    higher than the best's.  The best sit first, so ties keep the lowest
    id."""
    neg, pos = jax.lax.top_k(-jnp.concatenate([best, vals], 1), k)
    cat = jnp.concatenate([best_ids, jnp.broadcast_to(ids, vals.shape)], 1)
    return -neg, jnp.take_along_axis(cat, pos, 1)


def running_topk(block, n_blocks: int, k: int):
    """The k lowest (value, id) pairs of each row of ``block(j)``'s
    values over blocks j = 0..n_blocks-1, and the sum of their extras.
    ``block(j)`` returns (values (q, rows), ids (rows,), extra); ids
    rise with j.  Returns ((values (q, k), ids (q, k)), extra)."""
    vals, ids, extra = block(0)
    neg, pos = jax.lax.top_k(-vals, k)

    def merge(j, carry):
        best, acc = carry
        vals, ids, extra = block(j)
        return (merge_topk(*best, vals, ids, k),
                jax.tree.map(jnp.add, acc, extra))

    return jax.lax.fori_loop(1, n_blocks, merge, ((-neg, ids[pos]), extra))
