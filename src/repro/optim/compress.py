"""Gradient compression for the cross-pod (DCN) axis.

Inter-pod links are the scarce resource at multi-pod scale (DCN bandwidth
<< ICI).  We compress the cross-pod gradient reduction to int8 with
per-tensor max-abs scales and *error feedback* (the quantization residual
is added back into the next step's gradient), which keeps convergence
unharmed in practice (1-bit Adam / EF-SGD literature).

``compressed_pod_allreduce`` is written for use inside
``jax.shard_map`` over the 'pod' axis: it all-gathers int8 payloads
(1 byte/element over DCN instead of 4) and reduces locally.  HLO
collective bytes drop ~4x on the pod axis — visible in the §Roofline collective term (see EXPERIMENTS.md
§Perf hillclimb #3).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "quantize_int8",
    "dequantize_int8",
    "error_feedback_init",
    "compressed_pod_allreduce",
]


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8: returns (q, scale)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def error_feedback_init(params) -> Any:
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )


def compressed_pod_allreduce(grads, err, axis_name: str = "pod"):
    """Mean-reduce ``grads`` over ``axis_name`` in int8 with error feedback.

    Call inside shard_map with the pod axis un-reduced.  Returns
    (reduced_grads, new_err).  Per-leaf: g' = mean_pods(Q(g + e)),
    e' = (g + e) - deQ(Q(g + e)).
    """
    n = jax.lax.psum(1, axis_name)

    def one(g, e):
        target = g.astype(jnp.float32) + e
        q, scale = quantize_int8(target)
        local_dq = dequantize_int8(q, scale)
        new_e = target - local_dq
        # all-gather int8 payloads + scales, reduce locally (1B/elt on DCN)
        qs = jax.lax.all_gather(q, axis_name)  # [P, ...] int8
        ss = jax.lax.all_gather(scale, axis_name)  # [P]
        red = jnp.tensordot(
            ss.astype(jnp.float32), qs.astype(jnp.float32), axes=((0,), (0,))
        ) / n
        return red.astype(g.dtype), new_e

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = jax.tree_util.tree_leaves(err)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    new_g = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_e = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    return new_g, new_e
