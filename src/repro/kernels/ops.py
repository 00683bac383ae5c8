"""jit'd public wrappers over the Pallas kernels.

The backend picks the mode: on a TPU every kernel compiles to Mosaic; on
any other backend it runs in Pallas interpret mode (the kernel body as
XLA ops, one grid step at a time), which is how the tests exercise the
kernels with ``JAX_PLATFORMS=cpu``.  ``python chip_smoke.py [--chips 4]``
runs the served path on the chip and fails where JAX finds no TPU.  Entry
points keep JAX's compile cache in ``$JAX_COMPILATION_CACHE_DIR`` when set,
else in ``<repo>/.jax_cache`` (``repro.compile_cache``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import lift_compact as _lc
from repro.kernels import pairwise as _pw
from repro.kernels import query_topk as _qt


def _interpret() -> bool:
    """Shared backend key: the query top-k and nearest-distance kernels
    resolve their ``interpret=None`` default through this helper
    (``lift_compact`` keys off the backend itself)."""
    return jax.default_backend() != "tpu"


def donate_default() -> bool:
    """Buffer-donation policy, keyed off the backend like ``_interpret``.

    Donating a dead input buffer (``donate_argnums``) lets XLA write the
    output in place — a win on TPU/GPU where dispatch is asynchronous.
    Under CPU dispatch semantics, however, issuing a dispatch that donates
    a buffer BLOCKS the caller until the donated buffer's producer has
    finished, which serializes exactly the overlap the donation was meant
    to cheapen (PR 9 measurement: the overlapped serving loop lost its
    entire win with donation on).  Callers that take ``donate=None``
    ("auto") resolve it here: on for TPU/GPU, off for CPU.  Byte-identity
    between the two settings is asserted in tests/test_serving_loop.py.
    """
    return jax.default_backend() not in ("cpu",)


@partial(jax.jit, static_argnums=(3,))
def query_topk(q, embeds, active, k: int):
    return _qt.query_topk_pallas(q, embeds, active, k,
                                 interpret=_interpret())


@partial(jax.jit, static_argnums=(3,))
def query_topk_multi(qs, embeds, active, k: int):
    """[Q, E] query batch: one embedding-table sweep serves all Q queries."""
    return _qt.query_topk_multi_pallas(qs, embeds, active, k,
                                       interpret=_interpret())


@partial(jax.jit, static_argnums=(3,))
def query_topk_bias(qs, embeds, bias, k: int):
    """[Q, E] queries + [Q, N] score bias (NEG = slot masked out): the
    declarative query engine's fused predicate+score+top-k sweep."""
    return _qt.query_topk_bias_pallas(qs, embeds, bias, k,
                                      interpret=_interpret())


@partial(jax.jit, static_argnames=("stride", "budget", "lift_cap"))
def lift_compact(depth, masks, intrinsics, pose, *, stride: int = 1,
                 budget: int, lift_cap: int = 4096):
    """Fused frame-ingest geometry: lift -> compact -> downsample -> stats
    for all D detections in one pass (the seed ``lift_depth`` +
    ``downsample`` + ``centroid_bbox`` composition, minus the per-object
    argsort and the [D, HW, 3] intermediate).

    On TPU this dispatches the Pallas streaming kernel; elsewhere the
    algorithmically identical XLA gather formulation — the kernel's
    one-hot-matmul scatter only pays for itself on the MXU, and running it
    in interpret mode would forfeit the fusion win the pipeline is built
    around.  Both are parity-tested against ``ref.lift_compact_ref``.
    """
    kw = dict(stride=stride, budget=budget, lift_cap=lift_cap)
    if jax.default_backend() == "tpu":
        return _lc.lift_compact_pallas(depth, masks, intrinsics, pose,
                                       interpret=False, **kw)
    return _lc.lift_compact_xla(depth, masks, intrinsics, pose, **kw)


@jax.jit
def nearest_dist(a, b, b_valid):
    """Pads coords to 8 lanes then runs the blocked kernel."""
    D = a.shape[1]
    padd = (-D) % 8
    if padd:
        a = jnp.pad(a, ((0, 0), (0, padd)))
        b = jnp.pad(b, ((0, 0), (0, padd)))
    return _pw.nearest_dist_pallas(a, b, b_valid, interpret=_interpret())

