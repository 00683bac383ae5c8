"""Per-object semantic embeddings: the MobileCLIP role in the paper's
pipeline (Sec. 4.1).

Two interchangeable backends behind one interface:

* OracleEmbedder — deterministic class-conditioned unit vectors + per-view
  noise.  Retrieval quality is controlled and measurable (class cosine
  margins), which is exactly what the paper's system evaluation needs:
  quality differences must come from SYSTEM choices (downsampling, deferral),
  not model noise.
* ClipEmbedder — a real two-tower (object-crop tower + text tower,
  perception/clip.py) trained contrastively in
  examples/train_perception.py.  Used by the end-to-end demo.

Both produce unit-norm [E] embeddings for observations and queries.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from repro.data.scenes import N_CLASSES


@dataclass
class OracleEmbedder:
    embed_dim: int = 512
    noise: float = 0.4
    seed: int = 7

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        basis = rng.normal(size=(N_CLASSES, self.embed_dim))
        basis /= np.linalg.norm(basis, axis=1, keepdims=True)
        self._basis = jnp.asarray(basis, jnp.float32)

    def embed_observation(self, class_ids: jax.Array, key: jax.Array,
                          *, quality: jax.Array | float = 1.0) -> jax.Array:
        """[D] class ids -> [D, E] noisy view embeddings.  ``quality`` in
        (0,1] scales noise up for degraded observations (small/deferred
        objects observed anyway in ablations)."""
        base = self._basis[class_ids]
        # ``noise`` is the total perturbation norm (dim-independent): the
        # per-component sigma scales by 1/sqrt(E)
        sigma = self.noise / jnp.maximum(jnp.asarray(quality), 1e-3)
        sigma = sigma / (self.embed_dim ** 0.5)
        noise = jax.random.normal(key, base.shape) * sigma
        e = base + noise
        return e / jnp.maximum(jnp.linalg.norm(e, axis=-1, keepdims=True),
                               1e-9)

    def embed_text(self, class_id: int) -> jax.Array:
        """Query-side embedding for 'where is my <class>?'."""
        return self._basis[class_id]
