"""Mini-CLIP: a trainable two-tower embedder over the synthetic world.

The paper's MobileCLIP role, rebuilt small: an object tower over rendered
depth crops (the observation the mapping server actually has per detection)
and a text tower over caption tokens, trained with a symmetric InfoNCE loss.
examples/train_perception.py trains it and reports retrieval accuracy; the
OracleEmbedder remains the controlled backend for system benchmarks.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from repro.data.scenes import CLASS_NAMES, N_CLASSES
from repro.data.tokens import VOCAB, VOCAB_SIZE

CROP = 16  # depth-crop resolution fed to the object tower


@dataclass(frozen=True)
class ClipConfig:
    embed_dim: int = 64
    width: int = 128
    depth: int = 2
    temperature_init: float = 0.07


def clip_param_specs(ccfg: ClipConfig) -> dict:
    w, e = ccfg.width, ccfg.embed_dim
    specs: dict = {
        "obj_in": jax.ShapeDtypeStruct((CROP * CROP + 4, w), jnp.float32),
        "txt_embed": jax.ShapeDtypeStruct((VOCAB_SIZE, w), jnp.float32),
        "logit_scale": jax.ShapeDtypeStruct((), jnp.float32),
    }
    for t in ("obj", "txt"):
        for i in range(ccfg.depth):
            specs[f"{t}_w{i}"] = jax.ShapeDtypeStruct((w, w), jnp.float32)
            specs[f"{t}_b{i}_bias"] = jax.ShapeDtypeStruct((w,), jnp.float32)
        specs[f"{t}_out"] = jax.ShapeDtypeStruct((w, e), jnp.float32)
    return specs


def _leaf_init(key, name: str, shape, dtype):
    """Biases and the logit scale start at zero; matrices truncated-normal
    over sqrt(fan_in)."""
    if name.endswith("bias") or name == "logit_scale":
        return jnp.zeros(shape, dtype)
    std = 1.0 / math.sqrt(max(shape[-2], 1))
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std
    return w.astype(dtype)


def init_clip_params(ccfg: ClipConfig, key: jax.Array):
    specs = clip_param_specs(ccfg)
    names = sorted(specs)        # the flatten order of a dict pytree
    keys = jax.random.split(key, len(names))
    p = {n: _leaf_init(k, n, specs[n].shape, specs[n].dtype)
         for n, k in zip(names, keys)}
    p["logit_scale"] = jnp.log(1.0 / ccfg.temperature_init)
    return p


def _mlp(params, prefix, x, depth):
    for i in range(depth):
        x = jax.nn.gelu(x @ params[f"{prefix}_w{i}"] +
                        params[f"{prefix}_b{i}_bias"])
    x = x @ params[f"{prefix}_out"]
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def encode_object(params, crops, stats, ccfg: ClipConfig):
    """crops: [B, CROP, CROP] normalized depth; stats: [B, 4] (bbox h/w in
    pixels /100, mean depth, valid fraction)."""
    x = jnp.concatenate([crops.reshape(crops.shape[0], -1), stats], axis=-1)
    return _mlp(params, "obj", x @ params["obj_in"], ccfg.depth)


def encode_text(params, tokens, ccfg: ClipConfig):
    """tokens: [B, L] int32 (0-padded) -> mean-pooled tower."""
    emb = jnp.take(params["txt_embed"], tokens, axis=0)
    mask = (tokens > 0)[..., None]
    x = jnp.sum(emb * mask, axis=1) / jnp.maximum(mask.sum(axis=1), 1)
    return _mlp(params, "txt", x, ccfg.depth)


def clip_loss(params, batch, ccfg: ClipConfig):
    oe = encode_object(params, batch["crops"], batch["stats"], ccfg)
    te = encode_text(params, batch["tokens"], ccfg)
    scale = jnp.exp(params["logit_scale"])
    logits = scale * oe @ te.T
    labels = jnp.arange(logits.shape[0])
    li = -jnp.mean(jax.nn.log_softmax(logits, axis=1)[labels, labels])
    lt = -jnp.mean(jax.nn.log_softmax(logits, axis=0)[labels, labels])
    return 0.5 * (li + lt), {"scale": scale}


# ---------------------------------------------------------------------------
# data: (depth crop, class caption) pairs from rendered frames
# ---------------------------------------------------------------------------

def class_tokens(cid: int, max_len: int = 4) -> np.ndarray:
    words = f"find the {CLASS_NAMES[cid]}".split()
    ids = [VOCAB.get(w, 0) for w in words][:max_len]
    return np.asarray(ids + [0] * (max_len - len(ids)), np.int32)


def crop_from_frame(depth: np.ndarray, mask: np.ndarray):
    ys, xs = np.nonzero(mask)
    y0, y1, x0, x1 = ys.min(), ys.max() + 1, xs.min(), xs.max() + 1
    d = np.where(mask, depth, 0.0)[y0:y1, x0:x1]
    # nearest-resize to CROP x CROP
    iy = np.linspace(0, d.shape[0] - 1, CROP).astype(int)
    ix = np.linspace(0, d.shape[1] - 1, CROP).astype(int)
    crop = d[np.ix_(iy, ix)]
    mu = crop[crop > 0].mean() if (crop > 0).any() else 1.0
    stats = np.asarray([(y1 - y0) / 100.0, (x1 - x0) / 100.0, mu / 5.0,
                        float((crop > 0).mean())], np.float32)
    return (crop / max(mu, 1e-3)).astype(np.float32), stats


def pair_batches(scene, classes, *, batch: int, seed: int = 0, h=120, w=160,
                 n_frames: int = 60):
    """Yield contrastive batches with one object per distinct class."""
    from repro.data.scenes import scene_stream
    rng = np.random.default_rng(seed)
    samples: dict[int, list] = {}
    for fr in scene_stream(scene, n_frames=n_frames, keyframe_interval=3,
                           h=h, w=w):
        for oid in fr.visible_ids:
            cid = classes[int(oid)]
            crop, stats = crop_from_frame(fr.depth, fr.inst == oid)
            samples.setdefault(cid, []).append((crop, stats))
    cids = [c for c, v in samples.items() if len(v) >= 2]
    while True:
        picks = rng.choice(cids, size=min(batch, len(cids)), replace=False)
        crops, stats, toks = [], [], []
        for c in picks:
            i = rng.integers(len(samples[c]))
            crops.append(samples[c][i][0])
            stats.append(samples[c][i][1])
            toks.append(class_tokens(int(c)))
        yield {"crops": jnp.asarray(np.stack(crops)),
               "stats": jnp.asarray(np.stack(stats)),
               "tokens": jnp.asarray(np.stack(toks)),
               "class_ids": np.asarray(picks)}
