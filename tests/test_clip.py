"""Mini-CLIP two-tower embedder: contrastive loss decreases and retrieval
beats chance after a short budget (full training in
examples/train_perception.py reaches ~86% top-1 over 20 classes), and
its initial parameters are pinned bit for bit."""
import hashlib

import numpy as np
import jax
import jax.numpy as jnp

from repro.data.scenes import make_scene, N_CLASSES
from repro.optim import adamw
from repro.perception import clip as clip_mod


def test_clip_learns():
    ccfg = clip_mod.ClipConfig(width=64, depth=2, embed_dim=32)
    params = clip_mod.init_clip_params(ccfg, jax.random.key(0))
    ocfg = adamw.AdamWConfig(lr=2e-3, total_steps=80, warmup_steps=10,
                             weight_decay=0.01)
    opt = adamw.init_opt_state(params, ocfg)
    scene = make_scene(n_objects=40, seed=4)
    classes = {o.oid: o.class_id for o in scene.objects}
    it = clip_mod.pair_batches(scene, classes, batch=12, h=80, w=100,
                               n_frames=30)

    @jax.jit
    def step(params, opt, batch):
        (loss, _), g = jax.value_and_grad(
            lambda p: clip_mod.clip_loss(p, batch, ccfg),
            has_aux=True)(params)
        params, opt, _ = adamw.adamw_update(g, opt, params, ocfg)
        return params, opt, loss

    losses = []
    for _ in range(80):
        b = next(it)
        b.pop("class_ids")
        params, opt, loss = step(params, opt, b)
        losses.append(float(loss))
    assert np.mean(losses[-10:]) < 0.7 * np.mean(losses[:10])

    # retrieval beats chance
    all_toks = jnp.asarray(np.stack([clip_mod.class_tokens(c)
                                     for c in range(N_CLASSES)]))
    te = clip_mod.encode_text(params, all_toks, ccfg)
    b = next(it)
    oe = clip_mod.encode_object(params, b["crops"], b["stats"], ccfg)
    pred = np.asarray(jnp.argmax(oe @ te.T, axis=1))
    acc = float((pred == b["class_ids"]).mean())
    assert acc > 3.0 / N_CLASSES, f"retrieval acc {acc:.2f}"


# sha256 prefixes of ``init_clip_params(ClipConfig(), jax.random.key(0))``,
# recorded when the init moved into perception/clip.py from the shared
# spec-driven initializer it used before
_INIT_DIGESTS = {
    "logit_scale": "e4ba018bb7df07be",
    "obj_b0_bias": "076a27c79e5ace2a",
    "obj_b1_bias": "076a27c79e5ace2a",
    "obj_in": "e36e6d8fba28b11e",
    "obj_out": "79255429e0e29814",
    "obj_w0": "99bea7eab91d413c",
    "obj_w1": "97a5c7cbc25f13e2",
    "txt_b0_bias": "076a27c79e5ace2a",
    "txt_b1_bias": "076a27c79e5ace2a",
    "txt_embed": "0d49dc7f251e1c7a",
    "txt_out": "4e384819d0ab3a4f",
    "txt_w0": "6d9181c61dacd844",
    "txt_w1": "81979efec7175f45",
}


def test_init_params_bit_identical():
    params = clip_mod.init_clip_params(clip_mod.ClipConfig(),
                                       jax.random.key(0))
    got = {}
    for name, leaf in params.items():
        a = np.asarray(leaf)
        assert a.dtype == np.float32, name
        got[name] = hashlib.sha256(a.tobytes()).hexdigest()[:16]
    assert got == _INIT_DIGESTS
