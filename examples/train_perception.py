"""Train the mini-CLIP two-tower embedder on synthetic scene crops and
report open-vocabulary retrieval accuracy (the learned alternative to the
OracleEmbedder in SemanticXR's perception stack).

    PYTHONPATH=src python examples/train_perception.py [--steps 300]
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro.data.scenes import make_scene
from repro.perception import clip as clip_mod
from repro.optim import adamw
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    args = ap.parse_args()

    ccfg = clip_mod.ClipConfig()
    params = clip_mod.init_clip_params(ccfg, jax.random.key(0))
    ocfg = adamw.AdamWConfig(lr=1e-3, total_steps=args.steps,
                             warmup_steps=20, weight_decay=0.01)
    opt = adamw.init_opt_state(params, ocfg)

    scene = make_scene(n_objects=60, seed=5)
    classes = {o.oid: o.class_id for o in scene.objects}
    it = clip_mod.pair_batches(scene, classes, batch=args.batch)

    @jax.jit
    def step(params, opt, batch):
        (loss, m), g = jax.value_and_grad(
            lambda p: clip_mod.clip_loss(p, batch, ccfg), has_aux=True)(params)
        params, opt, om = adamw.adamw_update(g, opt, params, ocfg)
        return params, opt, loss

    for i in range(1, args.steps + 1):
        b = next(it)
        b.pop("class_ids")
        params, opt, loss = step(params, opt, b)
        if i % 50 == 0:
            print(f"step {i:4d} contrastive loss {float(loss):.4f}")

    # retrieval eval: held-out crops vs all class captions
    eval_it = clip_mod.pair_batches(scene, classes, batch=16, seed=99)
    hits = tot = 0
    from repro.data.scenes import N_CLASSES
    all_toks = jnp.asarray(np.stack([clip_mod.class_tokens(c)
                                     for c in range(N_CLASSES)]))
    te = clip_mod.encode_text(params, all_toks, ccfg)
    for _ in range(6):
        b = next(eval_it)
        oe = clip_mod.encode_object(params, b["crops"], b["stats"], ccfg)
        pred = np.asarray(jnp.argmax(oe @ te.T, axis=1))
        hits += int((pred == b["class_ids"]).sum())
        tot += len(pred)
    print(f"open-vocab retrieval top-1: {hits}/{tot} = {hits/tot:.1%} "
          f"(chance {1/N_CLASSES:.1%})")


if __name__ == "__main__":
    main()
