"""Paper §4.5: Hyena as a general operator — image classification with the
attention layers of a ViT replaced by the (unchanged) Hyena operator.
Offline container: synthetic CIFAR-shaped data (two separable classes).

    PYTHONPATH=src python examples/hyena_vit.py [--steps 40]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import compile_cache
from repro.common.param import split_params
from repro.models.vit import ViTConfig, init_vit, vit_loss
from repro.train import optim as O


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = ViTConfig(image_size=16, patch_size=4, d_model=48, n_layers=2,
                    d_ff=96, n_classes=2)
    params, _ = split_params(init_vit(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(0)
    imgs = rng.normal(size=(64, 16, 16, 3)).astype(np.float32)
    labels = (imgs[:, :8].mean(axis=(1, 2, 3)) > 0).astype(np.int32)
    imgs[labels == 1, :8] += 0.7  # class-1 brightens the top half
    imgs_j, labels_j = jnp.asarray(imgs), jnp.asarray(labels)

    ocfg = O.AdamWConfig(lr=3e-3, warmup_steps=0, schedule="constant",
                         weight_decay=0.0)
    opt = O.init_adamw(params)

    @jax.jit
    def step(params, opt):
        (loss, m), g = jax.value_and_grad(vit_loss, has_aux=True)(
            params, cfg, imgs_j, labels_j
        )
        params, opt, _ = O.adamw_update(ocfg, g, opt, params)
        return params, opt, loss, m["acc"]

    for i in range(args.steps):
        params, opt, loss, acc = step(params, opt)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:3d} loss {float(loss):.3f} acc {float(acc):.2f}")
    assert float(acc) > 0.8, "Hyena-ViT failed to fit the synthetic task"
    print("OK")


if __name__ == "__main__":
    main()
