"""Distributed training entrypoint.

Builds the mesh from the real device set (any shape that fits — the
production 16x16 needs real hardware; on one host it degrades to a 1x1
mesh), pins param/opt shardings from repro.dist rules, and runs the
fault-tolerant training loop on synthetic char-LM data.

  python -m repro.launch.train --arch gemma-2b --reduced --steps 50
  python -m repro.launch.train --arch qwen3-32b --mesh 16x16 \
      --steps 1000 --ckpt /ckpts/qwen3   # on a real pod

``--reduced`` uses the smoke-scale config (CPU-feasible); otherwise the
full assigned config is instantiated (requires the memory of a real pod).
"""
from __future__ import annotations

import argparse
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.data import CharLMTask, lm_batch_iterator, sharded_batches
from repro.dist import (
    ShardingPolicy, activation_rules, batch_specs, param_specs, use_rules,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.shardspec import to_named
from repro.models import forward_loss, init_params
from repro.optim import linear_warmup_cosine
from repro.train import TrainConfig, train


def make_mesh(spec: str | None) -> Mesh:
    devs = jax.devices()
    if spec:
        dims = tuple(int(x) for x in spec.split("x"))
    else:
        dims = (len(devs), 1)
    need = math.prod(dims)
    if need > len(devs):
        raise SystemExit(f"mesh {dims} needs {need} devices, "
                         f"have {len(devs)}")
    axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return Mesh(np.asarray(devs[:need]).reshape(dims), axes)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default=None, help="e.g. 16x16 or 2x16x16")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.reduced and cfg.n_img_tokens:
        args.seq = max(args.seq, cfg.n_img_tokens + 32)
    mesh = make_mesh(args.mesh)
    policy = ShardingPolicy(fsdp=cfg.param_count() > 3e10)
    rules = activation_rules(cfg, mesh, policy, global_batch=args.batch)
    print(f"arch={cfg.name} params={cfg.param_count():,} "
          f"mesh={dict(mesh.shape)}")

    with use_rules(rules):
        params = init_params(jax.random.PRNGKey(0), cfg)
        pspecs = param_specs(cfg, params, mesh, policy)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, pspecs, is_leaf=lambda x: isinstance(x, P))

        task = CharLMTask(vocab=min(cfg.vocab, 256), seed=0)
        bspecs = batch_specs(cfg, "train", mesh, global_batch=args.batch)
        batches = sharded_batches(
            lm_batch_iterator(task, args.batch, args.seq), mesh, bspecs)

        tcfg = TrainConfig(lr=args.lr, steps=args.steps, log_every=10,
                           ckpt_dir=args.ckpt, ckpt_every=50)
        sched = linear_warmup_cosine(args.lr, warmup=10, steps=args.steps)

        def loss_fn(p, b):
            b = dict(b)
            if cfg.n_img_tokens:
                b["img_embeds"] = jnp.zeros(
                    (b["tokens"].shape[0], cfg.n_img_tokens, 1024),
                    jnp.dtype(cfg.dtype))
            if cfg.n_codebooks:
                b["tokens"] = jnp.repeat(
                    b["tokens"][..., None], cfg.n_codebooks, -1)
                b["labels"] = jnp.repeat(
                    b["labels"][..., None], cfg.n_codebooks, -1)
            return forward_loss(p, b, cfg)

        params, history = train(loss_fn, params, batches, tcfg,
                                lr_schedule=sched)
    if history:
        print(f"final loss {history[-1]['loss']:.4f} "
              f"({len(history)} steps)")


if __name__ == "__main__":
    main()
