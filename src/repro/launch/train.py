"""Sharded training launcher — the production entry point.

    python -m repro.launch.train --arch qwen2-1.5b --steps 20 \
        --mesh 2x2 --devices 4 [--reduced] [--grad-compress]

Builds the mesh, shards params/optimizer/batches with the same MeshPolicy the
dry-run certifies, and EXECUTES jitted train steps (on simulated host devices
here; on a real pod the same flags select the 16x16 or 2x16x16 mesh). This is
the step from 'it compiles' to 'it runs sharded'.
"""

import argparse
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mesh", default="2x2", help="DxM, e.g. 2x2 or 16x16")
    ap.add_argument("--devices", type=int, default=None,
                    help="simulate N host devices (default: product of mesh)")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()

    shape = tuple(int(v) for v in args.mesh.split("x"))
    n_dev = args.devices or 1
    for v in shape:
        n_dev = max(n_dev, 1)
    need = 1
    for v in shape:
        need *= v
    if "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={max(need, args.devices or 0)}"
        )

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.mesh import policy_for
    from repro.models.common import sharding_tree
    from repro.models.registry import get_model
    from repro.train import data as data_mod
    from repro.train import optimizer as opt_mod
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import make_train_step

    axes = ("data", "model") if len(shape) == 2 else ("pod", "data", "model")
    from repro.distrib.sharding import make_mesh

    mesh = make_mesh(shape, axes)
    policy = policy_for(mesh)
    cfg = get_config(args.arch, reduced=args.reduced)
    model = get_model(cfg)
    print(f"mesh {dict(mesh.shape)}; arch {cfg.name} "
          f"({model.param_count()/1e6:.1f}M params, reduced={args.reduced})")

    params = model.init_params(jax.random.PRNGKey(0))
    params = jax.device_put(params, sharding_tree(model.recs, policy))
    opt_state = opt_mod.init(params)

    opt_cfg = AdamWConfig(total_steps=args.steps, warmup_steps=max(args.steps // 10, 1))
    step_fn = jax.jit(
        make_train_step(cfg, opt_cfg, policy, grad_compress=args.grad_compress),
        donate_argnums=(0, 1),
    )
    dcfg = data_mod.DataConfig(
        vocab=cfg.vocab, batch=args.batch, seq=args.seq, seed=0
    )

    import time

    from repro.train import checkpoint as ckpt_mod

    with mesh:
        for step in range(args.steps):
            t0 = time.perf_counter()
            batch = data_mod.lm_batch(dcfg, step)
            if cfg.family in ("vlm", "encdec"):
                batch["frontend"] = data_mod.frontend_batch(
                    dcfg, step, cfg.n_frontend_tokens, cfg.frontend_dim
                )
            batch = jax.device_put(
                batch, jax.tree_util.tree_map(
                    lambda _: policy.sharding_for(_.shape, ("dp",) + (None,) * (_.ndim - 1)),
                    batch,
                )
            )
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if args.log_every and step % args.log_every == 0:
                print(f"step {step:4d} loss {float(metrics['loss']):.4f} "
                      f"({(time.perf_counter()-t0)*1e3:.0f} ms)")

    if args.ckpt_dir:
        ckpt_mod.save(args.ckpt_dir, args.steps, {"params": params, "opt": opt_state})
        print(f"saved checkpoint at step {args.steps} -> {args.ckpt_dir}")
    print(f"done: final loss {float(metrics['loss']):.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
