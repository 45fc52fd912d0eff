"""Train a small LM end-to-end with the full fault-tolerance stack.

    PYTHONPATH=src python examples/train_lm.py --arch qwen2-1.5b --steps 200

Uses the REDUCED config of the chosen architecture scaled up to ~10M params
(CPU-friendly; pass --full-width for the real config if you have a TPU pod),
trains a few hundred steps with AdamW + cosine schedule + checkpointing,
simulates a preemption at 60% and resumes from the last checkpoint —
the restart path a 1000-node run exercises weekly.
"""

import argparse
import tempfile


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=256, help="width override")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--full-width", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--preempt", action="store_true",
                    help="simulate preemption at 60%% and auto-resume")
    args = ap.parse_args()

    from repro.common import use_compile_cache
    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.train.loop import train

    use_compile_cache()
    cfg = get_config(args.arch, reduced=not args.full_width)
    if not args.full_width:
        cfg = cfg.replace(
            n_layers=args.layers,
            d_model=args.d_model,
            n_heads=max(cfg.n_heads, 4),
            head_dim=args.d_model // max(cfg.n_heads, 4),
            d_ff=args.d_model * 3,
            vocab=8192,
        )
    n_params = get_model(cfg).param_count()
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"({cfg.n_layers}L d={cfg.d_model})")

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    print(f"checkpoints -> {ckpt_dir}")

    if args.preempt:
        try:
            train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                  ckpt_dir=ckpt_dir, ckpt_every=25, log_every=20,
                  preempt_at=int(args.steps * 0.6))
        except KeyboardInterrupt as e:
            print(f"!! {e} — restarting from latest checkpoint")

    res = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                ckpt_dir=ckpt_dir, ckpt_every=25, log_every=20)
    if res.resumed_from is not None:
        print(f"resumed from step {res.resumed_from}")
    print(f"final loss {res.losses[-1]:.4f} (first {res.losses[0]:.4f}); "
          f"stragglers detected: {len(res.straggler_events)}")


if __name__ == "__main__":
    main()
