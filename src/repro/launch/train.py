"""Production training launcher.

    python -m repro.launch.train --arch qwen2-0.5b --steps 200 \
        --ckpt-dir /tmp/ckpt [--devices 8 --mesh 2x4]

On a real TPU fleet this binary runs once per host (jax.distributed
initializes from the TPU environment); in this container ``--devices``
fakes host devices for an end-to-end multi-process-free rehearsal.
Auto-resumes from the newest valid checkpoint; survives preemption.
"""

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--devices", type=int, default=0,
                    help="fake host devices (0 = real devices)")
    ap.add_argument("--mesh", default="", help="e.g. 2x4 (data x model)")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced config (CPU-trainable)")
    args = ap.parse_args()

    if args.devices:
        from repro.xla_env import force_host_device_count
        force_host_device_count(args.devices)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import get_config
    from repro.configs.reduced import reduced_model_cfg
    from repro.data.pipeline import ShardedBatchIterator
    from repro.data.synthetic import lm_dataset
    from repro.models import transformer as T
    from repro.train.trainer import TrainConfig, Trainer

    spec = get_config(args.arch)
    if spec.family != "lm":
        print("this launcher trains LM archs; see examples/ for others")
        sys.exit(2)
    cfg = reduced_model_cfg(args.arch) if args.reduced else spec.model_cfg

    mesh = None
    param_specs = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        from repro.utils import compat
        mesh = jax.make_mesh(shape, ("data", "model")[: len(shape)],
                                axis_types=compat.auto_axis_types(len(shape)))
        param_specs = T.param_specs(cfg)

    toks = lm_dataset(0, args.batch * args.seq * 64, cfg.vocab,
                      args.seq + 1)
    data = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    tc = TrainConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                     total_steps=args.steps, ckpt_every=50)
    tr = Trainer(lambda p, b: T.lm_loss(p, b, cfg),
                 lambda k: T.init_params(k, cfg), tc,
                 ckpt_dir=args.ckpt_dir, mesh=mesh,
                 param_specs=param_specs)
    it = ShardedBatchIterator(data, args.batch, mesh=mesh)
    state, hist = tr.fit(jax.random.PRNGKey(0), it, args.steps)
    print(f"done: step {int(state.step)} loss {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
