"""Datacenter training launcher: the PAOTA round step on a real device mesh.

On TPU this drives the same ``make_paota_train_step`` the dry-run lowers;
on this CPU container it runs a 1x1 mesh demo (use --demo) or validates
lowering for the production mesh (use repro.launch.dryrun for that).

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --demo \
        --rounds 5
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--demo", action="store_true",
                    help="reduced config + tiny shapes on local devices")
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args()

    from repro.configs import get_config, get_reduced
    from repro.data.synthetic import token_stream
    from repro.launch.shapes import SHAPES, InputShape
    from repro.launch.steps import make_paota_train_step, runtime_config
    from repro.models import init_model

    if args.demo:
        cfg = get_reduced(args.arch)
        import dataclasses
        cfg = dataclasses.replace(cfg, remat="block")
        shape = InputShape("demo", seq_len=128, global_batch=8, kind="train")
        from repro.launch.mesh import make_cpu_mesh
        mesh = make_cpu_mesh(data=1, model=1)
        client_axes = ("data",)
    else:
        cfg = runtime_config(get_config(args.arch), SHAPES[args.shape])
        shape = SHAPES[args.shape]
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh()
        client_axes = None

    with jax.set_mesh(mesh):
        step, structs, _ = make_paota_train_step(
            cfg, mesh, shape, lr=args.lr, local_steps=args.local_steps,
            client_axes=client_axes, donate=False)
        k = structs[2].shape[0]
        params = init_model(jax.random.PRNGKey(0), cfg)
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (k,) + x.shape), params)
        mb = structs[1]["tokens"].shape[2] if "tokens" in structs[1] else 1
        stream = token_stream(cfg.vocab_size, k * args.local_steps * mb,
                              shape.seq_len, args.rounds)
        rng = np.random.default_rng(0)
        for r, batch in enumerate(stream):
            toks = batch["tokens"].reshape(k, args.local_steps, mb,
                                           shape.seq_len)
            mask = (rng.random(k) < 0.8).astype(np.float32)
            if mask.sum() == 0:
                mask[0] = 1.0
            powers = np.full(k, 15.0, np.float32)
            t0 = time.time()
            seed = jax.random.key_data(jax.random.PRNGKey(r)).astype(jnp.uint32)
            stacked, metrics = step(stacked, {"tokens": jnp.asarray(toks)},
                                    jnp.asarray(powers), jnp.asarray(mask),
                                    seed)
            print(f"round {r}: loss={float(metrics['loss']):.4f} "
                  f"participants={int(metrics['participants'])} "
                  f"({time.time() - t0:.1f}s)")
        if args.checkpoint:
            from repro.checkpoint import save_checkpoint
            save_checkpoint(args.checkpoint, jax.device_get(stacked),
                            step=args.rounds)
            print(f"checkpoint -> {args.checkpoint}")


if __name__ == "__main__":
    main()
