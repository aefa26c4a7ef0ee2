"""Training launcher: any assigned arch on any mesh.

On real hardware this is the per-host entry point (jax.distributed
initialization + the production mesh); in this container it runs reduced
configs on the host mesh with the same code path.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
        --steps 50 --quant w3a8 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro import checkpoint as ckpt_lib
from repro.configs import TrainConfig, get_config, reduced
from repro.core.precision import FLOAT, W3A8
from repro.data.pipeline import HostLoader
from repro.data.synthetic import lm_batch
from repro.distributed.context import sharding_rules
from repro.distributed import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import get_model
from repro.training.loop import Trainer, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--quant", default="w3a8", choices=["float", "w3a8"])
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config (same family structure)")
    ap.add_argument("--mesh", default="host", choices=["host", "single", "multi"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    policy = W3A8 if args.quant == "w3a8" else FLOAT
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(args.steps // 10, 1))
    mesh = (make_host_mesh() if args.mesh == "host" else
            make_production_mesh(multi_pod=args.mesh == "multi"))

    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    step_fn, init_state = make_train_step(cfg, tcfg, policy)
    state = init_state(params)

    start_step = 0
    ck = None
    if args.ckpt_dir:
        ck = ckpt_lib.Checkpointer(args.ckpt_dir, keep=3)
        if args.resume and ckpt_lib.latest_step(args.ckpt_dir) is not None:
            # elastic restore: re-shard onto the current mesh
            specs = shd.state_specs(cfg, state, mesh)
            shardings = shd.tree_shardings(mesh, specs)
            tree, meta = ckpt_lib.restore(args.ckpt_dir, shardings=shardings)
            state = jax.tree_util.tree_map(jnp.asarray, tree)
            start_step = meta["step"]
            print(f"resumed from step {start_step}")

    rules = shd.activation_rules(
        cfg, type("S", (), {"global_batch": args.batch})(), mesh) \
        if args.mesh != "host" else {}
    step_fn = jax.jit(step_fn, donate_argnums=0)
    loader = HostLoader(lambda seed, s: lm_batch(
        jnp.asarray(seed), jnp.asarray(s), batch=args.batch, seq=args.seq,
        vocab=cfg.vocab_size), start_step=start_step)

    with mesh:
        with sharding_rules(rules):
            trainer = Trainer(step_fn, state, checkpointer=ck,
                              ckpt_every=max(args.steps // 5, 10))
            trainer.run(loader, args.steps,
                        on_log=lambda r: print(
                            f"step {r['step']:5d} loss {r['loss']:.4f} "
                            f"lr {r['lr']:.2e} {r['dt'] * 1e3:.0f}ms"))
    print(f"done; stragglers {trainer.monitor.slow_steps}/"
          f"{trainer.monitor.total_steps}")


if __name__ == "__main__":
    main()
