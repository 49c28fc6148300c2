"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 300 --global-batch 64 --seq 1024 --ckpt-dir /tmp/ck

On a real TPU pod each host runs this under the cluster scheduler
(jax.distributed.initialize picks up the pod topology); in this container it
runs on whatever devices exist.  The mesh is the production (data, model)
layout scaled down to the local device count; shardings come from
launch/sharding.py, identical code to the dry-run.
"""
import argparse

import jax

from repro.data.pipeline import DataConfig
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as sh_lib
from repro.models.registry import get_api, get_config, list_archs
from repro.optim.adamw import AdamWConfig
from repro.train import Trainer, TrainerConfig, TrainHParams
from repro.train import step as tsl


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--remat", default="nothing", choices=["nothing", "dots", "none"])
    args = ap.parse_args()

    cfg = get_config(args.arch)
    api = get_api(cfg)
    mesh = mesh_lib.make_local_mesh(args.model_parallel)
    print(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} devices")

    hp = TrainHParams(
        optimizer=AdamWConfig(lr=args.lr), accum=args.accum,
        total_steps=args.steps, warmup_steps=max(args.steps // 20, 1),
        grad_compression=args.grad_compression, remat=args.remat,
    )
    tc = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every, log_every=10)
    data = DataConfig(global_batch=args.global_batch, seq_len=args.seq)

    with jax.sharding.set_mesh(mesh):
        pshapes = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
        psh = sh_lib.params_shardings(pshapes, mesh, cfg.use_tp)
        ssh = sh_lib.state_shardings(
            jax.eval_shape(lambda: tsl.init_state(cfg, api, jax.random.PRNGKey(0), hp)),
            psh, mesh,
        )
        trainer = Trainer(cfg, api, hp, tc, data, shardings=ssh)
        history = trainer.run()
    for rec in history:
        print(f"step {rec['step']:6d}  loss {rec['loss']:.4f}  "
              f"lr {rec['lr']:.2e}  {rec['seconds']*1e3:.0f} ms")


if __name__ == "__main__":
    main()
