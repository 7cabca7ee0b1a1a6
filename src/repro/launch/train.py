"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch olmo_1b --smoke \
        --steps 50 --ckpt /tmp/ck_olmo

Builds the host mesh, instantiates the fault-tolerant Trainer (auto-
resumes from --ckpt if a checkpoint exists) and runs. The arch's
published config is the default; --smoke selects its reduced
same-family config for CPU runs.
"""
import argparse
import logging

from repro.configs import ARCH_IDS, get_config
from repro.data.synthetic import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.train.optimizer import OptimizerConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-test scale)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh(model=args.model_parallel)
    trainer = Trainer(
        cfg, mesh,
        opt_cfg=OptimizerConfig(lr=args.lr, warmup_steps=10,
                                total_steps=args.steps),
        tcfg=TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt,
                           ckpt_every=25, log_every=10),
        dcfg=DataConfig(batch=args.batch, seq=args.seq))
    print(trainer.run())


if __name__ == "__main__":
    main()
