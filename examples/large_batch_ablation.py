"""The paper's central claim, reproduced at small scale: plain SGD-momentum
degrades as the batch (and linearly-scaled lr) grows; LARS + warm-up +
label smoothing holds accuracy. Prints a mini Table-I.

  PYTHONPATH=src python examples/large_batch_ablation.py [--steps 60]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.shapes import InputShape
from repro.core import lars
from repro.core.schedule import ScheduleConfig, linear_scaled_lr, \
    make_schedule
from repro.data.synthetic import make_batch_fn, prototype_imagenet
from repro.models.registry import build_model
from repro.train.state import init_state
from repro.train.step import make_eval_step, make_train_step
from repro.launch.mesh import make_mesh


def run(cfg, model, mesh, *, batch, steps, opt, warmup, smoothing):
    lr = linear_scaled_lr(16.0, batch) / 4   # toy-task tuned
    sched = make_schedule(ScheduleConfig(
        base_lr=lr, warmup_steps=int(steps * 0.15) if warmup else 0,
        total_steps=steps, decay="poly2"))
    step = jax.jit(make_train_step(
        model, lars.OptConfig(kind=opt), sched, smoothing=smoothing,
        mesh=mesh))
    bf = make_batch_fn(cfg, InputShape("t", "train", 0, batch), mesh=mesh)
    s = init_state(model, 0, mesh)
    for _ in range(steps):
        s, m = step(s, bf(s.step))
    ev = jax.jit(make_eval_step(model, mesh=mesh))
    accs = [float(ev(s.params, prototype_imagenet(
        cfg, batch=64, step=jnp.int32(10_000 + k)), s.bn_state)["acc"])
        for k in range(4)]
    return float(np.mean(accs)), float(m["loss"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()
    cfg = get_config("resnet50").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    model = build_model(cfg)

    print(f"{'batch':>6} {'recipe':>22} {'eval_acc':>9} {'loss':>7}")
    for batch in (16, 64, 256):
        for name, kw in [
            ("sgdm (no warmup/smooth)", dict(opt="sgdm", warmup=False,
                                             smoothing=0.0)),
            ("LARS+warmup+smoothing", dict(opt="lars", warmup=True,
                                           smoothing=0.1)),
        ]:
            acc, loss = run(cfg, model, mesh, batch=batch,
                            steps=args.steps, **kw)
            print(f"{batch:>6} {name:>22} {acc:>9.3f} {loss:>7.3f}",
                  flush=True)


if __name__ == "__main__":
    main()
