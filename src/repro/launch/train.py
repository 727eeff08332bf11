"""Training driver: jit'd train_step factory + fault-tolerant loop.

``make_train_step`` builds the donated, fully-sharded step used both by the
real trainer below and by the multi-pod dry-run (launch/dryrun.py lowers the
exact same function against the production mesh).
"""
from __future__ import annotations

import argparse
import functools
import json
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, Prefetcher, batches
from repro.models import api
from repro.optim import adamw
from repro.runtime.fault_tolerance import FaultTolerantLoop
from . import sharding as shlib
from .mesh import make_debug_mesh


def make_train_step(model, opt_cfg: adamw.OptConfig, mesh):
    shard_fn = shlib.make_shard_fn(model.cfg, mesh)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss_fn(p, batch, shard_fn))(params)
        params, opt_state, stats = adamw.update(opt_cfg, params, grads,
                                                opt_state)
        return params, opt_state, {"loss": loss, **stats}

    return train_step


def shardings_for(model, mesh, batch_spec, opt_cfg):
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_sh = shlib.param_shardings(model.cfg, mesh, params_shape)
    opt_shape = jax.eval_shape(
        functools.partial(adamw.init_state, opt_cfg), params_shape)
    o_sh = shlib.opt_shardings(model.cfg, mesh, opt_shape, p_sh)
    b_sh = shlib.batch_shardings(model.cfg, mesh, batch_spec)
    return params_shape, p_sh, o_sh, b_sh


def jit_train_step(model, opt_cfg, mesh, batch_spec, donate=True):
    step = make_train_step(model, opt_cfg, mesh)
    _, p_sh, o_sh, b_sh = shardings_for(model, mesh, batch_spec, opt_cfg)
    rep = NamedSharding(mesh, P())
    stats_sh = {"loss": rep, "lr": rep, "grad_norm": rep}
    return jax.jit(
        step,
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, stats_sh),
        donate_argnums=(0, 1) if donate else (),
    ), (p_sh, o_sh, b_sh)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def train(arch: str, *, steps: int = 100, smoke: bool = True,
          batch: int = 8, seq: int = 128, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 50, log_every: int = 10,
          peak_lr: float = 3e-4, seed: int = 0,
          fault_hook=None) -> Dict[str, Any]:
    cfg = configs.get(arch, smoke=smoke)
    model = api.build(cfg)
    mesh = make_debug_mesh(len(jax.devices()), 1)
    opt_cfg = adamw.OptConfig(peak_lr=peak_lr, warmup_steps=max(steps // 10, 5),
                              total_steps=steps)
    dc = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                    seed=seed)
    batch_spec = jax.eval_shape(
        lambda: configs.concrete_batch(cfg, batch, seq))
    with mesh:
        step_jit, (p_sh, o_sh, b_sh) = jit_train_step(
            model, opt_cfg, mesh, batch_spec)
        params = model.init(jax.random.PRNGKey(seed))
        opt_state = adamw.init_state(opt_cfg, params)

        data = Prefetcher(batches(dc), depth=2)
        losses = []
        manager = CheckpointManager(ckpt_dir, keep_n=2) if ckpt_dir else None

        def one_step(state, i):
            params, opt_state = state
            raw = next(data)
            b = configs.concrete_batch(cfg, batch, seq,
                                       key=jax.random.PRNGKey(i))
            if cfg.family not in ("encdec",):
                b["tokens"] = jnp.asarray(raw["tokens"])
                b["labels"] = jnp.asarray(raw["labels"])
            params, opt_state, stats = step_jit(params, opt_state, b)
            losses.append(float(stats["loss"]))
            if i % log_every == 0:
                print(f"step {i:5d}  loss {losses[-1]:.4f}  "
                      f"lr {float(stats['lr']):.2e}  "
                      f"gnorm {float(stats['grad_norm']):.3f}")
            return (params, opt_state)

        if manager is not None:
            loop = FaultTolerantLoop(manager, ckpt_every=ckpt_every,
                                     fault_hook=fault_hook)
            report = loop.run((params, opt_state),
                              lambda st, i: one_step(st, i), steps)
        else:
            st = (params, opt_state)
            for i in range(steps):
                st = one_step(st, i)
            report = {"final_step": steps, "restarts": 0}
        data.close()
    report["losses"] = losses
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=list(configs.ARCHS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full config (default: reduced smoke config)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--inject-fault", default=None, metavar="STEP[,STEP...]",
                    help="raise an injected fault at these step numbers "
                         "(repro.runtime.chaos); the fault-tolerant loop "
                         "must recover via checkpoints, so --ckpt-dir is "
                         "required")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    fault_hook = None
    if args.inject_fault:
        if not args.ckpt_dir:
            ap.error("--inject-fault requires --ckpt-dir (recovery "
                     "restores from checkpoints)")
        from repro.runtime.chaos import ChaosInjector, ChaosPlan
        steps = [int(s) for s in args.inject_fault.split(",") if s.strip()]
        fault_hook = ChaosInjector(ChaosPlan.for_steps(steps)) \
            .train_fault_hook()
    report = train(args.arch, steps=args.steps, smoke=not args.full,
                   batch=args.batch, seq=args.seq, ckpt_dir=args.ckpt_dir,
                   fault_hook=fault_hook)
    print(json.dumps({k: v for k, v in report.items() if k != "losses"}))
    l = report["losses"]
    print(f"loss: first={l[0]:.4f} last={l[-1]:.4f}")


if __name__ == "__main__":
    main()
