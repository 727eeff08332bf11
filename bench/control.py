"""Readings that the limits of ``bench/cells/<cell>.json`` are set from.

    python bench/control.py --workload <cell> --seeds 12 --control-seeds 3 \\
        --seconds 2 [--first-seed N]

On the chip, at the cell's own size and load, in one process (one engine,
one compile): for each of ``--seeds`` seeds the program's timed path runs a
short closed-loop window and its sampled outputs are compared with the
reference and judged against the cell's limits, as a benchmark run judges
them; then the control takes the program's place for ``--control-seeds``
seeds. The control is the plain reference computed in bfloat16 (storage
and arithmetic), the nearest precision below the configuration's float32;
every program seed has to come out correct and every control seed not.
The benchmark's own runs never run this. The last line of standard output
is a JSON object with every reading and verdict, and for each limited
number the largest program reading (``lower``), the smallest control
reading (``upper``) and the limit. Exits 1 where a verdict is not the one
expected.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import check, run  # noqa: E402


def control_engine(ref):
    """The reference in bfloat16, called as the engine is called."""
    import jax
    import jax.numpy as jnp

    probs = jax.jit(lambda params, inputs: jax.nn.softmax(
        ref.logits(params, inputs, dtype=jnp.bfloat16, precision=None)
    ).astype(jnp.float32))
    return lambda inputs, params: {"control": probs(params, inputs)}


def readings(b, eng, seed: int, seconds: float) -> dict:
    import jax

    params, pool = run.make_inputs(b, seed)
    for p in pool:
        jax.block_until_ready(eng(p, params))
    win = run.closed_loop(eng, params, pool, seconds, run.sample_rng(seed),
                          b.cell.traffic["sample"])
    checks, failed, values = run.compare(b, win.kept, pool, params)
    return dict(values, calls=win.calls, failed=failed,
                correct=check.passed(checks))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    run._paths()
    from bench import spec

    b = run.build(spec.cell(args.workload))
    seeds = range(args.first_seed, args.first_seed + args.seeds)
    prog = {s: readings(b, b.engine, s, args.seconds) for s in seeds}
    for s, r in prog.items():
        run._log(f"program seed {s}: {json.dumps(r)}")
    ctl_eng = control_engine(b.ref)
    cseeds = range(args.first_seed + args.seeds,
                   args.first_seed + args.seeds + args.control_seeds)
    ctl = {s: readings(b, ctl_eng, s, args.seconds) for s in cseeds}
    for s, r in ctl.items():
        run._log(f"control seed {s}: {json.dumps(r)}")
    limits = {k: {"lower": max(r[k] for r in prog.values()),
                  "upper": min(r[k] for r in ctl.values()), "limit": v}
              for k, v in b.limits.items()}
    ok = (all(r["correct"] for r in prog.values())
          and not any(r["correct"] for r in ctl.values()))
    for k, v in limits.items():
        run._log(f"{k}: lower {v['lower']!r} limit {v['limit']!r} upper "
                 f"{v['upper']!r} ({v['upper'] / v['lower']:.2f}x)")
    run._log("verdicts as expected" if ok else "FAIL: a verdict is not the "
             "one expected")
    print(json.dumps({"workload": args.workload, "program": prog,
                      "control": ctl, "limits": limits, "as_expected": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
