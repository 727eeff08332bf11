"""Run one cell of ``BENCHMARK.json`` on the chip this process holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's chain (``repro.models.cnn``), plans it with
``compile_chain(backend="auto")``, makes the weights and a pool of input
batches on the device from the seed, and calls the engine once per pool
entry, which compiles (or loads from the persistent cache) the one program
the window drives. The window is a closed loop over
``CompiledChain.__call__``: each call is waited for (``block_until_ready``)
before the next, as a synchronous inference API would be. With
``--trace 1`` a short traced window follows the measured one and the
per-layer metrics are read; without it the end-to-end ones are printed.

After the window, a sample of the calls' outputs, drawn from the seed, is
compared with the configuration's plain reference (``bench/check.py``).
The last line of standard output is the result as one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the last key of that object.

Exits 2, with no result line, when JAX finds no TPU, fewer chips than the
cell asks for, Pallas in interpret mode, or a device kind that
``bench/peaks.json`` does not list.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# seconds of the traced window that follows the measured one (--trace 1)
TRACE_SECONDS = 1.0


class NoChip(RuntimeError):
    pass


def _log(msg: str):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _paths():
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def device_peaks(chips: int, require_chip: bool):
    """The first device and its row of ``bench/peaks.json``; raises
    :class:`NoChip` where the cell cannot be measured here."""
    import jax

    from repro.kernels.common import use_interpret

    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    dev = jax.devices()[0]
    if not require_chip:
        return dev, table.get(dev.device_kind)
    if jax.default_backend() != "tpu":
        raise NoChip(f"JAX backend is {jax.default_backend()!r}, not 'tpu'")
    if len(jax.devices()) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(jax.devices())}")
    if use_interpret():
        raise NoChip("Pallas would run in interpret mode")
    if dev.device_kind not in table:
        raise NoChip(f"device kind {dev.device_kind!r} is not in "
                     f"bench/peaks.json")
    return dev, table[dev.device_kind]


def _check_against_reference(chain, ref, batch: int):
    """The program's chain must take exactly the inputs and parameters the
    reference defines."""
    from bench.spec import SpecError

    want = {n: tuple(s) for n, (s, _r, _f) in ref.param_specs().items()}
    have = {n: tuple(i.shape) for n, i in chain.params.items()}
    if want != have:
        diff = sorted(set(want.items()) ^ set(have.items()))[:6]
        raise SpecError(f"chain parameters differ from the reference's: "
                        f"{diff}")
    want_in = {n: tuple(s) for n, s in ref.input_specs(batch).items()}
    have_in = {n: tuple(i.shape) for n, i in chain.inputs.items()}
    if want_in != have_in:
        raise SpecError(f"chain inputs {have_in} differ from the "
                        f"reference's {want_in}")


def closed_loop(eng, params, pool, seconds: float, rng, keep: int,
                annotate: bool = False):
    """Back-to-back calls for ``seconds``; each call's latency runs from
    the call to the end of ``block_until_ready`` on its output. Keeps, as
    ``[(call, pool index, output)]``, the outputs of the last pass over the
    pool (an answer for every distinct input) and ``keep`` more drawn from
    all the calls by reservoir sampling."""
    import jax

    if annotate:
        from jax.profiler import TraceAnnotation
    lat, host, kept = [], [], []
    last = [None] * len(pool)
    n = 0
    t0 = time.perf_counter()
    t_end = t0 + seconds
    now = t0
    while now < t_end:
        j = n % len(pool)
        ts = time.perf_counter()
        if annotate:
            with TraceAnnotation("bench.call"):
                out = eng(pool[j], params)
            th = time.perf_counter()
            with TraceAnnotation("bench.wait"):
                jax.block_until_ready(out)
        else:
            out = eng(pool[j], params)
            th = time.perf_counter()
            jax.block_until_ready(out)
        now = time.perf_counter()
        lat.append(now - ts)
        host.append(th - ts)
        if keep:
            last[j] = (n, j, out)
            if len(kept) < keep:
                kept.append((n, j, out))
            else:
                r = int(rng.integers(0, n + 1))
                if r < keep:
                    kept[r] = (n, j, out)
        n += 1
    if keep:
        seen = {c for c, _j, _o in kept}
        kept += [r for r in last if r is not None and r[0] not in seen]
    return SimpleNamespace(calls=n, seconds=now - t0, latency_s=lat,
                           host_call_s=host, kept=kept)


def _memory_peak(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _traced_window(eng, params, pool, rng):
    import jax

    from bench import devtrace

    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        # the benchmark's own host spans and the runtime's, no Python
        # function tracing (which would slow the host it is measuring)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            w = closed_loop(eng, params, pool, TRACE_SECONDS, rng, 0,
                            annotate=True)
        finally:
            jax.profiler.stop_trace()
        summary = devtrace.reduce_file(devtrace.find_xplane(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    summary.calls = w.calls
    return summary


def compare(b, kept, pool, params):
    """``(checks, failed outputs, readings)`` of the kept outputs against
    the reference, run once per distinct input batch at the cell's own
    batch size (XLA's default-precision convs round differently at other
    batch sizes)."""
    import functools

    import jax
    import numpy as np

    from bench import check

    prec = b.cell.config["reference"]["precision"]
    logits = jax.jit(functools.partial(b.ref.logits, precision=prec))
    limits = b.limits
    outs = [(j, np.asarray(next(iter(o.values())))) for _n, j, o in kept]
    kept.clear()
    ref_logits = {j: np.asarray(logits(params, pool[j]))
                  for j in sorted({j for j, _ in outs})}
    values = check.readings(np.stack([p for _j, p in outs]),
                            np.stack([ref_logits[j] for j, _p in outs]))
    failed = sum(1 for j, p in outs
                 if not check.passed(check.verdict(
                     check.readings(p, ref_logits[j]), limits)))
    return check.verdict(values, limits), failed, values


def build(cell, *, require_chip: bool = True):
    """Device checks, the chain and its engine; the seed plays no part."""
    _paths()
    import jax

    from bench import spec
    from repro.exec import compile_chain
    from repro.models import cnn
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program of a run, however quick to compile, is found again by
    # the next run of the cell in this checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    dev, peaks = device_peaks(cell.chips, require_chip)
    batch = cell.traffic["batch"]
    ref = spec.reference(cell.config_name)
    chain = cnn.build(cell.config["net"], batch=batch)
    _check_against_reference(chain, ref, batch)
    t = time.perf_counter()
    eng = compile_chain(chain, backend="auto", lint="off")
    plan_s = time.perf_counter() - t
    return SimpleNamespace(cell=cell, dev=dev, peaks=peaks, ref=ref,
                           chain=chain, engine=eng, plan_s=plan_s,
                           limits=spec.cell_limits(cell.name))


def make_inputs(b, seed: int):
    """Weights and the pool of input batches of one seed, on the device."""
    import jax.numpy as jnp

    from bench import spec, weights

    cfg, chain = b.cell.config, b.chain
    params = weights.make_params(b.ref.param_specs(), cfg["weights"], seed)
    names = list(chain.inputs)
    fills = {n: jnp.full(chain.inputs[n].shape, v, jnp.float32)
             for n, v in cfg["fills"].items()}
    if set(names) != {names[0]} | set(fills):
        raise spec.SpecError(f"inputs {names}: the first is the image, the "
                             f"configuration's fills must give the rest")
    images = weights.make_images(chain.inputs[names[0]].shape,
                                 b.cell.traffic["pool"], seed)
    return params, [dict(fills, **{names[0]: img}) for img in images]


def sample_rng(seed: int):
    import numpy as np

    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, t_start: float = T_START) -> dict:
    """Everything after argument parsing; returns the result object."""
    b = build(cell, require_chip=require_chip)
    import jax

    from bench import check, spec
    from bench.stats import percentile

    eng, batch = b.engine, cell.traffic["batch"]
    params, pool = make_inputs(b, seed)
    for p in pool:                       # compiles, or loads, the program
        jax.block_until_ready(eng(p, params))
    # what set-up left is kept out of collections until the windows end, so
    # that a full collection in a window scans only what the calls allocate
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    rng = sample_rng(seed)
    win = closed_loop(eng, params, pool, seconds, rng, cell.traffic["sample"])
    slow = max(range(win.calls), key=win.latency_s.__getitem__)
    _log(f"window: {win.calls} calls in {win.seconds:.3f} s, median "
         f"{1e3 * percentile(win.latency_s, 50):.3f} ms, longest "
         f"{1e3 * win.latency_s[slow]:.3f} ms (call {slow})")
    device = {"platform": b.dev.platform, "kind": b.dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": _memory_peak(b.dev)}
    result = {}
    if trace:
        summary = _traced_window(eng, params, pool, rng)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = SimpleNamespace(cell=cell, engine=eng, chain=b.chain,
                              peaks=b.peaks, plan_s=b.plan_s, window=win,
                              trace=summary)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = summary.breakdown()
    else:
        e2e = {"images_per_s": batch * win.calls / win.seconds,
               "call_p95_ms": 1e3 * percentile(win.latency_s, 95),
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    gc.unfreeze()
    b.engine = eng = None                # the program's state is freed
    checks, failed, values = compare(b, win.kept, pool, params)
    _log("readings " + json.dumps(values))
    out = {"correct": check.passed(checks), "attempted": win.calls,
           "failed": failed, "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    _paths()
    try:
        from bench import spec
        cell = spec.cell(args.workload)
    except Exception as e:               # missing files, the program absent
        _log(f"FAIL: {type(e).__name__}: {e}")
        return 2
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (NoChip, spec.SpecError) as e:
        _log(f"FAIL: {e}")
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in out["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        _log(f"check {name}: {c['value']!r} limit {c['limit']!r} {ok}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
