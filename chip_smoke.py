"""Bring-up proof on the TPU: the two main paths, at published widths.

    python chip_smoke.py              # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4    # four chips: the mesh phases only

(a) CNN path: full-width AlexNet (batch 32, 227x227) through
    ``compile_chain(backend="auto")``, which on the chip plans Mosaic
    Pallas kernels. Every step output is compared with the same chain
    compiled with ``backend="jnp"`` (XLA's own matmul/conv, no Pallas) and
    run under ``jax.default_matmul_precision("highest")``.
(b) Serving path: ``Server("tinyllama-1.1b", smoke=False)`` (22 layers,
    d_model 2048, bf16) drains staggered requests; every request must end
    ``ok`` with tokens identical to ``sequential_reference`` at the same
    slot count and mesh (each request decoding alone through the same
    programs; see its docstring for why the slot count must match here).

With ``--chips 4``: AN under ``compile_chain(mesh=(4, 1))`` against the
single-device engine, and ``Server(mesh=mesh_from_spec("4"))`` against
``sequential_reference``.

Everything runs in this one process, which holds the chip(s). The script
fails (nonzero exit, no result line) when JAX finds no TPU, when Pallas
would run in interpret mode, when (a)'s plan holds no Pallas step, or when
any phase fails. Its last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
import traceback

# Default matmul precision rounds f32 operands to bf16 (unit roundoff
# 2**-9 ~ 2e-3) in every MXU pass; AlexNet chains eight such layers, so
# errors compound to about 8 x 2e-3. Each tensor's max abs error is held
# to this fraction of its own max abs value.
REL_TOL = 2e-2
SERVE_ARCH = "tinyllama-1.1b"


class SmokeError(RuntimeError):
    pass


def _log(msg: str):
    print(f"chip_smoke: {msg}", flush=True)


def _require_chip(chips: int):
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SmokeError(f"JAX backend is {backend!r}, not 'tpu'")
    have = len(jax.devices())
    if have < chips:
        raise SmokeError(f"--chips {chips} but JAX sees {have} device(s)")
    from repro.kernels.common import use_interpret
    if use_interpret():
        raise SmokeError("Pallas would run in interpret mode "
                         "(REPRO_FORCE_INTERPRET is set)")


def _rel_err(got, ref) -> float:
    import jax.numpy as jnp

    g = jnp.asarray(got, jnp.float32)
    r = jnp.asarray(ref, jnp.float32)
    if not bool(jnp.all(jnp.isfinite(g))):
        return float("inf")
    scale = float(jnp.max(jnp.abs(r)))
    return float(jnp.max(jnp.abs(g - r))) / (scale if scale else 1.0)


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _alexnet(seed: int):
    """Full-width AN, a random image and random dropout keep-masks (the
    all-zero masks of ``random_inputs`` would zero fc6 onwards, leaving
    the fc layers nothing to be compared on)."""
    import jax
    import numpy as np

    from repro.models import cnn

    chain = cnn.build("AN")
    inputs = cnn.random_inputs(chain, seed)
    key = jax.random.PRNGKey(seed)
    for name in inputs:
        if name.endswith(".mask"):
            key, sub = jax.random.split(key)
            inputs[name] = np.asarray(jax.random.bernoulli(
                sub, 0.5, inputs[name].shape), np.float32)
    return chain, inputs, key


def phase_cnn(seed: int):
    """(a): full-width AN, auto dispatch vs the XLA highest reference."""
    import jax

    from repro.exec import compile_chain

    chain, inputs, key = _alexnet(seed)
    eng = compile_chain(chain, backend="auto")
    hist = eng.backend_histogram()
    _log(f"(a) AN dispatch histogram {json.dumps(hist, sort_keys=True)}")
    kernels = {n: t for n, t in eng.dispatch.items()
               if t.startswith(("matmul:", "conv:"))}
    _log(f"(a) AN matmul/conv steps {json.dumps(kernels)}")
    if not (hist.get("matmul:pallas") and hist.get("conv:pallas")):
        raise SmokeError("AN plan holds no matmul:pallas or no conv:pallas "
                         "step")
    params = eng.init_params(key)
    out, t_first = _timed(lambda: eng(inputs, params))
    _, t_warm = _timed(lambda: eng(inputs, params))
    _log(f"(a) AN auto, input {chain.inputs['x'].shape}: first call "
         f"(compile + run) {t_first:.3f} s, warm call {t_warm * 1e3:.3f} ms")

    ref_eng = compile_chain(chain, backend="jnp")
    with jax.default_matmul_precision("highest"):
        ref, t_ref = _timed(lambda: ref_eng(inputs, params, keep_all=True))
    _log(f"(a) reference (jnp, highest) first call {t_ref:.3f} s")
    _check_steps("a", eng, eng(inputs, params, keep_all=True), out, ref)


def _check_steps(label: str, eng, env, out, ref):
    """Hold every step output of ``eng`` (``env``, a ``keep_all`` run) and
    every chain output (``out``) to ``ref`` within :data:`REL_TOL`."""
    errs = {s.name: _rel_err(env[s.name], ref[s.name]) for s in eng.steps}
    errs.update({f"out:{o}": _rel_err(out[o], ref[o]) for o in out})
    for name, e in errs.items():
        _log(f"({label})   {name:14s} {eng.dispatch.get(name, 'output'):22s} "
             f"rel err {e:.3e}")
    worst = max(errs, key=errs.get)
    _log(f"({label}) max rel err {errs[worst]:.3e} at {worst} "
         f"(tolerance {REL_TOL:g})")
    if not errs[worst] <= REL_TOL:
        raise SmokeError(f"AN {worst} rel err {errs[worst]:.3e} > {REL_TOL}")


def _requests(vocab: int, n: int, seed: int):
    import numpy as np

    from repro.launch.serve import Request

    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab,
                                        rng.integers(8, 33)).tolist(),
                    max_new=16)
            for i in range(n)]


def phase_serve(seed: int, mesh=None, label: str = "b"):
    """(b): staggered continuous batching vs each request decoded alone."""
    from repro.launch.serve import Request, Server, sequential_reference

    t0 = time.perf_counter()
    srv = Server(SERVE_ARCH, smoke=False, mesh=mesh)
    t_build = time.perf_counter() - t0
    cfg = srv.cfg
    _log(f"({label}) {cfg.name}: {cfg.n_layers} layers, d_model "
         f"{cfg.d_model}, {cfg.dtype}, slots {srv.slots}, max_len "
         f"{srv.max_len}, mesh "
         f"{None if mesh is None else dict(mesh.shape)}; built in "
         f"{t_build:.3f} s")
    reqs = _requests(cfg.vocab, 6, seed)
    stats = srv.run_workload(reqs, stagger_ticks=2)
    _log(f"({label}) served {stats['requests']} requests in "
         f"{stats['wall_s']:.3f} s (compilation included): statuses "
         f"{json.dumps(stats['statuses'])}, tokens_out "
         f"{stats['tokens_out']}, prefill_compiles "
         f"{stats['prefill_compiles']}")
    served = {r.rid: r for r in srv.finished}
    slots = srv.slots
    del srv
    ref = sequential_reference(
        SERVE_ARCH, [Request(rid=r.rid, prompt=list(r.prompt),
                             max_new=r.max_new) for r in reqs],
        slots=slots, smoke=False, mesh=mesh)
    not_ok = [rid for rid, r in served.items() if r.status != "ok"]
    differ = [r.rid for i, r in enumerate(reqs)
              if r.rid not in served or served[r.rid].out != ref[i]]
    _log(f"({label}) identical to sequential_reference: "
         f"{len(reqs) - len(differ)}/{len(reqs)} requests")
    if not_ok or differ or len(served) != len(reqs):
        raise SmokeError(f"serving: not ok {not_ok}, differ from "
                         f"sequential_reference {differ}")


def phase_cnn_mesh(seed: int):
    """AN on a (4, 1) data-parallel mesh vs the single-device engine."""
    from repro.exec import compile_chain
    from repro.launch.mesh import make_debug_mesh

    chain, inputs, key = _alexnet(seed)
    one = compile_chain(chain, backend="auto")
    params = one.init_params(key)
    ref = one(inputs, params, keep_all=True)
    eng = compile_chain(chain, mesh=make_debug_mesh(4, 1))
    _log(f"(mesh) AN dispatch histogram "
         f"{json.dumps(eng.backend_histogram(), sort_keys=True)}")
    out, t_first = _timed(lambda: eng(inputs, params))
    _, t_warm = _timed(lambda: eng(inputs, params))
    _log(f"(mesh) AN mesh (4, 1): first call {t_first:.3f} s, warm call "
         f"{t_warm * 1e3:.3f} ms; against the single-device engine:")
    _check_steps("mesh", eng, eng(inputs, params, keep_all=True), out, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    try:
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        _log(f"FAIL: the repro package is not beside this script ({e})")
        return 2
    try:
        _require_chip(args.chips)
    except SmokeError as e:
        _log(f"FAIL: {e}")
        return 2
    cache = enable_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    import jax

    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.update(
            [event] if event.startswith("/jax/compilation_cache/cache_")
            else []))
    dev = jax.devices()[0]
    _log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
         f"jax {jax.__version__}, compile cache {cache} "
         f"({n_cached} entries)")

    if args.chips == 4:
        from repro.launch.mesh import mesh_from_spec
        phases = [("cnn_mesh", lambda: phase_cnn_mesh(args.seed)),
                  ("serve_mesh", lambda: phase_serve(
                      args.seed, mesh=mesh_from_spec("4"), label="mesh"))]
    else:
        phases = [("cnn", lambda: phase_cnn(args.seed)),
                  ("serve", lambda: phase_serve(args.seed))]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        cache_events.clear()
        try:
            fn()
        except Exception:                # report every phase, then fail
            traceback.print_exc()
            failed.append(name)
        _log(f"phase {name}: {'FAIL' if name in failed else 'ok'} "
             f"({time.perf_counter() - t0:.1f} s; persistent compile cache "
             f"{cache_events['/jax/compilation_cache/cache_hits']} hits, "
             f"{cache_events['/jax/compilation_cache/cache_misses']} "
             f"misses written)")
    n_after = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    _log(f"compile cache {cache}: {n_cached} -> {n_after} entries")
    if failed:
        _log(f"FAIL: phases {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
