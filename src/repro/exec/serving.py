"""Batch-aware serving programs: the execution substrate of launch/serve.

The serving driver used to hand-roll its own execution (per-slot prefill
through teacher-forced decode steps of the *whole* batch, global position
bookkeeping), corrupting neighbouring slots' caches. This module moves
serving execution into ``repro.exec``, sharing the batched engine's
machinery rather than duplicating it: the same bucketed compile-cache
type that backs the batched :class:`~repro.exec.engine.CompiledChain`
(:class:`~repro.exec.batch.BucketedCache`) keys the prefill programs on
``(batch bucket, length bucket)``, decode is ONE fixed-shape jitted
program over the slot batch, and all slot-state surgery (KV-row splicing,
slot reset) is pure tree arithmetic over the model's ``serve_axes`` table
— no per-family code and no cross-slot writes. To be precise about the
layering: the serving programs jit the models' fused decode/prefill paths
(``models.common`` norm/attention — the very implementations the chain
engine's segment dispatch lowers to, equivalence-tested in
tests/test_exec.py); the per-GCONV lowerings themselves are the *offline*
face of ``repro.exec`` and are not re-derived per token here.

Layering::

    launch/serve.py   policy: queue, slots, admission, stats
    exec/serving.py   mechanism: compiled programs + slot-state surgery
    exec/batch.py     bucketing + compile cache (shared with CompiledChain)
    models/api.py     decode_step / prefill(lengths=...) / serve_axes

Correctness contract (regression-tested in tests/test_serve.py): a
staggered multi-slot workload produces byte-identical token streams to
sequential single-slot decode. This holds because every program here is
row-independent — per-slot positions mean a pad-token tick on an idle slot
never advances or overwrites an active slot's rows, and right-padded
prefill is masked (causally, then by ``pos``) so pad rows are inert.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import shardpolicy as policy
from .batch import BucketedCache, batch_bucket

MIN_LEN_BUCKET = 8      # shortest prompt-length bucket (compile-count floor)


class ServeEngine:
    """Compiled decode/prefill programs + slot-state surgery for one model.

    ``decode``  — one jitted program over the fixed ``slots`` batch.
    ``prefill`` — right-padded batched prefill over the newly admitted
                  requests, one compiled program per ``(batch bucket,
                  length bucket)`` via the shared bucketed cache; falls
                  back to per-request teacher-forced decode for families
                  without a batched prefill (SSM/hybrid) or with sliding
                  windows (where padded prefill is unsound).
    ``splice``  — write prefilled rows (K/V rows, SSM state, positions)
                  into their slots, ONE jitted scatter over the whole
                  admission (``splice_many``); ``reset_slot`` zeroes a
                  slot on release (also jitted).

    Mesh mode (``mesh=``): the engine runs data-parallel replicas of its
    one fixed-shape decode program — every program (decode, prefill,
    splice, reset) shards the SLOT axis of each serve-state leaf over the
    mesh's data bundle (the ``serve_axes`` table names the axis per leaf),
    params replicate, and the same divisibility guard as
    ``launch/sharding.py`` applies: a slot count the data axis doesn't
    divide falls back to replication (``repro.shardpolicy``). Per-slot
    row independence (the PR-4 correctness contract) is exactly what
    makes this sound: no program communicates across the slot axis, so
    each device decodes its own slots bit-for-bit as a single device
    would — staggered serving under a mesh stays byte-identical to the
    sequential single-slot reference (tests/test_exec_sharded.py).
    """

    def __init__(self, model, *, slots: int, max_len: int, mesh=None,
                 tracer=None, chaos=None):
        self.model = model
        self.cfg = model.cfg
        self.slots = int(slots)
        self.max_len = int(max_len)
        # optional repro.obs tracer: engine-category spans around the
        # compiled programs (decode / prefill / splice / reset), device-
        # synced so span durations are real device time. None (the
        # default) keeps every hot path on a single flag check.
        self.tracer = tracer
        # optional repro.runtime.chaos injector: each compiled-program
        # site calls chaos.enter(site) (which may raise/stall) and
        # applies the returned data faults to its outputs. None (the
        # default) keeps every hot path on a single flag check.
        self.chaos = chaos
        self.axes: Dict[str, int] = dict(model.serve_axes)
        self.mesh = None if mesh is None or mesh.empty else mesh
        if self.mesh is not None:
            self._dp = policy.dp_axes(self.mesh)
            self._dp_n = policy.axis_size(self.mesh, self._dp)
            slot_dp = self._dp if self.slots % self._dp_n == 0 else None
            state_shape = jax.eval_shape(
                lambda: model.serve_state_init(self.slots, self.max_len,
                                               per_slot_pos=True))
            self._cache_sh = {
                k: NamedSharding(self.mesh, P(*[
                    slot_dp if a == self.axes[k] else None
                    for a in range(leaf.ndim)]))
                for k, leaf in state_shape.items()}
            self._tok_sh = NamedSharding(self.mesh, P(slot_dp, None))
            self._decode_fn = jax.jit(
                model.decode_step,
                in_shardings=(None, self._tok_sh, self._cache_sh),
                out_shardings=(None, self._cache_sh))
            # surgery keeps the cache canonically slot-sharded so the next
            # decode never pays a reshard
            self._splice_fn = jax.jit(self._splice_many,
                                      out_shardings=self._cache_sh)
            self._reset_fn = jax.jit(self._reset_impl,
                                     out_shardings=self._cache_sh)
        else:
            self._dp_n = 1
            self._decode_fn = jax.jit(model.decode_step)
            # slot surgery compiles once per (row-state shape, admission
            # count) — both bucket-bounded; jitting fuses the per-leaf
            # updates into one program instead of eager per-leaf dispatch
            self._splice_fn = jax.jit(self._splice_many)
            self._reset_fn = jax.jit(self._reset_impl)
        self._prefill_cache = BucketedCache(self._build_prefill)
        self._batched_prefill_ok = (
            getattr(model, "prefill", None) is not None
            and not self.cfg.sliding_window)

    # -- state ----------------------------------------------------------
    def init_state(self):
        state = self.model.serve_state_init(self.slots, self.max_len,
                                            per_slot_pos=True)
        if self.mesh is not None:
            state = jax.device_put(state, self._cache_sh)
        return state

    def shard_params(self, params):
        """Replicate params across the mesh (the data-parallel serving
        story; tensor-parallel param rules stay in launch/sharding)."""
        if self.mesh is None:
            return params
        rep = jax.tree.map(lambda _: NamedSharding(self.mesh, P()), params)
        return jax.device_put(params, rep)

    # -- decode: ONE program, fixed (slots, 1) shape --------------------
    def decode(self, params, tokens, cache):
        """tokens: (slots, 1) int32 -> (logits, cache). Row-independent:
        idle slots step a pad token but only their own rows move."""
        ch = self.chaos
        post = ch.enter("decode") if ch is not None else ()
        tr = self.tracer
        if tr is not None and tr.enabled:
            with tr.span("engine.decode", cat="engine",
                         attrs={"slots": self.slots}):
                out = self._decode_fn(params, tokens, cache)
                jax.block_until_ready(out)
        else:
            out = self._decode_fn(params, tokens, cache)
        if post:
            out = ch.apply_decode(post, out[0], out[1], self.axes)
        return out

    # -- prefill: bucketed batched programs -----------------------------
    def _build_prefill(self, key):
        nb, lb = key
        if lb == 0:                       # fallback: single decode step
            return jax.jit(self.model.decode_step)
        fn = lambda params, tokens, lengths: \
            self.model.prefill(params, tokens, lengths=lengths)
        if self.mesh is not None:
            # admission rows data-parallel: nb is bucketed to a multiple
            # of the data-axis size, so the guard only fires for meshes
            # whose data axis is not a power of two
            row_dp = self._dp if nb % self._dp_n == 0 else None
            tok_sh = NamedSharding(self.mesh, P(row_dp, None))
            len_sh = NamedSharding(self.mesh, P(row_dp))
            return jax.jit(fn, in_shardings=(None, tok_sh, len_sh))
        return jax.jit(fn)

    def prefill(self, params, prompts: Sequence[Sequence[int]]):
        """Prefill ``prompts`` together; returns (logits, row_state, n).

        ``logits[j]`` is row j's own last-real-token logits (never another
        request's — the old driver's unbound/stale-``logits`` bug class);
        ``row_state`` holds the per-row caches to splice into slots.
        """
        n = len(prompts)
        if n == 0:
            raise ValueError("prefill of zero prompts")
        if any(len(p) == 0 for p in prompts):
            raise ValueError("empty prompt reached prefill; the driver "
                             "seeds BOS or rejects at submit")
        longest = max(len(p) for p in prompts)
        if longest > self.max_len:
            raise ValueError(f"prompt length {longest} > max_len "
                             f"{self.max_len}")
        ch = self.chaos
        post = ch.enter("prefill") if ch is not None else ()
        if not self._batched_prefill_ok:
            logits, row_state, n = self._prefill_loop(params, prompts)
            if post:
                logits, row_state = ch.apply_decode(post, logits, row_state,
                                                    self.axes)
            return logits, row_state, n
        # sharded engines raise the row-bucket floor to the data-axis size
        # (see exec.batch): every admission bucket then divides the mesh
        nb = batch_bucket(n, self._dp_n)
        # longest <= max_len (checked above), so the clamp keeps lb valid
        lb = min(batch_bucket(longest, MIN_LEN_BUCKET), self.max_len)
        tokens = np.zeros((nb, lb), np.int32)
        lengths = np.ones((nb,), np.int32)     # pad rows: 1 (inert, valid)
        for j, p in enumerate(prompts):
            tokens[j, :len(p)] = p
            lengths[j] = len(p)
        tr = self.tracer
        if tr is not None and tr.enabled:
            before = self._prefill_cache.compiles
            fn = self._prefill_cache.get((nb, lb))
            cat = "compile" if self._prefill_cache.compiles > before \
                else "execute"
            with tr.span("engine.prefill", cat=cat,
                         attrs={"n": n, "batch_bucket": nb,
                                "len_bucket": lb}):
                logits, row_state = fn(params, jnp.asarray(tokens),
                                       jnp.asarray(lengths))
                jax.block_until_ready((logits, row_state))
        else:
            fn = self._prefill_cache.get((nb, lb))
            logits, row_state = fn(params, jnp.asarray(tokens),
                                   jnp.asarray(lengths))
        if post:
            logits, row_state = ch.apply_decode(post, logits, row_state,
                                                self.axes)
        return logits, row_state, n

    def _prefill_loop(self, params, prompts):
        """Teacher-forced per-request prefill on a fresh single-row state
        (SSM/hybrid/windowed families): still isolated — the scratch state
        is private, nothing touches the live slot batch."""
        step = self._prefill_cache.get((1, 0))
        rows, logits = [], []
        for p in prompts:
            st = self.model.serve_state_init(1, self.max_len,
                                             per_slot_pos=True)
            lg = None
            for t in p:
                lg, st = step(params, jnp.asarray([[t]], jnp.int32), st)
            logits.append(lg[:, -1] if lg.ndim == 3 else lg)
            rows.append(st)
        row_state = {k: jnp.concatenate([r[k] for r in rows],
                                        axis=self.axes[k])
                     for k in rows[0]}
        return jnp.concatenate(logits), row_state, len(prompts)

    @property
    def prefill_compiles(self) -> int:
        return self._prefill_cache.compiles

    # -- slot-state surgery (tree arithmetic over serve_axes) -----------
    def _splice_many(self, cache, slots, row_state, js):
        """Scatter rows ``js`` of ``row_state`` into ``slots`` of
        ``cache`` — the ONLY slots whose leaves change; all other rows
        pass through untouched (no cross-slot cache writes, by
        construction). ``slots``/``js``: (m,) int32."""
        def one(leaf, rows_leaf, axis):
            rows = jnp.take(rows_leaf, js, axis=axis)
            rows = jnp.moveaxis(rows, axis, 0)               # (m, ...)
            tgt = jnp.moveaxis(leaf, axis, 0)                # (slots, ...)
            pad = [(0, 0)] + [(0, int(t) - int(r))
                              for t, r in zip(tgt.shape[1:], rows.shape[1:])]
            if any(p != (0, 0) for p in pad):                # lb -> max_len
                rows = jnp.pad(rows, pad)
            out = tgt.at[slots].set(rows.astype(leaf.dtype))
            return jnp.moveaxis(out, 0, axis)

        return {k: one(cache[k], row_state[k], self.axes[k]) for k in cache}

    def splice_many(self, cache, slots: Sequence[int], row_state,
                    js: Optional[Sequence[int]] = None):
        """Write each row ``js[i]`` of ``row_state`` into slot
        ``slots[i]``: one fused jitted scatter for the whole admission."""
        if js is None:
            js = list(range(len(slots)))
        if self.chaos is not None:
            self.chaos.enter("splice")
        tr = self.tracer
        if tr is not None and tr.enabled:
            with tr.span("engine.splice", cat="engine",
                         attrs={"rows": len(js)}):
                out = self._splice_fn(cache, jnp.asarray(slots, jnp.int32),
                                      row_state, jnp.asarray(js, jnp.int32))
                jax.block_until_ready(out)
                return out
        return self._splice_fn(cache, jnp.asarray(slots, jnp.int32),
                               row_state, jnp.asarray(js, jnp.int32))

    def splice(self, cache, slot: int, row_state, j: int = 0):
        """Single-slot convenience form of :meth:`splice_many`."""
        return self.splice_many(cache, [slot], row_state, [j])

    def _reset_impl(self, cache, slot):
        def one(leaf, axis):
            shape = list(leaf.shape)
            shape[axis] = 1
            zeros = jnp.zeros(shape, leaf.dtype)
            start = [0] * leaf.ndim
            start[axis] = slot
            return jax.lax.dynamic_update_slice(leaf, zeros, start)

        return {k: one(cache[k], self.axes[k]) for k in cache}

    def reset_slot(self, cache, slot: int):
        """Zero a slot's rows on release — a reused slot starts from a
        clean state even before its next splice."""
        if self.chaos is not None:
            self.chaos.enter("reset")
        tr = self.tracer
        if tr is not None and tr.enabled:
            with tr.span("engine.reset", cat="engine",
                         attrs={"slot": int(slot)}):
                out = self._reset_fn(cache, jnp.asarray(slot, jnp.int32))
                jax.block_until_ready(out)
                return out
        return self._reset_fn(cache, jnp.asarray(slot, jnp.int32))

    # -- degraded-mode fallback: one request, private single-row state --
    def decode_single(self, params, prompt: Sequence[int],
                      max_new: int) -> List[int]:
        """Greedy-decode ONE request end to end on a private single-row
        state, bypassing the live slot batch — the driver's graceful-
        degradation path when the batched decode program keeps failing.

        Byte-identity with a single-slot server holds by construction:
        the prompt goes through the SAME bucketed prefill program a
        ``slots=1`` server would use (``batch_bucket(1) == 1``, same
        length bucket), the row is spliced into a fresh 1-slot state by
        the same (eagerly evaluated — pure data movement, bitwise
        identical either way) splice arithmetic, and every decode step
        runs ``jax.jit(model.decode_step)`` at the same (1, 1) shape.
        The batched decode program — the thing that is failing — is
        never touched, and neither is the live slot cache.
        """
        tr = self.tracer
        if tr is not None and tr.enabled:
            with tr.span("engine.decode_single", cat="engine",
                         attrs={"prompt_len": len(prompt),
                                "max_new": int(max_new)}):
                return self._decode_single(params, prompt, max_new)
        return self._decode_single(params, prompt, max_new)

    def _decode_single(self, params, prompt, max_new):
        logits, rows, _n = self.prefill(params, [list(prompt)])
        st = self.model.serve_state_init(1, self.max_len,
                                         per_slot_pos=True)
        st = self._splice_many(st, jnp.asarray([0], jnp.int32), rows,
                               jnp.asarray([0], jnp.int32))
        step = self._prefill_cache.get((1, 0))   # jit(model.decode_step)
        tok = int(np.asarray(jnp.argmax(logits[:1], axis=-1)).reshape(-1)[0])
        out = [tok]
        while len(out) < max_new:
            lg, st = step(params, jnp.asarray([[tok]], jnp.int32), st)
            lg = lg[:, -1] if lg.ndim == 3 else lg
            tok = int(np.asarray(jnp.argmax(lg, axis=-1)).reshape(-1)[0])
            out.append(tok)
        return out
