"""Batched decode engine + PEQA multi-task serving.

The deployment half of the paper's pitch: ONE quantized integer backbone in
memory, per-task scale vectors hot-swapped from a ScaleBank in O(scale-size)
(§3.3 "swift switching of task-specific parameters").  The engine serves
greedy generation; `switch_task` is measured in benchmarks/kernel_bench.py
against a full-model reload.

Two serving modes:

  * ``generate`` — the lockstep baseline: one batch, every sequence decodes
    until the LAST one finishes.  Mixed-length traffic pays bubble steps
    (slots computing tokens nobody asked for).
  * **continuous batching** — a paged KV slot pool (``open_pool``): the
    cache batch dim becomes a fixed pool of slots, each with its own
    position (``pos``), activity bit and task id.  ``admit`` prefills one
    prompt and writes its KV rows into a free slot; the decode loop runs at
    ONE compiled shape (n_slots) with a per-slot position VECTOR, and
    finished sequences are evicted mid-loop so their slot is refilled on
    the next step.  ``serve`` is the scheduler: arrival-ordered admission,
    EOS/length eviction, and one of three schedulers — ``drain``
    (drain-before-switch, one live scale set), ``resident`` (scales for
    the k hottest tasks stay device-resident stacked ``(T, out, G)``;
    decode gathers each slot's row in-kernel through
    ``decode_step_slotted``, so admission never waits on a task mismatch)
    or ``auto`` (``resident`` where the workload allows it, else
    ``drain``).  Zero bubble steps, zero recompiles per traffic shape.

Mesh mode: construct with a ``dist.context.MeshContext`` (params already
homed on the mesh per ``dist.sharding.named_shardings``) and the engine
becomes the serving hot path of the dist subsystem —

  * ``switch_task`` swaps scales shard-locally (``ScaleBank.switch`` with
    ctx + donation): per-shard bytes only, no resharding collective, no
    transient second tree.
  * ``logitshard=True`` keeps logits vocab-sharded out of ``decode_step``
    (a sharding constraint on the returned logits, so the jit output stays
    P(batch, model)) and samples with the shard-local argmax of
    ``dist/sampling.py`` — the O(B·V) vocab all-gather disappears from the
    decode loop, replaced by O(B) scalar reductions.  The continuous loop
    samples through the masked variant (``shard_argmax_masked``), same
    collective payload.
  * the slot pool is created THROUGH ``_init_cache`` (jit out_shardings =
    ``dist.sharding.cache_specs``) and every admit re-constrains it, so the
    slot dim shards over the data axes exactly like the lockstep batch dim
    did and post-admit shardings always equal ``cache_specs``.
"""
from __future__ import annotations

import math
import time
import warnings
from collections import deque
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scale_bank import ResidentStack, ScaleBank
from repro.dist import context as dctx
from repro.dist import sampling
from repro.models.registry import ModelAPI
# the serving API types live in repro.serve (the production driver layer);
# re-exported here so pre-harness imports keep working
from repro.serve.config import ServeConfig
from repro.serve.metrics import (REJECTED, SERVED, SHED, RequestMetrics,
                                 ServeReport)
from repro.serve.request import Request
from repro.serve import telemetry

__all__ = ["Engine", "Request", "RequestMetrics", "ServeConfig",
           "ServeReport", "SlotPool"]

# attributes of a slotted MoE step's ``engine.step`` span, in the order of
# the step's routing stats (``transformer.decode_tokens``)
MOE_STEP_STATS = ("moe_layers", "experts_hit", "expert_rows",
                  "expert_rows_max")


class SlotPool:
    """Paged slot pool: a fixed pool of ``n_slots`` sequence slots.

    Device state: the cache tree (batch dim = slot dim, created sharded per
    ``cache_specs``).  Host mirrors (one int/bool per slot — the scheduler
    state): ``pos`` (next absolute position = tokens written so far),
    ``active``, ``tok`` (last sampled token, the next decode input), and
    per-slot metadata (request, collected output, task id).

    The pool is FAMILY-AGNOSTIC: admission/eviction key on the structural
    cache dims (``_cache_dims``) and the registry's ``FamilyCaps`` record,
    not on the family name.  Attention KV leaves page along their seq dim;
    position-free leaves (SSM/recurrent state, encdec cross-KV) admit as
    pure batch-dim row writes; prefix state (vlm image embeddings, encdec
    encoder frames) is admitted once per slot through the prefill.
    """

    def __init__(self, engine: "Engine", n_slots: int, cache_len: int):
        if n_slots < 1 or cache_len < 1:
            raise ValueError(f"need n_slots >= 1 and cache_len >= 1, got "
                             f"({n_slots}, {cache_len})")
        fam = getattr(engine.api.cfg, "family", None)
        if getattr(engine.api, "caps", None) is None:
            raise NotImplementedError(
                f"continuous batching needs a family capability record "
                f"(ModelAPI.caps) describing the decode-state protocol; "
                f"family {fam!r} does not provide one")
        self.n_slots = n_slots
        self.cache_len = cache_len
        self.cache = engine._init_cache(n_slots, cache_len)
        self.pos = np.zeros((n_slots,), np.int32)
        self.active = np.zeros((n_slots,), bool)
        self.tok = np.zeros((n_slots,), np.int32)
        self.tid = np.zeros((n_slots,), np.int32)   # resident-stack row
        self.slotted = False           # decode through the stacked-scale step
        self.meta: List[Optional[dict]] = [None] * n_slots
        self.task: List[Optional[str]] = [None] * n_slots
        # distinct prefill shapes admitted through this pool — the compile
        # meter prompt-length bucketing is judged by (ServeReport)
        self._prefill_keys: set = set()
        # device-resident (tok, pos, active) between scheduling events:
        # steps with no admit/evict reuse the previous step's outputs
        # instead of re-uploading the host mirrors (3 puts/step saved)
        self._dev = None
        # accounting (the benchmark's bubble/utilisation story)
        self.steps = 0                 # decode steps executed
        self.decoded = 0               # useful tokens decoded
        self.bubble_slot_steps = 0     # slot-steps spent on FINISHED seqs
        self.idle_slot_steps = 0       # inactive slot-steps while work waited
        # subset of idle_slot_steps: slots empty ONLY because an admissible
        # request targets a task the scheduler cannot co-run (drain-before-
        # switch, or resident stack full of pinned rows)
        self.task_drain_idle_slot_steps = 0

    def free_slot(self) -> Optional[int]:
        idx = np.flatnonzero(~self.active)
        return int(idx[0]) if idx.size else None

    def n_active(self) -> int:
        return int(self.active.sum())


class Engine:
    def __init__(self, api: ModelAPI, params: dict,
                 bank: Optional[ScaleBank] = None,
                 ctx=None, logitshard: bool = False):
        self.api = api
        self.params = params
        self.bank = bank
        self.ctx = ctx
        self.logitshard = bool(logitshard and ctx is not None)
        if self.logitshard and api.cfg.vocab_size % ctx.model_size:
            raise ValueError(
                f"logitshard needs vocab {api.cfg.vocab_size} divisible by "
                f"the model axis ({ctx.model_size})")
        self.current_task: Optional[str] = None
        # device-resident stacked scales for the drain-free mixed-task
        # scheduler; built lazily by serve(scheduler="resident"/"auto")
        self.resident: Optional[ResidentStack] = None
        self._prefill = jax.jit(self._shard_logits(api.prefill))
        self._decode = jax.jit(self._shard_logits(api.decode_step),
                               donate_argnums=(1,))
        self._decode_slotted = None
        self._prefill_slotted = None
        self._samplers = {}
        self._steppers = {}
        self._cache_inits = {}
        self._dims = None
        self._admit_jit = None

    # ----------------------------------------------------------- placement
    def _cache_dims(self):
        """(batch_dims, seq_dims) trees for this api's cache layout, both
        inferred STRUCTURALLY (trace at two extents, diff shapes) — never by
        extent matching, which breaks on collisions.  Memoized."""
        if self._dims is None:
            from repro.dist import sharding as shard_rules
            # SWA clamps capacity to the window: the seq probe must
            # straddle the clamp (seq_len < window) to see the dim move.
            # window == 1 leaves sl = 1 (probe blind), which is fine: a
            # 1-slot ring never grows, so the equal-shape path covers it.
            w = getattr(self.api.cfg, "swa_window", None)
            sl = 8 if w is None else max(1, min(8, w - 1))
            self._dims = (
                shard_rules.cache_batch_dims(self.api.init_cache, 2, sl),
                shard_rules.cache_seq_dims(self.api.init_cache, 2, sl))
        return self._dims

    def _has_seq_leaf(self) -> bool:
        """Does ANY cache leaf carry a position (seq) dim?  False for pure
        recurrent families (xlstm: ``init_cache`` ignores ``seq_len``
        entirely) — there, capacity budgeting and cache growth are
        meaningless and must not reject requests."""
        return any(sd >= 0 for sd in jax.tree.leaves(self._cache_dims()[1]))

    def _prefix_rows(self, prefix) -> int:
        """Decoder cache rows a request prefix occupies (0 when the prefix
        lives in its own position-free state, e.g. encdec cross-KV)."""
        caps = getattr(self.api, "caps", None)
        if prefix is None or caps is None or not caps.prefix_positions:
            return 0
        return int(np.asarray(prefix).shape[-2])

    def _check_prefix(self, prefix):
        """Validate a request prefix against the capability record."""
        caps = getattr(self.api, "caps", None)
        key = None if caps is None else caps.prefix_key
        if prefix is not None and key is None:
            raise ValueError(
                f"family {getattr(self.api.cfg, 'family', None)!r} takes no "
                f"per-request prefix state (FamilyCaps.prefix_key is None)")
        if prefix is None and caps is not None and caps.prefix_required:
            raise ValueError(
                f"family {getattr(self.api.cfg, 'family', None)!r} requires "
                f"prefix state {key!r} on every request (encoder inputs)")

    @staticmethod
    def _bucket_len(s: int, cap: int) -> int:
        """Smallest power of two >= s, clamped to the pool capacity."""
        return min(1 << (s - 1).bit_length(), cap)

    def _cache_shardings(self, cache, b):
        """NamedSharding tree for the cache at batch ``b`` — the SAME
        ``dist.sharding.cache_specs`` rules the dry-run cost model uses,
        so engine and cost model can never disagree on cache placement."""
        from repro.dist import sharding as shard_rules
        ctx = self.ctx
        specs = shard_rules.cache_specs(
            ctx, cache, b, ctx.batch_axes(b) is not None,
            n_kv_heads=getattr(self.api.cfg, "n_kv_heads", 0),
            batch_dims=self._cache_dims()[0])
        return jax.tree.map(lambda l, s: ctx.sharding(*s), cache, specs)

    def _shard_logits(self, fn):
        """Pin the layout of the returned (logits, cache).

        logitshard: logits vocab-sharded P(batch, model) — the jit output
        keeps it, so no all-gather ever materialises.  Mesh without
        logitshard: logits explicitly replicated — the host-style sampler
        reads full rows, so the gather belongs inside the step where it is
        visible to HLO analysis (and to the benchmark) instead of hiding
        in the first eager op that touches the logits.  Either mesh mode
        also pins the cache to ``dist.sharding.cache_specs``, so the
        runtime decode loop compiles against the exact layout the dry-run
        models (and the HLO guards scan).  Off-mesh: untouched.
        """
        if self.ctx is None:
            return fn
        ctx, ls = self.ctx, self.logitshard
        fn = self._mesh_scoped(fn)

        def wrapped(*args):
            logits, cache, *rest = fn(*args)
            b = logits.shape[0]
            spec = (ctx.logits_sharding(b) if ls
                    else ctx.sharding(ctx.batch_axes(b), None))
            logits = jax.lax.with_sharding_constraint(logits, spec)
            cache = jax.tree.map(jax.lax.with_sharding_constraint,
                                 cache, self._cache_shardings(cache, b))
            return (logits, cache, *rest)
        return wrapped

    def _mesh_scoped(self, fn):
        """Trace ``fn`` inside the engine's mesh scope
        (``dist.context.use_mesh``): quantized linears then run their
        Pallas kernels per model shard (``ops._kernel_matmul``), and the
        activation constraints match the dry-run's layout."""
        if self.ctx is None:
            return fn

        def scoped(*args, **kw):
            with dctx.use_mesh(self.ctx):
                return fn(*args, **kw)
        return scoped

    def _sampler(self, b: int):
        """Greedy sampler for batch ``b`` (cached): shard-local argmax +
        scalar max-reduce on a mesh, plain argmax off it."""
        if b not in self._samplers:
            self._samplers[b] = jax.jit(sampling.shard_argmax(
                self.ctx if self.logitshard else None, b))
        return self._samplers[b]

    def _stepper(self, b: int):
        """Masked sample + next-step input prep in ONE dispatch: returns
        (tokens (B,), next decode input (B, 1), advanced positions (B,)) so
        a no-scheduling-event step never round-trips through the host
        mirrors."""
        if b not in self._steppers:
            base = sampling.shard_argmax_masked(
                self.ctx if self.logitshard else None, b)

            def post(lg, act, pos):
                t = base(lg, act)
                return t, t[:, None], pos + act.astype(pos.dtype)
            self._steppers[b] = jax.jit(post)
        return self._steppers[b]

    def _slotted_decode_fn(self):
        """Jitted mixed-task decode step: ``(params, task_stack, cache, tok,
        pos, task_ids) -> (logits, cache)``, cache donated exactly like the
        plain decode step.  Lazy: families without ``decode_step_slotted``
        never pay for it (and raise only if the resident scheduler is
        actually requested)."""
        if self._decode_slotted is None:
            if self.api.decode_step_slotted is None:
                raise NotImplementedError(
                    f"family {getattr(self.api.cfg, 'family', None)!r} has no "
                    f"slotted decode step (decode_step_slotted is None)")
            self._decode_slotted = jax.jit(
                self._shard_logits(self.api.decode_step_slotted),
                donate_argnums=(2,))
        return self._decode_slotted

    def _slotted_prefill_fn(self):
        """Jitted resident-stack prefill: ``(params, task_stack, batch,
        task_ids) -> (logits, cache)``.  The prompt's quantized linears
        read the request's scales from its stack row, so admitting a
        resident task moves ZERO scale bytes host→device (the old path
        ran a full ``switch_task`` swap per task change at admit)."""
        if self._prefill_slotted is None:
            if self.api.prefill_slotted is None:
                raise NotImplementedError(
                    f"family {getattr(self.api.cfg, 'family', None)!r} has "
                    f"no slotted prefill (prefill_slotted is None)")
            self._prefill_slotted = jax.jit(
                self._shard_logits(self.api.prefill_slotted))
        return self._prefill_slotted

    # ------------------------------------------------------------- task swap
    def switch_task(self, name: str) -> float:
        """Install task scales; returns wall seconds (paper: 'fast').

        Blocks on EVERY swapped leaf (the whole tree), so the reported
        wall time covers the full transfer, not just the first leaf.  In
        mesh mode the old tree is donated — the engine must own its params.
        """
        assert self.bank is not None, "no ScaleBank attached"
        t0 = time.perf_counter()
        self.params = self.bank.switch(self.params, name, ctx=self.ctx,
                                       donate=self.ctx is not None)
        jax.block_until_ready(self.params)
        self.current_task = name
        return time.perf_counter() - t0

    # ------------------------------------------------------------- generate
    def generate(self, tokens: jnp.ndarray, n_new: int,
                 cache_len: Optional[int] = None,
                 prefix: Optional[jnp.ndarray] = None,
                 return_logits: bool = False):
        """Greedy decode (LOCKSTEP baseline). tokens (B, S) → (B, S + n_new).

        ``return_logits`` also returns the (B, n_new, V) f32 logits each
        new token was sampled from: row 0 from the prefill, row i from
        decode step i — what a reference forward is compared against.

        ``prefix``: (B, P, d) per-row prefix state, fed to the prefill
        under the family's ``FamilyCaps.prefix_key`` (vlm image embeddings
        occupy P decoder positions; encdec frames occupy none — the
        cross-KV is its own position-free state).

        ``cache_len`` is validated, not clamped: a dense cache too short
        for the generation would let XLA clamp the out-of-range
        ``dynamic_update_slice`` writes — every overflowing token would
        silently overwrite the LAST KV slot instead of erroring.  The
        deepest write is position prompt+n_new-2 (the final sampled token's
        KV is never written), so prompt+n_new-1 slots suffice.  Ring
        (sliding-window) caches wrap, and position-free caches have no
        capacity at all, so any positive value is legal there.
        """
        self._check_prefix(prefix)
        b, s = tokens.shape
        s_eff = s + self._prefix_rows(prefix)  # decoder positions consumed
        total = s_eff + n_new
        if cache_len is None:
            cache_len = total
        elif cache_len <= 0:
            raise ValueError(
                f"cache_len={cache_len} must be positive (omit it for the "
                f"default prompt+n_new={total})")
        elif (cache_len < total - 1
              and getattr(self.api.cfg, "swa_window", None) is None
              and self._has_seq_leaf()):
            raise ValueError(
                f"cache_len={cache_len} < prompt+n_new-1={total - 1}: a "
                f"dense cache cannot hold the generation; XLA would clamp "
                f"the overflowing writes onto the last KV slot")
        sample = self._sampler(b)
        batch = {"tokens": tokens}
        if prefix is not None:
            batch[self.api.caps.prefix_key] = jnp.asarray(prefix)
        # prefill builds a cache sized to the prompt; re-home it into a
        # cache with decode headroom
        logits, cache = self._prefill(self.params, batch)
        cache = self._grow_cache(cache, b, cache_len, s_eff)
        out = [tokens]
        seen = [logits] if return_logits else None
        tok = sample(logits)[:, None]
        for i in range(n_new):
            out.append(tok)
            if i == n_new - 1:
                break
            logits, cache = self._decode(self.params, cache, tok,
                                         jnp.int32(s_eff + i))
            if seen is not None:
                seen.append(logits)
            tok = sample(logits)[:, None]
        out = jnp.concatenate(out, axis=1)
        return out if seen is None else (out, jnp.stack(seen, axis=1))

    def _init_cache(self, b, cache_len):
        """Decode cache with headroom.  On a mesh it is CREATED sharded per
        ``cache_specs`` (jit with out_shardings, memoized per shape) — an
        eager ``init_cache`` would materialise the whole cache replicated
        on one device, exactly the blow-up the layout exists to avoid, and
        would make step 1 pay a reshard the guarded decode HLO never shows.
        """
        if self.ctx is None:
            return self.api.init_cache(b, cache_len)
        key = (b, cache_len)
        if key not in self._cache_inits:
            abs_full = jax.eval_shape(lambda: self.api.init_cache(b, cache_len))
            self._cache_inits[key] = jax.jit(
                lambda: self.api.init_cache(b, cache_len),
                out_shardings=self._cache_shardings(abs_full, b))
        return self._cache_inits[key]()

    def _grow_cache(self, cache, b, cache_len, s):
        """Re-home a prompt-sized prefill cache into one with headroom.

        The growth axis is the structurally inferred seq dim
        (``dist.sharding.cache_seq_dims``), NEVER the first mismatched dim:
        a first-match pick updates the wrong axis whenever two dims differ
        (batch-padded prompt cache) or the seq extent collides with another
        dim.  Any mismatch beyond the seq dim is a caller error and raises
        — in particular a POSITION-FREE leaf (seq dim -1: recurrent state,
        encdec cross-KV) has no axis to grow and only passes through when
        the shapes already agree.
        """
        full = self._init_cache(b, cache_len)
        sdims = self._cache_dims()[1]

        def place(dst, src, sd):
            if dst.shape == src.shape:
                return src
            mism = [i for i, (a, c) in enumerate(zip(dst.shape, src.shape))
                    if a != c]
            if sd < 0 or mism != [sd]:
                raise ValueError(
                    f"cannot grow cache leaf {src.shape} into {dst.shape}: "
                    f"dims {mism} differ but only the seq dim ({sd}, "
                    f"inferred structurally) may grow")
            # prompt cache occupies the first s slots along the seq axis
            return jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), 0, axis=sd)

        return jax.tree.map(place, full, cache, sdims)

    # ------------------------------------------------- continuous batching
    def open_pool(self, n_slots: int, cache_len: int) -> SlotPool:
        """Allocate the paged KV slot pool (created sharded on a mesh)."""
        return SlotPool(self, n_slots, cache_len)

    def _admit_write(self):
        """Jitted slot write: place a batch-1 prefill cache into slot ``i``
        of the pool cache (donated — the pool is updated in place).  Writes
        key on the STRUCTURAL batch dim per leaf; on a mesh the result is
        re-constrained to ``cache_specs`` so post-admit shardings are the
        guarded layout."""
        if self._admit_jit is None:
            bdims = self._cache_dims()[0]
            ctx = self.ctx

            def write_all(pool_cache, pcache, slot):
                def place(dst, src, bd):
                    if bd < 0:
                        return dst          # no batch dim: shared, untouched
                    starts = [0] * dst.ndim
                    starts[bd] = slot
                    return jax.lax.dynamic_update_slice(
                        dst, src.astype(dst.dtype), starts)
                new = jax.tree.map(place, pool_cache, pcache, bdims)
                if ctx is not None:
                    n = next(l.shape[bd] for l, bd in
                             zip(jax.tree.leaves(new), jax.tree.leaves(bdims))
                             if bd >= 0)
                    new = jax.tree.map(
                        jax.lax.with_sharding_constraint,
                        new, self._cache_shardings(new, n))
                return new
            self._admit_jit = jax.jit(write_all, donate_argnums=(0,))
        return self._admit_jit

    def _check_admit_shapes(self, pool: SlotPool, pcache):
        """Static validation: the prefill cache must be batch-1, must fit
        the pool capacity, and may differ from the pool ONLY on the batch
        and seq dims."""
        bdims, sdims = self._cache_dims()
        for dst, src, bd, sd in zip(jax.tree.leaves(pool.cache),
                                    jax.tree.leaves(pcache),
                                    jax.tree.leaves(bdims),
                                    jax.tree.leaves(sdims)):
            if bd < 0:
                continue
            if src.shape[bd] != 1:
                raise ValueError(f"admit needs a batch-1 prefill cache, got "
                                 f"batch {src.shape[bd]} in {src.shape}")
            if sd >= 0 and src.shape[sd] > dst.shape[sd]:
                raise ValueError(
                    f"prompt cache seq extent {src.shape[sd]} exceeds the "
                    f"pool capacity {dst.shape[sd]}")
            for d in range(len(dst.shape)):
                if d not in (bd, sd) and dst.shape[d] != src.shape[d]:
                    raise ValueError(
                        f"cache leaf {src.shape} does not fit pool leaf "
                        f"{dst.shape}: dim {d} differs (only batch dim {bd} "
                        f"and seq dim {sd} may)")

    def admit(self, pool: SlotPool, request: Request,
              rid: Optional[int] = None,
              task_row: Optional[int] = None,
              bucket: bool = True) -> int:
        """Prefill ``request`` and install it into a free slot. Returns the
        slot index.  The first generated token is sampled here (from the
        prefill logits), exactly as the lockstep path does.

        task_row: resident-stack row holding this request's scales — the
        prefill reads them through ``prefill_slotted`` (and the live
        ``current_task`` scales are NEVER consulted, so no ``switch_task``
        is needed at admit).  ``None`` = prefill from the live tree.

        bucket: right-pad the prompt to a power-of-two length so mixed
        traffic compiles O(log max_len) prefill shapes instead of one per
        distinct length.  Sound only when padded rows stay invisible —
        causal attention hides rows past the last real token and the head
        gathers that row (``last_pos``) — so it silently stays off for
        non-bucketable families (recurrent state integrates every input)
        and sliding-window ring caches (padded writes would wrap onto
        committed rows).  Token streams are unchanged either way.
        """
        with telemetry.span("engine.admit", rid=rid,
                            task_row=task_row) as sp:
            slot = pool.free_slot()
            if slot is None:
                raise RuntimeError("admit: no free slot (evict first)")
            toks = np.asarray(request.tokens, np.int32).reshape(-1)
            s = int(toks.shape[0])
            n_new = int(request.n_new)
            if s < 1 or n_new < 1:
                raise ValueError(f"need prompt >= 1 and n_new >= 1 tokens, "
                                 f"got ({s}, {n_new})")
            prefix = getattr(request, "prefix", None)
            self._check_prefix(prefix)
            # decoder positions the prefix eats
            p_rows = self._prefix_rows(prefix)
            s_eff = s + p_rows
            has_seq = self._has_seq_leaf()
            swa = getattr(self.api.cfg, "swa_window", None) is not None
            if has_seq and not swa and s_eff + n_new - 1 > pool.cache_len:
                raise ValueError(
                    f"request needs {s_eff + n_new - 1} cache slots, pool "
                    f"has {pool.cache_len}")
            if (task_row is None and request.task is not None
                    and self.bank is not None
                    and request.task != self.current_task):
                raise ValueError(
                    f"request targets task {request.task!r} but the engine "
                    f"serves {self.current_task!r}; switch_task first (the "
                    f"scheduler drains the pool before switching)")
            caps = self.api.caps
            bucket = bucket and caps.bucketable and has_seq and not swa
            s_pad = (self._bucket_len(s, pool.cache_len - p_rows) if bucket
                     else s)
            sp.set(prompt=s, bucket=s_pad)
            if s_pad != s:
                toks = np.pad(toks, (0, s_pad - s))   # masked filler rows
            prompt = jnp.asarray(toks)[None]
            if self.ctx is not None:
                prompt = jax.device_put(prompt, self.ctx.sharding())
            batch = {"tokens": prompt}
            if prefix is not None:
                pref = jnp.asarray(np.asarray(prefix))[None]
                if self.ctx is not None:
                    pref = jax.device_put(pref, self.ctx.sharding())
                batch[caps.prefix_key] = pref
            if s_pad != s:
                # traced scalar: every prompt bucketed to s_pad shares one
                # compile; unpadded prompts keep the original batch treedef
                batch["last_pos"] = jnp.int32(p_rows + s - 1)
            pool._prefill_keys.add((s_pad, p_rows, s_pad != s))
            if task_row is not None:
                tid = jnp.full((1,), task_row, jnp.int32)
                if self.ctx is not None:
                    tid = jax.device_put(tid, self.ctx.sharding())
                logits, pcache = self._slotted_prefill_fn()(
                    self.params, self.resident.stack, batch, tid)
            else:
                logits, pcache = self._prefill(self.params, batch)
            self._check_admit_shapes(pool, pcache)
            with telemetry.span("engine.admit.wait", rid=rid):
                t0 = int(np.asarray(self._sampler(1)(logits))[0])
            pool.cache = self._admit_write()(pool.cache, pcache,
                                             jnp.int32(slot))
            pool.pos[slot] = s_eff
            pool.active[slot] = True
            pool.tok[slot] = t0
            pool.task[slot] = request.task or self.current_task
            pool.meta[slot] = {"rid": rid, "request": request, "out": [t0]}
            pool.decoded += 1
            pool._dev = None               # host mirrors changed: re-upload
        return slot

    def _slot_done(self, pool: SlotPool, slot: int) -> bool:
        meta = pool.meta[slot]
        req = meta["request"]
        out = meta["out"]
        return (len(out) >= req.n_new
                or (req.eos_id is not None and out[-1] == req.eos_id))

    def evict(self, pool: SlotPool, slot: int) -> List[int]:
        """Free a slot mid-loop; returns the tokens it generated.  The KV
        rows are NOT cleared — every cache position is rewritten before it
        becomes visible (decode writes position p before attending to it),
        so stale rows can never leak into a later sequence."""
        if not pool.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        out = pool.meta[slot]["out"]
        pool.active[slot] = False
        pool.meta[slot] = None
        pool.task[slot] = None
        pool.tok[slot] = 0
        pool._dev = None                   # host mirrors changed: re-upload
        return out

    def _pool_inputs(self, pool: SlotPool):
        """(tok, pos, active, tid) for the decode step — the device-resident
        copies from the previous step when no scheduling event touched the
        host mirrors, one batched upload otherwise."""
        if pool._dev is not None:
            return pool._dev
        tok = jnp.asarray(pool.tok.reshape(-1, 1))
        pos = jnp.asarray(pool.pos)
        act = jnp.asarray(pool.active)
        tid = jnp.asarray(pool.tid)
        if self.ctx is None:
            return tok, pos, act, tid
        ba = self.ctx.batch_axes(pool.n_slots)
        return jax.device_put(
            (tok, pos, act, tid),
            (self.ctx.sharding(ba, None), self.ctx.sharding(),
             self.ctx.sharding(), self.ctx.sharding()))

    def step(self, pool: SlotPool) -> np.ndarray:
        """One continuous decode step over the whole pool: every slot
        advances by one token at its OWN position; inactive slots compute
        masked garbage (the price of one fixed compiled shape) and emit the
        pad token 0.  Returns the (n_slots,) sampled tokens; host metadata
        (pos/tok/out) is updated for active slots."""
        n_active = pool.n_active()
        if n_active == 0:
            raise ValueError("step: no active slot (admit first)")
        # kv_rows_read: the rows the step's attention reads, the whole pool
        with telemetry.span(
                "engine.step", active=n_active,
                kv_rows_live=int(pool.pos[pool.active].sum()) + n_active,
                kv_rows_read=pool.n_slots * pool.cache_len) as sp:
            tok, pos, act, tid = self._pool_inputs(pool)
            extra = []          # a MoE step's routing stats
            if pool.slotted:
                logits, pool.cache, *extra = self._slotted_decode_fn()(
                    self.params, self.resident.stack, pool.cache, tok, pos,
                    tid)
            else:
                logits, pool.cache = self._decode(self.params, pool.cache,
                                                  tok, pos)
            t, tok2d, npos = self._stepper(pool.n_slots)(logits, act, pos)
            with telemetry.span("engine.step.wait"):
                # the routing stats come with the tokens: one read
                nxt, extra = jax.device_get((t, extra))
            if extra and extra[0] is not None:
                sp.set(**dict(zip(MOE_STEP_STATS, map(int, extra[0]))))
            pool._dev = (tok2d, npos, act, tid)
            pool.steps += 1
            for slot in np.flatnonzero(pool.active):
                meta = pool.meta[slot]
                if self._slot_done(pool, slot):
                    # never happens through serve() — eviction is
                    # immediate — but count it honestly for hand-driven
                    # pools (and fall back to the host mirrors, which now
                    # disagree with the device copies' blind position
                    # advance)
                    pool.bubble_slot_steps += 1
                    pool._dev = None
                    continue
                pool.pos[slot] += 1
                pool.tok[slot] = int(nxt[slot])
                meta["out"].append(int(nxt[slot]))
                pool.decoded += 1
            pool.idle_slot_steps += pool.n_slots - pool.n_active()
        return nxt

    def _resident_supported(self, requests: Sequence[Request]) -> bool:
        """Can the RESIDENT scheduler run this workload?  Needs a ScaleBank,
        a family with a slotted decode step, and every request tasked (an
        untasked request has no stack row to read; an EMPTY workload is
        vacuously tasked — the resolved policy must still be reported
        honestly, see the empty-return in ``serve``)."""
        return (self.bank is not None
                and self._slotted_reason() is None
                and all(r.task is not None for r in requests))

    def _slotted_reason(self) -> Optional[str]:
        """Why this engine cannot run the slotted (resident-stack) prefill
        and decode steps, or None when it can."""
        caps = getattr(self.api, "caps", None)
        if (self.api.decode_step_slotted is None
                or self.api.prefill_slotted is None):
            return (caps.slotted_reason if caps is not None
                    and caps.slotted_reason
                    else "family has no slotted decode step")
        if self.ctx is not None and caps is not None:
            return caps.mesh_slotted_reason
        return None

    def _ensure_resident(self, resident_tasks: int) -> ResidentStack:
        cap = max(2, min(int(resident_tasks), len(self.bank.tasks)))
        if self.resident is None or self.resident.capacity != cap:
            self.resident = ResidentStack(self.bank, self.params, cap,
                                          ctx=self.ctx)
        return self.resident

    def _serve_config(self, config, n_slots, cache_len, scheduler,
                      resident_tasks) -> ServeConfig:
        """Resolve the ``serve`` entry point's arguments to a ServeConfig.

        New API: ``serve(requests, ServeConfig(...))``.  The pre-harness
        keyword sprawl (``n_slots=``, ``cache_len=``, ``scheduler=``,
        ``resident_tasks=``, or n_slots passed positionally) still works
        for one release behind a DeprecationWarning.
        """
        legacy = {k: v for k, v in (("n_slots", n_slots),
                                    ("cache_len", cache_len),
                                    ("scheduler", scheduler),
                                    ("resident_tasks", resident_tasks))
                  if v is not None}
        if isinstance(config, ServeConfig):
            if legacy:
                raise TypeError(
                    f"serve got a ServeConfig AND legacy keyword(s) "
                    f"{sorted(legacy)}; put every knob in the config")
            return config
        if config is not None:          # old positional n_slots
            if "n_slots" in legacy:
                raise TypeError("serve got n_slots twice (positionally "
                                "and by keyword)")
            legacy["n_slots"] = config
        if "n_slots" not in legacy:
            raise TypeError("serve needs a ServeConfig (or the deprecated "
                            "n_slots= keyword)")
        warnings.warn(
            "Engine.serve(requests, n_slots=..., cache_len=..., "
            "scheduler=..., resident_tasks=...) is deprecated: pass "
            "repro.serve.ServeConfig as the second argument",
            DeprecationWarning, stacklevel=3)
        legacy.setdefault("scheduler", "auto")
        legacy.setdefault("resident_tasks", 4)
        return ServeConfig(**legacy)

    def serve(self, requests: Sequence[Request], config=None,
              n_slots: Optional[int] = None,
              cache_len: Optional[int] = None, *,
              scheduler: Optional[str] = None,
              resident_tasks: Optional[int] = None) -> ServeReport:
        """Continuously-batched serving of a request stream.

        ``config`` is a ``repro.serve.ServeConfig`` (pool shape, scheduler,
        admission control, virtual clock); the remaining parameters are the
        deprecated pre-harness spelling (see ``_serve_config``).

        The loop is EVENT-DRIVEN: requests enter a bounded wait queue when
        the clock reaches their arrival (``arrival_s`` against the virtual
        clock — ``step_s`` per decode step, ``admit_cost_s`` per prefill —
        or ``arrival_step`` against the pool step counter), are admitted
        FIFO into free slots, and leave as exactly one of **served** /
        **rejected** (arrival would overflow ``queue_bound``; newest first)
        / **shed** (queue-wait exceeded ``shed_after_s`` by admission
        time).  Each gets a ``RequestMetrics`` row — TTFT, TPOT,
        queue-wait, e2e on the virtual clock — in ``report.requests``.

        Scheduler semantics (docs/DIST.md "Serving", docs/SERVING.md):
          * eviction is immediate on EOS or budget, so a finished sequence
            never occupies a decode step (zero bubble slot-steps);
          * mixed-task traffic, ``config.scheduler`` =

            - ``"drain"`` — a request for a different task than the engine
              currently serves waits until the pool DRAINS, then the scales
              are hot-swapped once (one backbone, one live scale set —
              in-flight sequences must finish under the scales they started
              with).  The wait is metered as
              ``task_drain_idle_slot_steps``.
            - ``"resident"`` — up to ``resident_tasks`` tasks' scales stay
              device-resident stacked ``(T, out, G)`` (``ResidentStack``,
              LRU over stack rows); PREFILL and decode both read each
              request's row in-kernel (``prefill_slotted`` /
              ``decode_step_slotted``), so admission never waits on a task
              mismatch and a task change moves ZERO scale bytes
              host→device (no ``switch_task`` at admit — the stack row IS
              the task's scales, so token-for-token equality with
              ``drain`` is pinned by construction).  The only residual
              wait is a FULL stack of pinned (in-flight) rows —
              impossible when ``resident_tasks`` > n_slots — still metered
              honestly in ``task_drain_idle_slot_steps``.
            - ``"auto"`` — ``resident`` when supported (ScaleBank attached,
              family has a slotted decode step, every request tasked),
              ``drain`` otherwise.

        Requesting ``"resident"`` on an unsupported workload raises;
        ``report.scheduler`` records which policy actually ran — including
        on the empty-workload early return (a hardcoded default here once
        mislabeled validated ``"resident"`` runs as ``"drain"``).
        """
        cfg = self._serve_config(config, n_slots, cache_len, scheduler,
                                 resident_tasks)
        requests = list(requests)
        use_resident = (cfg.scheduler != "drain"
                        and self._resident_supported(requests))
        if cfg.scheduler == "resident" and not use_resident:
            missing = ("no ScaleBank attached" if self.bank is None
                       else self._slotted_reason()
                       or "not every request names a task")
            raise ValueError(f"scheduler='resident' unsupported here: "
                             f"{missing}")
        sched_name = "resident" if use_resident else "drain"
        step_s, admit_cost = cfg.step_s, cfg.admit_cost_s
        metrics = [RequestMetrics(rid=i, task=r.task,
                                  arrival_s=r.arrival_time(step_s),
                                  n_prompt=r.n_prompt,
                                  n_budget=int(r.n_new))
                   for i, r in enumerate(requests)]
        if not requests:
            return ServeReport(requests=[], scheduler=sched_name,
                               config=cfg)
        eff_cache_len = cfg.cache_len
        if eff_cache_len is None:
            # prefix rows (vlm image tokens) share the slot's cache capacity
            eff_cache_len = max(
                self._prefix_rows(getattr(r, "prefix", None))
                + r.n_prompt + int(r.n_new) for r in requests)
        if use_resident:
            self._slotted_decode_fn()           # raise early if unsupported
            resident = self._ensure_resident(cfg.resident_tasks)
            installs0 = resident.installs
        # event-driven arrival feed: requests sit in ``arrivals`` until the
        # clock reaches them, then move through the bounded wait queue —
        # nothing is pre-admitted from a sorted list
        arrivals = deque(sorted(range(len(requests)),
                                key=lambda i: (metrics[i].arrival_s, i)))
        waitq: deque = deque()
        pool = self.open_pool(cfg.n_slots, eff_cache_len)
        pool.slotted = use_resident
        switches = 0
        peak_queue = 0
        now = 0.0                       # virtual seconds
        eps = 1e-9
        # --- tiered-bank bookkeeping (docs/SERVING.md "Tiered ScaleBank").
        # Real byte movement (npz deserialize, stack-row install) runs
        # eagerly at issue time; the VIRTUAL clock models each move's cost
        # (``disk_load_s`` on one serialized disk lane, ``install_s`` per
        # row write) and charges a request only the remainder the
        # prefetcher failed to hide before it reached the head.
        bank = self.bank
        tiering = bank is not None and hasattr(bank, "stats")
        if tiering and cfg.host_cache_tasks is not None:
            bank.host_capacity = cfg.host_cache_tasks
        stats0 = bank.stats.as_dict() if tiering else {}
        vhost_ready: dict = {}      # task -> virtual host-resident time
        vdev_ready: dict = {}       # task -> virtual resident-row-ready time
        disk_lane = 0.0             # virtual disk busy-until
        pf_cost: dict = {}          # task -> unattributed prefetch spend
        tier_hits = {"device": 0, "host": 0, "disk": 0}
        prefetch_issued = 0
        prefetch_hidden = 0.0
        t0 = time.perf_counter()

        def due(rid: int) -> bool:
            r = requests[rid]
            if r.arrival_s is not None:
                return metrics[rid].arrival_s <= now + eps
            return r.arrival_step <= pool.steps

        def steps_until_due() -> int:
            """Idle decode steps to jump so the earliest arrival is due."""
            rid = arrivals[0]
            r = requests[rid]
            if r.arrival_s is not None:
                return max(1, math.ceil(
                    (metrics[rid].arrival_s - now - eps) / step_s))
            return max(1, r.arrival_step - pool.steps)

        def finish_slot(slot: int) -> None:
            meta = pool.meta[slot]
            m = metrics[meta["rid"]]
            m.tokens = [int(t) for t in self.evict(pool, slot)]
            m.status = SERVED
            m.finish_s = now

        def host_was_ready(t: str) -> bool:
            """Payload host-resident AND virtually landed by ``now``?"""
            return (bank.loaded(t)
                    and vhost_ready.get(t, 0.0) <= now + eps)

        def host_ready(t: str) -> float:
            """Virtual time ``t``'s payload is host-resident, issuing the
            real disk load (and its lane slot) when it is not.  Idempotent
            — an entry evicted from the host tier after a prefetch (the
            prefetch-then-evict race) just reloads on the lane."""
            nonlocal disk_lane
            if bank.loaded(t):
                return max(0.0, vhost_ready.get(t, 0.0))
            bank.prefetch(t)    # a quarantined/unknown task surfaces as
            # KeyError at the ensure/switch below, not here
            start = max(now, disk_lane)
            disk_lane = start + cfg.disk_load_s
            vhost_ready[t] = disk_lane
            return disk_lane

        def attribute_swap(m, tier: str, wait: float) -> None:
            """Meter one admit's tier + charged swap remainder, crediting
            the prefetcher for whatever it hid."""
            nonlocal now, prefetch_hidden
            spent = pf_cost.pop(m.task, 0.0)
            prefetch_hidden += max(0.0, spent - wait)
            tier_hits[tier] += 1
            m.scale_tier = tier
            m.swap_wait_s = wait
            now += wait

        def prefetch_tick() -> None:
            """Warm the next ``prefetch_depth`` distinct upcoming tasks
            (wait queue first, then pending arrivals): disk→host on the
            virtual lane, then host→device once the payload has virtually
            landed (resident scheduler only).  Runs between admissions and
            the decode step, so the costs it books overlap decode/idle
            time — the admit path charges only what is still in flight."""
            nonlocal disk_lane, prefetch_issued
            if not tiering or cfg.prefetch_depth == 0:
                return
            upcoming: List[str] = []
            for rid in (*waitq, *arrivals):
                t = requests[rid].task
                if t is not None and t not in upcoming:
                    upcoming.append(t)
                if len(upcoming) >= cfg.prefetch_depth:
                    break
            for t in upcoming:
                if t not in bank.tasks:     # unknown or quarantined
                    continue
                if not bank.loaded(t):
                    if not bank.prefetch(t):
                        continue            # quarantined on this very load
                    start = max(now, disk_lane)
                    disk_lane = start + cfg.disk_load_s
                    vhost_ready[t] = disk_lane
                    pf_cost[t] = pf_cost.get(t, 0.0) + cfg.disk_load_s
                    prefetch_issued += 1
                if (use_resident and t not in resident.names
                        and vhost_ready.get(t, 0.0) <= now + eps):
                    # pin in-flight tasks AND the other upcoming ones, so a
                    # deep prefetch window never thrashes its own rows
                    pinned = {pool.task[s]
                              for s in np.flatnonzero(pool.active)}
                    pinned |= set(upcoming) - {t}
                    if resident.ensure(t, pinned=pinned) is not None:
                        vdev_ready[t] = now + cfg.install_s
                        pf_cost[t] = pf_cost.get(t, 0.0) + cfg.install_s
                        prefetch_issued += 1

        while arrivals or waitq or pool.n_active():
            # 1. arrivals whose time has come enter the wait queue
            while arrivals and due(arrivals[0]):
                waitq.append(arrivals.popleft())
            # 2. FIFO admission, shedding stale requests at consideration
            blocked_by_task = False
            while waitq:
                rid = waitq[0]
                m = metrics[rid]
                if (cfg.shed_after_s is not None
                        and now - m.arrival_s > cfg.shed_after_s + eps):
                    waitq.popleft()
                    m.status = SHED
                    continue
                if pool.free_slot() is None:
                    break
                req = requests[rid]
                if use_resident:
                    t = req.task
                    pinned = {pool.task[s]
                              for s in np.flatnonzero(pool.active)}
                    if t in resident.names:
                        # row already installed (warm start, earlier admit,
                        # or the prefetcher); charge only an install still
                        # virtually in flight — a true DEVICE hit waits 0
                        wait = max(0.0, vdev_ready.get(t, 0.0) - now)
                        tier = "device" if wait <= eps else "host"
                        row = resident.ensure(t, pinned=pinned)  # LRU touch
                    else:
                        was_host = tiering and host_was_ready(t)
                        hr = host_ready(t) if tiering else now
                        row = resident.ensure(t, pinned=pinned)
                        if row is not None:
                            wait = max(0.0, hr - now) + cfg.install_s
                            tier = "host" if was_host else "disk"
                            vdev_ready[t] = now + wait
                    if row is None:         # every row pinned by in-flight
                        blocked_by_task = True
                        break
                    # the prefill reads this stack row directly
                    # (prefill_slotted) — a task change at admit moves ZERO
                    # scale bytes host→device and the pool never drains
                    waitq.popleft()
                    attribute_swap(m, tier, wait)
                    m.admit_s = now
                    now += admit_cost
                    slot = self.admit(pool, req, rid=rid, task_row=row,
                                      bucket=cfg.bucket_prompts)
                    m.first_token_s = now
                    pool.tid[slot] = row
                    pool._dev = None
                else:
                    tier = None
                    wait = 0.0
                    if (req.task is not None and self.bank is not None
                            and req.task != self.current_task):
                        if pool.n_active():
                            blocked_by_task = True
                            break           # drain, then swap scales once
                        if tiering:
                            was_host = host_was_ready(req.task)
                            hr = host_ready(req.task)
                            wait = max(0.0, hr - now) + cfg.install_s
                            tier = "host" if was_host else "disk"
                        self.switch_task(req.task)
                        switches += 1
                    elif req.task is not None and tiering:
                        tier = "device"     # scales already live — no swap
                    waitq.popleft()
                    if tier is not None:
                        attribute_swap(m, tier, wait)
                    m.admit_s = now
                    now += admit_cost
                    slot = self.admit(pool, req, rid=rid,
                                      bucket=cfg.bucket_prompts)
                    m.first_token_s = now
                if self._slot_done(pool, slot):
                    finish_slot(slot)
            # 3. backpressure: arrivals past the queue bound are REJECTED,
            #    newest first, so overload degrades instead of queueing
            #    unboundedly (every outcome stays accounted)
            if cfg.queue_bound is not None:
                while len(waitq) > cfg.queue_bound:
                    metrics[waitq.pop()].status = REJECTED
            peak_queue = max(peak_queue, len(waitq))
            # 3b. warm upcoming tasks' tiers while the pool decodes (or the
            #     clock jumps) — the swap cost a request pays at the head
            #     is only whatever of this is still in flight
            prefetch_tick()
            # 4. advance: decode if anything is live, else jump the clock
            #    to the next arrival
            if pool.n_active() == 0:
                if not arrivals:
                    if waitq:
                        # unreachable by construction: with an idle pool the
                        # admission loop admits (task blocks need in-flight
                        # slots) — fail loudly rather than spin forever
                        raise RuntimeError(
                            f"serve: wait queue stuck with an idle pool "
                            f"({len(waitq)} waiting)")
                    break
                k = steps_until_due()
                pool.steps += k
                pool.idle_slot_steps += k * pool.n_slots
                now += k * step_s
                continue
            n_act = pool.n_active()
            self.step(pool)
            now += step_s
            if blocked_by_task:
                # the free slots this step could have hosted the blocked
                # request — the drain tax the resident scheduler deletes
                pool.task_drain_idle_slot_steps += pool.n_slots - n_act
            for slot in np.flatnonzero(pool.active):
                if self._slot_done(pool, slot):
                    finish_slot(slot)
        return ServeReport(
            requests=metrics, steps=pool.steps, decoded=pool.decoded,
            bubble_slot_steps=pool.bubble_slot_steps,
            idle_slot_steps=pool.idle_slot_steps,
            switches=switches, wall_s=time.perf_counter() - t0,
            task_drain_idle_slot_steps=pool.task_drain_idle_slot_steps,
            resident_installs=(resident.installs - installs0
                               if use_resident else 0),
            prefill_compiles=len(pool._prefill_keys),
            tier_device_hits=tier_hits["device"],
            tier_host_hits=tier_hits["host"],
            tier_disk_loads=tier_hits["disk"],
            prefetch_issued=prefetch_issued,
            prefetch_hidden_s=prefetch_hidden,
            bank_disk_loads=(bank.stats.disk_loads - stats0["disk_loads"]
                             if tiering else 0),
            bank_host_evictions=(
                bank.stats.host_evictions - stats0["host_evictions"]
                if tiering else 0),
            scheduler=sched_name, peak_queue_depth=peak_queue, config=cfg)

    # ------------------------------------------------------------ introspect
    def _decode_hlo(self, b: int, cache_len: int, pos_aval) -> str:
        def absr(l):
            if isinstance(l, jax.Array):
                return jax.ShapeDtypeStruct(l.shape, l.dtype,
                                            sharding=l.sharding)
            return l
        aparams = jax.tree.map(absr, self.params)
        acache = jax.eval_shape(lambda: self.api.init_cache(b, cache_len))
        if self.ctx is not None:
            # lower against the cache layout the runtime loop settles into,
            # so the guarded HLO is the executed HLO
            acache = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                  sharding=s),
                acache, self._cache_shardings(acache, b))
        tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        return self._decode.lower(aparams, acache, tok, pos_aval
                                  ).compile().as_text()

    def decode_hlo(self, b: int, cache_len: int) -> str:
        """Compiled HLO of one LOCKSTEP decode step at batch ``b`` — what
        the tests and the serve-smoke CI job scan for vocab all-gathers."""
        return self._decode_hlo(b, cache_len,
                                jax.ShapeDtypeStruct((), jnp.int32))

    def continuous_decode_hlo(self, n_slots: int, cache_len: int) -> str:
        """Compiled HLO of one CONTINUOUS decode step (per-slot position
        vector) over an ``n_slots`` pool — the same guard surface: under
        ``logitshard`` it must contain zero vocab-extent all-gathers."""
        return self._decode_hlo(n_slots, cache_len,
                                jax.ShapeDtypeStruct((n_slots,), jnp.int32))

    def slotted_decode_hlo(self, n_slots: int, cache_len: int,
                           resident_tasks: int = 4) -> str:
        """Compiled HLO of one MIXED-TASK decode step (stacked scales +
        per-slot task ids) — the resident scheduler's guard surface.  Same
        collective rules as ``continuous_decode_hlo`` apply; the stacked
        scales additionally must introduce no new gather collectives (the
        row select is shard-local: the task dim is replicated and the scale
        out-dim sharding matches the plain scales')."""
        if self.bank is None:
            raise ValueError("slotted_decode_hlo needs a ScaleBank")
        resident = self._ensure_resident(resident_tasks)

        def absr(l):
            if isinstance(l, jax.Array):
                return jax.ShapeDtypeStruct(l.shape, l.dtype,
                                            sharding=l.sharding)
            return l
        aparams = jax.tree.map(absr, self.params)
        astack = jax.tree.map(absr, resident.stack)
        acache = jax.eval_shape(
            lambda: self.api.init_cache(n_slots, cache_len))
        if self.ctx is not None:
            acache = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                  sharding=s),
                acache, self._cache_shardings(acache, n_slots))
        tok = jax.ShapeDtypeStruct((n_slots, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
        tid = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
        return self._slotted_decode_fn().lower(
            aparams, astack, acache, tok, pos, tid).compile().as_text()
