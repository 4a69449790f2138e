"""ScaleBank — the paper's task-switching story made concrete.

One frozen integer backbone, N tasks, each task = {path: scale array}
(plus zero-points for the peqa_z ablation).  Swapping tasks is an O(MBs)
pytree update — benchmarks/kernel_bench.py measures it vs full-model reload,
and train/serve.py uses it to serve multiple PEQA-tuned tasks from one
backbone in the same batch-serving process.

On a mesh the swap is SHARDED: each scale is ``device_put`` with its
``dist.sharding`` spec, so every device receives only its local slice
(column-parallel scales) or one small copy (replicated row-parallel
scales) — the layout guarantees no resharding collective (docs/DIST.md,
"Serving").  Installation into the param tree runs as a jitted pass-through
that DONATES the old tree, so the old scale buffers are freed in place and
a swap never holds two copies of anything bigger than one scale set.
"""
from __future__ import annotations

import os
import warnings
from collections import OrderedDict
from collections.abc import MutableMapping
from typing import Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.treepath import path_str as _path_str
from repro.serve import telemetry

SCALE_KEYS = ("scale", "zero")


def task_stack_dim(rank: int) -> int:
    """Axis the task dim occupies when stacking a scale leaf of ``rank``.

    Scale leaves always end in an (out, G) pair — per-layer ``(out, G)``
    or stacked-over-layers ``(L, out, G)`` — so the task dim sits at
    ``rank - 2``, just before that pair.  ``stack_scales`` (building the
    stack) and ``_stack_row_install`` (writing one task's row back into
    it) MUST agree on this axis; both route through here.  A rank < 2
    leaf has no (out, G) pair to sit behind — the old
    ``max(0, rank - 2)`` / ``ndim - 3`` pair silently disagreed there
    (row installs landed on the wrong axis), so refuse it loudly.
    """
    if rank < 2:
        raise ValueError(
            f"scale leaf of rank {rank} cannot carry a task dim: scale "
            f"leaves must end in an (out, G) pair (rank >= 2)")
    return rank - 2


def extract_scales(params: dict, include_zero: bool = False) -> Dict[str, np.ndarray]:
    """Pull every quantization scale (the task-specific parameters).

    Gathers to host numpy — on a mesh this all-gathers each (tiny) scale
    once at ``add`` time; swaps never call this.
    """
    keys = SCALE_KEYS if include_zero else ("scale",)
    out = {}

    def visit(kp, leaf):
        path = _path_str(kp)
        if path.split("/")[-1] in keys and "qw_sibling" not in path:
            out[path] = np.asarray(jax.device_get(leaf))
    jax.tree_util.tree_map_with_path(visit, params)
    return out


def _check_shapes(params: dict, scales: Dict[str, np.ndarray]):
    def check(kp, leaf):
        path = _path_str(kp)
        if path in scales and tuple(scales[path].shape) != tuple(leaf.shape):
            raise ValueError(f"scale shape mismatch at {path}: "
                             f"{tuple(scales[path].shape)} vs {leaf.shape}")
    jax.tree_util.tree_map_with_path(check, params)


def _install(params: dict, scales: dict) -> dict:
    """Replace scale leaves; everything else passes through (aliased under
    donation).  Pure rewiring — its HLO must contain zero collectives."""
    def replace(kp, leaf):
        path = _path_str(kp)
        if path in scales:
            return scales[path].astype(leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(replace, params)


_install_jit = jax.jit(_install)
_install_jit_donate = jax.jit(_install, donate_argnums=(0,))


def put_scales(scales: Dict[str, np.ndarray], ctx) -> dict:
    """Home a host scale set on the mesh with its partition specs — one
    BATCHED ``device_put`` so the per-shard local transfers overlap instead
    of serializing leaf by leaf (this is the swap hot path)."""
    from repro.dist import sharding as shard_rules
    shardings = {
        path: ctx.sharding(*shard_rules.spec_for_path(path, np.ndim(arr)))
        for path, arr in scales.items()}
    return jax.device_put({p: np.asarray(a) for p, a in scales.items()},
                          shardings)


def apply_scales(params: dict, scales: Dict[str, np.ndarray],
                 ctx=None, donate: bool = False) -> dict:
    """Install a task's scales into the (shared-backbone) param tree.

    Off-mesh (``ctx is None``) this is the original host path: new jnp
    leaves for the scales, shared references for everything else.  With a
    ``dist.context.MeshContext`` the scales are ``device_put`` per-spec
    (local bytes only) and installed by the jitted pass-through;
    ``donate=True`` additionally donates the old tree so the swap has no
    transient second copy (callers must own ``params`` outright).
    """
    _check_shapes(params, scales)
    if ctx is None:
        def replace(kp, leaf):
            path = _path_str(kp)
            if path in scales:
                return jnp.asarray(scales[path],
                                   dtype=jnp.asarray(leaf).dtype)
            return leaf
        return jax.tree_util.tree_map_with_path(replace, params)
    dev = put_scales(scales, ctx)
    fn = _install_jit_donate if donate else _install_jit
    return fn(params, dev)


def swap_hlo(params: dict, scales: Dict[str, np.ndarray], ctx) -> str:
    """Compiled HLO of the sharded install for ``params``/``scales`` —
    what the serve-smoke CI job and the sharding tests scan for resharding
    collectives (there must be none: the scale layout is swap-aligned).

    Lowers the DONATED install (the variant the serving hot path runs)
    against fully abstract inputs — no scale bytes actually move.
    """
    from repro.dist import sharding as shard_rules
    adev = {path: jax.ShapeDtypeStruct(
                np.shape(arr), np.asarray(arr).dtype,
                sharding=ctx.sharding(
                    *shard_rules.spec_for_path(path, np.ndim(arr))))
            for path, arr in scales.items()}
    aparams = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=l.sharding)
        if isinstance(l, jax.Array) else l, params)
    return _install_jit_donate.lower(aparams, adev).compile().as_text()


def _nest_paths(flat: Dict[str, np.ndarray]) -> dict:
    """{'a/b/c': arr} → {'a': {'b': {'c': arr}}} (the pruned params mirror)."""
    out: dict = {}
    for path, arr in flat.items():
        node = out
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = arr
    return out


def stack_scales(base: Dict[str, np.ndarray],
                 task_sets: Sequence[Dict[str, np.ndarray]]) -> dict:
    """Build the task-stacked scale pytree the slotted decode step consumes.

    ``base`` is ``extract_scales(params, include_zero=True)`` — the backbone's
    own scale/zero leaves, which double as the fallback row for any path a
    task set lacks (banks store scales only by default, so zero-points ride
    along frozen).  Each leaf gains a task dim just before the trailing
    (out, G) pair: a stacked-over-layers (L, N, G) leaf becomes (L, T, N, G),
    so ``lax.scan`` slices a (T, N, G) stack per layer — exactly the operand
    ``quant_gemv_pallas``'s in-kernel task gather wants; an expert-stacked
    (L, E, N, G) leaf becomes (L, E, T, N, G), each layer's (E, T, N, G)
    what ``quant_gmm_pallas`` takes.  Returned NESTED
    (mirroring the params tree pruned to scale leaves), host numpy.
    """
    flat = {}
    for path, b in base.items():
        b = np.asarray(b)
        rows = []
        for ts in task_sets:
            a = np.asarray(ts.get(path, b), dtype=b.dtype)
            if a.shape != b.shape:
                raise ValueError(f"scale shape mismatch at {path}: "
                                 f"{a.shape} vs {b.shape}")
            rows.append(a)
        flat[path] = np.stack(rows, axis=task_stack_dim(b.ndim))
    return _nest_paths(flat)


def _stack_row_install(stack: dict, rows: dict, idx) -> dict:
    """Donated write of ONE task's scale rows into stack row ``idx`` — the
    resident-stack analogue of the swap install.  Every leaf updates along
    its (replicated) task dim, so like ``_install`` the compiled HLO must
    contain zero collectives; ``idx`` is traced, so LRU rotation never
    recompiles."""
    def upd(dst, src):
        ax = task_stack_dim(src.ndim)   # same axis stack_scales stacked on
        starts = [jnp.int32(0)] * dst.ndim
        starts[ax] = jnp.int32(idx)
        return jax.lax.dynamic_update_slice(
            dst, jnp.expand_dims(src, ax).astype(dst.dtype), starts)
    return jax.tree.map(upd, stack, rows)


_stack_row_install_jit = jax.jit(_stack_row_install, donate_argnums=(0,))


class ResidentStack:
    """Device-resident stacked scale sets for the k hottest serving tasks.

    The drain-free mixed-task decode path (train/serve.py ``scheduler=
    'resident'``) reads per-slot scales from ``stack`` — the params tree
    pruned to scale/zero leaves with a task dim of extent ``capacity`` —
    instead of the live single-task set, so admitting a request for another
    task never drains the pool.  ``names[r]`` maps row r → resident task.
    A miss evicts the least-recently-used row NOT pinned by an in-flight
    slot and installs the new task through the same per-spec ``device_put``
    + donated jitted write the swap path uses: per-shard bytes only, no
    transient second stack.  ``ensure`` returns None when every row is
    pinned — the scheduler decodes one step and retries.
    """

    def __init__(self, bank: "ScaleBank", params: dict, capacity: int,
                 ctx=None, warm: Sequence[str] = ()):
        if capacity < 1:
            raise ValueError("ResidentStack needs capacity >= 1")
        self.bank = bank
        self.capacity = int(capacity)
        self.ctx = ctx
        # host snapshot NOW: params' scale buffers may later be donated away
        # by switch_task installs
        self._base = extract_scales(params, include_zero=True)
        warm = list(warm)
        if len(set(warm)) != len(warm):
            dupes = sorted({w for w in warm if warm.count(w) > 1})
            raise ValueError(
                f"ResidentStack: duplicate warm task(s) {dupes} — a "
                f"duplicated warm name would occupy two rows but only the "
                f"first is ever looked up, leaving a dead row for the "
                f"stack's lifetime")
        unknown = [w for w in warm if w not in bank.tasks]
        if unknown:
            warnings.warn(
                f"ResidentStack: dropping warm task(s) {unknown} not in "
                f"the bank", RuntimeWarning, stacklevel=2)
        warm = [w for w in warm if w in bank.tasks][: self.capacity]
        self.names: List[Optional[str]] = (
            warm + [None] * (self.capacity - len(warm)))
        sets = [bank.tasks[n] if n is not None else self._base
                for n in self.names]
        host = stack_scales(self._base, sets)
        self.stack = self._put(host)
        self._lru: List[int] = list(range(self.capacity))  # least-recent first
        self.installs = 0

    def _put(self, tree: dict):
        if self.ctx is None:
            return jax.tree.map(jnp.asarray, tree)
        from repro.dist import sharding as shard_rules
        return jax.device_put(
            tree, shard_rules.stacked_scale_shardings(self.ctx, tree))

    def _rows_for(self, name: str) -> dict:
        task = self.bank.tasks[name]
        flat = {}
        for path, b in self._base.items():
            a = np.asarray(task.get(path, b), dtype=b.dtype)
            if a.shape != b.shape:
                raise ValueError(f"scale shape mismatch at {path}: "
                                 f"{a.shape} vs {b.shape}")
            flat[path] = a
        return _nest_paths(flat)

    def _touch(self, row: int):
        self._lru.remove(row)
        self._lru.append(row)

    def ensure(self, name: str, pinned: Iterable[str] = ()) -> Optional[int]:
        """Row serving ``name``, installing on a miss (LRU, pin-aware)."""
        if name not in self.bank.tasks:
            raise KeyError(f"no task {name!r}; have {list(self.bank.tasks)}")
        if name in self.names:
            row = self.names.index(name)
            self._touch(row)
            return row
        pinned = set(pinned)
        victim = next((r for r in self._lru if self.names[r] is None), None)
        if victim is None:
            victim = next(
                (r for r in self._lru if self.names[r] not in pinned), None)
        if victim is None:
            return None
        with telemetry.span("stack.install", task=name, row=victim) as sp:
            host = self._rows_for(name)
            sp.set(bytes=sum(a.nbytes for a in jax.tree.leaves(host)))
            rows = self._put(host)
            self.stack = _stack_row_install_jit(self.stack, rows,
                                                jnp.int32(victim))
        self.names[victim] = name
        self._touch(victim)
        self.installs += 1
        return victim

    def install_hlo(self, name: str) -> str:
        """Compiled HLO of the donated row install — guarded like swap_hlo:
        the stacked layout must make every install collective-free."""
        from repro.dist import sharding as shard_rules

        def absr(tree):
            if self.ctx is None:
                return jax.tree.map(
                    lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
            sh = shard_rules.stacked_scale_shardings(self.ctx, tree)
            return jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                tree, sh)

        astack = absr(self.stack)
        arows = absr(self._rows_for(name))
        aidx = jax.ShapeDtypeStruct((), jnp.int32)
        return _stack_row_install_jit.lower(
            astack, arows, aidx).compile().as_text()


class TaskStoreStats:
    """Cumulative counters for one ``_TaskStore`` (reset never; callers
    snapshot and diff).  ``payload_bytes_loaded`` is the total npz payload
    deserialized from disk — ZERO right after ``ScaleBank(root)`` opens,
    however many tasks sit on disk (the lazy-init contract the tiering
    bench gates)."""

    def __init__(self):
        self.host_hits = 0          # __getitem__ served from the host tier
        self.disk_loads = 0         # npz payloads deserialized on demand
        self.host_evictions = 0     # disk-backed sets dropped under pressure
        self.payload_bytes_loaded = 0

    def as_dict(self) -> Dict[str, int]:
        return {"host_hits": self.host_hits, "disk_loads": self.disk_loads,
                "host_evictions": self.host_evictions,
                "payload_bytes_loaded": self.payload_bytes_loaded}


class _TaskStore(MutableMapping):
    """Tier 1 + tier 2 of the bank: bounded host LRU over deserialized
    scale sets, backed by a lazy disk index.

    ``__contains__`` / ``__len__`` / ``__iter__`` answer from the INDEX
    (filenames scanned once at init) — no payload touches.  ``store[name]``
    is the promotion path: host hit (LRU touch) or disk load (deserialize,
    insert, evict the least-recently-used DISK-BACKED set past
    ``host_capacity``).  Sets assigned directly (``store[name] = scales``
    with no backing file) are never evicted — they cannot be reloaded.

    A file that fails to deserialize quarantines THAT task (dropped from
    the index with a warning, ``KeyError`` on access) instead of refusing
    the whole bank — one crashed half-written ``add`` must not take every
    other task down with it.
    """

    def __init__(self, root: Optional[str] = None,
                 host_capacity: Optional[int] = None):
        self.root = root
        self.host_capacity = host_capacity
        # host tier, least-recently-used first (move_to_end on touch)
        self._host: "OrderedDict[str, Dict[str, np.ndarray]]" = OrderedDict()
        self._disk: Dict[str, str] = {}        # name -> npz path (tier 2)
        self.quarantined: Dict[str, str] = {}  # name -> load error
        self.stats = TaskStoreStats()
        if root:
            os.makedirs(root, exist_ok=True)
            for f in sorted(os.listdir(root)):
                if f.endswith(".npz"):
                    self._disk[f[:-4]] = os.path.join(root, f)

    # ---------------------------------------------------------- mapping
    def __contains__(self, name) -> bool:
        return name in self._host or name in self._disk

    def __len__(self) -> int:
        n = len(self._disk)
        return n + sum(1 for k in self._host if k not in self._disk)

    def __iter__(self):
        yield from self._disk
        yield from (k for k in self._host if k not in self._disk)

    def __getitem__(self, name: str) -> Dict[str, np.ndarray]:
        if name in self._host:
            self._host.move_to_end(name)
            self.stats.host_hits += 1
            return self._host[name]
        self.load(name)
        return self._host[name]

    def __setitem__(self, name: str, scales: Dict[str, np.ndarray]):
        self._host[name] = scales
        self._host.move_to_end(name)
        self.quarantined.pop(name, None)
        self._evict()

    def __delitem__(self, name: str):
        found = name in self._host or name in self._disk
        self._host.pop(name, None)
        self._disk.pop(name, None)      # drops the index entry, not the file
        if not found:
            raise KeyError(name)

    # ---------------------------------------------------------- tiering
    def loaded(self, name: str) -> bool:
        """Host-resident (tier 1 or unbacked) — answers without loading."""
        return name in self._host

    def load(self, name: str, path: Optional[str] = None) -> None:
        """Promote ``name`` disk→host (no-op when already host-resident).

        A corrupt/unreadable file quarantines the task: warning, dropped
        from the disk index, ``KeyError`` — the rest of the bank serves on.
        """
        if name in self._host:
            return
        if path is None:
            if name not in self._disk:
                raise KeyError(name)
            path = self._disk[name]
        try:
            with np.load(path) as z:
                # eager reads, then CLOSE: a bare dict(np.load(path)) keeps
                # the NpzFile handle open for the life of the process — one
                # leaked fd per task touched
                scales = {k: z[k] for k in z.files}
        except Exception as e:
            self.quarantined[name] = str(e)
            self._disk.pop(name, None)
            warnings.warn(
                f"ScaleBank: quarantining task {name!r} — corrupt or "
                f"unreadable file {path!r}: {e}", RuntimeWarning,
                stacklevel=2)
            raise KeyError(
                f"task {name!r} quarantined: corrupt or unreadable file "
                f"{path!r}: {e}") from e
        self.stats.disk_loads += 1
        self.stats.payload_bytes_loaded += sum(
            a.nbytes for a in scales.values())
        self._host[name] = scales
        self._host.move_to_end(name)
        self._evict()

    def _evict(self) -> None:
        """Shrink the host tier to ``host_capacity``, LRU-first, skipping
        unbacked sets (no file to reload them from) and the most recent
        entry (the one the caller is about to use)."""
        if self.host_capacity is None:
            return
        while len(self._host) > self.host_capacity:
            victim = next(
                (k for k in self._host
                 if k in self._disk and k != next(reversed(self._host))),
                None)
            if victim is None:
                return
            del self._host[victim]
            self.stats.host_evictions += 1


class ScaleBank:
    """Tiered per-task scale store: bounded host cache over a lazy disk
    index (plus the device tier, ``ResidentStack``, built on top).

    ``ScaleBank(root)`` scans FILENAMES only — opening a bank with a
    million task files touches zero task payloads.  ``bank.tasks`` keeps
    its dict shape (``in`` / ``len`` / iteration answer from the index;
    ``bank.tasks[name]`` promotes disk→host on demand), so pre-tiering
    callers and direct ``bank.tasks[name] = scales`` injection still work.
    ``host_capacity`` bounds tier 1 (LRU over deserialized sets; ``None``
    = unbounded, the pre-tiering memory behavior once everything has been
    touched).
    """

    def __init__(self, root: str | None = None,
                 host_capacity: Optional[int] = None):
        self.root = root
        self.tasks = _TaskStore(root, host_capacity=host_capacity)

    @property
    def host_capacity(self) -> Optional[int]:
        return self.tasks.host_capacity

    @host_capacity.setter
    def host_capacity(self, cap: Optional[int]):
        self.tasks.host_capacity = cap
        self.tasks._evict()

    @property
    def stats(self) -> TaskStoreStats:
        return self.tasks.stats

    @property
    def quarantined(self) -> Dict[str, str]:
        return self.tasks.quarantined

    def loaded(self, name: str) -> bool:
        """Host-resident already?  Never triggers a load."""
        return self.tasks.loaded(name)

    def prefetch(self, name: str) -> bool:
        """Warm ``name`` disk→host ahead of use.  True when the task is
        host-resident after the call; False (no raise) when it is unknown
        or quarantines on load — the prefetch path must never take the
        serving loop down for a task that may get shed anyway."""
        try:
            self.tasks.load(name)
        except KeyError:
            return False
        return True

    def warm_all(self) -> int:
        """Eagerly load every indexed task (the pre-tiering init behavior;
        quarantined files are skipped with their warning).  Returns the
        number of tasks host-resident afterwards — the tiered-vs-eager
        equality tests serve from a bank warmed through here."""
        for name in list(self.tasks._disk):
            self.prefetch(name)
        return sum(1 for _ in self.tasks)

    def add(self, name: str, params: dict, include_zero: bool = False):
        scales = extract_scales(params, include_zero)
        self.tasks[name] = scales
        if self.root:
            path = os.path.join(self.root, f"{name}.npz")
            tmp = f"{path}.tmp.{os.getpid()}"
            try:
                # write-then-rename: np.savez straight to the final path
                # would leave a truncated npz if the process dies mid-write,
                # poisoning every later ScaleBank(root) open of this task.
                # savez gets the open handle, not the name — handed a str
                # it appends ".npz", and the tmp name must stay outside
                # what the init scan indexes
                with open(tmp, "wb") as f:
                    np.savez(f, **scales)
                os.replace(tmp, path)
            finally:
                if os.path.exists(tmp):
                    os.remove(tmp)
            self.tasks._disk[name] = path

    def switch(self, params: dict, name: str,
               ctx=None, donate: bool = False) -> dict:
        if name not in self.tasks:
            raise KeyError(f"no task {name!r}; have {list(self.tasks)}")
        return apply_scales(params, self.tasks[name], ctx=ctx, donate=donate)

    def nbytes(self, name: str) -> int:
        return sum(a.nbytes for a in self.tasks[name].values())

    def local_nbytes(self, name: str, ctx: Optional[object] = None) -> int:
        """Bytes one device receives in a swap, computed from the actual
        ADDRESSABLE SHARD SHAPE: each sharded dim contributes
        ``ceil(extent / axis_size)`` rows per device — GSPMD pads the last
        shard when an extent does not divide its axes, and every device
        still receives the padded slice, so a plain ``nbytes // model_size``
        under-reports the transfer.  Replicated (row-parallel) scales
        contribute their full size.  With no ctx this equals ``nbytes``
        (single copy)."""
        if ctx is None:
            return self.nbytes(name)
        from repro.dist import sharding as shard_rules
        sizes = ctx.axis_sizes
        total = 0
        for path, arr in self.tasks[name].items():
            spec = tuple(shard_rules.spec_for_path(path, np.ndim(arr)))
            n = 1
            for dim, extent in enumerate(np.shape(arr)):
                ax = spec[dim] if dim < len(spec) else None
                axes = () if ax is None else (
                    ax if isinstance(ax, tuple) else (ax,))
                k = 1
                for a in axes:
                    k *= sizes[a]
                n *= -(-extent // k)        # ceil: the padded shard extent
            total += n * np.asarray(arr).dtype.itemsize
        return total

    def names(self) -> Iterable[str]:
        return self.tasks.keys()
