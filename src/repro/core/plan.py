"""UnitPlan: a static bucketed compression-execution engine.

The paper's subject is the *granularity* at which compression is applied
(entire model vs layer vs block). The first realization of layer-wise
granularity here was a Python loop over pytree leaves — O(#tensors) traced
compressor calls per step, exactly the per-layer operator-launch overhead
that Agarwal et al. (PAPERS.md) show can erase compression's wall-clock
benefit. This module removes it at the framework level: compute a *plan*
once at trace time, then execute compression as a handful of fused
dispatches.

Plan construction (pure Python, static — cached on the leaf shapes):

  (params treedef, stacked mask, Granularity)
      -> per-unit tables: (offset into the flat gradient, dim, leaf index)
      -> buckets: same-size units grouped into (n_units, dim) matrices
      -> per-unit PRNG fold indices reproducing the legacy key derivation
         bit-for-bit (single fold for loose leaves / blocks, double fold
         for scan-stacked layers)

Execution (traced, per step):

  gather   flat = concat(leaves)        one concat
  compress Y_b = vmap(fn)(X_b, keys_b)  ONE batched dispatch per bucket
  scatter  leaves = split(out_flat)     one split

All three granularities are the same plan shape: entire_model is a 1-unit
plan, blockwise is a fixed-size plan (one bucket), layerwise is the ragged
case bucketed by size class. Buckets whose units tile a contiguous range of
the flat gradient (scan-stacked layers, blockwise) gather by reshape —
no index arrays at all.

Numerical contract: `plan.execute(fn, ...)` produces exactly what the
legacy per-leaf path (`granularity.apply_unitwise_reference`) produces,
including the PRNG stream. tests/test_plan.py holds this property over
the operator zoo x granularities.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import math
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.granularity import Granularity

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One size class: all units of dimension `dim`, as rows of a matrix.

    `unit_ids` index the plan's per-unit tables (execution order).
    `offsets` are the units' start positions in the flat gradient.
    `runs` decomposes the rows into maximal contiguous segments
    (start_offset, n_units, leaf_index): each run gathers/scatters by
    reshape, never by element index arrays. leaf_index >= 0 means the run
    covers exactly that pytree leaf, so execution reads/writes the leaf
    directly — no flat staging buffer at all (the layerwise case, where
    units never straddle leaves). leaf_index == -1 (entire-model /
    blockwise spans) stages through the flat vector.

    `ready` is the bucket's backward-readiness rank: backward produces
    gradient leaves in reverse leaf order (leaf N-1 first, leaf 0 last),
    so leaf k's gradient is available at time (n_leaves-1-k) and a bucket
    is ready once EVERY leaf any of its units touches has been produced —
    i.e. at (n_leaves-1) - min(touched leaf index). Lower rank = ready
    earlier in backward. core.schedule orders wire messages by it.
    """
    dim: int
    unit_ids: Tuple[int, ...]
    offsets: Tuple[int, ...]
    runs: Tuple[Tuple[int, int, int], ...]
    ready: int = 0

    @property
    def n(self) -> int:
        return len(self.unit_ids)

    @property
    def contiguous(self) -> bool:
        return len(self.runs) == 1

    @property
    def nbytes(self) -> int:
        """Dense f32 bytes of the bucket's units — the size a Horovod-style
        fusion buffer reasons about (compressor-independent)."""
        return 4 * self.n * self.dim


@dataclasses.dataclass(frozen=True)
class UnitPlan:
    """Static compression-execution plan for one (pytree, granularity).

    Frozen + tuples throughout => hashable, so a plan is a valid static
    argument under jit and a safe lru_cache value.
    """
    granularity: Granularity
    treedef: jax.tree_util.PyTreeDef
    leaf_shapes: Tuple[Tuple[int, ...], ...]
    leaf_dtypes: Tuple[jnp.dtype, ...]
    total: int                       # true element count (sum of leaf sizes)
    exec_total: int                  # padded flat length the buckets tile
    unit_dims: Tuple[int, ...]       # ACCOUNTING dims (bits.py / theory.py)
    exec_dims: Tuple[int, ...]       # per exec-unit dim (blockwise pads tail)
    unit_offsets: Tuple[int, ...]    # per exec-unit flat offset
    unit_leaf: Tuple[int, ...]       # per exec-unit leaf index (-1: spans)
    buckets: Tuple[Bucket, ...]
    # PRNG fold tables reproducing the legacy derivation:
    #   double: key_u = fold_in(fold_in(key, base_u), inner_u)   (stacked)
    #   single: key_u = fold_in(key, base_u)                     (otherwise)
    fold_base: Tuple[int, ...]
    fold_inner: Tuple[int, ...]
    fold_double: Tuple[bool, ...]

    # ---- introspection ----------------------------------------------------
    @property
    def num_units(self) -> int:
        """Accounting units (== len(granularity.unit_dims))."""
        return len(self.unit_dims)

    @property
    def num_exec_units(self) -> int:
        return len(self.exec_dims)

    @property
    def num_dispatches(self) -> int:
        """Batched compressor dispatches per execution — one per bucket,
        i.e. O(#size classes), not O(#leaves)."""
        return len(self.buckets)

    @property
    def num_leaves(self) -> int:
        return len(self.leaf_shapes)

    def readiness_order(self) -> Tuple[int, ...]:
        """Bucket indices sorted by backward-readiness (earliest-ready
        first — i.e. the buckets whose gradients backward produces first,
        the late layers). Ties break on bucket index, so the order is
        deterministic and a pure function of the plan."""
        return tuple(sorted(range(len(self.buckets)),
                            key=lambda i: (self.buckets[i].ready, i)))

    def summary(self) -> str:
        bs = ", ".join(f"{b.n}x{b.dim}" for b in self.buckets)
        return (f"UnitPlan({self.granularity.kind}: {self.num_units} units, "
                f"{self.num_dispatches} dispatches [{bs}])")

    # ---- flat <-> tree ----------------------------------------------------
    def flatten(self, tree) -> Array:
        """Pytree -> f32 flat vector of length exec_total (zero-padded)."""
        leaves = jax.tree_util.tree_leaves(tree)
        flat = jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32) for l in leaves]) \
            if len(leaves) > 1 else leaves[0].reshape(-1).astype(jnp.float32)
        if self.exec_total > self.total:
            flat = jnp.pad(flat, (0, self.exec_total - self.total))
        return flat

    def unflatten(self, flat: Array):
        """f32 flat vector -> pytree with the plan's shapes/dtypes."""
        return self._assemble([None] * len(self.leaf_shapes), flat)

    # ---- PRNG -------------------------------------------------------------
    def unit_keys(self, key: Array) -> Array:
        """Per-exec-unit PRNG keys, identical to the legacy per-leaf
        derivation (vectorized over the fold tables)."""
        base = jnp.asarray(self.fold_base, jnp.int32)
        inner = jnp.asarray(self.fold_inner, jnp.int32)
        dbl = jnp.asarray(self.fold_double)
        k1 = jax.vmap(lambda b: jax.random.fold_in(key, b))(base)
        k2 = jax.vmap(lambda k, i: jax.random.fold_in(k, i))(k1, inner)
        typed = jnp.issubdtype(key.dtype, jax.dtypes.prng_key)
        if typed:
            kd = jnp.where(dbl[:, None], jax.random.key_data(k2),
                           jax.random.key_data(k1))
            return jax.random.wrap_key_data(kd,
                                            impl=jax.random.key_impl(key))
        return jnp.where(dbl[:, None], k2, k1)

    # ---- bucket gather / scatter -----------------------------------------
    @property
    def needs_flat(self) -> bool:
        """True when some run spans leaves (entire-model / blockwise):
        execution must stage through the flat vector. Layerwise plans are
        flat-free (every run reads/writes its leaf directly)."""
        return any(r[2] < 0 for b in self.buckets for r in b.runs)

    def _gather_runs(self, leaves, flat, b: Bucket) -> Array:
        mats = []
        for start, k, li in b.runs:
            if li >= 0 and leaves is not None:
                mats.append(leaves[li].reshape(k, b.dim).astype(jnp.float32))
            else:
                mats.append(flat[start:start + k * b.dim].reshape(k, b.dim))
        return mats[0] if len(mats) == 1 else jnp.concatenate(mats, axis=0)

    def gather_bucket(self, flat: Array, b: Bucket) -> Array:
        """(exec_total,) -> (n_units, dim) matrix of the bucket's units.

        Pure reshape per contiguous run — no element index arrays."""
        return self._gather_runs(None, flat, b)

    def scatter_bucket(self, out: Array, b: Bucket, y: Array) -> Array:
        row = 0
        for start, k, _ in b.runs:
            out = jax.lax.dynamic_update_slice(
                out, y[row:row + k].reshape(-1), (start,))
            row += k
        return out

    def _scatter_runs(self, out_leaves, out_flat, b: Bucket, y: Array):
        row = 0
        for start, k, li in b.runs:
            seg = y[row:row + k]
            if li >= 0:
                out_leaves[li] = seg.reshape(
                    self.leaf_shapes[li]).astype(self.leaf_dtypes[li])
            else:
                out_flat = jax.lax.dynamic_update_slice(
                    out_flat, seg.reshape(-1), (start,))
            row += k
        return out_flat

    def _assemble(self, out_leaves, out_flat):
        sizes = [math.prod(shape) for shape in self.leaf_shapes]
        offsets = [sum(sizes[:i]) for i in range(len(sizes))]
        if out_flat is not None:
            # Leaf offsets enter as runtime values: with static slices XLA
            # rewrites reshape(slice(flat)) into slice(reshape(flat)), and
            # a leaf with a narrow trailing dim (the (L, 256, 4) conv
            # weights) then reshapes the WHOLE flat buffer to (N/1024,
            # 256, 4), which TPU tiling pads 32x (172 GB at mamba2-1.3b).
            offsets = jax.lax.optimization_barrier(
                jnp.asarray(offsets, jnp.int32))
        outs = []
        for i, (shape, dtype) in enumerate(zip(self.leaf_shapes,
                                               self.leaf_dtypes)):
            if out_leaves[i] is not None:
                outs.append(out_leaves[i])
            else:
                outs.append(jax.lax.dynamic_slice(
                    out_flat, (offsets[i],), (sizes[i],)).reshape(shape)
                    .astype(dtype))
        return jax.tree_util.tree_unflatten(self.treedef, outs)

    # ---- execution --------------------------------------------------------
    def _dispatch(self, fn, b: Bucket, x: Array, keys: Array) -> Array:
        """ONE batched compressor dispatch for bucket `b` on its gathered
        (n, dim) matrix. The single definition both the plan path and the
        scheduled path (core.schedule) execute through — the scheduled-vs-
        unscheduled bit-identity contract rests on there being one copy of
        this key-indexing/vmap logic."""
        kb = keys[jnp.asarray(b.unit_ids, jnp.int32)]
        if b.n == 1:
            return fn(x[0], kb[0])[None]
        return jax.vmap(fn)(x, kb)

    def _dispatch_with_state(self, fn, b: Bucket, x: Array, m: Array,
                             keys: Array):
        """State-threading twin of _dispatch: fn(x, m, key) -> (y, m')."""
        kb = keys[jnp.asarray(b.unit_ids, jnp.int32)]
        if b.n == 1:
            y, mn = fn(x[0], m[0], kb[0])
            return y[None], mn[None]
        return jax.vmap(fn)(x, m, kb)

    def execute(self, fn: Callable[[Array, Array], Array], grads,
                key: Array, *, recorder=None):
        """Map fn(x_flat f32[d], key) -> f32[d] over every unit, batched
        per size class. Returns a pytree shaped/dtyped like `grads`.

        Leaf-aligned runs (all of layerwise) read/write leaves directly;
        only leaf-spanning plans stage through a flat buffer.

        `recorder` (duck-typed, obs.trace.TraceRecorder) instruments
        each dispatch with a named scope + end-of-stage mark; None or a
        disabled recorder leaves the traced graph untouched."""
        rec = (recorder if recorder is not None
               and getattr(recorder, "enabled", False) else None)
        leaves = jax.tree_util.tree_leaves(grads)
        flat = self.flatten(grads) if self.needs_flat else None
        keys = self.unit_keys(key)
        out_leaves = [None] * len(leaves)
        out_flat = (jnp.zeros((self.exec_total,), jnp.float32)
                    if flat is not None else None)
        if rec is not None and leaves:
            rec.begin(leaves[0], label="grads_ready")
        for bi, b in enumerate(self.buckets):
            x = self._gather_runs(leaves, flat, b)
            if rec is not None:
                with rec.scope(f"repro/dispatch/b{bi}"):
                    y = self._dispatch(fn, b, x, keys)
                rec.mark(y, "dispatch", cat="dispatch",
                         bucket_ids=(bi,), dims=(b.dim,), n_units=b.n,
                         label=f"dispatch b{bi}")
            else:
                y = self._dispatch(fn, b, x, keys)
            out_flat = self._scatter_runs(out_leaves, out_flat, b, y)
        return self._assemble(out_leaves, out_flat)

    def execute_with_state(self, fn, grads, state, key: Array, *,
                           recorder=None):
        """Like execute, but fn(x, m, key) -> (y, m_new) threads a
        same-shaped per-unit state (error-feedback memory)."""
        rec = (recorder if recorder is not None
               and getattr(recorder, "enabled", False) else None)
        leaves = jax.tree_util.tree_leaves(grads)
        sleaves = jax.tree_util.tree_leaves(state)
        need = self.needs_flat
        flat = self.flatten(grads) if need else None
        mflat = self.flatten(state) if need else None
        keys = self.unit_keys(key)
        out_leaves = [None] * len(leaves)
        mout_leaves = [None] * len(leaves)
        out_flat = (jnp.zeros((self.exec_total,), jnp.float32)
                    if need else None)
        mout_flat = (jnp.zeros((self.exec_total,), jnp.float32)
                     if need else None)
        if rec is not None and leaves:
            rec.begin(leaves[0], label="grads_ready")
        for bi, b in enumerate(self.buckets):
            x = self._gather_runs(leaves, flat, b)
            m = self._gather_runs(sleaves, mflat, b)
            if rec is not None:
                with rec.scope(f"repro/dispatch/b{bi}"):
                    y, mn = self._dispatch_with_state(fn, b, x, m, keys)
                rec.mark([y, mn], "dispatch", cat="dispatch",
                         bucket_ids=(bi,), dims=(b.dim,), n_units=b.n,
                         label=f"dispatch b{bi}")
            else:
                y, mn = self._dispatch_with_state(fn, b, x, m, keys)
            out_flat = self._scatter_runs(out_leaves, out_flat, b, y)
            mout_flat = self._scatter_runs(mout_leaves, mout_flat, b, mn)
        return (self._assemble(out_leaves, out_flat),
                self._assemble(mout_leaves, mout_flat))


# ==========================================================================
# plan construction
# ==========================================================================

def _first_touched_leaf(offset: int, unit_leaf_idx: int,
                        leaf_offsets: Sequence[int]) -> int:
    """Lowest-index leaf a unit starting at `offset` touches. Units tagged
    with a leaf use it directly; spanning units (entire-model / blockwise,
    leaf index -1) locate the leaf containing their start offset. Offsets
    landing in blockwise tail padding clamp to the last leaf."""
    if unit_leaf_idx >= 0:
        return unit_leaf_idx
    if not leaf_offsets:
        return 0
    return max(0, bisect.bisect_right(leaf_offsets, offset) - 1)


def _make_buckets(dims: Sequence[int], offsets: Sequence[int],
                  unit_leaf: Sequence[int],
                  leaf_offsets: Sequence[int],
                  leaf_sizes: Sequence[int]) -> Tuple[Bucket, ...]:
    """Group units by dim (first-occurrence order) and split each group
    into contiguous runs. Runs never merge across leaves: a run that
    covers one leaf exactly is tagged with its leaf index, enabling the
    flat-free direct-leaf execution path."""
    n_leaves = len(leaf_sizes)
    by_dim: dict = {}
    order: List[int] = []
    for uid, d in enumerate(dims):
        if d not in by_dim:
            by_dim[d] = []
            order.append(d)
        by_dim[d].append(uid)
    buckets = []
    for d in order:
        ids = by_dim[d]
        offs = [offsets[u] for u in ids]
        runs: List[List[int]] = []   # [start, count, leaf]
        for u, o in zip(ids, offs):
            li = unit_leaf[u]
            if (runs and li == runs[-1][2] and li >= 0
                    and o == runs[-1][0] + runs[-1][1] * d):
                runs[-1][1] += 1
            elif (runs and li < 0 and runs[-1][2] < 0
                    and o == runs[-1][0] + runs[-1][1] * d):
                runs[-1][1] += 1
            else:
                runs.append([o, 1, li])
        frozen = []
        for start, k, li in runs:
            whole = (li >= 0 and start == leaf_offsets[li]
                     and k * d == leaf_sizes[li])
            frozen.append((start, k, li if whole else -1))
        first = min((_first_touched_leaf(o, unit_leaf[u], leaf_offsets)
                     for u, o in zip(ids, offs)), default=0)
        ready = max(0, n_leaves - 1 - first)
        buckets.append(Bucket(dim=d, unit_ids=tuple(ids),
                              offsets=tuple(offs), runs=tuple(frozen),
                              ready=ready))
    return tuple(buckets)


@functools.lru_cache(maxsize=256)
def _build_plan(treedef, shapes: Tuple[Tuple[int, ...], ...],
                dtypes: Tuple[jnp.dtype, ...], marks: Tuple[bool, ...],
                gran: Granularity) -> UnitPlan:
    sizes = []
    for shape in shapes:
        n = 1
        for s in shape:
            n *= s
        sizes.append(n)
    total = sum(sizes)
    leaf_offsets = []
    off = 0
    for n in sizes:
        leaf_offsets.append(off)
        off += n

    exec_dims: List[int] = []
    offsets: List[int] = []
    unit_leaf: List[int] = []
    fold_base: List[int] = []
    fold_inner: List[int] = []
    fold_double: List[bool] = []

    if gran.kind == "entire_model":
        exec_dims, offsets, unit_leaf = [total], [0], [-1]
        fold_base, fold_inner, fold_double = [0], [0], [False]
        acct_dims = [total]
        exec_total = total
    elif gran.kind == "blockwise":
        b = gran.block_size
        nb = -(-total // b) if total else 0
        exec_dims = [b] * nb
        offsets = [i * b for i in range(nb)]
        unit_leaf = [-1] * nb
        fold_base = list(range(nb))
        fold_inner = [0] * nb
        fold_double = [False] * nb
        n_full, rem = divmod(total, b)
        acct_dims = [b] * n_full + ([rem] if rem else [])
        exec_total = nb * b
    else:  # layerwise
        uid = 0
        off = 0
        for li, (shape, size, stacked) in enumerate(zip(shapes, sizes,
                                                        marks)):
            if stacked and len(shape) >= 1 and shape[0] > 0:
                L = shape[0]
                d = size // L
                for i in range(L):
                    exec_dims.append(d)
                    offsets.append(off + i * d)
                    unit_leaf.append(li)
                    fold_base.append(uid)   # legacy: base folded at the
                    fold_inner.append(i)    # leaf's FIRST uid, then by row
                    fold_double.append(True)
                uid += L
            else:
                exec_dims.append(size)
                offsets.append(off)
                unit_leaf.append(li)
                fold_base.append(uid)
                fold_inner.append(0)
                fold_double.append(False)
                uid += 1
            off += size
        acct_dims = list(exec_dims)
        exec_total = total

    return UnitPlan(
        granularity=gran,
        treedef=treedef,
        leaf_shapes=shapes,
        leaf_dtypes=dtypes,
        total=total,
        exec_total=exec_total,
        unit_dims=tuple(acct_dims),
        exec_dims=tuple(exec_dims),
        unit_offsets=tuple(offsets),
        unit_leaf=tuple(unit_leaf),
        buckets=_make_buckets(exec_dims, offsets, unit_leaf,
                              leaf_offsets, sizes),
        fold_base=tuple(fold_base),
        fold_inner=tuple(fold_inner),
        fold_double=tuple(fold_double),
    )


def build_plan(tree, stacked, gran: Granularity) -> UnitPlan:
    """Build (or fetch the cached) UnitPlan for a gradient pytree.

    `tree` may hold arrays, tracers, or ShapeDtypeStructs — only static
    shape/dtype/structure is read, so this is free inside jit tracing
    (the cache key is (treedef, shapes, dtypes, stacked, granularity)).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(tuple(int(s) for s in l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    marks = tuple(bool(m) for m in jax.tree_util.tree_leaves(stacked))
    if gran.kind == "layerwise" and len(marks) != len(leaves):
        raise ValueError(
            f"stacked mask has {len(marks)} leaves, tree has {len(leaves)}")
    if gran.kind != "layerwise":
        marks = (False,) * len(leaves)  # irrelevant: canonicalize cache key
    return _build_plan(treedef, shapes, dtypes, marks, gran)


def plan_unit_dims(tree, stacked, gran: Granularity) -> List[int]:
    """Accounting dims via the plan (== granularity.unit_dims)."""
    return list(build_plan(tree, stacked, gran).unit_dims)
