"""Distributed robust reductions — the paper's aggregation as collectives.

These functions run *inside* a ``jax.shard_map`` body whose manual axes are
the worker axes (``('data',)`` single-pod, ``('pod','data')`` multi-pod).
Each data-parallel group is one "worker machine" of the paper; the model
axis stays automatic (GSPMD).

Three exact strategies (identical estimator, different collective schedule):

``gather``    paper-faithful. Every device all-gathers the m per-worker
              gradients for its model shard and applies the coordinate-wise
              aggregator locally. Collective bytes ≈ m·|g| per device.

``bucketed``  beyond-paper. The gradient is flattened and split into m
              equal buckets; an ``all_to_all`` routes bucket j of every
              worker to worker j, which aggregates its bucket over the m
              rows; an ``all_gather`` reassembles the full aggregated
              gradient. Bytes ≈ 2·|g| per device — the same volume as a
              plain all-reduce, i.e. Byzantine robustness at (almost) no
              extra bandwidth. Exact because coordinate-wise aggregators
              are embarrassingly parallel across coordinates. Small
              leaves are coalesced into size-binned super-buckets so the
              collective launch count is O(#size-bins), not O(#leaves).

``rs``        like ``bucketed`` but *leaves the result scattered* (a
              "robust reduce-scatter"): used by the FSDP integration where
              each worker only updates its own parameter shard.

Two approximate strategies:

``hierarchical``  median-of-medians across pods (aggregate within pod,
              then across pods). Cheaper DCN traffic but a *different*
              estimator (documented in DESIGN.md); off by default.

``chunked``   histogram-sketch aggregation via plain psums (the
              federated-scale estimator of repro.fed / DESIGN.md
              §Federated-scale): per-coordinate min/max by pmin/pmax,
              then each worker psums its local one-hot bin counts/sums
              and inverts the CDF locally. No per-worker rows are ever
              gathered, so bytes ≈ (2 + 2·nbins)·|g| *independent of m*
              — the only strategy whose collective volume does not grow
              with the worker count. Approximate: error ≤ one bin width
              (max−min)/nbins per coordinate. The coordinate space is
              processed in ``coord_chunk`` slices to bound the (nbins,
              chunk) sketch memory.

Byzantine simulation: gradient-space attacks are applied where per-worker
rows are visible, i.e. after the gather / all_to_all, using the row index
(= source worker id) against the attack's Byzantine mask.  Attacks come
from the repro.attacks registry via the AttackConfig shim; the chunked
(psum) strategy supports data/local/stats access levels — omniscient
attacks need gathered rows and raise there (see repro.attacks.base for
the access taxonomy).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import aggregators
from repro.core import attacks as attacks_mod
from repro.core.attacks import AttackConfig, apply_gradient_attack
from repro.launch import spans


def axis_size(axis_names: Sequence[str]) -> int:
    s = 1
    for a in axis_names:
        s *= jax.lax.axis_size(a)
    return s


def worker_index(axis_names: Sequence[str]) -> jax.Array:
    """Flat worker id over the (possibly multiple) worker mesh axes.

    Row-major over ``axis_names`` — consistent with how ``all_gather``
    tiles multiple axes.
    """
    idx = jnp.int32(0)
    for a in axis_names:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _maybe_attack(stacked: jax.Array, attack: Optional[AttackConfig], m: int,
                  key: Optional[jax.Array] = None) -> jax.Array:
    if attack is None or attack.name == "none" or attack.alpha == 0.0:
        return stacked
    mask = attack.byzantine_mask(m)
    with jax.named_scope(spans.ATTACK):
        return apply_gradient_attack(attack, stacked, mask, key=key)


# --------------------------------------------------------------------------
# gather strategy (paper-faithful Algorithm 1 aggregation)
# --------------------------------------------------------------------------


def robust_gather_agg(
    g,
    axis_names: Sequence[str],
    method: str = "median",
    beta: float = 0.1,
    attack: Optional[AttackConfig] = None,
    agg_dtype=None,
    attack_key=None,
):
    """All-gather per-worker gradients over the worker axes and aggregate.

    ``g``: pytree of local gradient leaves. Returns the aggregated pytree
    (replicated across worker axes).  ``attack_key`` seeds randomized
    attacks (fold the step index in per training step — launch/steps
    does — or every step replays the same draw).
    """
    m = axis_size(axis_names)

    def agg_leaf(leaf):
        stacked = jax.lax.all_gather(leaf, axis_names, axis=0, tiled=False)
        stacked = stacked.reshape((m,) + leaf.shape)
        if agg_dtype is not None:
            stacked = stacked.astype(agg_dtype)
        stacked = _maybe_attack(stacked, attack, m, attack_key)
        out = aggregators.get_aggregator(method, beta)(stacked)
        return out.astype(leaf.dtype)

    return jax.tree.map(agg_leaf, g)


# --------------------------------------------------------------------------
# bucketed strategy (beyond-paper: robust "all-reduce" via all_to_all)
# --------------------------------------------------------------------------


def _flatten_tree(g) -> Tuple[jax.Array, list]:
    leaves, treedef = jax.tree.flatten(g)
    flat = jnp.concatenate([l.reshape(-1) for l in leaves]) if leaves else jnp.zeros((0,))
    meta = [(l.shape, l.dtype, l.size) for l in leaves]
    return flat, [treedef, meta]


def _unflatten_tree(flat: jax.Array, aux) -> "jax.tree_util.PyTreeDef":
    treedef, meta = aux
    leaves = []
    off = 0
    for shape, dtype, size in meta:
        leaves.append(flat[off : off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree.unflatten(treedef, leaves)


def _robust_scatter_rows(
    x: jax.Array,
    axis_names: Sequence[str],
    method: str,
    beta: float,
    attack: Optional[AttackConfig],
    agg_dtype,
    attack_key=None,
) -> jax.Array:
    """Core of the bucketed strategies.

    Input: local gradient block ``x`` of shape (N, ...), N divisible by
    m. Output: this worker's aggregated bucket (N/m, ...) — rows
    [j*N/m : (j+1)*N/m] for worker j.  Splitting only the leading dim
    keeps the block's tiled device layout, so no relayout copy of the
    gradient enters the program.
    """
    axis_names = tuple(axis_names)
    m = axis_size(axis_names)
    sizes = tuple(jax.lax.axis_size(a) for a in axis_names)
    bs = x.shape[0] // m
    # Buckets laid out per worker axis: bucket (i_0, .., i_{k-1}) goes to the
    # worker at that mesh coordinate (flat index row-major = all_gather order).
    rows = x.reshape(sizes + (bs,) + x.shape[1:])
    # all_to_all each worker axis on its own bucket dim: afterwards, entry
    # (j_0, .., j_{k-1}) is worker (j_0, .., j_{k-1})'s copy of MY bucket.
    for dim, a in enumerate(axis_names):
        rows = jax.lax.all_to_all(rows, a, split_axis=dim, concat_axis=dim, tiled=True)
    # rows: (m, bs, ...) — row i is (flat) worker i's version of my bucket
    rows = rows.reshape((m, bs) + x.shape[1:])
    if agg_dtype is not None:
        rows = rows.astype(agg_dtype)
    rows = _maybe_attack(rows, attack, m, attack_key)
    out = aggregators.get_aggregator(method, beta)(rows)
    return out.astype(x.dtype)


def _robust_scatter_flat(
    flat: jax.Array,
    axis_names: Sequence[str],
    method: str,
    beta: float,
    attack: Optional[AttackConfig],
    agg_dtype,
    attack_key=None,
) -> Tuple[jax.Array, int]:
    """:func:`_robust_scatter_rows` of a flat gradient (G,), zero-padded to
    a multiple of m: returns this worker's aggregated bucket (ceil(G/m),)
    — coordinates [j*bs : (j+1)*bs] for worker j — plus the original
    size for unpadding by the caller.
    """
    m = axis_size(tuple(axis_names))
    size = flat.shape[0]
    pad = -size % m
    if pad:
        flat = jnp.pad(flat, (0, pad))
    out = _robust_scatter_rows(flat, axis_names, method, beta, attack,
                               agg_dtype, attack_key)
    return out, size


def _row_blocks(leaves, m: int):
    """2-D ``(rows, c)`` views of a coalesced group, or None.

    When every leaf has >= 2 dims, all share the last dim c, and the
    group's total row count divides by m, the group is bucketed along
    its rows: row-major order makes bucket j the very coordinates the
    flat view would give it, so the estimator and the bytes on the wire
    are unchanged.  The flat ``(m, G/m)`` view of a matrix is a relayout
    that the TPU compiler handles in time that grows with the leaf: one
    bf16 (32000, 2560) embedding gradient took ~100 s to compile that
    way and ~2.5 s by rows (v5e, compiled without a chip).
    """
    if any(l.ndim < 2 for l in leaves) or len({l.shape[-1] for l in leaves}) > 1:
        return None
    mats = [l.reshape(-1, l.shape[-1]) for l in leaves]
    if sum(x.shape[0] for x in mats) % m:
        return None
    return mats


# Element cap per coalesced super-bucket (16 MiB in f32): small leaves
# batch into one collective, while the concat copy a group pays stays
# bounded — the failure mode of the all-leaves 'flat' concat that
# EXPERIMENTS.md §Perf iteration 1 measured at ~4× HBM traffic on grok-1.
_COALESCE_MAX_ELEMS = 1 << 22


def _coalesce_groups(leaves, max_elems: int = _COALESCE_MAX_ELEMS):
    """Group leaf indices into size-binned super-buckets.

    Leaves are binned by (dtype, floor(log2(size))); within a bin they
    pack greedily into groups whose total stays ≤ ``max_elems`` (always
    ≥ 1 leaf per group). A pytree of many small leaves — every bias and
    norm scale of a transformer — thus costs O(#size-bins) collective
    launches instead of O(#leaves), without reintroducing an unbounded
    concat. Deterministic in leaf order, so every worker builds the
    identical grouping (a divergent grouping would deadlock the
    collectives).
    """
    bins: Dict[tuple, list] = {}
    for idx, leaf in enumerate(leaves):
        key = (str(jnp.result_type(leaf)), max(int(leaf.size), 1).bit_length())
        bins.setdefault(key, []).append(idx)
    groups = []
    for key in sorted(bins):
        cur, cur_elems = [], 0
        for idx in bins[key]:
            if cur and cur_elems + leaves[idx].size > max_elems:
                groups.append(cur)
                cur, cur_elems = [], 0
            cur.append(idx)
            cur_elems += leaves[idx].size
        groups.append(cur)
    return groups


def robust_bucketed_agg(
    g,
    axis_names: Sequence[str],
    method: str = "median",
    beta: float = 0.1,
    attack: Optional[AttackConfig] = None,
    agg_dtype=None,
    granularity: str = "leaf",
    attack_key=None,
):
    """Exact robust aggregation with all-reduce-like byte volume.

    per super-bucket (or the flat concat): all_to_all buckets → aggregate
    own bucket → all_gather. Returns the full aggregated pytree
    (replicated across worker axes).

    ``granularity='leaf'`` (default) coalesces leaves into size-binned
    super-buckets (see :func:`_coalesce_groups`): small leaves share one
    all_to_all + all_gather pair instead of paying a collective launch
    each, while large leaves still go alone — no concat copy of the full
    gradient, which matters at 100B+ scale (EXPERIMENTS.md §Perf
    iteration 1 found the flat concat multiplied grok-1's HBM traffic
    ~4×). Exact regardless of grouping: coordinate-wise aggregators are
    embarrassingly parallel across coordinates, and the gradient-space
    attacks are row-broadcast formulas, so concatenating coordinates
    changes nothing. ``'flat'`` keeps the original single-bucket-space
    formulation (one collective pair for everything — fine for small
    models).
    """
    if granularity == "leaf":
        m = axis_size(tuple(axis_names))
        leaves, treedef = jax.tree.flatten(g)
        out_leaves = [None] * len(leaves)
        for grp in _coalesce_groups(leaves):
            mats = _row_blocks([leaves[i] for i in grp], m)
            if mats is None:
                mats = [leaves[i].reshape(-1) for i in grp]
            block = mats[0] if len(grp) == 1 else jnp.concatenate(mats)
            if block.ndim == 2:
                mine = _robust_scatter_rows(block, axis_names, method, beta,
                                            attack, agg_dtype, attack_key)
                size = block.shape[0]
            else:
                mine, size = _robust_scatter_flat(block, axis_names, method,
                                                  beta, attack, agg_dtype,
                                                  attack_key)
            full = jax.lax.all_gather(mine, axis_names, axis=0, tiled=True)[:size]
            off = 0
            for i, part in zip(grp, mats):
                leaf = leaves[i]
                out_leaves[i] = (full[off : off + part.shape[0]]
                                 .reshape(leaf.shape).astype(leaf.dtype))
                off += part.shape[0]
        return jax.tree.unflatten(treedef, out_leaves)
    flat, aux = _flatten_tree(g)
    mine, size = _robust_scatter_flat(flat, axis_names, method, beta, attack,
                                      agg_dtype, attack_key)
    full = jax.lax.all_gather(mine, axis_names, axis=0, tiled=True)
    full = full[:size]
    return _unflatten_tree(full, aux)


def robust_reduce_scatter(
    flat: jax.Array,
    axis_names: Sequence[str],
    method: str = "median",
    beta: float = 0.1,
    attack: Optional[AttackConfig] = None,
    agg_dtype=None,
) -> jax.Array:
    """Robust replacement for ``psum_scatter`` on a flat vector.

    Returns only this worker's aggregated bucket (padded bucket size).
    Used by the robust-FSDP parameter gather's backward pass.
    """
    with jax.named_scope(spans.AGGREGATE):
        out, _ = _robust_scatter_flat(flat, axis_names, method, beta, attack, agg_dtype)
    return out


# --------------------------------------------------------------------------
# chunked strategy (approximate: histogram sketch via psum, O(1) in m)
# --------------------------------------------------------------------------


def _maybe_attack_chunked(
    flat: jax.Array,
    attack: Optional[AttackConfig],
    axis_names: Sequence[str],
    m: int,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Byzantine simulation without gathered rows: this worker's local
    flat gradient is replaced iff its worker index is under the attack's
    Byzantine cut.  The colluders' honest statistics are reproduced with
    psums over the honest workers and fed to the registry payloads via
    :func:`repro.core.attacks.byzantine_payload`, so the chunked strategy
    sees the identical threat model as gather/bucketed — up to access:
    omniscient (rows-needing) attacks like mimic/max_damage_tm cannot run
    here and raise; local attacks use this worker's own row and a
    worker-folded key.
    """
    if attack is None or attack.alpha == 0.0 or attack.name == "none":
        return flat
    if attack.is_data_attack():
        return flat  # data attacks corrupt samples upstream of the gradient
    q = attack.num_byzantine(m)
    if q == 0:
        return flat
    widx = worker_index(axis_names)
    is_byz = widx < q
    atk_spec = attack.resolve()[0]
    honest_mean = honest_var = None
    if attacks_mod.attack_base.access_rank(atk_spec.access) >= \
            attacks_mod.attack_base.access_rank(attacks_mod.attack_base.STATS):
        # the honest-statistics oracle costs one (or two) full-gradient
        # psums — only stats-level colluders get it; local/data attacks
        # keep the strategy's m-independent collective volume intact
        honest = jnp.where(is_byz, jnp.zeros_like(flat), flat)
        honest_mean = jax.lax.psum(honest, axis_names) / (m - q)
        if atk_spec.needs_variance:  # declared on the Attack spec
            dev = jnp.where(is_byz, jnp.zeros_like(flat), (flat - honest_mean) ** 2)
            honest_var = jax.lax.psum(dev, axis_names) / (m - q)
    if key is None:
        key = jax.random.PRNGKey(0)
    bad = attacks_mod.byzantine_payload(
        attack, honest_mean, honest_var, m=m, own=flat,
        key=jax.random.fold_in(key, widx))
    return jnp.where(is_byz, bad, flat)


def robust_chunked_agg(
    g,
    axis_names: Sequence[str],
    method: str = "median",
    beta: float = 0.1,
    attack: Optional[AttackConfig] = None,
    agg_dtype=None,
    nbins: int = 256,
    coord_chunk: int = 16384,
    attack_key=None,
):
    """Approximate robust aggregation with m-independent collective volume.

    Per leaf: (1) pmin/pmax over the worker axes give the per-coordinate
    bin range; (2) every worker histograms its own row locally (one-hot
    counts and sums, (nbins, chunk)) and psums them — a plain all-reduce;
    (3) the CDF is inverted locally (kernels/histogram_agg helpers), so
    all workers hold the identical aggregated gradient, like ``gather``.

    The coordinate space is processed in ``coord_chunk`` slices to bound
    the (nbins, chunk) sketch memory. Each chunk issues ONE psum: the
    counts and (for the trimmed mean) sums planes are concatenated into a
    single (2·nbins, chunk) buffer before the collective, halving the
    per-chunk launch count, and the chunk loop is a ``lax.scan`` — trace
    size (and therefore compile time) is O(1) in the number of chunks
    instead of O(#chunks) of inlined sketch bodies.

    ``method``: ``median`` | ``trimmed_mean`` (order statistics from the
    sketch) | ``mean`` (degenerate: one psum). Error ≤ one bin width
    (max−min)/nbins per coordinate; exact for the mean.
    """
    from repro.kernels import histogram_agg as H

    # chunked IS the histogram-sketch estimator, so the approx_* aggregator
    # names (configs/CLIs) are aliases of their exact counterparts here
    method = {"approx_median": "median",
              "approx_trimmed_mean": "trimmed_mean"}.get(method, method)
    axis_names = tuple(axis_names)
    m = axis_size(axis_names)

    def agg_leaf(leaf):
        flat = leaf.reshape(-1)
        if agg_dtype is not None:
            flat = flat.astype(agg_dtype)
        flat = flat.astype(jnp.float32)
        with jax.named_scope(spans.ATTACK):
            flat = _maybe_attack_chunked(flat, attack, axis_names, m, attack_key)
        if method == "mean":
            out = jax.lax.psum(flat, axis_names) / m
            return out.reshape(leaf.shape).astype(leaf.dtype)
        if method not in ("median", "trimmed_mean"):
            raise ValueError(
                f"chunked strategy supports mean|median|trimmed_mean, got {method!r}")
        with_sums = method == "trimmed_mean"
        lo = jax.lax.pmin(flat, axis_names)
        width = (jax.lax.pmax(flat, axis_names) - lo) / nbins
        size = flat.shape[0]
        chunk = min(coord_chunk, size)
        nchunks = -(-size // chunk)
        pad = nchunks * chunk - size
        if pad:
            # padded coords get lo=0/width=0 → all mass in bin 0, value 0;
            # sliced off below
            flat = jnp.pad(flat, (0, pad))
            lo = jnp.pad(lo, (0, pad))
            width = jnp.pad(width, (0, pad))

        def body(_, xs):
            seg, slo, sw = xs
            counts, sums = H.hist_update(
                *H.hist_init(chunk, nbins, with_sums=with_sums),
                seg[None, :], slo, sw)
            packed = jnp.concatenate([counts, sums]) if with_sums else counts
            packed = jax.lax.psum(packed, axis_names)  # one collective/chunk
            counts = packed[:nbins]
            if method == "median":
                out = H.median_from_hist(counts, slo, sw, m)
            else:
                out = H.trimmed_mean_from_hist(counts, packed[nbins:], slo, sw,
                                               m, beta)
            return None, out

        _, outs = jax.lax.scan(
            body, None,
            (flat.reshape(nchunks, chunk), lo.reshape(nchunks, chunk),
             width.reshape(nchunks, chunk)))
        out = outs.reshape(-1)[:size]
        return out.reshape(leaf.shape).astype(leaf.dtype)

    return jax.tree.map(agg_leaf, g)


# --------------------------------------------------------------------------
# psum strategy (plain data-parallel all-reduce mean — no robustness)
# --------------------------------------------------------------------------


def robust_psum_agg(
    g,
    axis_names: Sequence[str],
    method: str = "mean",
    beta: float = 0.1,
    attack: Optional[AttackConfig] = None,
    agg_dtype=None,
    attack_key=None,
):
    """Plain data-parallel mean: one psum per leaf, NO robustness.

    This is the throughput baseline the training-harness gate compares
    the robust strategies against — it is what a standard data-parallel
    trainer would do, so "robust aggregation adds <10% step-time
    overhead" is measured relative to this strategy.  It rejects any
    ``method`` other than ``mean`` (a psum cannot compute order
    statistics; asking for median here would silently change the
    estimator).  Attacks are simulated row-free exactly as in the
    chunked strategy (:func:`_maybe_attack_chunked`): Byzantine workers
    replace their own contribution before the all-reduce, honest-stats
    oracles cost extra psums, omniscient attacks are rejected upstream
    by the registry's STATS access cap.
    """
    axis_names = tuple(axis_names)
    if method != "mean":
        raise ValueError(
            f"psum strategy is the plain data-parallel mean baseline; it "
            f"cannot compute {method!r} (use gather/bucketed/chunked)")
    del beta  # meaningless for the mean; accepted for signature parity
    m = axis_size(axis_names)

    def agg_leaf(leaf):
        flat = leaf.reshape(-1)
        if agg_dtype is not None:
            flat = flat.astype(agg_dtype)
        flat = flat.astype(jnp.float32)
        with jax.named_scope(spans.ATTACK):
            flat = _maybe_attack_chunked(flat, attack, axis_names, m, attack_key)
        out = jax.lax.psum(flat, axis_names) / m
        return out.reshape(leaf.shape).astype(leaf.dtype)

    return jax.tree.map(agg_leaf, g)


# --------------------------------------------------------------------------
# hierarchical strategy (approximate: median-of-medians across pods)
# --------------------------------------------------------------------------


def robust_hierarchical_agg(
    g,
    inner_axis: str,
    outer_axis: str,
    method: str = "median",
    beta: float = 0.1,
    attack: Optional[AttackConfig] = None,
    attack_key=None,
):
    """Two-level aggregation: within ``inner_axis`` (ICI), then across
    ``outer_axis`` (DCN). NOTE: median-of-medians is a different estimator
    from the global median — documented in DESIGN.md; use for DCN savings
    only when the per-pod Byzantine fraction is controlled.
    """
    inner = robust_gather_agg(g, (inner_axis,), method, beta, attack,
                              attack_key=attack_key)
    return robust_gather_agg(inner, (outer_axis,), method, beta, attack=None)


# --------------------------------------------------------------------------
# robust FSDP parameter gather (custom_vjp)
# --------------------------------------------------------------------------


def make_robust_param_gather_dim(
    axis_names: Sequence[str],
    dim: int,
    method: str = "median",
    beta: float = 0.1,
    attack: Optional[AttackConfig] = None,
):
    """Like :func:`make_robust_param_gather` but gathers/scatters along an
    arbitrary tensor dimension ``dim`` (the per-leaf FSDP dim)."""
    axis_names = tuple(axis_names)

    @jax.custom_vjp
    def gather(w_shard: jax.Array) -> jax.Array:
        return jax.lax.all_gather(w_shard, axis_names, axis=dim, tiled=True)

    def fwd(w_shard):
        return gather(w_shard), None

    def bwd(_, ct):
        moved = jnp.moveaxis(ct, dim, 0)
        flat = moved.reshape(-1)
        shard_flat = robust_reduce_scatter(flat, axis_names, method, beta, attack)
        m = axis_size(axis_names)
        shard_shape = (moved.shape[0] // m,) + moved.shape[1:]
        shard = jnp.moveaxis(shard_flat.reshape(shard_shape), 0, dim)
        return (shard,)

    gather.defvjp(fwd, bwd)
    return gather


def make_robust_param_gather(
    axis_names: Sequence[str],
    method: str = "median",
    beta: float = 0.1,
    attack: Optional[AttackConfig] = None,
):
    """Return ``gather(w_shard) -> w_full`` whose backward pass is a
    *robust reduce-scatter* instead of the usual ``psum_scatter``.

    Forward: all-gather the FSDP-sharded flat parameter shard over the
    worker axes. Backward: each worker's full-gradient cotangent is
    bucketed with ``all_to_all`` and aggregated coordinate-wise, so the
    parameter-shard update each worker applies is the exact paper
    estimator over the m per-worker gradients.
    """
    axis_names = tuple(axis_names)

    @jax.custom_vjp
    def gather(w_shard: jax.Array) -> jax.Array:
        return jax.lax.all_gather(w_shard, axis_names, axis=0, tiled=True)

    def fwd(w_shard):
        return gather(w_shard), None

    def bwd(_, ct):
        flat = ct.reshape(-1)
        shard = robust_reduce_scatter(flat, axis_names, method, beta, attack)
        m = axis_size(axis_names)
        # ct has shape (m * shard_rows, ...) == w_full; our shard is rows
        # [j*shard_rows : (j+1)*shard_rows]. robust_reduce_scatter returned
        # exactly those coordinates (flattened), so reshape back.
        shard_shape = (ct.shape[0] // m,) + ct.shape[1:]
        return (shard.reshape(shard_shape),)

    gather.defvjp(fwd, bwd)
    return gather
