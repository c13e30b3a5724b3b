"""Scatter-at-index KV-cache write — the O(1)-per-token step op.

The continuous-batching decode step (serving/decode.py) keeps per-slot
KV caches in the fixed ``(slots, max_len, d)`` layout of PAPERS.md
arxiv 2603.09555 and, until this op existed, wrote one row per step
with a one-hot blend::

    oh    = one_hot(pos, depth=max_len)            # (N, T)
    cache = cache * (1 - oh[..., None]) + row[:, None, :] * oh[..., None]

because a blend is the only formulation XLA reliably fuses into the
step program (arxiv 2301.13062 frames exactly this gap: the pattern is
semantically a scatter, but the fusion layer sees three broadcasts and
two elementwise ops and happily materializes O(max_len * d) work per
generated token).  ``_cache_write_row`` states the scatter directly:

    out[i, pos[i], :] = row[i, :]        (every other element unchanged)

- **TPU, rank-3 cache**: a Pallas kernel (write positions
  scalar-prefetched, the cache aliased input->output) reads, patches
  and writes back only the sublane tile(s) that hold the written rows
  — O(d) per slot per token, never O(max_len * d);
- **CPU / any other rank**: a vmapped ``jax.lax.dynamic_update_slice``
  — XLA lowers it to an in-place row update when the buffer is
  donated, so tier-1 (CPU) exercises the same graph shape and the same
  O(1) cache discipline;
- ``MXNET_CACHE_SCATTER_IMPL=interpret`` runs the Pallas kernel in
  interpreter mode on any backend — how the tests pin the kernel
  bitwise against the XLA fallback without TPU hardware
  (tests/test_chip_compile.py compiles it for a described v5e).

Bitwise contract (tests/test_decode_fastpath.py): for finite cache
values the scatter is bitwise-identical to the one-hot blend it
replaces — at the written position the blend computes ``c*0 + r*1 ==
r``, elsewhere ``c*1 + r*0 == c`` (the decode engine zeroes joining
slots, so the overwritten cell is never non-finite).  The optimizer's
fused-op selection stage (analysis/optimize.py "select" pass) swaps
the blend subgraph for this op behind the same verdict gate as every
other rewrite.

Gradient: the fallback path is plain jax (``dynamic_update_slice``),
so ``jax.vjp`` through the op is exact — cotangents route to ``cache``
with the written row zeroed and to ``row`` via the gathered slice.
The Pallas path is inference-only (decode serving; ``pallas_call``
defines no autodiff rule): the op registers ``mode_dependent``, and
training-mode traces take the fallback on every backend — the two
impls are bitwise-identical, so train-vs-serve parity is unaffected.
"""
from __future__ import annotations

import numpy as np

from .registry import register, P


def _impl_mode(cache):
    """Which implementation this dispatch should trace.

    ``MXNET_CACHE_SCATTER_IMPL``: ``auto`` (the Pallas kernel on TPU
    for the rank-3 ``(slots, max_len, d)`` layout it tiles, XLA
    ``dynamic_update_slice`` elsewhere), ``pallas`` (force the kernel),
    ``interpret`` (Pallas interpreter — CPU-runnable, the tests'
    bitwise pin of the kernel), ``xla`` (force the fallback).
    """
    from .. import config
    mode = str(config.get("MXNET_CACHE_SCATTER_IMPL") or "auto").lower()
    if mode == "auto":
        import jax
        on_tpu = jax.default_backend() == "tpu"
        return "pallas" if on_tpu and cache.ndim == 3 else "xla"
    return mode


def _scatter_xla(cache, row, idx):
    """Fallback: one ``dynamic_update_slice`` per slot row, vmapped
    over the slot axis.  The index is a traced scalar per slot, so the
    compiled program is shape-stable across every write position."""
    import jax

    def write_one(c, r, p):
        # dynamic_update_slice clamps the start index into range, the
        # same containment the engine's pos bookkeeping guarantees
        return jax.lax.dynamic_update_slice_in_dim(c, r[None], p, axis=0)
    return jax.vmap(write_one)(cache, row, idx)


def _scatter_rows_xla(cache, rows, idx, cnt):
    """Fallback for the multi-row commit: per slot, K sequential
    conditional row writes.  Row ``j`` is written only when
    ``j < count[i]`` — expressed as a select between the new row and
    the row currently at the target position, followed by an
    unconditional ``dynamic_update_slice`` (a masked write stays one
    shape-stable compiled program whatever the counts are).  Writes
    ascend ``j`` so clamped-position collisions resolve last-writer-
    wins, matching the kernel's grid order."""
    import jax
    import jax.numpy as jnp
    K = rows.shape[1]

    def write_one(c, rs, p, n):
        T = c.shape[0]
        for j in range(K):
            pj = jnp.clip(p + j, 0, T - 1)
            ok = jnp.logical_and(j < n,
                                 jnp.logical_and(p + j >= 0,
                                                 p + j < T))
            cur = jax.lax.dynamic_slice_in_dim(c, pj, 1, axis=0)
            new = jnp.where(ok, rs[j][None], cur)
            c = jax.lax.dynamic_update_slice_in_dim(c, new, pj, axis=0)
        return c
    return jax.vmap(write_one)(cache, rows, idx, cnt)


def _scatter_rows_pallas(cache, rows, idx, cnt, interpret):
    """The Pallas TPU kernel behind both ops (the single-row write is
    the K = 1, count = 1 case).  The chip's DMA engine moves whole
    ``(sublane, 128)`` tiles — 8 rows of float32, 16 of bfloat16 — so
    a one-row slice of the cache is below what Mosaic will address.
    The kernel therefore works a tile at a time: grid step ``(i, w)``
    takes the w-th tile of slot i's write window as a blocked VMEM
    window (block index computed from the scalar-prefetched position),
    patches the rows of ``rows[i]`` that land in it with ``j <
    count[i]``, and the aliased output window is written back to the
    same place.  Each step is a pure function of its input tile, so a
    tile revisited because the window was clamped at the cache end
    gets the same bytes twice.  O(K * d) traffic per slot whatever
    ``max_len`` is; every other tile of the aliased cache is never
    read, copied, or written.  A row whose position falls outside
    ``[0, max_len)`` matches no tile row and is dropped."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if cache.ndim != 3:
        raise ValueError(
            "the Pallas cache scatter tiles a (slots, max_len, d) "
            "cache; got shape %s" % (tuple(cache.shape),))
    n, T, d = cache.shape
    K = rows.shape[1]
    tile = min(8 * max(1, 4 // cache.dtype.itemsize), T)
    n_tiles = pl.cdiv(T, tile)
    # K consecutive rows starting on a tile's last row span this many
    span = min((K - 1 + tile - 1) // tile + 1, n_tiles)

    def tile_of(i, w, pos_ref):
        first = jnp.clip(pos_ref[i], 0, T - 1) // tile
        return jnp.minimum(first + w, n_tiles - 1)

    def kernel(pos_ref, cnt_ref, cache_ref, rows_ref, out_ref):
        i = pl.program_id(0)
        t = tile_of(i, pl.program_id(1), pos_ref)
        at = jax.lax.broadcasted_iota(jnp.int32, (tile, d), 0) + t * tile
        blk = cache_ref[0]
        for j in range(K):
            hit = jnp.logical_and(at == pos_ref[i] + j, j < cnt_ref[i])
            blk = jnp.where(hit, rows_ref[0, pl.ds(j, 1), :], blk)
        out_ref[0] = blk

    cache_spec = pl.BlockSpec(
        (1, tile, d),
        lambda i, w, pos_ref, cnt_ref: (i, tile_of(i, w, pos_ref), 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, span),
        in_specs=[cache_spec,
                  pl.BlockSpec((1, K, d),
                               lambda i, w, pos_ref, cnt_ref: (i, 0, 0))],
        out_specs=cache_spec,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        # operand order with scalar prefetch: (idx, cnt, cache, rows) —
        # the cache (operand 2) aliases the output for in-place update
        input_output_aliases={2: 0},
        interpret=bool(interpret),
    )(idx, cnt, cache, rows)


@register("_cache_write_rows", nin=4,
          input_names=["cache", "rows", "pos", "count"],
          mode_dependent=True)
def _cache_write_rows(attrs, cache, rows, pos, count):
    """Multi-token commit — the speculative-decode widening of
    ``_cache_write_row`` (ISSUE 15)::

        out[i, pos[i] + j, ...] = rows[i, j, ...]   for j < count[i]

    all other elements of ``cache`` pass through untouched.  ``cache``
    is ``(slots, max_len) + tail``, ``rows`` is ``(slots, K) + tail``
    (K = spec window width, a compile-time constant baked per engine),
    ``pos`` a ``(slots,)`` vector of window start positions and
    ``count`` a ``(slots,)`` vector of ACCEPTED row counts in
    ``[0, K]`` — a draft-k-verify step commits only the tokens the
    target model accepted, in one kernel, instead of K round-trips.

    A row whose position falls OUTSIDE ``[0, max_len)`` is DROPPED
    (not clamped, unlike the single-row op): that is exactly what the
    count-masked one-hot blend chain this op replaces computes (an
    out-of-range one-hot row is all zero), so the select pass's
    "bitwise-identical long-hand spelling" contract holds even when a
    speculative window straddles the cache end — and a finishing
    slot's overshoot can never overwrite the last real row.  Same
    impl selection (``MXNET_CACHE_SCATTER_IMPL``), same training-mode
    fallback, same bitwise kernel-vs-fallback contract pinned by
    interpret mode on CPU CI (tests/test_decode_spec.py)."""
    import jax.numpy as jnp
    idx = pos.astype(jnp.int32)
    cnt = jnp.clip(count.astype(jnp.int32), 0, rows.shape[1])
    rows = jnp.asarray(rows, cache.dtype)
    mode = _impl_mode(cache)
    if mode in ("pallas", "interpret") and attrs.get("_training"):
        # pallas_call defines no autodiff rule (see _cache_write_row)
        mode = "xla"
    if mode in ("pallas", "interpret"):
        return _scatter_rows_pallas(cache, rows, idx, cnt,
                                    interpret=(mode == "interpret"))
    return _scatter_rows_xla(cache, rows, idx, cnt)


@register("_cache_write_row", nin=3,
          input_names=["cache", "row", "pos"],
          mode_dependent=True,
          params={"clip": P(bool, True)})
def _cache_write_row(attrs, cache, row, pos):
    """out[i, pos[i], ...] = row[i, ...]; all other elements of
    ``cache`` pass through untouched.  ``cache`` is ``(slots, max_len)
    + tail``, ``row`` is ``(slots,) + tail``, ``pos`` a ``(slots,)``
    vector of write positions (any real dtype; cast to int32)."""
    import jax.numpy as jnp
    # clamped whatever ``clip`` says: dynamic_update_slice clamps by
    # contract and the kernel would drop an out-of-range row, so the
    # explicit clip is what gives the op ONE out-of-range story
    # instead of a per-backend one
    idx = jnp.clip(pos.astype(jnp.int32), 0, cache.shape[1] - 1)
    row = jnp.asarray(row, cache.dtype)
    mode = _impl_mode(cache)
    if mode in ("pallas", "interpret") and attrs.get("_training"):
        # pallas_call defines no autodiff rule: training graphs trace
        # the differentiable fallback on EVERY backend (mode_dependent
        # threads the flag in; the two impls are bitwise-identical, so
        # train-vs-serve parity is unaffected)
        mode = "xla"
    if mode in ("pallas", "interpret"):
        return _scatter_rows_pallas(cache, row[:, None], idx,
                                    jnp.ones_like(idx),
                                    interpret=(mode == "interpret"))
    return _scatter_xla(cache, row, idx)
