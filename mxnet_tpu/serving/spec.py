"""Speculative draft-k-verify decoding — the engine-side bundle
(ISSUE 15, ROADMAP item 4's last fast-path residual).

The decode engine's persistent step is shape-stable, so a speculative
step is "just" a wider program: per scheduler iteration, a cheap DRAFT
model proposes ``k`` continuation tokens autoregressively in-graph,
the TARGET model scores all ``k+1`` positions in the same single
dispatch (the fusion-boundary argument of arxiv 2301.13062: draft,
verify, and accept stay inside one compiled program instead of k
round-trips), and acceptance commits a variable number of tokens per
slot per step:

- **greedy** (:class:`~.decode.GreedySampler`): exact prefix match —
  a draft token is accepted iff it equals the target's own argmax at
  that position, so the emitted stream is BITWISE-identical to
  ``greedy_decode`` whatever the draft proposes (the draft only moves
  throughput, never content);
- **stochastic** (:class:`~.decode.TemperatureSampler`): standard
  speculative rejection sampling (accept ``x ~ q`` with probability
  ``min(1, p(x)/q(x))``, resample the first rejection from
  ``norm(max(p - q, 0))``, bonus draw from ``p`` after k accepts) on
  the engine's per-step key stream — a fixed seed replays bitwise.

Per-slot KV caches commit ONLY the accepted tokens.  This module
builds the symbolic COMMIT graph — per declared cache state, a chain
of K count-masked one-hot blends writing rows ``pos..pos+count-1`` —
which the optimizer's verdict-gated ``select`` pass swaps for the
widened ``_cache_write_rows`` scatter (ops/cache.py) with slot-axis
row-locality re-proven under pad-dirty seeding, exactly the ISSUE 13
single-row precedent.  A rejected plan serves the blend chain, which
is the bitwise-identical long-hand spelling.

States are declared cache-like with ``{"name": ..., "shape": (T, d),
"cache": True}`` in ``state_info``: the step graph must write exactly
row ``pos[i]`` of such a buffer per consumed token (the fixed O(1)
layout of arxiv 2603.09555).  Undeclared states commit by selecting
the chain state at the accepted count — always correct, but it
materializes K full candidates, so declare your KV caches.
"""
from __future__ import annotations

from ..base import MXNetError

__all__ = ["SpecConfig", "build_commit_sym"]


def build_commit_sym(cache_specs, K):
    """Build the symbolic multi-token commit graph over the declared
    cache states: for each ``(key, buffer_shape, dtype)`` in
    ``cache_specs``, a chain of ``K`` count-masked one-hot blends
    writing ``rows[:, j]`` at position ``pos + j`` when ``count > j``.
    Inputs are ``__spec_cache__<key>`` / ``__spec_rows__<key>`` per
    state plus shared ``__spec_pos__`` / ``__spec_count__`` vectors.

    Returns ``(symbol, shapes, cache_names, rows_names)`` where
    ``shapes`` maps every input to its full slot-pool shape — the spec
    the selection optimizer re-analyzes under (slot axis 0 padded,
    caches and rows seeded pad-dirty)."""
    from .. import symbol as sym
    from ..base import NameManager
    with NameManager():
        # a FRESH name counter: auto-named nodes (the + / 1-x scalar
        # forms have no name kwarg) must come out identical however
        # many graphs this process built before — the commit graph's
        # canonical JSON rides the AOT entry key and the validity
        # fingerprint, and an engine restarted in a warmer process
        # must hash to the same program
        return _build_commit_sym(sym, cache_specs, K)


def _build_commit_sym(sym, cache_specs, K):
    pos = sym.Variable("__spec_pos__")
    count = sym.Variable("__spec_count__")
    n_slots = cache_specs[0][1][0]
    shapes = {"__spec_pos__": (n_slots,), "__spec_count__": (n_slots,)}
    outs, cache_names, rows_names = [], [], []
    for key, shape, _dt in cache_specs:
        if len(shape) != 3:
            raise MXNetError(
                "spec commit: cache state %r has buffer shape %s; the "
                "one-hot-blend commit form (and the _cache_write_rows "
                "selection) support (slots, max_len, d) caches only"
                % (key, (shape,)))
        T = int(shape[1])
        cname = "__spec_cache__%s" % key
        rname = "__spec_rows__%s" % key
        cache = sym.Variable(cname)
        rows = sym.Variable(rname)
        shapes[cname] = tuple(shape)
        shapes[rname] = (shape[0], K) + tuple(shape[2:])
        cache_names.append(cname)
        rows_names.append(rname)
        c = cache
        for j in range(K):
            posj = pos + float(j)
            mje = sym.expand_dims(count > float(j), axis=1)
            ohm = sym.broadcast_mul(sym.one_hot(posj, depth=T), mje)
            ohe = sym.expand_dims(ohm, axis=2)
            rowj = sym.slice_axis(rows, axis=1, begin=j, end=j + 1)
            c = sym.broadcast_mul(c, 1.0 - ohe) \
                + sym.broadcast_mul(rowj, ohe)
        outs.append(c)
    return sym.Group(outs), shapes, cache_names, rows_names


def select_commit(commit, shapes, cache_names, rows_names):
    """Run the verdict-gated ``_cache_write_rows`` selection over a
    built commit graph — ONE implementation of the gate spec (slot
    axis 0 padded everywhere, caches AND rows pad-dirty) shared by
    the engine (:meth:`SpecConfig.build`) and the offline audit
    (``graph_lint --decode-step --draft``), so the two can never
    drift.  Returns ``(served_sym, selection, plan)``: the optimized
    graph + its selections when the plan accepted with rewrites, the
    input graph verbatim (selection ``[]``) otherwise.  Raises only
    what ``optimize_graph`` raises; callers own crash policy."""
    from ..analysis import optimize_graph, SELECT_OPT_PASSES
    plan = optimize_graph(
        commit, data_shapes=shapes,
        pad_axes={"slot": {n: 0 for n in shapes}},
        pad_dirty=tuple(cache_names) + tuple(rows_names),
        passes=SELECT_OPT_PASSES)
    if plan.accepted and plan.symbol is not None and plan.rewrites:
        sel = [{"op": "_cache_write_rows", "site": a.node}
               for a in plan.actions if a.kind == "select"]
        return plan.symbol, sel, plan
    return commit, [], plan


class SpecConfig(object):
    """Everything the wider step program needs about the draft half:
    the draft graph (already head-less: outputs ``[logits] +
    next_draft_states``), its params and per-slot state info, and —
    after :meth:`build` — the verdict-gated commit graph shared by
    every replica's program (built and optimized ONCE per engine; the
    per-replica StepPrograms only re-trace it into their own compiled
    step)."""

    def __init__(self, k, draft_sym, draft_arg_params=None,
                 draft_aux_params=None, draft_state_info=None,
                 token_name="token", pos_name="pos",
                 valid_name="valid"):
        self.k = int(k)
        if self.k < 1:
            raise MXNetError("speculative decode needs k >= 1 draft "
                             "tokens per step (k=0 is the plain "
                             "single-token engine — leave spec off)")
        self.K = self.k + 1
        self.draft_sym = draft_sym
        self.draft_arg_params = draft_arg_params or {}
        self.draft_aux_params = draft_aux_params or {}
        self.draft_state_info = [dict(s)
                                 for s in (draft_state_info or [])]
        self.token_name = token_name
        self.pos_name = pos_name
        self.valid_name = valid_name
        # filled by build()
        self.commit_sym = None
        self.commit_shapes = None
        self.commit_plan = None
        self.selection = []
        self.commit_digest = None
        self.draft_digest = None
        self._built = False

    # ------------------------------------------------------------------
    def build(self, layout):
        """Build + verdict-gate the commit graph once (idempotent) over
        the cache states of both models in ``layout`` (a
        :class:`~.slot_state.SlotLayout`: target states under their own
        names, draft states under their prefixed pool keys).

        The selection outcome (``_cache_write_rows`` adopted or the
        blend chain served with a reason) is recorded on
        ``self.selection`` / ``self.commit_plan`` — it rides the
        engine's AOT validity fingerprint and ``stats()`` block, and
        ``graph_lint --decode-step --draft`` reports the same audit
        offline."""
        if self._built:
            return self
        from .aot_cache import graph_digest
        self.draft_digest = graph_digest(self.draft_sym)
        specs = [(s.key, layout.pool_shape(s), s.dtype)
                 for s in layout.caches()]
        if not specs:
            self._built = True
            return self
        commit, shapes, cache_names, rows_names = build_commit_sym(
            specs, self.K)
        served = commit
        from .. import config
        if config.get("MXNET_SERVE_OPTIMIZE") \
                and config.get("MXNET_ANALYSIS_ON") \
                and config.get("MXNET_OPT_SELECT_KERNELS"):
            import warnings
            try:
                served, self.selection, self.commit_plan = \
                    select_commit(commit, shapes, cache_names,
                                  rows_names)
            except Exception as e:    # optimizer crash must never block
                warnings.warn("speculative commit optimization crashed "
                              "(%r); serving the blend-chain commit"
                              % (e,))
            if self.commit_plan is not None \
                    and not self.commit_plan.accepted:
                warnings.warn("speculative commit optimization "
                              "rejected (%s); serving the blend-chain "
                              "commit" % self.commit_plan.reason)
        self.commit_sym = served
        self.commit_shapes = shapes
        self.commit_digest = graph_digest(served)
        self._built = True
        return self

    def describe(self):
        """The AOT-fingerprint-visible (and stats-visible) summary."""
        return {"k": self.k,
                "draft_digest": self.draft_digest,
                "commit_selection": self.selection,
                "commit_accepted": (bool(self.commit_plan.accepted)
                                    if self.commit_plan is not None
                                    else None)}


# ---------------------------------------------------------------------------
# jax-land accept logic (runs INSIDE the compiled spec step)
# ---------------------------------------------------------------------------

def greedy_accept(xs, tlogits):
    """Exact-prefix greedy acceptance: ``xs`` is the draft's input
    chain (``xs[0]`` the staged token, ``xs[1..k]`` the proposals),
    ``tlogits`` the K per-position target logits.  Returns ``(toks,
    a)``: the (N, K) matrix of the target's own argmax at every
    position — the exact tokens ``greedy_decode`` would emit — and the
    (N,) count of leading proposals that matched it."""
    import jax.numpy as jnp
    g = [jnp.argmax(L, axis=1).astype(L.dtype) for L in tlogits]
    toks = jnp.stack(g, axis=1)
    K = len(tlogits)
    if K > 1:
        matches = jnp.stack(
            [(xs[j + 1] == g[j]).astype(jnp.float32)
             for j in range(K - 1)], axis=1)
        a = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)
    else:
        a = jnp.zeros((toks.shape[0],), jnp.float32)
    return toks, a


def rejection_accept(kstep, xs, tlogits, dlogits, transform):
    """Standard speculative rejection sampling (Leviathan/Chen):
    proposal ``x_j ~ q_j`` is accepted with probability
    ``min(1, p_j(x_j) / q_j(x_j))``; the first rejection at position j
    emits a draw from ``norm(max(p_j - q_j, 0))`` instead, and k
    accepts earn one bonus draw from ``p_k``.  ``transform`` maps raw
    logits to the sampler's log-space distribution (temperature +
    top-k mask), applied identically to both models so the emitted
    stream is distributed exactly as the single-token sampler.

    All draws chain off ``kstep`` (the engine's tick-folded step key)
    with a fixed fold-in schedule — draft proposal j uses ``2j``,
    accept uniform j uses ``2j+1``, the position-i fallback draw uses
    ``2K+i`` — so a seeded engine replays bitwise."""
    import jax
    import jax.numpy as jnp
    K = len(tlogits)
    N = tlogits[0].shape[0]
    dt = tlogits[0].dtype
    zt = [transform(L) for L in tlogits]
    p = jnp.stack([jax.nn.softmax(z, axis=-1) for z in zt], axis=1)
    if K > 1:
        zq = [transform(d) for d in dlogits[:K - 1]]
        q = jnp.stack([jax.nn.softmax(z, axis=-1) for z in zq], axis=1)
        xi = jnp.stack([x.astype(jnp.int32) for x in xs[1:K]], axis=1)
        px = jnp.take_along_axis(p[:, :K - 1], xi[..., None],
                                 axis=2)[..., 0]
        qx = jnp.take_along_axis(q, xi[..., None], axis=2)[..., 0]
        ratio = jnp.where(qx > 0, px / jnp.where(qx > 0, qx, 1.0), 0.0)
        us = jnp.stack(
            [jax.random.uniform(jax.random.fold_in(kstep, 2 * j + 1),
                                shape=(N,))
             for j in range(K - 1)], axis=1)
        accept = (us < jnp.minimum(ratio, 1.0)).astype(jnp.float32)
        a = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)
    else:
        a = jnp.zeros((N,), jnp.float32)
    cols = []
    for i in range(K):
        kk = jax.random.fold_in(kstep, 2 * K + i)
        if i < K - 1:
            # residual distribution at the first rejection: the part
            # of p the draft under-covered, renormalized; degenerate
            # all-zero residuals (p == q exactly) fall back to p —
            # statistically unreachable (the accept test passed with
            # probability 1 there) but a NaN-free compiled program
            # must not depend on that
            r = jnp.maximum(p[:, i] - q[:, i], 0.0)
            rs = jnp.sum(r, axis=-1, keepdims=True)
            logr = jnp.where(r > 0, jnp.log(jnp.where(r > 0, r, 1.0)),
                             -jnp.inf)
            logits_i = jnp.where(rs > 0, logr, zt[i])
        else:
            logits_i = zt[i]
        fresh = jax.random.categorical(kk, logits_i, axis=-1).astype(dt)
        acc_tok = xs[i + 1].astype(dt) if i < K - 1 else fresh
        cols.append(jnp.where(i < a, acc_tok, fresh))
    return jnp.stack(cols, axis=1), a


def commit_select(chain, idx):
    """Commit one NON-cache state by selecting the chain candidate at
    the accepted count: ``chain`` is the list of K per-step state
    values (state after consuming 1..K tokens), ``idx`` the (N,)
    int32 ``count - 1``.  Always correct for any state semantics —
    the rows path exists because this materializes K full candidates,
    which for a (slots, max_len, d) cache is exactly the O(K * T * d)
    traffic the widened scatter avoids."""
    import jax.numpy as jnp
    stacked = jnp.stack(chain, axis=1)
    ix = idx.reshape((-1, 1) + (1,) * (stacked.ndim - 2))
    return jnp.take_along_axis(stacked, ix, axis=1)[:, 0]


def gather_rows(chain, pos, T):
    """Collect the per-step written row of one CACHE state: step j of
    the chain wrote exactly row ``pos + j`` (clamped like the write
    itself), so gathering it back yields the row value bitwise.
    Returns the (N, K) + tail rows tensor the commit graph consumes."""
    import jax.numpy as jnp
    rows = []
    for j, s in enumerate(chain):
        ix = jnp.clip(pos.astype(jnp.int32) + j, 0, T - 1)
        ix = ix.reshape((-1, 1) + (1,) * (s.ndim - 2))
        rows.append(jnp.take_along_axis(s, ix, axis=1))
    return jnp.concatenate(rows, axis=1)
