"""What one slot of the decode pool holds, and the pool laid out over it.

A model declares its per-slot states as ``state_info``, a list of dicts
in the order its step graph returns their next values::

    {"name": str, "shape": row shape[, "dtype"][, "cache"][, "window"]}

``dtype`` (absent or None: the engine's) is the dtype of the state's pool
buffer.  ``"cache": True`` marks a positional cache: the row's leading
axis is positions, the step writes the row at ``pos`` and reads under a
mask by position, so a join need not zero it and a prefill may hand it
the keys or values of every prompt position.  ``"window": n`` makes the
cache a ring of ``n`` rows written at ``pos mod n``.

This module is the one reader of that format.  :class:`SlotLayout` is
built once per :class:`~.decode.StepProgram` (and once by the engine,
for the analyses that run before any program exists); the step program,
the engine's preflights, the speculative commit and the goodput pricer
ask it for pool shapes, dtypes, bytes, the step graph's input grid and
for what ``cache`` and ``window`` mean.  A new kind of per-slot state is
a change to this module, to the model's graph and to its op rules.

A state that is no cache is a *plain row*: the step returns it whole,
a join without prefill zeroes it (``reset_names``) and a prefill output
of the row's own shape replaces it (``lay_prefill``).  An LSTM's ``h``
and ``c`` are plain rows, and so is a short convolution's state, the
last ``taps - 1`` rows of its gated input: what it needs of the pool is
what a recurrent row needs, so a model may declare caches and plain rows
side by side (``models/lfm2.py``) and one prefill dispatch and one
commit lay both.
"""
from __future__ import annotations

import collections

import numpy as np

from ..base import MXNetError

__all__ = ["SlotLayout", "SlotState", "StepGrid"]

#: A draft model's states ride the pool's one dict under prefixed keys,
#: so a draft ``h`` never collides with the target's.
DRAFT_PREFIX = "draft:"

#: One declared state.  ``key`` names its buffer in the pool's dict,
#: ``name`` the step graph's input (they differ for a draft state),
#: ``row`` is one slot's shape, ``window`` 0 where the state is no ring.
SlotState = collections.namedtuple(
    "SlotState", "key name row dtype cache window")

#: A step graph's inputs at pool extent (:meth:`SlotLayout.grid`):
#: ``donate`` maps a state input to the output that aliases it.
StepGrid = collections.namedtuple(
    "StepGrid", "shapes dtypes state_names donate")


def _read(info, dtype, prefix=""):
    cache = bool(info.get("cache"))
    window = int(info.get("window") or 0)
    if window and not cache:
        raise MXNetError(
            "state %r declares a window of %d rows but is no cache: only "
            "a positional cache (\"cache\": True) is written at pos mod "
            "window" % (info["name"], window))
    return SlotState(prefix + info["name"], info["name"],
                     tuple(info["shape"]),
                     np.dtype(info.get("dtype") or dtype), cache, window)


class SlotLayout(object):
    """The states of a pool of ``num_slots`` slots: the target model's
    (``state_info``) and, under a speculative program, the draft's.
    ``which`` below is ``"target"``, ``"draft"`` or ``"all"``."""

    def __init__(self, state_info, num_slots, dtype, draft_state_info=None):
        self.num_slots = int(num_slots)
        self.target = tuple(_read(i, dtype) for i in state_info)
        self.draft = tuple(_read(i, dtype, DRAFT_PREFIX)
                           for i in draft_state_info or ())

    def states(self, which="all"):
        if which == "all":
            return self.target + self.draft
        return {"target": self.target, "draft": self.draft}[which]

    def caches(self, which="all"):
        return tuple(s for s in self.states(which) if s.cache)

    def pool_shape(self, state):
        return (self.num_slots,) + state.row

    def zeros(self, which="all", pool=False, xp=np):
        """``(state, zeros)`` pairs, one slot's row each or, with
        ``pool``, the whole buffer, made one at a time as the caller
        places them.  Host zeros by default (a sharded pool is placed
        shard by shard and never staged whole on one device);
        ``xp=jax.numpy`` makes them on the device."""
        for s in self.states(which):
            yield s, xp.zeros(self.pool_shape(s) if pool else s.row,
                              dtype=s.dtype)

    # -------------------------------------------------------- step graph
    def grid(self, graph, token_name, pos_name, valid_name,
             which="target"):
        """The :class:`StepGrid` of one model's step graph: the token
        vector and, where the graph takes them, the position and valid
        vectors at ``(num_slots,)`` float32 (the host's vectors, whatever
        the pool holds), every state at pool extent in its buffer's
        dtype, and state ``i`` donated to output ``1 + i``, as
        ``StepProgram`` donates."""
        args = set(graph.list_arguments())
        states = self.states(which)
        shapes = {token_name: (self.num_slots,)}
        shapes.update((s.name, self.pool_shape(s)) for s in states)
        shapes.update((n, (self.num_slots,))
                      for n in (pos_name, valid_name) if n in args)
        dtypes = {n: np.dtype(np.float32) for n in shapes}
        dtypes.update((s.name, s.dtype) for s in states)
        return StepGrid(shapes, dtypes, [s.name for s in states],
                        {s.name: 1 + i for i, s in enumerate(states)})

    def reset_names(self, which="target"):
        """The states a join zeroes inside the plain step.  A cache is
        not among them: every row a request reads of it is one that
        request wrote.  Zeroing is a select over the whole buffer in
        front of the step, which for recurrent rows is nothing and for a
        cache of gigabytes is a copy of the pool every step."""
        return [s.name for s in self.states(which) if not s.cache]

    # ------------------------------------------------------------- bytes
    def pool_bytes(self, sharding=None, which="target"):
        """Bytes of the pool's buffers on one device, divided along the
        axes that ``sharding``'s state rules partition."""
        from ..analysis.memory import shard_divisor
        total = 0
        for s in self.states(which):
            shape = self.pool_shape(s)
            total += int(np.prod(shape)) * s.dtype.itemsize \
                // shard_divisor(sharding, s.name, shape, kind="state")
        return total

    def slot_bytes(self, sharding=None, which="target"):
        return self.pool_bytes(sharding, which) // self.num_slots

    def row_state_bytes(self, which="target"):
        """Bytes one slot's plain rows hold (the states that are no
        cache): what a join zeroes and a prefill replaces."""
        return sum(int(np.prod(s.row)) * s.dtype.itemsize
                   for s in self.states(which) if not s.cache)

    # ------------------------------------------------------------ caches
    def cache_rows(self, which="target", pos_name=None, has_pos=True):
        """``(key, rows)`` of each cache state.  The speculative commit
        writes a cache's accepted rows at ``pos``: it says whether the
        model's graph ``has_pos``, and is refused where it does not."""
        caches = self.caches(which)
        if caches and not has_pos:
            raise MXNetError(
                "%s state %r is cache-declared but its step graph has no "
                "%r input — a positional cache commit needs the write "
                "position" % (which, caches[0].name, pos_name))
        return [(s.key, int(s.row[0])) for s in caches]

    def commit_tag(self):
        """The AOT tag of the prefill commit program: what
        :meth:`lay_prefill` reads of a state beside its shape."""
        return "lay_rows_v1|" + ",".join(
            "%d:%d" % (s.cache, s.window) for s in self.target)

    def lay_prefill(self, bufs, rows, slots, lens):
        """``StepProgram.commit_prefill``, traced: one prefill
        dispatch's rows (``(batch,) + row``, or for a cache the keys or
        values of every prompt position, ``(batch, T) + tail``) laid
        into slots ``slots`` of the target's buffers."""
        return [_lay_rows(b, r, s, slots, lens)
                for b, r, s in zip(bufs, rows, self.target)]


def _lay_rows(buf, rows, state, slots, lens):
    import jax.numpy as jnp
    from jax import lax
    held = buf.shape[1:]
    seq = rows.shape[1:] != held
    if seq and not (state.cache and rows.ndim == buf.ndim
                    and rows.shape[2:] == held[1:]):
        raise MXNetError(
            "prefill rows %s fit neither state %r's row %s nor, as keys "
            "or values a position, a cache state's"
            % (rows.shape[1:], state.name, held))
    for i in reversed(range(rows.shape[0])):
        one = rows[i]
        if seq and one.shape[0] > held[0]:
            if state.window:
                # ring order: row j takes the last position p < lens[i]
                # with p mod window == j, where the step will look
                last = lens[i] - 1
                at = last - jnp.mod(
                    last - jnp.arange(held[0], dtype=jnp.int32), held[0])
                one = one[jnp.clip(at, 0, one.shape[0] - 1)]
            else:
                one = one[:held[0]]
        buf = lax.dynamic_update_slice(
            buf, one[None].astype(buf.dtype),
            (slots[i],) + (0,) * (buf.ndim - 1))
    return buf
