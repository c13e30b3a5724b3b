"""When a join waits: how many of the requests that could be seated now
the decode scheduler seats, from costs the engine itself has observed.

A prefill dispatch stops every decoding slot for as long as it runs, so
seating ``n`` prompts in one dispatch that costs the device ``c(n)``
seconds costs ``L * c(n) / s`` slot-steps (``L`` slots decoding, ``s``
seconds a step), and holding a free slot for a step costs one.  Where
``c(n)`` is about ``a + b * n`` with a fixed part ``a`` worth several
steps (the read of the weights, blocks padded whatever the rows), a
join is cheapest in a batch near ``sqrt(2 * r * L * a / s)`` at ``r``
requests becoming seatable a step; where a dispatch costs by its rows
(``a`` about 0) nothing is gained by waiting and nothing waits.

:func:`seats_now` is that decision, a pure function of the observed
quantities; both scheduler loops of ``DecodeEngine`` call it.
:func:`cost_table` completes the engine's table of observed dispatch
costs over the warm batches it has not timed yet (at the least they can
cost, so that they are tried), and :class:`JoinState`
keeps one replica's running means.  Nothing here reads a clock, an
environment variable or a model's name.
"""
from __future__ import annotations

import collections
import math

__all__ = ["seats_now", "cost_table", "dispatch_cost", "JoinState"]

# What a hold, or a batch cut down to a warm extent, has to save of a
# join's cost before it is chosen over seating what is there.  The
# observed costs carry a few percent of noise, and a hold delays a first
# token, which the cost in slot-steps does not price: an even choice goes
# to the request that is waiting.
MARGIN = 0.05


def cost_table(observed, batches):
    """``{batch: seconds}`` over every warm batch of one bucket, from
    the costs observed so far (``{batch: seconds}``).  An observed
    batch keeps its reading, unless smaller dispatches that were timed
    would do its work for less (a reading that held a pause of the
    host may be the only one of a batch the policy then avoids).  One
    not yet timed is taken to cost by the
    rows of the cheapest larger batch that was (of the largest timed,
    where none is larger), and no less than the next smaller: the least
    it can cost, so that a batch that may be worth gathering is tried
    once and then known.  One reading alone therefore says nothing of
    a fixed part, and nothing is held on it.  None where nothing was
    observed."""
    if not observed:
        return None
    ns = sorted(observed)
    seen = {}
    for n in ns:
        seen[n] = min([observed[n]]
                      + [-(-n // m) * c for m, c in seen.items()])
    out = {}
    for n in batches:
        if n in seen:
            out[n] = seen[n]
            continue
        a_row = min([seen[m] / m for m in ns if m > n]
                    or [seen[ns[-1]] / ns[-1]])
        below = [seen[m] for m in ns if m < n]
        out[n] = max(n * a_row, below[-1] if below else 0.0)
    return out


def dispatch_cost(costs, m):
    """Seconds the device is stopped to prefill ``m`` prompts with the
    warm batches of ``costs``: whole dispatches of the largest, then
    the smallest that holds the rest (``_join_many``'s grouping)."""
    batches = sorted(costs)
    full, rest = divmod(m, batches[-1])
    cost = full * costs[batches[-1]]
    if rest:
        cost += costs[next(b for b in batches if b >= rest)]
    return cost


def seats_now(w, live, step_s, rate, costs, held_steps):
    """How many of the ``w`` requests that could be seated now (a free
    slot each) the scheduler seats in this iteration; the rest stay in
    their queue for the next.

    ``live``: slots decoding now, which a dispatch would stop;
    ``step_s``: seconds a step; ``rate``: requests that become seatable
    a step (running mean); ``costs``: ``{batch: seconds}`` of a prefill
    dispatch over the warm batches of the waiting prompt's bucket, None
    where a join rides the step and costs no dispatch; ``held_steps``:
    steps the oldest of the ``w`` has been seatable.

    Seating ``m`` now costs each of them ``live / step_s *
    dispatch_cost(m) / m`` slot-steps; gathering ``n > w`` first costs
    ``live / step_s * costs[n] / n`` and the empty seats meanwhile,
    ``(n*n - w*w) / (2 * rate * n)`` a join.  The cheapest wins, a
    smaller batch unless a larger saves ``MARGIN``; a batch that
    overshoots a warm extent is cut back to it where the padding would
    cost more.  A hold lasts no longer than gathering its batch was
    expected to, ``ceil((n - 1) / rate)`` steps: then what is there is
    seated."""
    if w <= 0:
        return 0
    if not live or not costs or not step_s:
        return w
    stall = live / float(step_s)
    keep = 1.0 - MARGIN
    batches = sorted(costs)
    m, best = w, stall * dispatch_cost(costs, w) / w
    cut = min((n for n in batches if n < w),
              key=lambda n: (costs[n] / n, -n), default=None)
    if cut is not None and stall * costs[cut] / cut < best * keep:
        m, best = cut, stall * costs[cut] / cut
    target = None
    for n in batches if rate > 0.0 else ():
        if n <= w:
            continue
        join = stall * costs[n] / n + (n * n - w * w) / (2.0 * rate * n)
        if join < best * keep:
            target, best = n, join
    if target is not None \
            and held_steps < math.ceil((target - 1) / rate):
        return 0
    return m


class JoinState(object):
    """One replica's running means for :func:`seats_now`, counted in
    scheduler iterations (a step each), and what its last decision
    left: ``rate`` (requests that became seatable a step), ``step_s``
    (seconds between the reads of consecutive steps with no prefill
    between them), ``held`` (seatable requests the last decision left
    waiting), ``decoding`` (slots decoding when the last join began),
    ``t_flight`` (when the step now in flight began on the device, as
    the host can tell: the read before it returning, or its own
    dispatch onto an idle device) and ``stalled`` (a prefill dispatch
    ran since that stamp, so the next read is no step's time)."""
    __slots__ = ("rate", "step_s", "held", "decoding", "t_flight",
                 "stalled", "_step", "_since")

    RATE_MEAN = 32.0        # steps the rate's mean looks back over
    STEP_MEAN = 8.0         # reads the step time's mean looks back over

    def __init__(self):
        self.rate = 0.0
        self.step_s = None
        self.held = 0
        self.decoding = 0
        self.t_flight = None
        self.stalled = False
        self._step = 0
        # [iteration, count] of the seatable requests not yet seated,
        # oldest first: the age of the oldest bounds a hold
        self._since = collections.deque()

    @property
    def held_steps(self):
        """Iterations the oldest seatable request has waited."""
        return self._step - self._since[0][0] if self._since else 0

    def idle(self):
        """Nothing decodes and no step is in flight: nobody is held."""
        self._since.clear()
        self.held = 0
        self.t_flight = None
        self.stalled = False

    def seatable(self, w):
        """A scheduler iteration finds ``w`` requests seatable: what is
        new since the last decision feeds the rate."""
        self._step += 1
        new = w - self.held         # ``held``: what the last decision left
        if new > 0:
            self._since.append([self._step, new])
        else:
            # fewer than were left: a deadline, a cancel, a steal
            self._drop(-new, newest=True)
        self.rate += (max(new, 0) - self.rate) / self.RATE_MEAN

    def seated(self, n, w):
        """The decision seated ``n`` of the ``w``, oldest first."""
        self._drop(n)
        self.held = w - n

    def _drop(self, n, newest=False):
        since = self._since
        while n > 0 and since:
            entry = since[-1] if newest else since[0]
            took = min(n, entry[1])
            entry[1] -= took
            n -= took
            if not entry[1]:
                since.pop() if newest else since.popleft()

    def step_read(self, now, next_in_flight):
        """A step's read returned at ``now``; ``next_in_flight``: the
        step after it is already dispatched.  Its time since
        ``t_flight`` is a step's, unless a prefill ran in between."""
        if self.t_flight is not None and not self.stalled:
            self.step_time(now - self.t_flight)
        self.t_flight = now if next_in_flight else None
        self.stalled = False

    def step_time(self, seconds):
        self.step_s = seconds if self.step_s is None else \
            self.step_s + (seconds - self.step_s) / self.STEP_MEAN

    def in_flight_left(self, now):
        """Seconds the step in flight still has to run at ``now``, by
        the mean step time: what a dispatch queued behind it waits for
        and is not its own cost."""
        if self.t_flight is None or self.step_s is None:
            return 0.0
        return max(0.0, self.step_s - (now - self.t_flight))
