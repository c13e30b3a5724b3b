"""dist_async straggler simulation — the measurement behind the decision
(close dist_async with numbers, not fiat).

Two measurable quantities decide sync-vs-async:

1. SYNC STRAGGLER PENALTY: a synchronous allreduce round takes
   max_i(t_i), so sync throughput is mean(t)/E[max_N(t)] of async's.
   Measured here for per-step time distributions from TPU-pod reality
   (single-tenant chips, lognormal sigma ~0.03) to the 2016 commodity
   clusters that motivated async PS (sigma 0.4 + 5% chance of a 10x
   straggler).

2. ASYNC STALENESS PENALTY: an async update applies a gradient computed
   on weights that are ~(N-1) updates old.  On a strongly convex problem
   the max STABLE learning rate shrinks with staleness; measured here by
   grid search (largest lr whose loss stays finite and reaches target) at
   staleness 0, 3, 7, 15, 31.  Since convergence wall-clock scales ~1/lr
   in the stability-limited regime, lr_max(k)/lr_max(0) IS async's
   slowdown factor.

Verdict = penalty(1) vs penalty(2).  Prints JSON lines.
"""
import json

import numpy as np


def make_problem(rng, d=64, n=4096, noise=0.01):
    # unit-scale covariance (Hessian ~= I, L ~= 1.3) so the stability
    # boundary lr*L*staleness ~ 1 sits inside the measured lr grid
    X = rng.standard_normal((n, d)).astype(np.float64)
    w_true = rng.standard_normal(d)
    y = X @ w_true + noise * rng.standard_normal(n)
    return X, y, w_true


def loss(X, y, w):
    r = X @ w - y
    return float(r @ r / (2 * len(y)))


def grad(X, y, w, idx):
    Xb, yb = X[idx], y[idx]
    return Xb.T @ (Xb @ w - yb) / len(idx)


def straggler_penalty(rng, N, sigma, straggler_p, straggler_x, rounds=20000):
    """E[max over N] / E[mean over N] of per-step times."""
    t = np.exp(rng.normal(0.0, sigma, size=(rounds, N)))
    mask = rng.random((rounds, N)) < straggler_p
    t = np.where(mask, t * straggler_x, t)
    return float(t.max(axis=1).mean() / t.mean())


def stale_sgd_converges(X, y, target, lr, staleness, batch, rng,
                        max_updates=20000):
    """Delayed SGD: the gradient applied at update u was computed on the
    weights as of update u - staleness."""
    d = X.shape[1]
    w = np.zeros(d)
    hist = [w.copy()] * (staleness + 1)
    for u in range(max_updates):
        w_seen = hist[0]
        idx = rng.integers(0, len(y), batch)
        w = w - lr * grad(X, y, w_seen, idx)
        if not np.all(np.isfinite(w)) or loss(X, y, w) > 1e6:
            return None
        hist.append(w.copy())
        hist.pop(0)
        if loss(X, y, w) < target:
            return u + 1
    return None


def max_stable_lr(X, y, target, staleness, batch):
    best = None
    for lr in (1.6, 1.2, 0.8, 0.6, 0.4, 0.3, 0.2, 0.15, 0.1, 0.07,
               0.05, 0.03, 0.02):
        rng = np.random.default_rng(1)
        u = stale_sgd_converges(X, y, target, lr, staleness, batch, rng)
        if u is not None:
            best = (lr, u)
            break
    return best


def main():
    rng = np.random.default_rng(0)
    X, y, w_true = make_problem(rng)
    target = loss(X, y, w_true) * 1.5
    N = 8

    for name, sigma, sp, sx in [("tpu_pod", 0.03, 0.0, 1.0),
                                ("mild_jitter", 0.15, 0.0, 1.0),
                                ("commodity_2016", 0.4, 0.05, 10.0)]:
        pen = straggler_penalty(rng, N, sigma, sp, sx)
        print(json.dumps({"measure": "sync_straggler_penalty",
                          "config": name, "workers": N,
                          "sync_slowdown_vs_async_throughput":
                              round(pen, 3)}))

    base = max_stable_lr(X, y, target, 0, batch=32)
    for k in (0, 3, 7, 15, 31):
        got = max_stable_lr(X, y, target, k, batch=32)
        lr, updates = got if got else (None, None)
        print(json.dumps({
            "measure": "async_staleness_penalty", "staleness": k,
            "max_stable_lr": lr, "updates_to_target": updates,
            "slowdown_vs_fresh": round(base[1] and updates / base[1], 3)
            if got else None}))


if __name__ == "__main__":
    main()
